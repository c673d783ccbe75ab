"""The least bytes that the grid program ``_group_kernel`` has to move.

One dispatch evaluates every trace group of a sweep. The least any
implementation that meets the device-mode tolerance must read and
write is the live, unpadded data, at 4 bytes a value (float32 holds
each input to that tolerance):

- per stage row of each group: prefill tokens, decode tokens, score
  FLOPs and KV bytes (4 values);
- per scenario of each group: its PUE and grid CI in, its operational
  carbon out (3 values);
- per group: 12 roofline parameters, 5 power-curve constants, the
  device count and the embodied rate in; summed energy, MFU x time,
  duration, peak power and embodied carbon out (24 values).

The program does no matrix work, so memory bounds it: the least time
is these bytes over the chips' HBM bandwidth.
"""
from __future__ import annotations

from typing import Sequence

VALUE_BYTES = 4
PER_ROW, PER_SCENARIO, PER_GROUP = 4, 3, 24


def live_bytes(stages: Sequence[int], scenarios: Sequence[int]) -> int:
    """Bytes of one dispatch over groups with ``stages[g]`` rows and
    ``scenarios[g]`` scenarios."""
    if len(stages) != len(scenarios):
        raise ValueError("one stage count and one scenario count per group")
    values = sum(PER_ROW * s + PER_SCENARIO * k + PER_GROUP
                 for s, k in zip(stages, scenarios))
    return VALUE_BYTES * values
