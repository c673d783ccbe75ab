"""The comparison that decides ``correct``.

Four numbers, each with its limit (see PERF.md for the readings each
limit was set from):

- ``trace_rel``: the widest relative gap, over the sampled records, of
  the columns that come from trace acquisition (latency percentiles,
  stage count, MFU and batch averages, throughput, grid CI);
- ``duration_rel``: that of the columns the device program computes
  from the summed stage durations alone, in float64 (duration, GPU
  hours, embodied carbon);
- ``power_rel``: that of the columns it computes through the float32
  Eq. 1 power (Eq. 2-3 energy, power, operational and total carbon);
- ``assembly_faults``: records that are missing, extra, or whose tag,
  parameters, key or column set differ from the reference's.

A relative gap is ``|a - b| / max(|a|, |b|)``, and 0 where both are
equal; a value that is not finite on one side only counts as 1.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

from reference import DURATION_COLS, POWER_COLS, RECORD_COLS

#: limits, from the readings in PERF.md ("How correct is decided")
LIMITS = {
    # sound runs read 0; the float32 event-loop control reads 2.8e-4
    # or more
    "trace_rel": 1e-6,
    "duration_rel": 1e-10,
    "power_rel": 5e-5,
    "assembly_faults": 0,
}

_NUMBER = {**{c: "duration_rel" for c in DURATION_COLS},
           **{c: "power_rel" for c in POWER_COLS}}


def rel_gap(a: float, b: float) -> float:
    if a == b:
        return 0.0
    if not (math.isfinite(a) and math.isfinite(b)):
        return 1.0
    return abs(a - b) / max(abs(a), abs(b))


def gaps(ref: Sequence[dict], got: Sequence[dict]) -> Dict[str, float]:
    """The three numbers for records ``got`` against ``ref``, matched
    by scenario tag."""
    by_tag = {r["scenario"]: r for r in got}
    faults = len(got) - len({r["scenario"] for r in got})  # duplicates
    out = {"trace_rel": 0.0, "duration_rel": 0.0, "power_rel": 0.0}
    for a in ref:
        b = by_tag.get(a["scenario"])
        if b is None:
            faults += 1
            continue
        if (a["key"] != b.get("key") or a["params"] != b.get("params")
                or set(a["metrics"]) != set(b.get("metrics", {}))):
            faults += 1
            continue
        for col in RECORD_COLS:
            g = rel_gap(float(a["metrics"][col]), float(b["metrics"][col]))
            k = _NUMBER.get(col, "trace_rel")
            out[k] = max(out[k], g)
    faults += len(set(by_tag) - {a["scenario"] for a in ref})
    return {**out, "assembly_faults": faults}


def passes(numbers: Dict[str, float]) -> bool:
    return all(numbers[k] <= LIMITS[k] for k in LIMITS)


def worst(readings: List[Dict[str, float]]) -> Dict[str, float]:
    """The largest reading of each number over several comparisons."""
    out = {k: 0 for k in LIMITS}
    for r in readings:
        for k in LIMITS:
            out[k] = max(out[k], r[k])
    return out
