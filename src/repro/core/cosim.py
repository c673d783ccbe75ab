"""Vidur->Vessim bridge: turn simulator batch-stage logs into a power
signal, run the microgrid co-simulation, and report paper-Table-2
metrics.

Pipeline (paper Section 3.2):
  1. timestamp batch stages (simulator clock)
  2. Eq. 1 power per stage from MFU
  3. Eq. 5 duration-weighted aggregation into fixed bins
  4. microgrid scan against solar + CI signals
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax.numpy as jnp
import numpy as np

from repro.core.host import on_host
from repro.core.microgrid import MicrogridConfig, simulate, summarize
from repro.core.power import PowerModel
from repro.core.signals import Signal, aggregate_power


@dataclasses.dataclass
class CosimResult:
    load: Signal
    solar: Signal
    ci: Signal
    traces: Dict[str, np.ndarray]
    metrics: Dict[str, float]


def stages_to_load_signal(stage_start_s, stage_dur_s, stage_mfu,
                          power_model: PowerModel, n_devices: int = 1,
                          pue: float = 1.0, resolution_s: float = 60.0,
                          include_idle: bool = True) -> Signal:
    """Stages -> per-bin average power (W, whole deployment)."""
    p = np.asarray(power_model.power(np.asarray(stage_mfu)))
    sig = aggregate_power(stage_start_s, stage_dur_s, p, resolution_s)
    vals = sig.values.copy()
    if include_idle:
        # bins with no recorded stage still draw idle power
        vals = np.where(vals > 0, vals, power_model.dev.p_idle)
    return Signal(sig.times, vals * n_devices * pue, interp="previous")


def trace_to_load_signal(trace, power_model: PowerModel,
                         n_devices: int = 1, pue: float = 1.0,
                         resolution_s: float = 60.0,
                         include_idle: bool = True) -> Signal:
    """``stages_to_load_signal`` directly over a ``StageTrace``."""
    return stages_to_load_signal(trace.start_s, trace.dur_s, trace.mfu,
                                 power_model, n_devices=n_devices, pue=pue,
                                 resolution_s=resolution_s,
                                 include_idle=include_idle)


def run_cosim(load: Signal, solar: Signal, ci: Signal,
              cfg: Optional[MicrogridConfig] = None) -> CosimResult:
    cfg = cfg or MicrogridConfig()
    # align all signals on the load grid
    t = load.times
    with on_host():
        lw = jnp.asarray(load.values)
        sw = jnp.asarray(solar.at(t))
        cw = jnp.asarray(ci.at(t))
        tr = simulate(lw, sw, cw, cfg)
    tr_np = {k: np.asarray(v) for k, v in tr.items()}
    metrics = summarize(np.asarray(lw), np.asarray(sw), np.asarray(cw),
                        tr_np, cfg)
    return CosimResult(load=load, solar=Signal(t, np.asarray(sw)),
                       ci=Signal(t, np.asarray(cw)), traces=tr_np,
                       metrics=metrics)
