"""Vessim-analogue microgrid co-simulation as a JAX ``lax.scan``.

Actors (load, solar), a battery with SoC constraints (the ``ClcBattery``
analogue), and a grid connection are stepped at fixed resolution
(default 1 minute). Because the step loop is a scan over jnp arrays, a
whole scenario grid (battery sizes x solar scales x policies) can be
``vmap``-ed and evaluated in one compiled call — a beyond-paper
capability the benchmarks use for sweeps.

Power-flow convention per step (all W, averaged over the step):
  load >= 0 (consumption), solar >= 0 (generation)
  surplus = solar - load
  surplus > 0: charge battery (up to c-rate/SoC-max), export remainder
  surplus < 0: discharge battery (down to SoC-min), import remainder
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.host import on_host


@dataclasses.dataclass(frozen=True)
class BatteryConfig:
    capacity_wh: float = 100.0
    soc_init: float = 0.5
    soc_min: float = 0.2
    soc_max: float = 0.8
    max_charge_w: float = 1000.0
    max_discharge_w: float = 1000.0
    efficiency: float = 0.95        # round-trip split evenly


@dataclasses.dataclass(frozen=True)
class MicrogridConfig:
    battery: BatteryConfig = BatteryConfig()
    step_s: float = 60.0
    ci_threshold_low: float = 100.0    # gCO2/kWh (paper Table 1b)
    ci_threshold_high: float = 200.0


def simulate(load_w: jnp.ndarray, solar_w: jnp.ndarray, ci: jnp.ndarray,
             cfg: MicrogridConfig) -> Dict[str, jnp.ndarray]:
    """Run the co-simulation. load/solar/ci: (T,) aligned at cfg.step_s.

    Returns per-step traces + aggregate metrics (all jnp; differentiable
    and vmap-able over scenario parameters)."""
    b = cfg.battery
    dt_h = cfg.step_s / 3600.0
    eff = jnp.sqrt(b.efficiency)

    def step(soc_wh, inp):
        load, solar, ci_t = inp
        surplus = solar - load
        # charge path
        room = jnp.maximum(b.soc_max * b.capacity_wh - soc_wh, 0.0)
        max_charge = jnp.minimum(b.max_charge_w, room / dt_h / eff)
        charge = jnp.clip(surplus, 0.0, max_charge)
        # discharge path
        avail = jnp.maximum(soc_wh - b.soc_min * b.capacity_wh, 0.0)
        max_dis = jnp.minimum(b.max_discharge_w, avail * eff / dt_h)
        discharge = jnp.clip(-surplus, 0.0, max_dis)
        new_soc = soc_wh + charge * eff * dt_h - discharge / eff * dt_h
        grid = surplus - charge + discharge   # >0 export, <0 import
        grid_import = jnp.maximum(-grid, 0.0)
        grid_export = jnp.maximum(grid, 0.0)
        emis_g = grid_import * dt_h / 1000.0 * ci_t
        solar_used = jnp.minimum(solar, load + charge)
        out = {
            "soc": new_soc / b.capacity_wh,
            "grid_import_w": grid_import,
            "grid_export_w": grid_export,
            "charge_w": charge,
            "discharge_w": discharge,
            "emissions_g": emis_g,
            "solar_used_w": solar_used,
        }
        return new_soc, out

    with on_host():
        soc0 = jnp.asarray(b.soc_init * b.capacity_wh)
        _, tr = jax.lax.scan(step, soc0, (load_w, solar_w, ci))
    return tr


def summarize(load_w, solar_w, ci, tr, cfg: MicrogridConfig) -> Dict[str, float]:
    """Aggregate metrics matching the paper's Table 2."""
    dt_h = cfg.step_s / 3600.0
    load = np.asarray(load_w)
    solar = np.asarray(solar_w)
    ci = np.asarray(ci)
    soc = np.asarray(tr["soc"])
    imp = np.asarray(tr["grid_import_w"])
    chg = np.asarray(tr["charge_w"])
    dis = np.asarray(tr["discharge_w"])
    emis = np.asarray(tr["emissions_g"])
    solar_used = np.asarray(tr["solar_used_w"])

    e_total = load.sum() * dt_h                     # Wh
    e_solar_gen = solar.sum() * dt_h
    e_solar_used = solar_used.sum() * dt_h
    e_grid = imp.sum() * dt_h
    total_emis = emis.sum()
    # counterfactual: all load from grid at prevailing CI
    emis_nosolar = float(np.sum(load * ci) * dt_h / 1000.0)
    offset = emis_nosolar - total_emis
    b = cfg.battery
    full_cycles = float(chg.sum() * dt_h / max(b.capacity_wh, 1e-9))
    return {
        "total_energy_kwh": e_total / 1000.0,
        "solar_generation_kwh": e_solar_gen / 1000.0,
        "grid_consumption_kwh": e_grid / 1000.0,
        "renewable_share_pct": 100.0 * e_solar_used / max(e_total, 1e-9),
        "grid_dependency_pct": 100.0 * e_grid / max(e_total, 1e-9),
        "total_emissions_nosolar_kg": emis_nosolar / 1000.0,
        "net_emissions_kg": total_emis / 1000.0,
        "offset_kg": offset / 1000.0,
        "carbon_offset_pct": 100.0 * offset / max(emis_nosolar, 1e-9),
        "avg_soc_pct": 100.0 * float(soc.mean()) if len(soc) else 0.0,
        "hours_below_50_soc": float(np.sum(soc < 0.5) * dt_h),
        "hours_above_80_soc": float(np.sum(soc >= 0.795) * dt_h),
        "charging_pct": 100.0 * float(np.mean(chg > 1e-6)),
        "discharging_pct": 100.0 * float(np.mean(dis > 1e-6)),
        "idle_pct": 100.0 * float(np.mean((chg <= 1e-6) & (dis <= 1e-6))),
        "battery_full_cycles": full_cycles,
        "avg_ci": float(ci.mean()),
        "hours_high_ci": float(np.sum(ci > cfg.ci_threshold_high) * dt_h),
        "duration_h": len(load) * dt_h,
    }
