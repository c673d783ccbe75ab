"""Device-batched whole-grid evaluation (sweep ``--mode device``).

One ``jax.jit`` + ``vmap`` program evaluates EVERY trace group's
post-simulation passes at once: the groups' ``StageTrace`` composition
columns are zero-padded and ragged-stacked into one ``(G, S)`` tensor
set, and the batched roofline (the same ``_roofline`` kernel
``stage_cost_batch`` runs), the Eq. 1-3 power/energy reductions and
the Eq. 4 emissions — including the per-group scenario fan-out over
the ``pue`` / ``grid_ci`` axes as a stacked ``(G, K)`` axis — compile
into a single device dispatch for the whole grid, instead of one numpy
pass per group (``repro.sweep.vectorized``).

Trace acquisition composes with ``repro.sweep.divergence``: groups
whose configs differ only in device/TP/PP and provably cannot diverge
in admission timing share one composition schedule (replayed per
config, bit-identically to the event loop) — the event loop runs only
for groups the conservative predicate rejects. Record assembly reuses
``runner.single_site_metrics``, so device-mode records carry exactly
the event-loop columns.

**Tolerance contract** (see README): numpy modes are bit-identical to
the event loop; device mode is NOT. (a) The roofline and the Eq. 2-4
arithmetic run in float64, which a TPU emulates: ~1e-14 relative per
operation and float32's exponent range, so duration and embodied
carbon land ~1e-14 off, as do the reassociated trace reductions
(``sum(P_i*dt_i)``, ``sum(dt_i)``, ``sum(MFU_i*dt_i)``). (b) The Eq. 1
power curve is evaluated in float32, mirroring ``core.power.power``;
XLA:CPU's ``pow`` agrees with the host's to an ulp (~1e-7 on the
records), a TPU v5e's strays up to 67 ulps (~5e-6 on the factor,
1.7e-6 on the records of the paper grids). ``DEVICE_MODE_RTOL`` bounds
both; columns that never pass through the device program (latency
percentiles, throughput, MFU/batch averages, stage counts) come from
the host-side trace and stay bitwise.
"""
from __future__ import annotations

import dataclasses
import os
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.carbon import reports_from_arrays
from repro.core.energy import reports_from_sums
from repro.core.power import DEVICES
from repro.fleet.config import FleetConfig
from repro.obs.spans import PROFILER
from repro.sim.execmodel import (PARAMS_FIELDS, _Params, _roofline,
                                 cached_execution_model)
from repro.sweep import divergence
from repro.sweep.grid import Scenario
from repro.sweep.vectorized import group_by_trace

#: documented ulp-level equivalence bound for device-mode records
#: against event-loop records (relative, per metric column) — the f32
#: Eq. 1 power evaluation dominates: ~1e-7 on the CPU, 1.7e-6 on a TPU
#: v5e (its f32 pow), so 5e-6 leaves 3x margin there while still
#: catching any real logic divergence. CI pins the perf grid under
#: this bound on the CPU (benchmarks/perf_sweep.py --check-device);
#: chip_smoke.py pins every paper grid on the chip.
DEVICE_MODE_RTOL = 5e-6

#: record columns the device program computes (f32 Eq. 1 power and
#: reassociated reductions, so held to ``DEVICE_MODE_RTOL``); every
#: other column comes from the host-side trace and is bitwise
DEVICE_COLS = frozenset({
    "energy_wh", "energy_kwh", "avg_power_w", "peak_power_w",
    "duration_s", "gpu_hours", "carbon_operational_g",
    "carbon_embodied_g", "carbon_total_g",
})


@dataclasses.dataclass
class DeviceStats:
    """How the device mode acquired and evaluated its traces."""
    trace_groups: int = 0
    event_loops: int = 0     # groups driven through the event loop
    replayed: int = 0        # groups served by divergence replay
    devices: int = 1         # accelerators the dispatch sharded over
    platform: str = ""       # jax platform the program ran on ("tpu")
    device_kind: str = ""    # e.g. "TPU v5 lite"; "cpu" on the host
    bucket: Tuple[int, int, int] = (0, 0, 0)   # padded (G, S, K)


def _next_pow2(n: int) -> int:
    """Padding bucket: shapes quantize to powers of two so jit
    recompiles O(log) times across grids, not per grid size."""
    return 1 if n <= 1 else 1 << (int(n) - 1).bit_length()


def _group_kernel(comp_pre, comp_dec, comp_score, comp_kv,
                  params, powerp, ndev, phi, pues, cis):
    """Per-group pass (vmapped over G): roofline -> Eq. 1 power ->
    Eq. 2-3 reductions -> Eq. 4 terms over the scenario axis.

    Zero-padded rows have tokens == 0, which the roofline kernel
    already masks (all outputs zero), so only the power factor needs
    an explicit ``live`` mask (P(0) = p_idle, not 0)."""
    import jax.numpy as jnp

    p = _Params(*(params[i] for i in range(len(PARAMS_FIELDS))))
    t = _roofline(comp_pre, comp_dec, comp_score, comp_kv, p, jnp)
    dur_s, mfu = t[0], t[6]
    live = (comp_pre + comp_dec) > 0

    # Eq. 1 in float32, mirroring core.power.power() op for op; the
    # (p_max - p_idle) delta is precomputed host-side in f64 (powerp[4])
    # exactly as the eager path subtracts python floats
    mfu32 = jnp.clip(jnp.asarray(mfu, jnp.float32), 0.0, None)
    x = jnp.minimum(mfu32, powerp[2]) / powerp[2]
    pw = powerp[0] + powerp[4] * jnp.power(x, powerp[3])
    pw64 = jnp.where(live, pw.astype(jnp.float64), 0.0)

    e_sum = jnp.sum(pw64 * dur_s)                 # W*s
    m_sum = jnp.sum(mfu * dur_s)
    dur = jnp.sum(dur_s)
    peak = jnp.max(pw64)                          # 0 for empty groups
    gpu_h = dur / 3600.0 * ndev
    energy_wh = e_sum / 3600.0 * ndev * pues      # (K,) scenario axis
    op_g = energy_wh / 1000.0 * cis               # Eq. 4 operational
    emb_g = gpu_h * phi * 1000.0                  # Eq. 4 embodied
    return e_sum, m_sum, dur, peak, op_g, emb_g


_PROGRAM = None
_PMAP_PROGRAMS: Dict[int, object] = {}

# padded shapes this process has already dispatched: a new (G, S, K)
# bucket pays XLA compilation inside the call, a seen one replays the
# jit cache — the wall-clock profiler labels the two differently
_SEEN_SHAPES: set = set()

#: persistent compilation cache when ``$JAX_COMPILATION_CACHE_DIR`` is
#: unset: anchored on the checkout, not the cwd, because the directory
#: is part of every entry's key and a moving path never hits
DEFAULT_JAX_CACHE_DIR = (Path(__file__).resolve().parents[3]
                         / "results" / "jax_cache")

_CACHE_CONFIGURED = False


def _configure_compile_cache() -> None:
    """Keep the device program's XLA compile on disk so it is paid once
    per shape bucket per machine instead of once per process. JAX reads
    ``$JAX_COMPILATION_CACHE_DIR`` itself; only without it does the
    cache go to ``DEFAULT_JAX_CACHE_DIR``. A caller that needs a cold
    compile turns the cache off with
    ``jax.config.update("jax_enable_compilation_cache", False)``."""
    global _CACHE_CONFIGURED
    if _CACHE_CONFIGURED:
        return
    _CACHE_CONFIGURED = True
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        DEFAULT_JAX_CACHE_DIR.mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir",
                          str(DEFAULT_JAX_CACHE_DIR))
    # the grid program compiles in well under the default 1s
    # persistence threshold, so lower both floors to "always"
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def _program():
    global _PROGRAM
    if _PROGRAM is None:
        import jax
        _configure_compile_cache()
        _PROGRAM = jax.jit(jax.vmap(_group_kernel))
    return _PROGRAM


def _pmap_program(n_dev: int):
    """pmap(vmap(kernel)): the same per-group kernel, with the padded
    group axis split ``(G,) -> (D, G/D)`` so each local device
    evaluates its own slab — numerically the identical program per
    group, so the ``DEVICE_MODE_RTOL`` contract is unchanged."""
    prog = _PMAP_PROGRAMS.get(n_dev)
    if prog is None:
        import jax
        _configure_compile_cache()
        prog = jax.pmap(jax.vmap(_group_kernel))
        _PMAP_PROGRAMS[n_dev] = prog
    return prog


def _acquire_results(scenarios: Sequence[Scenario],
                     single: List[List[int]], stats: DeviceStats
                     ) -> Tuple[list, List[float]]:
    """One SimResult per single-site trace group: divergence-shared
    families replay one composition schedule per config; everything
    else runs the event loop."""
    from repro.sim import run_simulation

    fams: Dict[str, List[int]] = {}
    for gi, g in enumerate(single):
        blob = divergence.family_blob(scenarios[g[0]].cfg)
        fams.setdefault(blob, []).append(gi)

    results: list = [None] * len(single)
    sim_elapsed = [0.0] * len(single)
    for members in fams.values():
        cfgs = [scenarios[single[gi][0]].cfg for gi in members]
        shared = (len(members) > 1
                  and divergence.trace_shareable(cfgs)[0])
        for gi, cfg in zip(members, cfgs):
            t0 = time.perf_counter()
            if shared:
                results[gi] = divergence.replay_result(cfg)
                stats.replayed += 1
            else:
                results[gi] = run_simulation(cfg)
                stats.event_loops += 1
            sim_elapsed[gi] = time.perf_counter() - t0
    return results, sim_elapsed


def execute_device_grid(scenarios: Sequence[Scenario],
                        max_devices: Optional[int] = None
                        ) -> Tuple[List[dict], DeviceStats]:
    """Execute a whole cache-missed grid: fleet scenarios pass through
    their own rollup; every single-site trace group is padded into one
    batched tensor set and evaluated by a single device program, on the
    default backend's local devices (at most ``max_devices`` of them)."""
    import jax

    from repro.sweep.runner import (_execute_fleet_scenario,
                                    shared_result_metrics,
                                    single_site_metrics,
                                    single_site_record)

    groups = group_by_trace(scenarios)
    stats = DeviceStats(trace_groups=len(groups))
    records: List[Optional[dict]] = [None] * len(scenarios)

    single: List[List[int]] = []
    for g in groups:
        if isinstance(scenarios[g[0]].cfg, FleetConfig):
            # fleet rollups bake CI signals and PUE into per-site
            # co-sims — no stacked axis; identical to the other modes
            for i in g:
                records[i] = _execute_fleet_scenario(scenarios[i])
        else:
            single.append(g)
    if not single:
        return [r for r in records if r is not None], stats

    with PROFILER.span("device.acquire_traces"):
        results, sim_elapsed = _acquire_results(scenarios, single, stats)

    # ---- pad + ragged-stack into one (G, S) / (G, K) tensor set ----
    n_g = len(single)
    gp = _next_pow2(n_g)
    sp = _next_pow2(max(max(len(r.stages) for r in results), 1))
    kp = _next_pow2(max(max(len(g) for g in single), 1))
    comp = np.zeros((4, gp, sp))
    params = np.ones((gp, len(PARAMS_FIELDS)))
    powerp = np.zeros((gp, 5), np.float32)
    powerp[:, 2] = 0.5                   # padded groups: x = 0/0 guard
    powerp[:, 3] = 1.0
    ndev = np.ones(gp)
    phi = np.zeros(gp)
    pues = np.zeros((gp, kp))
    cis = np.zeros((gp, kp))
    for gi, (g, res) in enumerate(zip(single, results)):
        cfg = res.cfg
        tr = res.stages
        m = len(tr)
        comp[0, gi, :m] = tr.n_prefill_tokens
        comp[1, gi, :m] = tr.n_decode_tokens
        comp[2, gi, :m] = tr.score_flops
        comp[3, gi, :m] = tr.kv_rw_bytes
        em = cached_execution_model(cfg.model, cfg.device, cfg.tp,
                                    cfg.pp, cfg.execmodel)
        params[gi] = em.params_vector()
        dev = DEVICES[cfg.device]
        powerp[gi] = np.asarray(
            [dev.p_idle, dev.p_max_inst, dev.mfu_sat, dev.gamma,
             dev.p_max_inst - dev.p_idle], np.float32)
        ndev[gi] = float(cfg.n_devices)
        phi[gi] = dev.embodied_kg_per_hour
        for k, i in enumerate(g):
            pues[gi, k] = scenarios[i].pue
            cis[gi, k] = scenarios[i].grid_ci

    # ---- the single dispatch for the whole grid ----
    # enable_x64 is scoped: the program traces/executes in f64 without
    # flipping the process-global default (kernel/launcher tests in the
    # same process rely on f32 defaults). With >1 local accelerator the
    # padded group axis shards (D, G/D) across devices via pmap —
    # always exact: gp is a power of two, and so is D
    local = jax.local_devices()
    n_local = min(len(local), max_devices or len(local))
    d = 1
    while d * 2 <= min(n_local, gp):
        d *= 2
    args = (comp[0], comp[1], comp[2], comp[3],
            params, powerp, ndev, phi, pues, cis)
    shape_sig = (gp, sp, kp, d)
    dispatch_span = ("device.jit_compile_and_execute"
                     if shape_sig not in _SEEN_SHAPES
                     else "device.execute")
    with jax.enable_x64(True):
        with PROFILER.span(dispatch_span):
            if d > 1:
                sharded = tuple(
                    a.reshape((d, gp // d) + a.shape[1:]) for a in args)
                out = _pmap_program(d)(*sharded)
                e_sum, m_sum, dur, peak, op_g, emb_g = tuple(
                    np.asarray(o).reshape((gp,) + np.asarray(o).shape[2:])
                    for o in out)
            else:
                out = _program()(*args)
                e_sum, m_sum, dur, peak, op_g, emb_g = tuple(
                    np.asarray(o) for o in out)
    _SEEN_SHAPES.add(shape_sig)
    stats.devices = d
    stats.platform = local[0].platform
    stats.device_kind = local[0].device_kind
    stats.bucket = (gp, sp, kp)

    # ---- record assembly through the shared single-site path ----
    for gi, (g, res) in enumerate(zip(single, results)):
        scs = [scenarios[i] for i in g]
        cfg = res.cfg
        shared_m = shared_result_metrics(res)
        reps = reports_from_sums(
            float(e_sum[gi]), float(m_sum[gi]), float(dur[gi]),
            float(peak[gi]), n_devices=cfg.n_devices,
            pues=[sc.pue for sc in scs])
        emb = float(emb_g[gi])
        ops = [float(o) for o in op_g[gi, :len(g)]]
        carbons = reports_from_arrays(
            ops, [emb] * len(g), [o + emb for o in ops],
            [sc.grid_ci for sc in scs])
        for i, sc, rep, carbon in zip(g, scs, reps, carbons):
            rec_t0 = time.perf_counter() - sim_elapsed[gi]
            metrics = single_site_metrics(res, sc, rep, carbon=carbon,
                                          shared=shared_m)
            records[i] = single_site_record(
                sc, metrics, rec_t0, mode="device",
                trace_scenarios=len(scs))
    return [r for r in records if r is not None], stats


def records_max_rel_err(recs_a: Sequence[dict], recs_b: Sequence[dict]
                        ) -> float:
    """Worst relative metric divergence between two aligned record
    sets (aligned by cache key) — what the CI perf job and the
    equivalence tests bound by ``DEVICE_MODE_RTOL``."""
    by_key = {r["key"]: r for r in recs_b}
    worst = 0.0
    for a in recs_a:
        b = by_key[a["key"]]
        for col, va in a["metrics"].items():
            vb = b["metrics"][col]
            if va == vb:
                continue
            rel = abs(va - vb) / max(abs(va), abs(vb))
            worst = max(worst, rel)
    return worst
