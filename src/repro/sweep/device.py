"""Device-batched whole-grid evaluation (sweep ``--mode device``).

One ``jax.jit`` + ``vmap`` program evaluates EVERY trace group's
post-simulation passes at once: the groups' ``StageTrace`` composition
columns are zero-padded and ragged-stacked into one ``(G, S)`` tensor
set, and the batched roofline (the same ``_roofline`` kernel
``stage_cost_batch`` runs), Eq. 1 (``core.power.eq1_power``, the
expression the host's ``power`` evaluates), the Eq. 2-3 reductions and
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

The event loops of a sweep's groups do not depend on each other, so
they run side by side in a host process pool that the module keeps for
the life of the process (``runner.host_pool``'s spawn context and CPU-only
initializer, one process short of the usable cores, started on the
first sweep that needs it, shut down at exit). It engages only where
it can pay for its round trip: at least two event-loop groups holding
``POOL_MIN_REQUESTS`` requests together, at least two usable cores,
and a process that may have children (not a daemon); otherwise the
loops run in this process as before. Replay and fleet scenarios always
stay here. A child imports the event loop and no JAX (``core.host``),
and returns the same ``SimResult`` the loop gives here, less the config
the parent already holds, so records are bitwise those of the
in-process path (``tests/test_acquire_pool.py``).

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

import atexit
import dataclasses
import multiprocessing
import os
import time
from concurrent.futures import ProcessPoolExecutor
from concurrent.futures.process import BrokenProcessPool
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.carbon import reports_from_arrays
from repro.core.energy import reports_from_sums
from repro.core.power import DEVICES, eq1_power
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
    loop_iterations: int = 0  # iterations of those event loops
    ff_iterations: int = 0   # of which advanced in decode-run steps
    devices: int = 1         # accelerators the dispatch sharded over
    platform: str = ""       # jax platform the program ran on ("tpu")
    device_kind: str = ""    # e.g. "TPU v5 lite"; "cpu" on the host
    bucket: Tuple[int, int, int] = (0, 0, 0)   # padded (G, S, K)
    compiles: int = 0        # executables built or loaded in the dispatch
    pooled: int = 0          # event loops run in host pool children
    pool_workers: int = 0    # pool processes that served them


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

    # Eq. 1 in float32, the expression core.power.power() evaluates;
    # the (p_max - p_idle) delta is precomputed host-side in f64
    # (powerp[4]) exactly as the eager path subtracts python floats
    pw = eq1_power(mfu, powerp[0], powerp[4], powerp[2], powerp[3])
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

#: JAX's monitoring event around each executable's backend compile or
#: persistent-cache load (``compile_or_get_cached``): once per
#: executable either way. A cache load also records
#: ``/jax/compilation_cache/cache_hits``, so counting both events would
#: count a load twice
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# executables this process has built or loaded, counted by one
# jax.monitoring listener that the first dispatch registers
_COMPILES = 0
_LISTENING = False


def _on_duration(name, secs, **kw):
    global _COMPILES
    if name == COMPILE_EVENT:
        _COMPILES += 1


def _listen_for_compiles() -> None:
    global _LISTENING
    if _LISTENING:
        return
    _LISTENING = True
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


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


#: event-loop requests that a sweep's groups must hold together before
#: their loops go to the host pool. A warm pool's round trip (submit,
#: pickle, wake a child, pickle the ``SimResult`` back) is ~1.3 ms: two
#: groups of 8 requests took 2.39 ms pooled against 2.12 ms in process
#: (x86 host, 8 cores). The loop costs ~0.18 ms a request on the chip's
#: host (9 x 512 requests in 0.83 s), so two groups break even near
#: 2 x 1.3 / 0.18 ~ 15 requests. The threshold sits well above that,
#: because a process pays once for starting the children (~0.7 s on the
#: chip's host): at 512 requests a sweep saves ~45 ms, so only grids
#: that save tens of milliseconds a sweep start processes, and the small
#: grids of tests and self-checks keep the in-process path.
POOL_MIN_REQUESTS = 512

_POOL: Optional[ProcessPoolExecutor] = None


def _usable_cores() -> int:
    return len(os.sched_getaffinity(0))


def _pool_engages(loop_cfgs: Sequence) -> bool:
    """Whether the event loops of ``loop_cfgs`` go to the host pool: the
    rule reads only the grid, the machine and the process."""
    return (len(loop_cfgs) >= 2 and _usable_cores() >= 2
            and not multiprocessing.current_process().daemon
            and sum(c.workload.n_requests for c in loop_cfgs)
            >= POOL_MIN_REQUESTS)


def _loop_pool() -> ProcessPoolExecutor:
    """The module's host pool, started on first use (its children spawn
    as tasks need them) and shut down at exit."""
    global _POOL
    if _POOL is None:
        from repro.sweep.runner import host_pool
        _POOL = host_pool(_usable_cores() - 1)
        atexit.register(_POOL.shutdown)
    return _POOL


def _drop_pool() -> None:
    global _POOL
    pool, _POOL = _POOL, None
    if pool is not None:
        pool.shutdown(wait=False, cancel_futures=True)


def _acquire_results(scenarios: Sequence[Scenario],
                     single: List[List[int]], stats: DeviceStats
                     ) -> Tuple[list, List[float]]:
    """One SimResult per single-site trace group: divergence-shared
    families replay one composition schedule per config; everything
    else runs the event loop, in the host pool where it engages
    (largest groups first), else here."""
    from repro.sim import run_simulation
    from repro.sim.simulator import run_simulation_in_child

    cfgs = [scenarios[g[0]].cfg for g in single]
    fams: Dict[str, List[int]] = {}
    for gi, cfg in enumerate(cfgs):
        fams.setdefault(divergence.family_blob(cfg), []).append(gi)

    replays: List[int] = []
    loops: List[int] = []
    for members in fams.values():
        shared = (len(members) > 1 and divergence.trace_shareable(
            [cfgs[gi] for gi in members])[0])
        (replays if shared else loops).extend(members)

    results: list = [None] * len(single)
    sim_elapsed = [0.0] * len(single)

    def here(gi, acquire):
        t0 = time.perf_counter()
        results[gi] = acquire(cfgs[gi])
        sim_elapsed[gi] = time.perf_counter() - t0

    futures = {}
    try:
        if _pool_engages([cfgs[gi] for gi in loops]):
            pool = _loop_pool()
            for gi in sorted(loops, key=lambda gi: (
                    -cfgs[gi].workload.n_requests, gi)):
                futures[gi] = pool.submit(run_simulation_in_child, cfgs[gi])
        # the parent's own share runs while the children work
        for gi in replays:
            here(gi, divergence.replay_result)
        for gi in loops:
            if gi not in futures:
                here(gi, run_simulation)
        if futures:
            pids = set()
            with PROFILER.span("device.acquire_pool"):
                for gi, fut in futures.items():
                    results[gi], sim_elapsed[gi], pid = fut.result()
                    results[gi].cfg = cfgs[gi]
                    pids.add(pid)
            stats.pooled = len(futures)
            stats.pool_workers = len(pids)
    except BrokenProcessPool:
        _drop_pool()            # a child died: the next sweep starts anew
        raise
    stats.replayed = len(replays)
    stats.event_loops = len(loops)
    for gi in loops:
        stats.loop_iterations += results[gi].loop_iterations
        stats.ff_iterations += results[gi].ff_iterations
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

    with PROFILER.span("device.group"):
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
    with PROFILER.span("device.pad_stack"):
        n_g = len(single)
        gp = _next_pow2(n_g)
        sp = _next_pow2(max(max(len(r.stages) for r in results), 1))
        kp = _next_pow2(max(max(len(g) for g in single), 1))
        comp = np.zeros((4, gp, sp))
        params = np.ones((gp, len(PARAMS_FIELDS)))
        powerp = np.zeros((gp, 5), np.float32)
        powerp[:, 2] = 0.5               # padded groups: x = 0/0 guard
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
    # same process rely on f32 defaults), and the inputs are placed on
    # the device inside it, so they stay f64. With >1 local accelerator
    # the padded group axis shards (D, G/D) across devices via pmap —
    # always exact: gp is a power of two, and so is D. The blocks wait
    # for the device only when profiling, so that each span times what
    # its name says, and the unprofiled path gains no sync
    local = jax.local_devices()
    n_local = min(len(local), max_devices or len(local))
    d = 1
    while d * 2 <= min(n_local, gp):
        d *= 2
    args = (comp[0], comp[1], comp[2], comp[3],
            params, powerp, ndev, phi, pues, cis)
    _listen_for_compiles()
    compiles0 = _COMPILES
    with jax.enable_x64(True), PROFILER.span("device.execute"):
        with PROFILER.span("device.h2d"):
            if d > 1:
                mesh = jax.sharding.Mesh(np.asarray(local[:d]), ("d",))
                placed = jax.device_put(
                    tuple(a.reshape((d, gp // d) + a.shape[1:])
                          for a in args),
                    jax.sharding.NamedSharding(
                        mesh, jax.sharding.PartitionSpec("d")))
            else:
                placed = jax.device_put(args, local[0])
            if PROFILER.enabled:
                jax.block_until_ready(placed)
        with PROFILER.span("device.run"):
            out = (_pmap_program(d) if d > 1 else _program())(*placed)
            if PROFILER.enabled:
                jax.block_until_ready(out)
        with PROFILER.span("device.d2h"):
            host = [np.asarray(o) for o in out]
    if d > 1:
        host = [h.reshape((gp,) + h.shape[2:]) for h in host]
    e_sum, m_sum, dur, peak, op_g, emb_g = host
    stats.compiles = _COMPILES - compiles0
    stats.devices = d
    stats.platform = local[0].platform
    stats.device_kind = local[0].device_kind
    stats.bucket = (gp, sp, kp)

    # ---- record assembly through the shared single-site path ----
    with PROFILER.span("device.assemble_records"):
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
