"""Sweep-engine perf trajectory: device vs vectorized vs event loop.

Times the ``perf`` smoke grid (plane A: 4 workloads x 16 PUE x 16
grid-CI; plane B: a device x TP x PP family over one isolated-arrival
stream) through all three runner modes with the cache disabled, checks
the equivalence contract — vectorized records bit-identical to the
event loop, device records within ``DEVICE_MODE_RTOL`` — and writes
the scenarios/sec baseline to ``BENCH_sweep.json`` at the repo root so
future PRs can compare against it. CI runs
``--smoke --check 5 --check-device 2`` and fails if vectorized drops
below 5x the event-loop throughput or device below 2x vectorized.

Vectorized and device are timed best-of-2 so the device number
reflects steady-state dispatch, not the one-time jit compile (the
compile cost is reported separately as ``device_first_call_s``).

The mode runs execute under the ``repro.obs`` wall-clock profiler, so
the bench JSON carries a ``phases`` breakdown (cache lookup, event
loops, stacked passes, device compile vs execute). The probe-
neutrality *cost* contract is measured too: one persistent probe per
trial side (matching how ``SweepRunner`` attaches a single probe for
a whole sweep), each scenario timed back to back under both sides
with alternating order so machine drift cancels pairwise, and the
overhead estimated as median(paired deltas) / median(baseline times)
over 3 trials — the paired-median estimator is robust to the
scheduler-noise spikes any single sample can take. The probe cost is
always measured on a stratified subset of the FULL-SIZE grid (even
under ``--smoke``): the pin is a statement about production sweeps,
and smoke scenarios are ~3-15x shorter than the grid's real
workloads, so their percentage is dominated by per-scenario fixed
costs (rollup, finalize, run reset) rather than the per-event audit
scaling the pin is meant to bound. Probe-off vs ``NULL_PROBE`` is
reported as ``obs_probe_overhead_pct`` and bounded by ``--check-obs``
(CI pins <= 2%); ``NULL_PROBE`` vs ``AuditProbe`` isolates the
streaming-invariant checks from the hook dispatch both sides share —
reported as ``audit_probe_overhead_pct`` and bounded by
``--check-audit`` (CI pins <= 3%).

The ``remote`` entry times the distributed backend on the FULL perf
grid (like the probe-cost protocol, the pin is a statement about
production sweeps), with each side measured at its own operational
steady state. The baseline is the single-process vectorized backend
in a fresh process per run (best of 2, timed inside the subprocess
around ``run()``) — exactly how ``python -m repro.sweep.cli``
executes a sweep, paying the per-process numpy/eager-jax warm-up on
every invocation. The remote side is a coordinator plus a resident
2-worker fleet (``repro.sweep.remote``): workers spawn and warm once,
then serve successive jobs (fresh result cache each; best of 5 job
times reported, since racing shard claims mean a few jobs pass before
every worker has warmed every trace-group shape) — exactly how a worker fleet amortizes process
start-up across the jobs of a campaign, and the remote analogue of
the best-of-2 steady-state convention the jit-dispatch numbers
already use. Both sides persist records into a fresh cache — apples
to apples, since writing records into the shared cache IS how the
remote backend returns results. ``--check-remote`` (CI pins >= 1.5x)
fails on speedup below the bound, non-bit-identical records, or any
expired lease on the happy path.

Usage: python -m benchmarks.perf_sweep [--smoke] [--check MIN_SPEEDUP]
                                       [--check-device MIN_SPEEDUP]
                                       [--check-obs MAX_OVERHEAD_PCT]
                                       [--check-audit MAX_OVERHEAD_PCT]
                                       [--check-remote MIN_SPEEDUP]
"""
from __future__ import annotations

import gc
import json
import statistics
import sys
import time
from pathlib import Path

import jax

# device_first_call_s must stay an honest per-process compile cost:
# a warm persistent compilation cache would report disk-replay time
# instead
jax.config.update("jax_enable_compilation_cache", False)

# the committed/CI baseline is the smoke grid (by design: ~1k scenarios
# in seconds); a full-scale run writes its own file so it never
# clobbers — nor is clobbered by — the smoke baseline
_ROOT = Path(__file__).resolve().parent.parent
BENCH_PATHS = {True: _ROOT / "BENCH_sweep.json",
               False: _ROOT / "BENCH_sweep_full.json"}


def _best_of(fn, reps: int):
    best, out = float("inf"), None
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        dt = time.perf_counter() - t0
        times.append(dt)
        best = min(best, dt)
    return best, times, out


_LOCAL_BASELINE_SCRIPT = """
import json, sys, time
from repro.sweep import ResultCache, SweepRunner, SWEEPS
scenarios = SWEEPS["perf"].build(False)
cache = ResultCache(sys.argv[1])
t0 = time.perf_counter()
records, stats = SweepRunner(cache=cache, mode="vectorized").run(scenarios)
print(json.dumps({"s": time.perf_counter() - t0, "n": len(records)}))
"""


def _measure_remote(scenarios) -> dict:
    """Coordinator + 2 resident workers vs the single-process
    vectorized backend, each measured at its own operational steady
    state: the baseline is a FRESH process per run (exactly how
    ``python -m repro.sweep.cli`` executes a sweep — every invocation
    pays the per-process numpy/eager-jax warm-up; timed inside the
    subprocess around ``run()``, imports excluded, best of 2 runs),
    while the remote side is a long-lived fleet — workers spawn, warm,
    register alive, then serve several jobs (fresh result cache each,
    best of 3) and the steady-state job time is reported, matching the
    bench's existing best-of-N convention for jit dispatch. Both sides
    persist records into a fresh cache (writing into the shared cache
    IS how the remote backend returns results). Records are compared
    key-by-key for bit-identity."""
    import os as _os
    import shutil
    import subprocess
    import tempfile

    from repro.sweep import ResultCache, SweepRunner
    from repro.sweep.remote import (RemoteOptions, spawn_worker,
                                    wait_for_workers)

    td = Path(tempfile.mkdtemp(prefix="bench_remote_"))
    try:
        import repro
        pkg_root = str(Path(repro.__file__).resolve().parent.parent)
        env = dict(_os.environ)
        env["PYTHONPATH"] = pkg_root + (
            _os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        env.setdefault("JAX_PLATFORMS", "cpu")
        local_s = float("inf")
        for rep in range(2):
            cache_dir = td / f"cache_local{rep}"
            out = subprocess.run(
                [sys.executable, "-c", _LOCAL_BASELINE_SCRIPT,
                 str(cache_dir)],
                env=env, capture_output=True, text=True, check=True)
            local_s = min(local_s,
                          json.loads(out.stdout.strip().splitlines()[-1])["s"])
        local_cache = ResultCache(cache_dir)
        local_recs = [local_cache.get(sc.key) for sc in scenarios]
        assert all(local_recs), "baseline cache is missing records"

        queue = td / "queue"
        procs = [spawn_worker(queue, f"bench-w{i}",
                              log_path=td / f"w{i}.log")
                 for i in range(2)]
        try:
            wait_for_workers(queue, 2, timeout_s=300)
            opts = RemoteOptions(queue_dir=queue, spawn_workers=0,
                                 lease_s=60.0, timeout_s=900.0)
            rep_times = []
            for rep in range(5):
                cache_remote = ResultCache(td / f"cache_remote{rep}")
                t0 = time.perf_counter()
                remote_recs, stats = SweepRunner(
                    cache=cache_remote, backend="remote",
                    remote=opts).run(scenarios)
                rep_times.append(round(time.perf_counter() - t0, 3))
            remote_s = min(rep_times)
        finally:
            (queue / "stop").touch()
            for p in procs:
                try:
                    p.wait(timeout=15)
                except Exception:
                    p.terminate()
                    p.wait(timeout=10)

        by_key = {r["key"]: r for r in local_recs}
        bit_identical = all(
            r["metrics"] == by_key[r["key"]]["metrics"]
            for r in remote_recs)
        n = len(scenarios)
        return {
            "workers": 2,
            "cpus": _os.cpu_count() or 1,
            "shards": stats.shards,
            "vectorized_s": round(local_s, 3),
            "remote_s": round(remote_s, 3),
            "remote_rep_s": rep_times,
            "speedup": round(local_s / remote_s, 2),
            "vectorized_scenarios_per_s": round(n / local_s, 1),
            "remote_scenarios_per_s": round(n / remote_s, 1),
            "bit_identical": bit_identical,
            "lease_expired": stats.lease_expired,
            "retried": stats.retried,
            "quarantined": stats.quarantined,
        }
    finally:
        shutil.rmtree(td, ignore_errors=True)


def measure(smoke: bool = False) -> dict:
    from repro.obs.probe import NULL_PROBE
    from repro.obs.spans import PROFILER
    from repro.sweep import SCHEMA_VERSION, SWEEPS, SweepRunner
    from repro.sweep.device import DEVICE_MODE_RTOL, records_max_rel_err

    scenarios = SWEEPS["perf"].build(smoke)

    # the timed mode runs double as the wall-clock phase breakdown
    # (span overhead is a handful of perf_counter pairs per scenario)
    PROFILER.enable(reset=True)
    try:
        t0 = time.perf_counter()
        ev_records, ev_stats = SweepRunner(cache=None,
                                           mode="event_loop").run(scenarios)
        event_loop_s = time.perf_counter() - t0

        vectorized_s, _, (ve_records, ve_stats) = _best_of(
            lambda: SweepRunner(cache=None, mode="vectorized").run(scenarios),
            reps=2)

        device_s, dev_times, (dv_records, dv_stats) = _best_of(
            lambda: SweepRunner(cache=None, mode="device").run(scenarios),
            reps=2)
    finally:
        PROFILER.disable()
    phases = {name: {"count": int(a["count"]),
                     "total_s": round(a["total_s"], 3)}
              for name, a in sorted(PROFILER.aggregate().items())}

    # probe-cost protocol: a probe's true per-scenario cost (tens of
    # microseconds) sits far below the machine noise of any whole-pass
    # timing, so each scenario executes under both trial sides back to
    # back (alternating order to cancel warm-cache bias) and the two
    # samples of a pair see near-identical machine state. One
    # persistent probe instance serves a whole trial side — matching
    # how SweepRunner attaches a single probe for an entire sweep
    # (execute_scenario marks each scenario via on_run_begin). The
    # cost set is a stratified subset of the full-size grid (see the
    # module docstring for why smoke scenarios misprice the probes).
    from repro.sweep.runner import execute_scenario

    cost_source = scenarios if not smoke else SWEEPS["perf"].build(False)
    stride = max(1, len(cost_source) // 32)
    cost_set = cost_source[::stride][:32]
    for sc in cost_set:                 # warm the jit/exec caches
        execute_scenario(sc, probe=None)

    def _paired_trial(base_probe, test_probe):
        gc.collect()
        base_ts, test_ts = [], []
        for k, sc in enumerate(cost_set):
            pair = ((base_probe, test_probe) if k % 2 == 0
                    else (test_probe, base_probe))
            for probe in pair:
                t0 = time.perf_counter()
                execute_scenario(sc, probe=probe)
                dt = time.perf_counter() - t0
                (base_ts if probe is base_probe else test_ts).append(dt)
        return base_ts, test_ts

    def _overhead_pct(trials):
        # median-of-pairs: each scenario pair contributes one delta,
        # and the median over all pairs (3 trials x grid) is immune to
        # the scheduler-noise spikes that dominate sum-of-side ratios;
        # normalizing by the median baseline scenario yields the pct
        base_all = [b for bt, _ in trials for b in bt]
        delta_all = [t - b for bt, tt in trials
                     for b, t in zip(bt, tt)]
        return (statistics.median(delta_all)
                / statistics.median(base_all) * 100.0)

    # obs-neutrality cost: NULL_PROBE (every hook dispatched, empty
    # bodies) vs probe-off
    obs_trials = [_paired_trial(None, NULL_PROBE) for _ in range(3)]
    obs_off_s = min(sum(bt) for bt, _ in obs_trials)
    obs_on_s = min(sum(tt) for _, tt in obs_trials)
    obs_overhead_pct = _overhead_pct(obs_trials)

    # audit cost: the streaming invariant checks vs the no-op probe
    # (the NULL_PROBE baseline isolates the check bodies, not the
    # hook dispatch both sides share); a fresh auditor per trial so
    # report state never accretes across trials
    from repro.obs.audit import AuditProbe

    audit_trials = [_paired_trial(NULL_PROBE, AuditProbe())
                    for _ in range(3)]
    audit_s = min(sum(tt) for _, tt in audit_trials)
    audit_overhead_pct = _overhead_pct(audit_trials)

    # distributed backend: always on the FULL grid (cost_source) — the
    # smoke grid's traces are too short for dispatch to dominate, and
    # the pin is about production sweeps
    remote = _measure_remote(cost_source)

    bit_identical = all(a["metrics"] == b["metrics"]
                        for a, b in zip(ev_records, ve_records))
    device_max_rel_err = records_max_rel_err(dv_records, ev_records)
    n = len(scenarios)
    return {
        "grid": "perf",
        "smoke": smoke,
        "schema": SCHEMA_VERSION,
        "n_scenarios": n,
        "n_trace_groups": ve_stats.trace_groups,
        "event_loop_s": round(event_loop_s, 3),
        "vectorized_s": round(vectorized_s, 3),
        "device_s": round(device_s, 3),
        "device_first_call_s": round(dev_times[0], 3),
        "device_event_loops": dv_stats.event_loops,
        "device_replayed": dv_stats.replayed,
        "event_loop_scenarios_per_s": round(n / event_loop_s, 1),
        "vectorized_scenarios_per_s": round(n / vectorized_s, 1),
        "device_scenarios_per_s": round(n / device_s, 1),
        "speedup": round(event_loop_s / vectorized_s, 2),
        "device_speedup": round(vectorized_s / device_s, 2),
        "bit_identical": bit_identical,
        "device_max_rel_err": device_max_rel_err,
        "device_rtol": DEVICE_MODE_RTOL,
        "probe_cost_scenarios": len(cost_set),
        "obs_probe_off_s": round(obs_off_s, 3),
        "obs_null_probe_s": round(obs_on_s, 3),
        "obs_probe_overhead_pct": round(obs_overhead_pct, 2),
        "audit_probe_s": round(audit_s, 3),
        "audit_probe_overhead_pct": round(audit_overhead_pct, 2),
        "remote": remote,
        "phases": phases,
    }


def run(smoke: bool = False):
    """``benchmarks.run`` entry: (rows, derived, us_per_call)."""
    t0 = time.time()
    result = measure(smoke=smoke)
    BENCH_PATHS[smoke].write_text(json.dumps(result, indent=1) + "\n")
    derived = (f"speedup={result['speedup']}x"
               f"(target>=5);bit_identical={result['bit_identical']};"
               f"device_speedup={result['device_speedup']}x(target>=2);"
               f"device_max_rel_err={result['device_max_rel_err']:.2e};"
               f"{result['n_scenarios']}scen/"
               f"{result['n_trace_groups']}traces;"
               f"vec={result['vectorized_scenarios_per_s']}scen_per_s;"
               f"obs_overhead={result['obs_probe_overhead_pct']}%"
               f"(target<=2);"
               f"audit_overhead={result['audit_probe_overhead_pct']}%"
               f"(target<=3);"
               f"remote_speedup={result['remote']['speedup']}x"
               f"(target>=1.5,2workers,"
               f"bit_identical={result['remote']['bit_identical']})")
    return [result], derived, (time.time() - t0) * 1e6


def main() -> int:
    args = sys.argv[1:]
    smoke = "--smoke" in args
    check = None
    if "--check" in args:
        i = args.index("--check")
        check = float(args[i + 1]) if i + 1 < len(args) else 5.0
    check_device = None
    if "--check-device" in args:
        i = args.index("--check-device")
        check_device = float(args[i + 1]) if i + 1 < len(args) else 2.0
    check_obs = None
    if "--check-obs" in args:
        i = args.index("--check-obs")
        check_obs = float(args[i + 1]) if i + 1 < len(args) else 2.0
    check_audit = None
    if "--check-audit" in args:
        i = args.index("--check-audit")
        check_audit = float(args[i + 1]) if i + 1 < len(args) else 3.0
    check_remote = None
    if "--check-remote" in args:
        i = args.index("--check-remote")
        check_remote = float(args[i + 1]) if i + 1 < len(args) else 1.5
    rows, derived, _ = run(smoke=smoke)
    result = rows[0]
    print(json.dumps(result, indent=1))
    print(f"wrote {BENCH_PATHS[smoke]}")
    if not result["bit_identical"]:
        print("FAIL: vectorized records diverge from event-loop records",
              file=sys.stderr)
        return 1
    if result["device_max_rel_err"] > result["device_rtol"]:
        print(f"FAIL: device records diverge from event-loop records by "
              f"{result['device_max_rel_err']:.3e} > rtol "
              f"{result['device_rtol']:.1e}", file=sys.stderr)
        return 1
    if check is not None and result["speedup"] < check:
        print(f"FAIL: speedup {result['speedup']}x < required {check}x",
              file=sys.stderr)
        return 1
    if check_device is not None and result["device_speedup"] < check_device:
        print(f"FAIL: device speedup {result['device_speedup']}x < "
              f"required {check_device}x", file=sys.stderr)
        return 1
    if check_obs is not None and \
            result["obs_probe_overhead_pct"] > check_obs:
        print(f"FAIL: null-probe overhead "
              f"{result['obs_probe_overhead_pct']}% > allowed "
              f"{check_obs}%", file=sys.stderr)
        return 1
    if check_audit is not None and \
            result["audit_probe_overhead_pct"] > check_audit:
        print(f"FAIL: audit-probe overhead "
              f"{result['audit_probe_overhead_pct']}% > allowed "
              f"{check_audit}%", file=sys.stderr)
        return 1
    if check_remote is not None:
        rem = result["remote"]
        if not rem["bit_identical"]:
            print("FAIL: remote records diverge from single-process "
                  "vectorized records", file=sys.stderr)
            return 1
        if rem["lease_expired"]:
            print(f"FAIL: {rem['lease_expired']} lease(s) expired on "
                  "the happy path (workers wedged or heartbeats lost)",
                  file=sys.stderr)
            return 1
        if rem["speedup"] < check_remote:
            print(f"FAIL: remote speedup {rem['speedup']}x < required "
                  f"{check_remote}x", file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
