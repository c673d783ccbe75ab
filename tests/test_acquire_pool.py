"""The device mode's host pool for trace acquisition.

A sweep's event-loop groups run in the module's persistent spawn pool
(``repro.sweep.device``) when the grid, the machine and the process
allow it. The pool changes where the loops run and nothing else:
records, keys, order and every ``DeviceStats`` field but the two pool
counters are bitwise those of the in-process path. Engagement is forced
here by lowering ``POOL_MIN_REQUESTS``; the perf smoke grid holds 4
event-loop groups of 16 requests and one replayed family of 8 hardware
points.
"""
import dataclasses
import multiprocessing
import os
import pickle
import signal
import subprocess
import sys
from concurrent.futures.process import BrokenProcessPool

import numpy as np
import pytest

from repro.sweep import SWEEPS, SweepRunner
from repro.sweep import device
from repro.sweep.device import DeviceStats, execute_device_grid
from repro.sweep.vectorized import group_by_trace

POOL_COUNTERS = ("pooled", "pool_workers")


@pytest.fixture(scope="module")
def perf_grid():
    return SWEEPS["perf"].build(True, n_requests=16)


def _loop_part(scenarios):
    """Plane A of the perf grid: one event-loop group per QPS point."""
    return [sc for sc in scenarios if "qps" in sc.params]


def _replay_part(scenarios):
    """Plane B: the hardware family that divergence replay serves."""
    return [sc for sc in scenarios if "device" in sc.params]


def _in_process(scenarios, monkeypatch):
    with monkeypatch.context() as m:
        m.setattr(device, "POOL_MIN_REQUESTS", 10 ** 12)
        return execute_device_grid(scenarios)


def test_pooled_records_bitwise_equal_in_process(perf_grid, monkeypatch):
    """Every column, key, tag, parameter set and record order, and every
    ``DeviceStats`` field but the pool counters, match the in-process
    acquisition on a grid of event loops and a replayed family."""
    _in_process(perf_grid, monkeypatch)     # its bucket compiled once
    ref, ref_stats = _in_process(perf_grid, monkeypatch)
    monkeypatch.setattr(device, "POOL_MIN_REQUESTS", 1)
    recs, stats = execute_device_grid(perf_grid)

    assert stats.event_loops >= 3 and stats.replayed >= 2
    assert stats.pooled == stats.event_loops
    assert 1 <= stats.pool_workers <= stats.pooled
    assert ref_stats.pooled == ref_stats.pool_workers == 0
    for f in dataclasses.fields(DeviceStats):
        if f.name not in POOL_COUNTERS:
            assert getattr(stats, f.name) == getattr(ref_stats, f.name), \
                f.name
    assert len(recs) == len(ref)
    for a, b in zip(recs, ref):
        assert (a["scenario"], a["key"], a["params"]) == \
            (b["scenario"], b["key"], b["params"])
        assert list(a["metrics"]) == list(b["metrics"])
        assert a["metrics"] == b["metrics"], a["scenario"]


def test_sweep_summary_reports_pool_counters(perf_grid, monkeypatch):
    """The device-mode summary line names the pooled loops and the
    processes that served them."""
    monkeypatch.setattr(device, "POOL_MIN_REQUESTS", 1)
    _, stats = SweepRunner(cache=None, mode="device").run(
        _loop_part(perf_grid))
    assert stats.pooled == stats.event_loops == 4
    assert stats.pool_workers >= 1
    assert (f"4 event loop(s), 4 pooled on {stats.pool_workers} "
            "process(es)") in stats.summary()


def _under_threshold(grid, monkeypatch):
    loops = _loop_part(grid)
    assert sum(loops[g[0]].cfg.workload.n_requests
               for g in group_by_trace(loops)) < device.POOL_MIN_REQUESTS
    return grid


def _one_loop_group(grid, monkeypatch):
    monkeypatch.setattr(device, "POOL_MIN_REQUESTS", 1)
    loops = _loop_part(grid)
    return [loops[i] for i in group_by_trace(loops)[0]] + _replay_part(grid)


def _replay_only(grid, monkeypatch):
    monkeypatch.setattr(device, "POOL_MIN_REQUESTS", 1)
    return _replay_part(grid)


def _one_core(grid, monkeypatch):
    monkeypatch.setattr(device, "POOL_MIN_REQUESTS", 1)
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: {0})
    return grid


def _daemon(grid, monkeypatch):
    monkeypatch.setattr(device, "POOL_MIN_REQUESTS", 1)
    monkeypatch.setattr(multiprocessing.current_process(), "daemon", True)
    return grid


@pytest.mark.parametrize("case,loops", [
    pytest.param(_under_threshold, 4, id="under_threshold"),
    pytest.param(_one_loop_group, 1, id="one_loop_group"),
    pytest.param(_replay_only, 0, id="replay_only"),
    pytest.param(_one_core, 4, id="one_core"),
    pytest.param(_daemon, 4, id="daemon")])
def test_pool_stays_off(case, loops, perf_grid, monkeypatch):
    """The rule: fewer requests than the threshold, one event-loop
    group, no event loop at all, one usable core, or a daemonic process
    each keep every loop in this process."""
    scenarios = case(perf_grid, monkeypatch)
    recs, stats = execute_device_grid(scenarios)
    assert stats.event_loops == loops
    assert stats.pooled == 0 and stats.pool_workers == 0
    assert len(recs) == len(scenarios)


def test_second_sweep_starts_no_process(perf_grid, monkeypatch):
    """The pool outlives the sweep: a second grid is served by the child
    processes the first one left running."""
    monkeypatch.setattr(device, "POOL_MIN_REQUESTS", 1)
    loops = _loop_part(perf_grid)
    _, first = execute_device_grid(loops)
    before = {p.pid for p in multiprocessing.active_children()}
    _, second = execute_device_grid(loops)
    after = {p.pid for p in multiprocessing.active_children()}
    assert first.pooled == second.pooled == 4
    assert before and after == before
    assert second.pool_workers <= len(before)


def test_lost_child_fails_one_sweep_and_the_next_gets_a_new_pool(
        perf_grid, monkeypatch):
    """A child that dies (killed, out of memory) fails the sweep that
    needed it with ``BrokenProcessPool``; the module then drops the
    broken pool, so the next sweep is served by new children."""
    monkeypatch.setattr(device, "POOL_MIN_REQUESTS", 1)
    loops = _loop_part(perf_grid)
    execute_device_grid(loops)
    victims = multiprocessing.active_children()
    os.kill(victims[0].pid, signal.SIGKILL)
    victims[0].join(timeout=60)
    with pytest.raises(BrokenProcessPool):
        execute_device_grid(loops)
    recs, stats = execute_device_grid(loops)
    assert stats.pooled == 4 and len(recs) == len(loops)
    assert victims[0].pid not in {
        p.pid for p in multiprocessing.active_children()}


@pytest.mark.parametrize("pooled", [True, False],
                         ids=["pooled", "in_process"])
def test_pool_wait_span_nests_in_acquisition(pooled, perf_grid, monkeypatch):
    """``device.acquire_pool`` times the wait for the pooled loops, once a
    sweep inside ``device.acquire_traces``; a sweep that pools nothing
    records no such span."""
    from repro.obs.spans import PROFILER

    monkeypatch.setattr(device, "POOL_MIN_REQUESTS",
                        1 if pooled else 10 ** 12)
    loops = _loop_part(perf_grid)
    execute_device_grid(loops)          # children started, bucket compiled
    PROFILER.enable(reset=True)
    try:
        execute_device_grid(loops)
        spans = PROFILER.spans()
    finally:
        PROFILER.disable()
        PROFILER.reset()
    waits = [s for s in spans if s[0] == "device.acquire_pool"]
    assert len(waits) == (1 if pooled else 0)
    for _, t0, dur, depth in waits:
        assert any(n == "device.acquire_traces" and d == depth - 1
                   and s <= t0 and t0 + dur <= s + sd
                   for n, s, sd, d in spans)


#: a pool child's whole life, in a process where importing JAX fails:
#: the initializer, the config's unpickling and the pool target
_NO_JAX_CHILD = """
import pickle, sys

class NoJax:
    def find_spec(self, name, path=None, target=None):
        if name.split(".")[0] in ("jax", "jaxlib"):
            raise ImportError("JAX imported by " + name)

sys.meta_path.insert(0, NoJax())
from repro.core.host import use_cpu_only
use_cpu_only()
cfg = pickle.loads(sys.stdin.buffer.read())
from repro.sim.simulator import run_simulation_in_child
res, elapsed, pid = run_simulation_in_child(cfg)
sys.stdout.buffer.write(pickle.dumps((res, elapsed, pid)))
"""


def test_pool_child_imports_no_jax(perf_grid):
    """A child imports the event loop and never JAX, whose import costs
    seconds on a chip host and would come before the first loop; its
    result is the in-process loop's, less the config."""
    from repro.sim import run_simulation

    cfg = _loop_part(perf_grid)[0].cfg
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(sys.path))
    done = subprocess.run([sys.executable, "-c", _NO_JAX_CHILD],
                          input=pickle.dumps(cfg), capture_output=True,
                          env=env, timeout=120)
    assert done.returncode == 0, done.stderr.decode()[-2000:]
    res, elapsed, pid = pickle.loads(done.stdout)
    ref = run_simulation(cfg)
    assert res.cfg is None and elapsed > 0 and pid != os.getpid()
    assert res.loop_iterations == ref.loop_iterations
    assert [r.t_done for r in res.requests] == \
        [r.t_done for r in ref.requests]
    for col in dataclasses.fields(ref.stages):
        assert np.array_equal(getattr(res.stages, col.name),
                              getattr(ref.stages, col.name)), col.name
