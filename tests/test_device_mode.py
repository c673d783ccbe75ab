"""Device-mode equivalence + trace-divergence soundness pins.

The device-batched runner (``--mode device``) evaluates the whole grid
as one jit+vmap program, so its contract is looser than vectorized
mode's bitwise guarantee: device-computed energy/power/carbon columns
must agree with the event loop within ``DEVICE_MODE_RTOL`` while every
host-side column (MFU, timing, throughput, latency percentiles, stage
counts) stays bit-identical. This file pins that contract on every
benchmark grid, exercises the padding/masking machinery on ragged and
empty groups, and proves the trace-divergence analysis *sound*:
whenever ``trace_shareable`` accepts a config family, the
independently event-loop-generated traces really do share one batch
composition and ``replay_result`` reproduces the full ``SimResult``
bit-for-bit.
"""
import dataclasses
import hashlib
import json
from pathlib import Path

import numpy as np
import pytest
from _hypothesis_support import given, settings, st

from repro.configs.paper_models import PAPER_MODELS
from repro.core.power import DEVICES
from repro.sim import (PAPER_DEFAULT, SchedulerConfig, SimConfig,
                       WorkloadConfig, run_simulation)
from repro.sim.execmodel import (PARAMS_FIELDS, ExecutionModel, StageBatch,
                                 _Params, _roofline)
from repro.sim.trace import StageTrace
from repro.sweep import SCHEMA_VERSION, SWEEPS, SweepRunner
from repro.sweep import divergence
from repro.sweep.device import (DEVICE_COLS, DEVICE_MODE_RTOL,
                                execute_device_grid, records_max_rel_err)
from repro.sweep.grid import Scenario
from repro.sweep.runner import execute_scenario


def _assert_device_contract(ev, dv):
    assert len(ev) == len(dv)
    for a, b in zip(ev, dv):
        assert a["scenario"] == b["scenario"]
        assert a["params"] == b["params"]
        assert a["key"] == b["key"]
        for col, va in a["metrics"].items():
            vb = b["metrics"][col]
            if col in DEVICE_COLS:
                assert vb == pytest.approx(va, rel=DEVICE_MODE_RTOL), \
                    (col, a["scenario"])
            else:
                assert vb == va, (col, a["scenario"])
    assert records_max_rel_err(dv, ev) <= DEVICE_MODE_RTOL


# ---------------------------------------------------------------------------
# runner-mode equivalence on the pinned benchmark grids
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sweep", ["fig1", "fig3", "exp5"])
def test_device_matches_event_loop_single_site(sweep):
    scenarios = SWEEPS[sweep].build(True, n_requests=16)
    ev, _ = SweepRunner(cache=None, mode="event_loop").run(scenarios)
    dv, _ = SweepRunner(cache=None, mode="device").run(scenarios)
    _assert_device_contract(ev, dv)


def test_device_matches_event_loop_perf_grid():
    # the full perf smoke grid: plane A (workload x pue x grid_ci) plus
    # plane B (device x tp x pp hardware family over one isolated
    # stream) — the grid the CI perf gate times and pins
    scenarios = SWEEPS["perf"].build(True, n_requests=16)
    ev, _ = SweepRunner(cache=None, mode="event_loop").run(scenarios)
    dv, stats = SweepRunner(cache=None, mode="device").run(scenarios)
    _assert_device_contract(ev, dv)
    # plane B's 8 hardware configs form one shareable family (uniform
    # isolated arrivals), so only plane A's 4 workloads run the loop
    assert stats.trace_groups == 12
    assert stats.replayed == 8
    assert stats.event_loops == 4


@pytest.mark.parametrize("sweep", ["fleet", "shift"])
def test_device_fleet_passthrough_bit_identical(sweep):
    # FleetConfig scenarios bypass the device program entirely — the
    # fleet rollup runs as-is, so records stay bitwise
    scenarios = SWEEPS[sweep].build(True, n_requests=10)
    ev, _ = SweepRunner(cache=None, mode="event_loop").run(scenarios)
    dv, _ = SweepRunner(cache=None, mode="device").run(scenarios)
    for a, b in zip(ev, dv):
        assert a["key"] == b["key"]
        assert a["metrics"] == b["metrics"], a["scenario"]


# ---------------------------------------------------------------------------
# padding/masking: ragged, empty, and single-stage groups
# ---------------------------------------------------------------------------

def _device_vs_event_loop(scenarios):
    dv, _ = execute_device_grid(scenarios)
    ev = [execute_scenario(sc) for sc in scenarios]
    _assert_device_contract(ev, dv)


def test_padding_empty_and_single_stage_groups():
    # deterministic coverage of the mask edge cases independent of
    # hypothesis availability: an empty trace, a single-stage trace
    # (one request, one prefill + one decode), and a ragged large group
    wls = [WorkloadConfig(n_requests=0, qps=1.0, seed=0),
           WorkloadConfig(n_requests=1, qps=1.0, min_len=8, max_len=8,
                          pd_ratio=8.0, seed=1),
           WorkloadConfig(n_requests=12, qps=6.0, min_len=32,
                          max_len=128, seed=2)]
    scenarios = []
    for j, wl in enumerate(wls):
        cfg = dataclasses.replace(PAPER_DEFAULT, workload=wl)
        for i in range(j + 1):          # ragged scenario fan-out 1/2/3
            scenarios.append(Scenario(cfg=cfg, params={"g": j, "i": i},
                                      pue=1.0 + 0.15 * i,
                                      grid_ci=100.0 * (i + 1)))
    _device_vs_event_loop(scenarios)


@given(st.lists(st.tuples(st.integers(0, 6),
                          st.sampled_from([0.5, 2.0, 8.0]),
                          st.integers(1, 3)),
                min_size=1, max_size=4),
       st.integers(0, 2**16))
@settings(max_examples=10, deadline=None)
def test_padding_and_masking_property(groups, seed):
    # arbitrary ragged group sizes (incl. empty workloads) and scenario
    # fan-outs: padded lanes must never leak into real outputs
    scenarios = []
    for j, (n, qps, k) in enumerate(groups):
        wl = WorkloadConfig(n_requests=n, qps=qps, min_len=8,
                            max_len=48, seed=seed + j)
        cfg = dataclasses.replace(PAPER_DEFAULT, workload=wl)
        for i in range(k):
            scenarios.append(Scenario(cfg=cfg, params={"g": j, "i": i},
                                      pue=1.0 + 0.1 * i,
                                      grid_ci=50.0 * (i + 1)))
    _device_vs_event_loop(scenarios)


# ---------------------------------------------------------------------------
# trace-divergence analysis: soundness of the sharing predicate
# ---------------------------------------------------------------------------

_HW = [("a100", 1, 1), ("a100", 2, 1), ("a100", 1, 2), ("a100", 2, 2),
       ("h100", 1, 1), ("h100", 2, 1), ("h100", 1, 2), ("h100", 2, 2)]

_COMPOSITION = ("n_prefill_tokens", "n_decode_tokens", "score_flops",
                "kv_rw_bytes", "batch_size")


def _assert_family_sound(cfgs):
    """trace_shareable accepted this family: prove it was right."""
    results = [run_simulation(c) for c in cfgs]
    base = results[0].stages.iteration_rows(cfgs[0].pp)
    for c, r in zip(cfgs, results):
        it = r.stages.iteration_rows(c.pp)
        for col in _COMPOSITION:
            assert np.array_equal(getattr(it, col),
                                  getattr(base, col)), (col, c.device,
                                                        c.tp, c.pp)
        # and the replay reconstructs the full result bit-for-bit
        rp = divergence.replay_result(c)
        for f in dataclasses.fields(StageTrace):
            assert np.array_equal(getattr(rp.stages, f.name),
                                  getattr(r.stages, f.name)), \
                (f.name, c.device, c.tp, c.pp)
        assert len(rp.requests) == len(r.requests)
        for a, b in zip(rp.requests, r.requests):
            assert (a.t_first_token, a.t_done, a.decoded, a.prefilled) \
                == (b.t_first_token, b.t_done, b.decoded, b.prefilled)


def test_divergence_sharing_sound_on_perf_family():
    # the exact family the perf grid shares: every plane-B hardware
    # point replays one uniform isolated stream bit-identically
    wl = WorkloadConfig(n_requests=8, qps=0.5, arrival="uniform",
                        min_len=64, max_len=256, seed=0)
    cfgs = [dataclasses.replace(PAPER_DEFAULT, workload=wl, device=d,
                                tp=tp, pp=pp) for d, tp, pp in _HW]
    ok, reason = divergence.trace_shareable(cfgs)
    assert ok, reason
    _assert_family_sound(cfgs)


@given(st.integers(1, 5), st.floats(0.05, 0.4),
       st.integers(0, 2**16),
       st.lists(st.sampled_from(_HW), min_size=2, max_size=4,
                unique=True))
@settings(max_examples=8, deadline=None)
def test_divergence_soundness_property(n, qps, seed, hw):
    # hypothesis-generated arrival streams: whenever the conservative
    # predicate declares the family shareable, the independently
    # event-loop-generated traces must be bit-equal in composition and
    # the replay bit-equal in full (a reject is always allowed — the
    # predicate is conservative, not complete)
    wl = WorkloadConfig(n_requests=n, qps=qps, arrival="uniform",
                        min_len=16, max_len=64, seed=seed)
    cfgs = [dataclasses.replace(PAPER_DEFAULT, workload=wl, device=d,
                                tp=tp, pp=pp) for d, tp, pp in hw]
    ok, _ = divergence.trace_shareable(cfgs)
    if ok:
        _assert_family_sound(cfgs)


def test_divergence_predicate_rejects_unsafe_families():
    base = dataclasses.replace(
        PAPER_DEFAULT,
        workload=WorkloadConfig(n_requests=64, qps=50.0, seed=0))
    # tight poisson arrivals: gaps under the service bound
    cfgs = [dataclasses.replace(base, device=d, tp=tp, pp=pp)
            for d, tp, pp in (("a100", 1, 1), ("h100", 2, 1))]
    ok, reason = divergence.trace_shareable(cfgs)
    assert not ok
    assert "gap" in reason
    # chunked prefill: schedules depend on timing even when isolated
    wl = WorkloadConfig(n_requests=4, qps=0.1, arrival="uniform",
                        min_len=64, max_len=128, seed=0)
    chunked = dataclasses.replace(
        PAPER_DEFAULT, workload=wl,
        scheduler=SchedulerConfig(chunk_prefill=256))
    ok, reason = divergence.trace_shareable([chunked, chunked])
    assert not ok
    assert "chunked" in reason
    # non-hardware divergence: different batch caps are not a family
    a = dataclasses.replace(PAPER_DEFAULT, workload=wl)
    b = dataclasses.replace(a, device="h100",
                            scheduler=SchedulerConfig(batch_cap=4))
    ok, reason = divergence.trace_shareable([a, b])
    assert not ok
    assert "differ beyond" in reason


# ---------------------------------------------------------------------------
# cache-key stability: the digest the device mode (and cache) keys on
# ---------------------------------------------------------------------------

def _reference_digest(cfg, extra) -> str:
    payload = {"cfg": dataclasses.asdict(cfg), "extra": extra,
               "schema": SCHEMA_VERSION}
    blob = json.dumps(payload, sort_keys=True, default=str,
                      separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()


def test_scenario_digests_match_reference_construction():
    sc = SWEEPS["fig1"].build(True)[0]
    assert sc.key == _reference_digest(
        sc.cfg, {"pue": sc.pue, "grid_ci": sc.grid_ci, "post": sc.post,
                 "post_params": sc.post_params})
    assert sc.trace_key == _reference_digest(sc.cfg, {})
    # trace_key deliberately ignores the fan-out knobs
    other = Scenario(cfg=sc.cfg, params=sc.params, pue=sc.pue + 0.2,
                     grid_ci=sc.grid_ci + 100.0)
    assert other.trace_key == sc.trace_key
    assert other.key != sc.key


# ---------------------------------------------------------------------------
# the device kernel's roofline form against numpy, on every paper model
# ---------------------------------------------------------------------------

def _kernel_roofline(pre, dec, score, kv, params):
    """``_roofline`` as ``_group_kernel`` runs it: jax.numpy, with the
    parameters a traced vector rebuilt into ``_Params``."""
    import jax.numpy as jnp
    p = _Params(*(params[i] for i in range(len(PARAMS_FIELDS))))
    return _roofline(pre, dec, score, kv, p, jnp)


@pytest.mark.parametrize("name", sorted(PAPER_MODELS))
def test_jax_backend_parity_all_paper_models(name):
    # the same f64 arithmetic on XLA:CPU and numpy: at most a few ulps
    # apart (XLA may fuse ops), so 1e-12 still catches any change of
    # expression
    import jax

    kernel = jax.jit(_kernel_roofline)
    for dev, tp, pp in (("a100", 1, 1), ("h100", 2, 2)):
        em = ExecutionModel(PAPER_MODELS[name], DEVICES[dev],
                            tp=tp, pp=pp)
        rows = [em.stage_cost_scalar(*comp)[1:] for comp in (
            ([512], [128, 4096], None),
            ([], [64] * 32, None),
            ([128, 1], [], [0, 1024]),
            ([1], [1], None),
            ([], [], None))]                     # an empty (padded) stage
        batch = StageBatch(*(np.array(col) for col in zip(*rows)))
        ref = em.stage_cost_batch(batch)
        with jax.enable_x64(True):
            jx = kernel(batch.prefill_tokens, batch.decode_count,
                        batch.score_flops, batch.kv_rw_bytes,
                        em.params_vector())
        for f, v in zip(("t_total", "t_compute", "t_memory",
                         "t_collective", "flops_mlp", "flops_attn", "mfu"),
                        jx):
            np.testing.assert_allclose(
                np.asarray(v), np.asarray(getattr(ref, f)),
                rtol=1e-12, err_msg=f"{name} {dev} {f}")


# --------------------------------------------------------------------------
# profiled dispatch, compile counts, the program's name
# --------------------------------------------------------------------------

def test_records_bitwise_equal_with_profiler_on_and_off():
    """The profiler adds spans and device syncs, never other numbers:
    on the perf grid (event loops and replay) the records are equal."""
    from repro.obs.spans import PROFILER

    scenarios = SWEEPS["perf"].build(True, n_requests=16)
    off, _ = execute_device_grid(scenarios)
    PROFILER.enable(reset=True)
    try:
        on, _ = execute_device_grid(scenarios)
    finally:
        PROFILER.disable()
        PROFILER.reset()
    assert [r["key"] for r in on] == [r["key"] for r in off]
    assert [r["metrics"] for r in on] == [r["metrics"] for r in off]


def test_compiles_counted_at_a_new_bucket_and_not_on_a_repeat(monkeypatch):
    """``DeviceStats.compiles`` counts the executables JAX builds in the
    dispatch: the one grid program when it was never compiled
    (persistent cache off), none when the same bucket runs again."""
    import jax

    from repro.sweep import device as dev

    scenarios = SWEEPS["fig4"].build(True, n_requests=8)
    monkeypatch.setattr(dev, "_PROGRAM", None)
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        _, first = execute_device_grid(scenarios)
        _, again = execute_device_grid(scenarios)
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
    assert first.compiles == 1
    assert again.compiles == 0
    assert again.bucket == first.bucket
    _, stats = SweepRunner(cache=None, mode="device").run(scenarios)
    assert stats.compiles == 0
    assert "0 compile(s)" in stats.summary()


def test_grid_program_module_is_named_for_the_kernel():
    """The device trace finds the grid program by its module name,
    ``jit__group_kernel``: renaming ``_group_kernel`` fails here rather
    than silencing the benchmark's roofline reading."""
    import jax

    from repro.sim.execmodel import PARAMS_FIELDS
    from repro.sweep import device as dev

    g, s, k = 2, 8, 4
    shapes = [(g, s)] * 4 + [(g, len(PARAMS_FIELDS)), (g, 5), (g,), (g,),
                             (g, k), (g, k)]
    with jax.enable_x64(True):
        args = [jax.ShapeDtypeStruct(
            sh, np.float32 if i == 5 else np.float64)
            for i, sh in enumerate(shapes)]
        text = dev._program().lower(*args).as_text()
    assert "module @jit__group_kernel" in text


# --------------------------------------------------------------------------
# multi-device sharded dispatch + persistent compilation cache
# --------------------------------------------------------------------------

_SHARDED_DISPATCH_SCRIPT = """
import json, os
import jax
jax.config.update("jax_enable_compilation_cache", False)
assert jax.local_device_count() == 2, jax.local_device_count()
from repro.sweep import SWEEPS, SweepRunner
from repro.sweep.device import (DEVICE_MODE_RTOL, execute_device_grid,
                                records_max_rel_err)
from repro.obs.spans import PROFILER
scenarios = SWEEPS["fig4"].build(True)
recs, dstats = execute_device_grid(scenarios)
PROFILER.enable()
profiled, _ = execute_device_grid(scenarios)
PROFILER.disable()
ref, _ = SweepRunner(cache=None, mode="event_loop").run(scenarios)
print(json.dumps({"devices": dstats.devices,
                  "err": records_max_rel_err(recs, ref),
                  "rtol": DEVICE_MODE_RTOL,
                  "profiled_equal": [r["metrics"] for r in profiled]
                  == [r["metrics"] for r in recs]}))
"""


@pytest.mark.slow
def test_sharded_dispatch_across_two_host_devices():
    """With 2 local devices the padded group axis shards (D, G/D) via
    pmap; records stay within the same DEVICE_MODE_RTOL contract as
    the single-device program. XLA device-count forcing must precede
    jax init, hence the subprocess."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=2"})
    out = subprocess.run([sys.executable, "-c", _SHARDED_DISPATCH_SCRIPT],
                         env=env, capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["devices"] == 2
    assert res["err"] <= res["rtol"]
    assert res["profiled_equal"]


_ENV_CACHE_SCRIPT = """
import os, sys
import jax
from repro.sweep import SWEEPS
from repro.sweep.device import execute_device_grid
_, stats = execute_device_grid(SWEEPS["fig4"].build(True))
root = os.environ["JAX_COMPILATION_CACHE_DIR"]
assert jax.config.jax_compilation_cache_dir == root, \
    jax.config.jax_compilation_cache_dir
n = sum(len(fs) for _, _, fs in os.walk(root))
print(stats.compiles)
sys.exit(0 if n > 0 else 3)
"""


def test_compile_cache_follows_jax_env(tmp_path):
    """With $JAX_COMPILATION_CACHE_DIR set, the program sets no cache
    directory of its own: JAX's, as the variable set it, receives the
    device program's compile."""
    import os
    import subprocess
    import sys

    import repro

    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache"),
                "PYTHONPATH": str(Path(repro.__file__).resolve().parents[1])})
    for _ in range(2):
        out = subprocess.run([sys.executable, "-c", _ENV_CACHE_SCRIPT],
                             env=env, capture_output=True, text=True,
                             cwd=tmp_path)
        assert out.returncode == 0, (out.returncode, out.stderr)
        # the first process builds the program, the second loads it
        # from the persistent cache: one executable each
        assert out.stdout.split()[-1] == "1", out.stdout
    assert not (tmp_path / "results").exists()


def test_compile_cache_default_is_checkout_anchored(monkeypatch, tmp_path):
    """Without the variable, the cache lives in <checkout>/results/
    jax_cache whatever the cwd: the path is part of the cache key."""
    import jax

    import repro
    from repro.sweep import device as dev

    checkout = Path(repro.__file__).resolve().parents[2]
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(dev, "_CACHE_CONFIGURED", False)
    try:
        dev._configure_compile_cache()
        assert jax.config.jax_compilation_cache_dir == \
            str(checkout / "results" / "jax_cache")
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
    assert not (tmp_path / "results").exists()
