"""Vectorized-core equality pins.

The whole point of the array-native refactor is that nothing moves:
(a) the batched roofline kernel replayed over a logged trace is
bit-identical to what the event loop recorded stage by stage, (b) the
vectorized runner mode produces records bit-identical to the event
loop mode on every pinned benchmark grid (fig1/fig3/exp5 single-site,
exp6 fleet, exp7 shift), and (c) the stacked energy/carbon passes
equal their per-scenario counterparts exactly.
"""
import dataclasses

import numpy as np
import pytest
from _hypothesis_support import given, settings, st

from repro.configs.paper_models import CODELLAMA_34B, LLAMA3_8B
from repro.core.energy import operational_energy, stacked_energy_reports
from repro.core.power import DEVICES, PowerModel, eq1_power, power
from repro.sim import (PAPER_DEFAULT, SchedulerConfig, SimConfig,
                       StageBatch, StageCost, WorkloadConfig,
                       run_simulation)
from repro.sim.execmodel import ExecutionModel, cached_execution_model
from repro.sweep.device import _group_kernel
from repro.sweep import SWEEPS, GridSpec, SweepRunner
from repro.sweep.vectorized import group_by_trace


# ---------------------------------------------------------------------------
# runner-mode equality on the pinned benchmark grids
# ---------------------------------------------------------------------------

def _run_both_modes(scenarios):
    ev, _ = SweepRunner(cache=None, mode="event_loop").run(scenarios)
    ve, _ = SweepRunner(cache=None, mode="vectorized").run(scenarios)
    return ev, ve


def _assert_records_bit_identical(ev, ve):
    assert len(ev) == len(ve)
    for a, b in zip(ev, ve):
        assert a["scenario"] == b["scenario"]
        assert a["params"] == b["params"]
        assert a["key"] == b["key"]
        assert a["metrics"] == b["metrics"], a["scenario"]


@pytest.mark.parametrize("sweep", ["fig1", "fig3", "exp5"])
def test_modes_bit_identical_single_site(sweep):
    scenarios = SWEEPS[sweep].build(True, n_requests=16)
    ev, ve = _run_both_modes(scenarios)
    _assert_records_bit_identical(ev, ve)


@pytest.mark.parametrize("sweep", ["fleet", "shift"])
def test_modes_bit_identical_fleet(sweep):
    # exp6/exp7 grids: FleetConfig scenarios pass through the fleet
    # rollup in both modes — vectorized mode must not perturb them
    scenarios = SWEEPS[sweep].build(True, n_requests=10)
    ev, ve = _run_both_modes(scenarios)
    _assert_records_bit_identical(ev, ve)


def test_vectorized_groups_shared_traces():
    spec = GridSpec(base=PAPER_DEFAULT, tag="g",
                    axes={"workload.qps": [2.0, 5.0],
                          "pue": [1.0, 1.4],
                          "grid_ci": [50.0, 450.0]},
                    fixed={"workload.n_requests": 8,
                           "workload.min_len": 64,
                           "workload.max_len": 128})
    scenarios = spec.expand()
    groups = group_by_trace(scenarios)
    assert len(scenarios) == 8
    assert len(groups) == 2                    # one per qps point
    assert sorted(i for g in groups for i in g) == list(range(8))
    ev, ve = _run_both_modes(scenarios)
    _assert_records_bit_identical(ev, ve)
    # the shared-trace axes really move the metrics
    e = {r["params"]["pue"]: r["metrics"]["energy_wh"] for r in ve
         if r["params"]["qps"] == 2.0 and r["params"]["grid_ci"] == 50.0}
    assert e[1.4] == pytest.approx(e[1.0] * 1.4)
    c = {r["params"]["grid_ci"]: r["metrics"]["carbon_operational_g"]
         for r in ve
         if r["params"]["qps"] == 2.0 and r["params"]["pue"] == 1.0}
    assert c[450.0] == pytest.approx(c[50.0] * 9.0)


# ---------------------------------------------------------------------------
# trace replay: batched kernel == per-stage event-loop records
# ---------------------------------------------------------------------------

def _replay(res):
    em = cached_execution_model(res.cfg.model, res.cfg.device, res.cfg.tp,
                                res.cfg.pp, res.cfg.execmodel)
    return em.stage_cost_batch(StageBatch.from_trace(res.stages))


@pytest.mark.parametrize("chunk", [None, 256])
def test_stage_trace_replay_bit_identical(chunk):
    cfg = SimConfig(model=LLAMA3_8B,
                    workload=WorkloadConfig(n_requests=24, qps=4.0,
                                            min_len=64, max_len=512,
                                            seed=0),
                    scheduler=SchedulerConfig(batch_cap=8,
                                              chunk_prefill=chunk))
    res = run_simulation(cfg)
    cb = _replay(res)
    assert np.array_equal(cb.t_total, res.stages.dur_s)
    assert np.array_equal(cb.mfu, res.stages.mfu)
    assert np.array_equal(cb.flops_mlp, res.stages.flops_mlp)
    assert np.array_equal(cb.flops_attn, res.stages.flops_attn)


def test_fleet_site_trace_replays():
    from repro.fleet import run_fleet_simulation
    from repro.fleet.config import FleetConfig, SiteConfig

    cfg = FleetConfig(
        model=LLAMA3_8B,
        sites=(SiteConfig(name="a", ci_trace="hydro"),
               SiteConfig(name="b", ci_trace="coal")),
        workload=WorkloadConfig(n_requests=12, qps=4.0, min_len=64,
                                max_len=256, seed=0))
    res = run_fleet_simulation(cfg)
    for s in res.sites:
        em = cached_execution_model(cfg.model, s.site.device, s.site.tp,
                                    s.site.pp, cfg.execmodel)
        cb = em.stage_cost_batch(StageBatch.from_trace(s.stages))
        assert np.array_equal(cb.t_total, s.stages.dur_s)
        assert np.array_equal(cb.mfu, s.stages.mfu)


# ---------------------------------------------------------------------------
# scalar stage_cost_scalar == batched kernel rows (property test)
# ---------------------------------------------------------------------------

_COMPOSITION = st.tuples(
    st.lists(st.tuples(st.integers(1, 4096), st.integers(0, 4096)),
             min_size=0, max_size=5),                 # (chunk len, offset)
    st.lists(st.integers(1, 8192), min_size=0, max_size=8))  # decode ctxs


@given(st.lists(_COMPOSITION, min_size=1, max_size=12),
       st.sampled_from(["llama3-8b", "codellama-34b"]),
       st.sampled_from([(1, 1), (2, 1), (1, 2), (2, 2)]))
@settings(max_examples=25, deadline=None)
def test_batch_rows_match_scalar_path(comps, model_name, tp_pp):
    """The contract replay depends on: the event loop's scalar path and
    the batched roofline over its logged aggregates agree bitwise."""
    model = {"llama3-8b": LLAMA3_8B, "codellama-34b": CODELLAMA_34B}[model_name]
    tp, pp = tp_pp
    em = ExecutionModel(model, DEVICES["a100"], tp=tp, pp=pp)
    costs, aggs = [], []
    for (pre, ctxs) in comps:
        cost, *agg = em.stage_cost_scalar([p for p, _ in pre], ctxs,
                                          [o for _, o in pre])
        costs.append(cost)
        aggs.append(agg)
    batch = StageBatch(*(np.array(col) for col in zip(*aggs)))
    cb = em.stage_cost_batch(batch)
    for i, (cost, agg) in enumerate(zip(costs, aggs)):
        assert agg == [float(getattr(batch, f.name)[i])
                       for f in dataclasses.fields(StageBatch)]
        assert cost == StageCost(*(float(getattr(cb, f.name)[i])
                                   for f in dataclasses.fields(StageCost)))


def test_jax_backend_matches_numpy_closely():
    """Eq. 1 has one expression. Given the device kernel's float32
    constants it equals the host's ``power`` bitwise for every device
    profile; jitted, as the kernel runs it, XLA:CPU fuses
    ``p_idle + p_delta * x**gamma`` into one multiply-add, which stays
    within one float32 ulp. The kernel's ``peak`` is exactly the largest
    jitted ``eq1_power`` of the live stages' MFU."""
    import jax

    mfu = np.concatenate([np.linspace(0.0, 0.8, 401),
                          [-0.1, 0.45, 1.0, 1e-9]])
    eq1 = jax.jit(eq1_power)

    def consts(dev):
        return np.asarray([dev.p_idle, dev.p_max_inst, dev.mfu_sat,
                           dev.gamma, dev.p_max_inst - dev.p_idle],
                          np.float32)

    for name, dev in sorted(DEVICES.items()):
        c = consts(dev)
        ref = np.asarray(power(mfu, dev))
        with jax.enable_x64(True):
            eager = np.asarray(eq1_power(mfu, c[0], c[4], c[2], c[3]))
            jitted = np.asarray(eq1(mfu, c[0], c[4], c[2], c[3]))
        assert np.array_equal(eager, ref), name
        np.testing.assert_array_max_ulp(jitted, ref, maxulp=1)

    cfg = SimConfig(model=LLAMA3_8B,
                    workload=WorkloadConfig(n_requests=12, qps=4.0,
                                            min_len=64, max_len=512,
                                            seed=0),
                    scheduler=SchedulerConfig(batch_cap=8,
                                              chunk_prefill=256))
    res = run_simulation(cfg)
    em = cached_execution_model(cfg.model, cfg.device, cfg.tp, cfg.pp,
                                cfg.execmodel)
    c = consts(DEVICES[cfg.device])
    tr = res.stages
    pad = np.zeros(8)                       # padded rows, as the grid pads
    comp = [np.concatenate([np.asarray(v, np.float64), pad])
            for v in (tr.n_prefill_tokens, tr.n_decode_tokens,
                      tr.score_flops, tr.kv_rw_bytes)]
    with jax.enable_x64(True):
        out = jax.jit(_group_kernel)(*comp, em.params_vector(), c, 1.0,
                                     0.0, np.ones(1), np.zeros(1))
        live_max = np.max(np.asarray(eq1(tr.mfu, c[0], c[4], c[2], c[3])))
    peak = np.float32(out[3])
    assert peak == live_max
    np.testing.assert_array_max_ulp(
        peak, np.max(np.asarray(power(tr.mfu, DEVICES[cfg.device]))),
        maxulp=1)


# ---------------------------------------------------------------------------
# stacked energy pass == per-PUE operational_energy
# ---------------------------------------------------------------------------

def test_stacked_energy_reports_bit_identical():
    rng = np.random.default_rng(5)
    mfu = rng.uniform(0.0, 0.6, 200)
    dt = rng.uniform(1e-3, 2.0, 200)
    pm = PowerModel("a100")
    pues = [1.0, 1.12, 1.5, 2.0]
    stacked = stacked_energy_reports(mfu, dt, pm, n_devices=4, pues=pues)
    for pue, rep in zip(pues, stacked):
        solo = operational_energy(mfu, dt, pm, n_devices=4, pue=pue)
        assert rep == solo


# ---------------------------------------------------------------------------
# chunked-prefill accounting (cross-chunk KV reads + score context)
# ---------------------------------------------------------------------------

def test_chunk_offset_adds_kv_read_and_score_context():
    em = ExecutionModel(LLAMA3_8B, DEVICES["a100"])
    fresh, _, _, fresh_score, fresh_kv = em.stage_cost_scalar([256], [], [0])
    cont, _, _, cont_score, cont_kv = em.stage_cost_scalar([256], [], [2048])
    kvpt = LLAMA3_8B.kv_bytes_per_token()
    # the continuation re-reads exactly the prior context's KV
    assert cont_kv - fresh_kv == pytest.approx(2048 * kvpt)
    # and its score FLOPs see the offset context
    assert cont_score > fresh_score
    # continuation chunks therefore cost more wall-clock than a fresh
    # chunk of the same size (the under-counting the fix removes)
    assert cont.t_total > fresh.t_total


def test_chunked_prefill_conserves_score_flops():
    """Summed over all chunks, score FLOPs must match the whole-prompt
    prefill (each token's average context is preserved by offsetting),
    where the old accounting under-counted by ~2x at 4 chunks."""
    em = ExecutionModel(LLAMA3_8B, DEVICES["a100"])
    L, C = 4096, 512
    whole = em.stage_cost_scalar([L], [])[3]
    chunked = sum(em.stage_cost_scalar([C], [], [off])[3]
                  for off in range(0, L, C))
    assert chunked == pytest.approx(whole, rel=0.01)


def test_chunked_prefill_charges_more_memory_traffic():
    """End to end: a chunked run must log at least the unchunked run's
    KV traffic for the same workload (cross-chunk reads added)."""
    def kv_total(chunk):
        wl = WorkloadConfig(n_requests=4, qps=1.0, min_len=1024,
                            max_len=1024, length_dist="fixed", seed=0)
        res = run_simulation(SimConfig(
            model=LLAMA3_8B, workload=wl,
            scheduler=SchedulerConfig(batch_cap=8, chunk_prefill=chunk)))
        return float(np.sum(res.stages.kv_rw_bytes))

    assert kv_total(256) > kv_total(None)


# ---------------------------------------------------------------------------
# per-model invariants cached at construction
# ---------------------------------------------------------------------------

def test_execution_model_caches_invariants():
    em = ExecutionModel(LLAMA3_8B, DEVICES["a100"])
    assert em.active_params == LLAMA3_8B.active_param_count()
    assert em.kv_bytes_per_token == LLAMA3_8B.kv_bytes_per_token(2)
    assert em.fpt_mlp == LLAMA3_8B.flops_per_token_mlp_total()
    # linearized score model reproduces the config method exactly
    for ctx in (1, 17, 1024, 100_000):
        assert em.decode_terms(ctx)[0] == \
            LLAMA3_8B.flops_attn_score_per_token(ctx)
    # the process-level constructor cache returns shared instances
    a = cached_execution_model(LLAMA3_8B, "a100", 1, 1, em.cfg)
    b = cached_execution_model(LLAMA3_8B, "a100", 1, 1, em.cfg)
    assert a is b
    assert cached_execution_model(LLAMA3_8B, "a100", 2, 1, em.cfg) is not a
