"""Decode-run fast-forward in the event loop (``drive``).

A run of decode-only iterations with an unchanged batch is advanced in
one array step (``fleet.simulation._advance_decode_run``). It must be
invisible in the output: every ``StageTrace`` column and every
request's ``t_first_token`` / ``t_done`` are bitwise what the loop
gives when it takes each iteration alone. Attaching a no-op probe keeps
the loop on that one-at-a-time path, so each case compares a probe-off
run against a probe-attached one, and checks that the fast-forward did
engage in the probe-off run (else the comparison proves nothing).
"""
import dataclasses

import numpy as np
import pytest

from repro.configs.paper_models import LLAMA3_8B, PHI2_2_7B, QWEN_72B
from repro.fleet import FleetConfig, SiteConfig, run_fleet_simulation
from repro.fleet.routing import RoundRobinRouter
from repro.fleet import simulation as fleet_sim
from repro.obs import Probe
from repro.sim import (SchedulerConfig, SimConfig, WorkloadConfig,
                       run_simulation)
from repro.sim.execmodel import ExecModelConfig, ExecutionModel, StageCost
from repro.sim.requests import Request
from repro.sim.trace import StageTraceBuilder
from repro.core.power import DEVICES
from repro.sweep import SweepRunner
from repro.sweep.grid import Scenario

# Table 1a's scheduler and request lengths, as the paper's Exp. 1 runs
_TABLE1A = SchedulerConfig(batch_cap=128, max_tokens=4096)


def _workload(qps, n=96, seed=7, **kw):
    return WorkloadConfig(n_requests=n, qps=qps, arrival="poisson",
                          length_dist="zipf", zipf_theta=0.6, min_len=128,
                          max_len=4096, pd_ratio=20.0, seed=seed, **kw)


def _sim(model=PHI2_2_7B, tp=1, pp=1, qps=6.45, **kw):
    return SimConfig(model=model, device="a100", tp=tp, pp=pp,
                     workload=kw.pop("workload", _workload(qps)),
                     scheduler=kw.pop("scheduler", _TABLE1A), **kw)


def _bits(a):
    return np.ascontiguousarray(a).view(np.uint8)


def _assert_same(trace_a, reqs_a, trace_b, reqs_b):
    for f in dataclasses.fields(trace_a):
        x, y = getattr(trace_a, f.name), getattr(trace_b, f.name)
        assert x.dtype == y.dtype and x.shape == y.shape, f.name
        assert np.array_equal(_bits(x), _bits(y)), f.name
    assert [(r.rid, r.t_first_token, r.t_done, r.decoded)
            for r in reqs_a] == [(r.rid, r.t_first_token, r.t_done,
                                  r.decoded) for r in reqs_b]
    assert all(type(r.t_done) is float for r in reqs_a)


@pytest.fixture
def runs(monkeypatch):
    """Counts the decode runs that ``drive`` advanced in one step."""
    taken = []
    advance = fleet_sim._advance_decode_run

    def counted(*args):
        end = advance(*args)
        taken.append(end is not None)
        return end

    monkeypatch.setattr(fleet_sim, "_advance_decode_run", counted)
    return taken


def _long_run_row(trace):
    """A row in the middle of the longest stretch of decode-only rows
    with one batch size (single replica, PP 1)."""
    dec = (trace.n_prefill_tokens == 0)
    same = dec[1:] & dec[:-1] & (trace.batch_size[1:] == trace.batch_size[:-1])
    best, start, cur = 0, 0, 0
    for j, s in enumerate(same):
        cur = cur + 1 if s else 0
        if cur > best:
            best, start = cur, j + 1 - cur
    assert best >= 8
    return start + best // 2


def _case_horizon(where):
    def build():
        cfg = _sim(qps=0.5)
        full = run_simulation(cfg, probe=Probe()).stages
        r = _long_run_row(full)
        cut = (full.start_s[r] if where == "exact"
               else full.start_s[r] + 0.5 * full.dur_s[r])
        return cfg, float(cut)
    return build


_SIM_CASES = {
    **{f"phi2-tp1pp1-{q}qps": (lambda q=q: (_sim(qps=q), None))
       for q in (0.5, 6.45, 12.6)},
    **{f"qwen72b-tp2pp2-{q}qps":
       (lambda q=q: (_sim(QWEN_72B, tp=2, pp=2, qps=q), None))
       for q in (0.5, 6.45, 12.6)},
    # round-robin over three replicas: their clocks interleave, so a run
    # ends at another replica's clock (ties broken by replica order)
    "phi2-3-replicas": lambda: (_sim(qps=12.6, n_replicas=3), None),
    "qwen72b-3-replicas": lambda: (_sim(QWEN_72B, tp=2, pp=2, qps=6.45,
                                        n_replicas=3), None),
    "phi2-chunked-prefill": lambda: (
        _sim(qps=6.45, scheduler=dataclasses.replace(
            _TABLE1A, chunk_prefill=256)), None),
    # a KV budget of a few prompts: admission blocks until completions
    # free room, and arrivals queue behind it
    "llama3-tight-kv": lambda: (
        _sim(LLAMA3_8B, qps=6.45, auto_kv_budget=False,
             scheduler=dataclasses.replace(_TABLE1A,
                                           kv_budget_tokens=12_000)),
        None),
    # the horizon cuts a decode run in its middle, or exactly at the
    # start of one of its iterations (which the loop still takes)
    "phi2-horizon-mid-run": _case_horizon("mid"),
    "phi2-horizon-at-start": _case_horizon("exact"),
}


def _two_site_fleet(router):
    sites = (SiteConfig(name="hydro", ci_trace="hydro", n_replicas=2,
                        scheduler=_TABLE1A),
             SiteConfig(name="coal", ci_trace="coal", tp=2, pp=2,
                        scheduler=_TABLE1A))
    return FleetConfig(model=LLAMA3_8B, sites=sites,
                       workload=_workload(8.0, n=80), router=router)


_CASES = [("sim", name) for name in _SIM_CASES] + [
    ("fleet", "round_robin"), ("fleet", "least_loaded")]


@pytest.mark.parametrize("kind,name", _CASES,
                         ids=[f"{k}-{n}" for k, n in _CASES])
def test_fast_forward_is_bitwise_invisible(kind, name, runs):
    if kind == "sim":
        cfg, horizon = _SIM_CASES[name]()
        kw = {} if horizon is None else {"max_sim_s": horizon}
        slow = run_simulation(cfg, probe=Probe(), **kw)
        n_slow = len(runs)
        fast = run_simulation(cfg, **kw)
        assert n_slow == 0 and slow.ff_iterations == 0
        assert slow.loop_iterations == fast.loop_iterations
        assert fast.ff_iterations > 0
        _assert_same(fast.stages, fast.requests, slow.stages, slow.requests)
        if horizon is not None:
            last = slow.stages.start_s[-1]
            assert last <= horizon < last + slow.stages.dur_s[-1]
    else:
        cfg = _two_site_fleet(name)
        slow = run_fleet_simulation(cfg, probe=Probe())
        assert not runs
        fast = run_fleet_simulation(cfg)
        assert len(fast.sites) == 2
        for a, b in zip(fast.sites, slow.sites):
            _assert_same(a.stages, a.requests, b.stages, b.requests)
        assert fast.summary() == slow.summary()
    assert any(runs)


class _QuarterSecondModel:
    """A stand-in execution model whose stages take 1/4, 1/2 or 3/4 s
    (by the batch's summed tokens), so replica clocks and ready times,
    all quarters, tie exactly and often: the loop's tie-breaks (routing
    before processing, replicas in order) then decide the schedule."""

    @staticmethod
    def _t(tokens):
        return 0.25 * (1 + tokens % 3)

    def stage_cost_scalar(self, plens, ctxs, offs):
        s = sum(plens) + sum(ctxs)
        t = float(self._t(s))
        return (StageCost(t, t, 0.0, 0.0, 0.0, float(s), 0.5),
                float(sum(plens)), float(len(ctxs)), float(s), 0.0)

    def decode_run(self, ctxs, k):
        s = sum(ctxs) + len(ctxs) * np.arange(k)
        t = self._t(s).astype(np.float64)
        return (t, 0.0, s.astype(np.float64), np.full(k, 0.5),
                s.astype(np.float64), np.zeros(k))


@pytest.mark.parametrize("n_replicas,pp", [(2, 1), (3, 2)])
def test_fast_forward_keeps_the_loops_tie_breaks(n_replicas, pp, runs):
    rng = np.random.default_rng(n_replicas)
    for trial in range(30):
        n = int(rng.integers(4, 16))
        reqs = [(0.25 * int(a), int(p), int(d)) for a, p, d in zip(
            np.sort(rng.integers(0, 40, n)), rng.integers(1, 9, n),
            rng.integers(1, 30, n))]
        horizon = (0.25 * int(rng.integers(20, 200)) if trial % 3 == 0
                   else 10_000_000.0)
        out = []
        for probe in (Probe(), None):
            site = fleet_sim.LoopSite(
                RoundRobinRouter(n_replicas, SchedulerConfig(batch_cap=3)),
                _QuarterSecondModel(), pp)
            requests = [Request(rid, a, p, d)
                        for rid, (a, p, d) in enumerate(reqs)]
            fleet_sim.drive([site], site.add, requests, horizon,
                            probe=probe)
            out.append((site.stage_log(), requests))
        _assert_same(*out[1], *out[0])
    assert any(runs)


def test_fast_forward_share_of_a_low_rate_stream():
    res = run_simulation(_sim(qps=0.5, workload=_workload(0.5, n=512)))
    assert res.loop_iterations > 0
    assert res.ff_iterations / res.loop_iterations > 0.9


def test_replayed_stream_runs_no_loop_iterations():
    """Groups that divergence replay serves never enter the loop: the
    sweep reports no iterations, fast-forwarded or not."""
    wl = WorkloadConfig(n_requests=6, qps=0.1, arrival="uniform",
                        length_dist="fixed", min_len=64, max_len=64,
                        pd_ratio=1.0, seed=3)
    scenarios = [Scenario(cfg=_sim(tp=tp, workload=wl), params={"tp": tp},
                          tag=f"tp{tp}") for tp in (1, 2)]
    _, stats = SweepRunner(cache=None, mode="device").run(scenarios)
    assert stats.replayed == 2 and stats.event_loops == 0
    assert stats.loop_iterations == 0 and stats.ff_iterations == 0
    assert "fast-forward 0.0% of 0 iterations" in stats.summary()


@pytest.mark.parametrize("model", [PHI2_2_7B, QWEN_72B],
                         ids=lambda m: m.name)
@pytest.mark.parametrize("window", [None, 1500])
def test_decode_run_matches_scalar_stages(model, window):
    """``ExecutionModel.decode_run`` over ``k`` stages is ``k`` calls of
    ``stage_cost_scalar`` at growing contexts, bit for bit."""
    if window is not None:
        model = dataclasses.replace(model, attention=dataclasses.replace(
            model.attention, sliding_window=window))
    em = ExecutionModel(model, DEVICES["a100"], 2, 2, ExecModelConfig())
    rng = np.random.default_rng(5)
    for n in (1, 7, 128):
        ctxs = [int(c) for c in rng.integers(128, 4096, n)]
        k = 40
        t, f_mlp, f_attn, mfu, score, kv = em.decode_run(ctxs, k)
        for j in range(k):
            cost, npt, nd, f_score, kv_rw = em.stage_cost_scalar(
                [], [c + j for c in ctxs], [])
            assert (npt, nd) == (0.0, float(n))
            assert (t[j], f_mlp, f_attn[j], mfu[j], score[j], kv[j]) == (
                cost.t_total, cost.flops_mlp, cost.flops_attn, cost.mfu,
                f_score, kv_rw)


def test_extend_equals_appends_across_a_doubling():
    rng = np.random.default_rng(0)
    fields = ("start_s", "dur_s", "flops_mlp", "flops_attn", "mfu",
              "n_prefill_tokens", "n_decode_tokens", "replica",
              "batch_size", "score_flops", "kv_rw_bytes")
    head = rng.random((5, len(fields)))
    m, pp = 23, 2                  # 46 rows: 16 -> 64, past one doubling
    block = {"start_s": rng.random((m, pp)), "dur_s": rng.random((m, 1)),
             "flops_mlp": 3.5, "flops_attn": rng.random((m, 1)),
             "mfu": rng.random((m, 1)), "n_prefill_tokens": 0.0,
             "n_decode_tokens": 9.0, "replica": np.arange(pp) + 4.0,
             "batch_size": 9.0, "score_flops": rng.random((m, 1)),
             "kv_rw_bytes": rng.random((m, 1))}
    a, b = StageTraceBuilder(16), StageTraceBuilder(16)
    for row in head:
        a.append(*row)
        b.append(*row)
    a.extend(block)
    for j in range(m):
        for s in range(pp):
            b.append(**{f: np.broadcast_to(v, (m, pp))[j, s]
                        for f, v in block.items()})
    assert len(a) == len(b) == 5 + m * pp
    ta, tb = a.build(), b.build()
    for f in fields:
        assert np.array_equal(_bits(getattr(ta, f)), _bits(getattr(tb, f)))
    a.extend({f: np.empty((0, 1)) if f == "start_s" else 0.0
              for f in fields})
    assert len(a) == 5 + m * pp
