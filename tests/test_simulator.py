"""Simulator + scheduler invariants (incl. hypothesis property tests)."""
import dataclasses

import numpy as np
import pytest
from _hypothesis_support import given, settings, st

from repro.configs.paper_models import LLAMA3_8B
from repro.sim import (PAPER_DEFAULT, SchedulerConfig, SimConfig,
                       WorkloadConfig, energy_report, run_simulation)
from repro.sim.requests import generate
from repro.sim.simulator import kv_budget_tokens
from repro.core.power import DEVICES


def small_sim(**kw):
    wl = WorkloadConfig(n_requests=kw.pop("n_requests", 64),
                        qps=kw.pop("qps", 5.0),
                        seed=kw.pop("seed", 0),
                        min_len=kw.pop("min_len", 64),
                        max_len=kw.pop("max_len", 512))
    sched = SchedulerConfig(batch_cap=kw.pop("batch_cap", 16))
    return SimConfig(model=LLAMA3_8B, workload=wl, scheduler=sched, **kw)


def test_all_requests_complete():
    res = run_simulation(small_sim())
    assert all(r.t_done >= 0 for r in res.requests)
    assert all(r.t_first_token >= r.arrival_s for r in res.requests)
    assert all(r.t_done >= r.t_first_token for r in res.requests)


def test_stage_log_consistency():
    res = run_simulation(small_sim())
    s = res.stages
    assert np.all(s.dur_s > 0)
    assert np.all(s.mfu >= 0) and np.all(s.mfu <= 1.0 + 1e-6)
    assert np.all((s.n_prefill_tokens > 0) ^ (s.n_decode_tokens > 0))


@given(st.integers(0, 10_000), st.floats(0.5, 30.0),
       st.integers(1, 64))
@settings(max_examples=15, deadline=None)
def test_scheduler_batch_cap_respected(seed, qps, cap):
    res = run_simulation(small_sim(seed=seed, qps=qps, batch_cap=cap,
                                   n_requests=48))
    assert np.max(res.stages.batch_size) <= cap
    done = [r for r in res.requests if r.t_done >= 0]
    assert len(done) == 48  # everything eventually served


def test_decode_tokens_counted_exactly():
    cfg = small_sim(n_requests=32)
    res = run_simulation(cfg)
    expected = sum(r.decode_tokens for r in res.requests)
    # decode stages emit one token per running sequence
    emitted = int(np.sum(res.stages.n_decode_tokens))
    assert emitted == expected


def test_energy_scales_linearly_with_requests():
    e = []
    for n in (64, 128, 256):
        res = run_simulation(small_sim(n_requests=n, qps=4.0))
        e.append(energy_report(res).energy_wh)
    r1 = e[1] / e[0]
    r2 = e[2] / e[1]
    assert 1.6 < r1 < 2.4 and 1.6 < r2 < 2.4  # ~2x per doubling


def test_higher_qps_higher_power_lower_energy():
    lo = energy_report(run_simulation(small_sim(qps=0.5, n_requests=96)))
    hi = energy_report(run_simulation(small_sim(qps=8.0, n_requests=96)))
    assert hi.avg_power_w > lo.avg_power_w      # paper Fig. 5A
    assert hi.energy_wh < lo.energy_wh          # paper Fig. 5B


def test_kv_budget_large_model_small():
    from repro.configs.paper_models import CODELLAMA_34B
    b34 = kv_budget_tokens(CODELLAMA_34B, DEVICES["a100"], 1, 1)
    b8 = kv_budget_tokens(LLAMA3_8B, DEVICES["a100"], 1, 1)
    assert 0 < b34 < 40_000          # 34B barely fits A100-80GB
    assert b8 > 100_000
    assert kv_budget_tokens(CODELLAMA_34B, DEVICES["a100"], 2, 1) > 2 * b34


def test_tp_reduces_stage_time():
    from repro.sim.execmodel import ExecutionModel
    m1 = ExecutionModel(LLAMA3_8B, DEVICES["a100"], tp=1)
    m2 = ExecutionModel(LLAMA3_8B, DEVICES["a100"], tp=2)
    c1 = m1.stage_cost_scalar([2048], [])[0]
    c2 = m2.stage_cost_scalar([2048], [])[0]
    assert c2.t_total < c1.t_total
    assert c2.t_collective > 0 and c1.t_collective == 0


def test_workload_pd_ratio():
    wl = WorkloadConfig(n_requests=200, pd_ratio=20.0, min_len=1024,
                        max_len=1024, length_dist="fixed")
    reqs = generate(wl)
    ratios = [r.prefill_tokens / r.decode_tokens for r in reqs]
    assert np.median(ratios) == pytest.approx(20.0, rel=0.1)


def test_zipf_lengths_skewed():
    wl = WorkloadConfig(n_requests=2000, zipf_theta=0.9, min_len=100,
                        max_len=4000, seed=1)
    reqs = generate(wl)
    lens = np.array([r.prefill_tokens + r.decode_tokens for r in reqs])
    assert np.median(lens) < np.mean(lens)  # right-skew
    assert lens.min() >= 100 and lens.max() <= 4000


def _chunk_sim(chunk):
    wl = WorkloadConfig(n_requests=4, qps=1.0, min_len=1024, max_len=1024,
                        length_dist="fixed", seed=0)
    sched = SchedulerConfig(batch_cap=8, chunk_prefill=chunk)
    return run_simulation(SimConfig(model=LLAMA3_8B, workload=wl,
                                    scheduler=sched))


def test_chunked_prefill_stage_count():
    """chunk_prefill=256 splits each 975-token prompt into 4 chunk
    stages (Sarathi), vs one whole-prompt prefill stage unchunked."""
    base = _chunk_sim(None)
    chunked = _chunk_sim(256)
    n_base = int(np.sum(base.stages.n_prefill_tokens > 0))
    n_chunked = int(np.sum(chunked.stages.n_prefill_tokens > 0))
    total_prefill = sum(r.prefill_tokens for r in chunked.requests)
    assert n_base <= 4                       # one stage per prompt
    assert n_chunked >= -(-total_prefill // 256)   # >= ceil(3900/256)=16
    assert n_chunked > n_base
    # every chunk stage respects the token budget
    chunk_stages = chunked.stages.n_prefill_tokens
    assert np.all(chunk_stages[chunk_stages > 0] <= 256)
    # no prefill work is lost or duplicated
    assert int(np.sum(base.stages.n_prefill_tokens)) == total_prefill
    assert int(np.sum(chunked.stages.n_prefill_tokens)) == total_prefill
    # the workload still completes, decode accounting intact
    assert all(r.t_done >= 0 for r in chunked.requests)
    assert int(np.sum(chunked.stages.n_decode_tokens)) == \
        sum(r.decode_tokens for r in chunked.requests)


def test_chunked_prefill_coalesces_decodes():
    """Sarathi-style iterations mix prefill chunks with ongoing decodes
    once earlier requests finish their prompts."""
    res = _chunk_sim(256)
    mixed = np.sum((res.stages.n_prefill_tokens > 0)
                   & (res.stages.n_decode_tokens > 0))
    assert mixed > 0


def test_chunk_prefill_validation():
    with pytest.raises(ValueError):
        SchedulerConfig(chunk_prefill=0)
    with pytest.raises(ValueError):
        SchedulerConfig(chunk_prefill=-5)
