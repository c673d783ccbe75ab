"""Sweep engine: grid expansion, cache memoization, parallel==serial."""
import numpy as np

from repro.configs.paper_models import LLAMA3_8B
from repro.sim import SchedulerConfig, SimConfig, WorkloadConfig
from repro.sweep import (GridSpec, ResultCache, Scenario, SweepRunner,
                         config_digest, execute_scenario, flatten, to_csv,
                         with_overrides)


def tiny_base(n_requests=12):
    return SimConfig(
        model=LLAMA3_8B,
        workload=WorkloadConfig(n_requests=n_requests, qps=4.0,
                                min_len=64, max_len=256, seed=0),
        scheduler=SchedulerConfig(batch_cap=8))


def test_grid_cardinality_and_expansion():
    spec = GridSpec(base=tiny_base(),
                    axes={"workload.qps": [1.0, 2.0, 4.0],
                          "scheduler.batch_cap": [4, 8]})
    assert spec.cardinality == 6
    scenarios = spec.expand()
    assert len(scenarios) == 6
    combos = {(s.cfg.workload.qps, s.cfg.scheduler.batch_cap)
              for s in scenarios}
    assert combos == {(q, c) for q in (1.0, 2.0, 4.0) for c in (4, 8)}
    assert scenarios[0].params == {"qps": 1.0, "batch_cap": 4}


def test_joint_axis_moves_fields_in_lockstep():
    spec = GridSpec(base=tiny_base(), axes={"tp+pp": [(1, 1), (2, 2)]})
    assert spec.cardinality == 2
    scenarios = spec.expand()
    assert scenarios[1].cfg.tp == 2 and scenarios[1].cfg.pp == 2
    assert scenarios[1].params == {"tp": 2, "pp": 2}


def test_model_axis_resolves_registry():
    spec = GridSpec(base=tiny_base(),
                    axes={"model": ["llama3-8b", "phi2-2.7b"]})
    scenarios = spec.expand()
    assert scenarios[1].cfg.model.name == "phi2-2.7b"
    assert scenarios[0].params["model"] == "llama3-8b"


def test_digest_stable_and_config_sensitive():
    assert config_digest(tiny_base()) == config_digest(tiny_base())
    bumped = with_overrides(tiny_base(), {"workload.qps": 9.0})
    assert config_digest(bumped) != config_digest(tiny_base())
    # runner knobs key the cache too
    plain = Scenario(cfg=tiny_base(), params={})
    posted = Scenario(cfg=tiny_base(), params={}, post="microgrid_cosim")
    assert plain.key != posted.key


def test_cache_second_run_executes_zero(tmp_path):
    cache = ResultCache(tmp_path / "cache")
    scenarios = GridSpec(base=tiny_base(),
                         axes={"workload.qps": [2.0, 6.0]}).expand()
    r1, s1 = SweepRunner(cache=cache).run(scenarios)
    assert s1.executed == 2 and s1.cache_hits == 0
    r2, s2 = SweepRunner(cache=cache).run(scenarios)
    assert s2.executed == 0 and s2.cache_hits == 2
    assert [r["metrics"] for r in r1] == [r["metrics"] for r in r2]
    assert all(r["meta"]["cache_hit"] for r in r2)


def test_cross_sweep_hit_rebinds_params(tmp_path):
    cache = ResultCache(tmp_path / "cache")
    first = GridSpec(base=tiny_base(), tag="a",
                     axes={"workload.qps": [3.0]}).expand()
    SweepRunner(cache=cache).run(first)
    # same config reached through a different axis spelling
    second = GridSpec(base=with_overrides(tiny_base(),
                                          {"workload.qps": 3.0}),
                      tag="b", axes={"scheduler.batch_cap": [8]}).expand()
    records, stats = SweepRunner(cache=cache).run(second)
    assert stats.cache_hits == 1
    assert records[0]["params"] == {"batch_cap": 8}
    assert records[0]["scenario"].startswith("b/")


def test_parallel_matches_serial_at_fixed_seeds():
    scenarios = GridSpec(base=tiny_base(8),
                         axes={"workload.qps": [2.0, 5.0]}).expand()
    serial, _ = SweepRunner(cache=None, workers=1).run(scenarios)
    parallel, _ = SweepRunner(cache=None, workers=2).run(scenarios)
    assert [r["metrics"] for r in serial] == \
           [r["metrics"] for r in parallel]


def test_record_has_energy_carbon_columns_and_csv(tmp_path):
    record = execute_scenario(Scenario(cfg=tiny_base(6), params={"x": 1}))
    for col in ("energy_wh", "energy_kwh", "avg_power_w", "gpu_hours",
                "carbon_operational_g", "carbon_embodied_g",
                "carbon_total_g", "ttft_p50_s", "e2e_p99_s"):
        assert col in record["metrics"], col
    assert record["metrics"]["energy_wh"] > 0
    row = flatten([record])[0]
    assert row["x"] == 1
    path = to_csv([record], tmp_path / "out.csv")
    header = path.read_text().splitlines()[0].split(",")
    assert "x" in header and "energy_wh" in header


def test_smoke_sweeps_expand_for_every_figure():
    from repro.sweep import SWEEPS
    assert set(SWEEPS) == {"fig1", "fig2", "fig3", "fig4", "fig5",
                           "exp5", "table2", "carbon", "fleet", "shift",
                           "perf", "day"}
    # perf is the runner-throughput grid: deliberately ~1k scenarios
    # (1024 stacked-axis points + a 32-scenario hardware family for
    # device-mode divergence sharing), but they collapse to a handful
    # of unique traces; day's smoke is
    # four whole-day hybrid/event_loop runs over an array-native
    # stream, so its request count is epoch-planned, not event-stepped
    smoke_caps = {"shift": 18, "perf": 1056}
    request_caps = {"day": 10_000}
    for name, sweep in SWEEPS.items():
        scenarios = sweep.build(True)
        assert scenarios, name
        # smoke grids stay tiny so CI can afford every figure per push
        # (shift's policy x forecaster x trace-set grid is wider but
        # each scenario is a ~100-request fleet sim, seconds apiece)
        assert len(scenarios) <= smoke_caps.get(name, 8), name
        cap = request_caps.get(name, 2000)
        assert all(s.cfg.workload.n_requests <= cap
                   for s in scenarios), name


def test_scenario_knob_axes_route_correctly():
    import pytest

    from repro.configs.paper_models import LLAMA3_8B
    from repro.fleet.config import FleetConfig, SiteConfig
    from repro.sim import WorkloadConfig

    # SimConfig bases: pue/grid_ci land on the Scenario, not the config
    a, b = GridSpec(base=tiny_base(8), axes={"pue": [1.0, 1.5]}).expand()
    assert (a.pue, b.pue) == (1.0, 1.5)
    assert a.trace_key == b.trace_key          # shared simulation trace
    assert a.key != b.key                      # distinct cache entries

    # FleetConfig bases: the fleet rollup reads cfg.pue — a pue axis
    # must reach it (and grid_ci, which fleets ignore, must refuse)
    fleet = FleetConfig(
        model=LLAMA3_8B, sites=(SiteConfig(name="s0", ci_trace="hydro"),),
        workload=WorkloadConfig(n_requests=8, qps=4.0, min_len=64,
                                max_len=128, seed=0))
    fa, fb = GridSpec(base=fleet, axes={"pue": [1.0, 1.5]}).expand()
    assert (fa.cfg.pue, fb.cfg.pue) == (1.0, 1.5)
    with pytest.raises(ValueError):
        GridSpec(base=fleet, axes={"grid_ci": [100.0]}).expand()


def test_derived_seeds_ignore_report_knobs():
    spec = GridSpec(base=tiny_base(8),
                    axes={"workload.qps": [2.0], "pue": [1.0, 1.3]},
                    seed_per_scenario=True)
    a, b = spec.expand()
    # report knobs must not confound the workload draw: same seed,
    # same trace group across the pue axis
    assert a.cfg.workload.seed == b.cfg.workload.seed
    assert a.trace_key == b.trace_key


def test_seed_lives_in_config_not_execution_order():
    spec = GridSpec(base=tiny_base(), axes={"workload.qps": [1.0, 2.0]},
                    seed_per_scenario=True)
    a, b = spec.expand()
    assert a.cfg.workload.seed != b.cfg.workload.seed
    # re-expansion reproduces the same derived seeds
    a2, b2 = spec.expand()
    assert (a.cfg.workload.seed, b.cfg.workload.seed) == \
           (a2.cfg.workload.seed, b2.cfg.workload.seed)


def test_memo_eviction_is_lru(tmp_path):
    """The in-process memo evicts least-recently-used, so a long-lived
    worker keeps hot keys resident past the cap instead of freezing
    the first insertions (the old behavior dropped everything)."""
    cache = ResultCache(tmp_path / "cache")
    cache._MEMO_CAP = 3
    for k in ("k0", "k1", "k2"):
        cache.put(k, {"key": k, "metrics": {}})
    assert list(cache._memo) == ["k0", "k1", "k2"]
    cache.get("k0")                       # touch: k0 becomes most recent
    cache.put("k3", {"key": "k3", "metrics": {}})   # evicts k1, not k0
    assert list(cache._memo) == ["k2", "k0", "k3"]
    # k1 still serves from disk (authoritative) and re-enters the memo
    c0 = dict(cache.counters)
    assert cache.get("k1")["key"] == "k1"
    assert cache.counters["disk"] == c0["disk"] + 1
    assert "k1" in cache._memo and "k2" not in cache._memo


def test_memo_cap_holds_under_churn(tmp_path):
    cache = ResultCache(tmp_path / "cache")
    cache._MEMO_CAP = 4
    for i in range(20):
        cache.put(f"key{i:02d}", {"key": f"key{i:02d}", "metrics": {}})
    assert len(cache._memo) == 4
    assert list(cache._memo) == ["key16", "key17", "key18", "key19"]


def test_peak_rss_includes_pool_children():
    """The summary's peak-RSS figure must reflect the process *tree*:
    a child that allocates far more than the parent shows up via
    RUSAGE_CHILDREN once reaped."""
    import subprocess
    import sys

    from repro.sweep.runner import _peak_rss_mb

    before = _peak_rss_mb()
    # ~300 MB in a child; bytearray keeps it resident, touch every page
    subprocess.run(
        [sys.executable, "-c",
         "b = bytearray(300 * 1024 * 1024)\n"
         "b[::4096] = b'x' * len(b[::4096])"],
        check=True)
    after = _peak_rss_mb()
    assert after >= before
    assert after >= 250.0     # the child's footprint, not the parent's


def _child_jax_platforms(_):
    import jax
    return (jax.config.jax_platforms,
            sorted({d.platform for d in jax.devices()}))


def test_pool_children_start_cpu_only(monkeypatch):
    """A parent on a chip host holds the accelerator; the host-path
    pool children of SweepRunner(workers=2) start JAX on the CPU alone,
    whatever platforms the inherited environment names (here one no
    backend answers to, so any child that honoured it would fail)."""
    from repro.sweep.runner import host_pool

    monkeypatch.setenv("JAX_PLATFORMS", "accelerator")
    with host_pool(2) as pool:
        seen = list(pool.map(_child_jax_platforms, range(2)))
    assert seen == [("cpu", ["cpu"])] * 2

    scenarios = GridSpec(base=tiny_base(8), axes={
        "workload.qps": [2.0, 4.0]}).expand()
    par, _ = SweepRunner(cache=None, workers=2).run(scenarios)
    ser, _ = SweepRunner(cache=None, workers=1).run(scenarios)
    assert [r["metrics"] for r in par] == [r["metrics"] for r in ser]
