"""Sweep-wide key digests: ``fill_digests`` serialises each distinct
config object once and gives every scenario the key and trace key its
own lazy properties would, byte for byte; ``GridSpec.expand`` shares a
config object exactly among combinations that differ only in
report-only axes, so the CLI's grids engage it too."""
import copy
import dataclasses
import hashlib
import itertools
import json

import pytest

from repro.configs.paper_models import LLAMA3_8B
from repro.sim import SchedulerConfig, SimConfig, WorkloadConfig
from repro.sweep import (GridSpec, Scenario, SweepRunner, config_digest,
                         fill_digests, with_overrides)
from repro.sweep.grid import SCHEMA_VERSION


def tiny_base(n_requests=8):
    return SimConfig(
        model=LLAMA3_8B,
        workload=WorkloadConfig(n_requests=n_requests, qps=4.0,
                                min_len=64, max_len=256, seed=0),
        scheduler=SchedulerConfig(batch_cap=8))


def _knobs(sc):
    return {"pue": sc.pue, "grid_ci": sc.grid_ci, "post": sc.post,
            "post_params": sc.post_params}


def _reference_digest(cfg, extra):
    """The digest written out in full: one sha256 over the canonical
    JSON of the whole payload, independent of the runner's helpers."""
    payload = {"cfg": dataclasses.asdict(cfg), "extra": extra,
               "schema": SCHEMA_VERSION}
    blob = json.dumps(payload, sort_keys=True, default=str,
                      separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()


def _lazy(scenarios):
    """The keys of fresh copies that digest themselves one by one."""
    out = []
    for sc in scenarios:
        fresh = copy.copy(sc)
        fresh._key = fresh._trace_key = fresh._cfg_blob = None
        out.append((fresh.key, fresh.trace_key))
    return out


def _plane(n_cfg=8, n_pue=16, n_ci=16):
    """``n_cfg`` config objects, each shared by a PUE x CI plane of
    scenarios, built as the benchmark harness builds a window sweep."""
    combos = list(itertools.product(("a100", "h100"), (1, 2), (1, 2)))
    out = []
    for device, tp, pp in combos[:n_cfg]:
        cfg = SimConfig(model=LLAMA3_8B, device=device, tp=tp, pp=pp,
                        workload=WorkloadConfig(n_requests=64, qps=0.5,
                                                seed=7),
                        scheduler=SchedulerConfig(batch_cap=128))
        for i, j in itertools.product(range(n_pue), range(n_ci)):
            out.append(Scenario(cfg=cfg, params={"i": i, "j": j},
                                tag=f"{device}/{tp}/{pp}/{i}/{j}",
                                pue=1.0 + 0.05 * i, grid_ci=20.0 + 40 * j))
    return out


def _fleet_scenario():
    from repro.fleet.config import FleetConfig, SiteConfig
    fleet = FleetConfig(
        model=LLAMA3_8B, sites=(SiteConfig(name="s0", ci_trace="hydro"),),
        workload=WorkloadConfig(n_requests=8, qps=4.0, min_len=64,
                                max_len=128, seed=0))
    return Scenario(cfg=fleet, params={}, tag="fleet", pue=1.3)


def _grid():
    return GridSpec(base=tiny_base(),
                    axes={"workload.qps": [2.0, 4.0],
                          "pue": [1.1, 1.4], "grid_ci": [50.0, 400.0],
                          "post.solar_capacity_w": [0.0, 600.0]},
                    post="cosim", post_params={"battery_wh": 10.0},
                    seed_per_scenario=True).expand()


@pytest.mark.parametrize("build, n_cfgs", [
    (_plane, 8),
    (_grid, 2),
    (lambda: [_fleet_scenario(), _fleet_scenario()], 2),
    (lambda: [Scenario(cfg=tiny_base(), params={}, post="cosim",
                       post_params={"solar_capacity_w": 300.0,
                                    "trace": [1, 2]})], 1),
], ids=["plane_8x256", "gridspec_report_axes", "fleet", "post_params"])
def test_fill_digests_matches_lazy_and_config_digest(build, n_cfgs):
    scenarios = build()
    want = _lazy(scenarios)
    assert fill_digests(scenarios) == n_cfgs
    assert len({id(sc.cfg) for sc in scenarios}) == n_cfgs
    for sc, (key, trace_key) in zip(scenarios, want):
        assert sc._key is not None and sc._trace_key is not None
        assert sc.key == key == config_digest(sc.cfg, _knobs(sc)) \
            == _reference_digest(sc.cfg, _knobs(sc))
        assert sc.trace_key == trace_key == config_digest(sc.cfg) \
            == _reference_digest(sc.cfg, {})


def test_fill_digests_leaves_keyed_scenarios_alone():
    scenarios = _plane(n_cfg=2, n_pue=2, n_ci=2)
    first = scenarios[0]
    first._key = "preset"
    want = _lazy(scenarios[1:])
    assert fill_digests(scenarios) == 2
    assert first.key == "preset"
    assert [(sc.key, sc.trace_key) for sc in scenarios[1:]] == want
    # a second pass finds every key cached and serialises nothing
    assert fill_digests(scenarios) == 0


@pytest.mark.parametrize("seed_per_scenario", [False, True])
def test_expand_shares_cfg_only_across_report_axes(seed_per_scenario):
    spec = GridSpec(base=tiny_base(),
                    axes={"workload.qps": [2.0, 4.0, 8.0],
                          "tp+pp": [(1, 1), (2, 1)],
                          "pue": [1.1, 1.2, 1.5], "grid_ci": [50.0, 400.0],
                          "post.solar_capacity_w": [0.0, 600.0]},
                    post="cosim", seed_per_scenario=seed_per_scenario)
    scenarios = spec.expand()
    assert len(scenarios) == spec.cardinality == 72

    def config_point(sc):
        return sc.params["qps"], sc.params["tp"], sc.params["pp"]

    by_point = {}
    for sc in scenarios:
        by_point.setdefault(config_point(sc), set()).add(id(sc.cfg))
    # one object per config point, each one shared by 3 x 2 x 2 scenarios
    assert all(len(ids) == 1 for ids in by_point.values())
    assert len({i for ids in by_point.values() for i in ids}) == 6
    assert fill_digests(scenarios) == 6
    assert [(sc.key, sc.trace_key) for sc in scenarios] == _lazy(scenarios)


def test_expand_keeps_equal_looking_values_apart():
    # 1 and 1.0 compare (and hash) equal but serialise apart: they must
    # not share a config, or one scenario would digest the other's tree
    spec = GridSpec(base=tiny_base(), axes={"workload.qps": [1, 1.0, True]})
    scenarios = spec.expand()
    assert len({id(sc.cfg) for sc in scenarios}) == 3
    assert [type(sc.cfg.workload.qps) for sc in scenarios] == \
        [int, float, bool]
    assert [(sc.key, sc.trace_key) for sc in scenarios] == _lazy(scenarios)


def test_device_sweep_counts_config_blobs():
    cfgs = [tiny_base(), with_overrides(tiny_base(), {"workload.qps": 6.0})]
    scenarios = [Scenario(cfg=cfg, params={"c": c, "r": r},
                          tag=f"c{c}/r{r}", pue=1.0 + 0.1 * r,
                          grid_ci=100.0 * (r + 1))
                 for c, cfg in enumerate(cfgs) for r in range(4)]
    records, stats = SweepRunner(cache=None, mode="device").run(scenarios)
    assert len(records) == stats.total == 8
    assert stats.config_blobs == 2
    assert stats.trace_groups == 2
    assert "keys: 2 config(s) for 8 scenario(s)" in stats.summary()
    assert [r["key"] for r in records] == \
        [config_digest(sc.cfg, _knobs(sc)) for sc in scenarios]

