"""Set-up warms the buckets a mix reaches: a pad sweep has exactly the
stage rows asked for, and the buckets around a warm-up trace near a
power of two are both warmed."""
import reference
import traffic

from conftest import BENCH, load_run


def dep(name="phi2-a100"):
    return reference.load_json(BENCH / "configs" / f"{name}.json")


def test_pad_sweep_rows_are_exact():
    from repro.sweep import SweepRunner
    for name in ("phi2-a100", "qwen72b-a100-tp2pp2"):
        d = dep(name)
        groups = traffic.plan_pad_sweep(d, n_groups=3, k=2, rows=700,
                                        index=1)
        recs, stats = SweepRunner(cache=None, mode="device").run(
            traffic.to_program(groups))
        rows = {r["metrics"]["n_stages"] for r in recs}
        assert rows == {traffic.pad_rows(d, 700)}
        assert stats.replayed == 3 and stats.event_loops == 0
        assert 512 < traffic.pad_rows(d, 700) <= 1024


def test_setup_warms_both_sides_of_an_edge(monkeypatch):
    run = load_run()
    d = dep()
    mix = reference.load_json(BENCH / "traffic" / "hw_plane.json")
    seen = []

    def fake_run(runner, groups):
        if groups[0].scenarios[0]["tag"].startswith("pad"):
            rows = traffic.pad_rows(d, groups[0].tree["workload"]
                                    ["n_requests"] * 33 * d["pp"])
        else:
            rows = 4100                    # just past 4096
        seen.append(rows)
        recs = [{"metrics": {"n_stages": rows}}
                for g in groups for _ in g.scenarios]
        return recs, None, 0.0
    monkeypatch.setattr(run, "run_sweep", fake_run)
    buckets = run.setup(None, d, mix)
    assert buckets == [(8, 4096, 256), (8, 8192, 256)]
    assert len(seen) == 2 and 2048 < seen[1] <= 4096


def test_window_and_warm_seeds_differ():
    d = dep()
    mix = reference.load_json(BENCH / "traffic" / "qps_sweep.json")
    seeds = {g.tree["workload"]["seed"]
             for role in ("warm", "window") for i in range(3)
             for g in traffic.plan_sweep(d, mix, 2 ** 40 + 3, role, i)}
    assert len(seeds) == 2 * 3 * 9
