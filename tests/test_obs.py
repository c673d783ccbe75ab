"""Observability pins: probe neutrality, trace schema, dual clocks.

The ``repro.obs`` contract this file pins:

(a) **probe neutrality** — attaching a ``FlightRecorder`` to the sweep
    runner or the day driver produces records/summaries bit-identical
    to probe-off runs (fig1 single-site, fleet/shift multi-site, and a
    day-smoke hybrid window);
(b) **Chrome trace schema** — the export is valid JSON, metadata
    events lead, timestamps are monotonic, and wall-clock ``B``/``E``
    duration events pair and nest;
(c) the wall-clock ``SpanProfiler`` (nesting, aggregation, cross-
    process merge, disabled no-op) and the stderr logger;
(d) cache-effectiveness counters in the sweep summary line.
"""
import json
import logging

import numpy as np
import pytest

from repro.configs.paper_models import LLAMA3_8B
from repro.fleet.config import FleetConfig, SiteConfig
from repro.fleet.day import run_fleet_day
from repro.obs.chrometrace import (ADMISSION_PID, WALL_PID,
                                   chrome_trace_events,
                                   write_chrome_trace, write_csvs)
from repro.obs.log import configure, get_logger
from repro.obs.probe import NULL_PROBE, Probe, SiteIndexProbe
from repro.obs.recorder import (STAGE_FIELDS, ColumnBuilder,
                                FlightRecorder)
from repro.obs.spans import PROFILER, SpanProfiler
from repro.sim.hybrid import DayConfig
from repro.sim.requests import WorkloadConfig
from repro.sim.scheduler import SchedulerConfig
from repro.sweep import SWEEPS, ResultCache, SweepRunner
from repro.sweep.runner import execute_scenario


@pytest.fixture(autouse=True)
def _profiler_clean():
    """The module-level PROFILER is process-wide state: leave it
    disabled and empty regardless of what a test does."""
    yield
    PROFILER.disable()
    PROFILER.reset()


# ---------------------------------------------------------------------------
# (a) probe neutrality: probe-attached == probe-off, bitwise
# ---------------------------------------------------------------------------

def _assert_records_bit_identical(ev, ve):
    assert len(ev) == len(ve)
    for a, b in zip(ev, ve):
        assert a["scenario"] == b["scenario"]
        assert a["params"] == b["params"]
        assert a["key"] == b["key"]
        assert a["metrics"] == b["metrics"], a["scenario"]


@pytest.mark.parametrize("sweep,n_req", [("fig1", 16), ("fleet", 10),
                                         ("shift", 10)])
def test_probe_attached_records_bit_identical(sweep, n_req):
    scenarios = SWEEPS[sweep].build(True, n_requests=n_req)
    rec = FlightRecorder(resolution_s=30.0)
    off, _ = SweepRunner(cache=None, mode="event_loop").run(scenarios)
    on, _ = SweepRunner(cache=None, mode="event_loop",
                        probe=rec).run(scenarios)
    _assert_records_bit_identical(off, on)
    # the probe did observe the runs it rode along
    assert rec.n_stage_events > 0
    assert rec.timelines
    tl = next(iter(rec.timelines.values()))
    assert float(np.max(tl["power_w"])) > 0.0


def day_cfg(n=1200, span=900.0):
    wl = WorkloadConfig(
        n_requests=n, qps=n / span, min_len=192, max_len=192, seed=0,
        envelope="sinusoidal", envelope_amplitude=0.3,
        envelope_period_h=span / 3600.0, burst_gain=2.5,
        burst_mean_s=span / 15.0, burst_idle_mean_s=span / 2.5)
    return FleetConfig(
        model=LLAMA3_8B,
        sites=(SiteConfig(name="s0", ci_trace="caiso-night",
                          scheduler=SchedulerConfig(batch_cap=64)),),
        workload=wl, router="round_robin",
        day=DayConfig(mode="hybrid", epoch_s=300.0, pilot_requests=128,
                      warmup_requests=32, util_threshold=0.6))


def test_probe_attached_day_summary_bit_identical():
    cfg = day_cfg()
    rec = FlightRecorder(resolution_s=60.0)
    off = run_fleet_day(cfg).summary()
    on = run_fleet_day(cfg, probe=rec).summary()
    assert off == on
    # epoch evals + the site rollup timeline came through site-tagged
    assert rec.epochs and all(e["site"] == 0 for e in rec.epochs)
    assert 0 in rec.timelines
    assert rec.n_stage_events > 0


def test_null_probe_run_bit_identical():
    scenarios = SWEEPS["fig1"].build(True, n_requests=16)
    off, _ = SweepRunner(cache=None, mode="event_loop").run(scenarios)
    on, _ = SweepRunner(cache=None, mode="event_loop",
                        probe=NULL_PROBE).run(scenarios)
    _assert_records_bit_identical(off, on)


def test_probe_rejected_in_device_mode():
    with pytest.raises(ValueError, match="device"):
        SweepRunner(cache=None, mode="device", probe=NULL_PROBE)


def test_site_index_probe_retags_every_hook():
    rec = FlightRecorder()
    wrapped = SiteIndexProbe(rec, site=3)

    class _Sched:
        waiting, running, kv_tokens = (), (1, 2), 64

    wrapped.on_stage(1.0, 0.5, 0, 0, _Sched(), 10, 2, 2)
    wrapped.on_route(1.0, 7, 0)
    wrapped.on_scale(2.0, 0, 2, 1, "up")
    wrapped.on_requests(np.array([0.0]), np.array([5.0]))
    stages = rec.stage_table()
    assert int(stages["site"][0]) == 3
    assert int(rec.route_table()["site"][0]) == 3
    assert rec.scales[0]["site"] == 3
    assert rec._requests[0][0] == 3


def test_backlog_series_counts_held_requests():
    rec = FlightRecorder()
    rec.on_requests(np.array([0.0, 1.0, 2.0]),
                    np.array([10.0, 1.0, 12.0]))  # 2 of 3 deferred
    t, depth = rec.backlog_series()
    assert list(t) == [0.0, 2.0, 10.0, 12.0]
    assert list(depth) == [1, 2, 1, 0]


def test_column_builder_grows_and_casts():
    cb = ColumnBuilder(("a", "b"), int_fields=("b",), capacity=2)
    for i in range(9):  # forces two doublings
        cb.append(i * 0.5, i)
    out = cb.build()
    assert len(cb) == 9
    assert out["a"].dtype == np.float64 and out["b"].dtype == np.int64
    assert list(out["b"]) == list(range(9))


# ---------------------------------------------------------------------------
# (b) Chrome trace schema
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded_fleet():
    """One fleet scenario recorded with both clocks."""
    sc = SWEEPS["fleet"].build(True, n_requests=10)[0]
    rec = FlightRecorder(resolution_s=30.0)
    PROFILER.enable(reset=True)
    try:
        with PROFILER.span("execute_scenario"):
            execute_scenario(sc, probe=rec)
    finally:
        PROFILER.disable()
    events = chrome_trace_events(rec, PROFILER)
    yield rec, events
    PROFILER.reset()


def test_trace_is_valid_json_with_leading_metadata(recorded_fleet):
    _, events = recorded_fleet
    json.loads(json.dumps(events))  # round-trips
    phs = [e["ph"] for e in events]
    n_meta = phs.count("M")
    assert n_meta > 0 and all(p == "M" for p in phs[:n_meta])
    assert "M" not in phs[n_meta:]


def test_trace_timestamps_monotonic(recorded_fleet):
    _, events = recorded_fleet
    ts = [e["ts"] for e in events if e["ph"] != "M"]
    assert ts == sorted(ts)


def test_trace_wall_spans_pair_and_nest(recorded_fleet):
    _, events = recorded_fleet
    stack = []
    for e in events:
        if e.get("pid") != WALL_PID or e["ph"] not in ("B", "E"):
            continue
        if e["ph"] == "B":
            stack.append(e["name"])
        else:
            assert stack and stack.pop() == e["name"]
    assert not stack  # every B closed


def test_trace_carries_sim_counters_and_stages(recorded_fleet):
    rec, events = recorded_fleet
    counter_names = {e["name"] for e in events if e["ph"] == "C"}
    assert "power_w" in counter_names and "devices" in counter_names
    assert any(n.startswith("queue r") for n in counter_names)
    n_stage_x = sum(1 for e in events
                    if e["ph"] == "X" and e["name"] == "stage")
    assert n_stage_x == rec.n_stage_events
    # routing instants live on the admission track
    assert any(e.get("pid") == ADMISSION_PID for e in events)


def test_trace_and_csv_files(tmp_path, recorded_fleet):
    rec, _ = recorded_fleet
    info = write_chrome_trace(tmp_path / "t.json", rec, PROFILER)
    payload = json.loads((tmp_path / "t.json").read_text())
    assert len(payload["traceEvents"]) == info["n_events"] > 0
    paths = write_csvs(tmp_path / "csv", rec, PROFILER)
    names = {p.name for p in paths}
    assert {"stages.csv", "routes.csv", "spans.csv"} <= names
    header = (tmp_path / "csv" / "stages.csv").read_text() \
        .splitlines()[0]
    assert tuple(header.split(",")) == STAGE_FIELDS


# ---------------------------------------------------------------------------
# (c) wall-clock profiler + logger
# ---------------------------------------------------------------------------

def test_span_profiler_nesting_and_aggregate():
    prof = SpanProfiler()
    prof.enable()
    with prof.span("outer"):
        with prof.span("inner"):
            pass
        with prof.span("inner"):
            pass
    prof.disable()
    spans = prof.spans()
    assert [(n, d) for n, _, _, d in spans] == \
        [("outer", 0), ("inner", 1), ("inner", 1)]
    agg = prof.aggregate()
    assert agg["inner"]["count"] == 2 and agg["outer"]["count"] == 1
    assert agg["outer"]["total_s"] >= agg["inner"]["total_s"]
    assert "outer" in prof.format_aggregate()


def test_span_profiler_disabled_records_nothing():
    prof = SpanProfiler()
    with prof.span("phase"):
        pass
    assert prof.spans() == [] and prof.aggregate() == {}


#: every span of the device path, with the span it nests in
DEVICE_SPAN_PARENTS = {
    "sweep.key_digest": "cache.lookup",
    "device.group": "device.grid",
    "device.acquire_traces": "device.grid",
    "device.pad_stack": "device.grid",
    "device.execute": "device.grid",
    "device.h2d": "device.execute",
    "device.run": "device.execute",
    "device.d2h": "device.execute",
    "device.assemble_records": "device.grid",
}


def _parent(spans, child):
    """Name of the span one level up that encloses ``child``."""
    name, t0, dur, depth = child
    for n, s, d, dep in spans:
        if dep == depth - 1 and s <= t0 and t0 + dur <= s + d:
            return n
    return None


def _device_sweep():
    return SweepRunner(cache=None, mode="device").run(
        SWEEPS["fig1"].build(True, n_requests=8))


def test_device_sweep_spans_nest_under_their_parents():
    _device_sweep()                     # compile outside the profile
    PROFILER.enable(reset=True)
    _device_sweep()
    PROFILER.disable()
    spans = PROFILER.spans()
    names = [n for n, *_ in spans]
    for child, parent in DEVICE_SPAN_PARENTS.items():
        mine = [sp for sp in spans if sp[0] == child]
        assert len(mine) == 1, (child, names)
        assert _parent(spans, mine[0]) == parent, (child, spans)
    assert "device.jit_compile_and_execute" not in names


def test_disabled_profiler_records_nothing_in_a_device_sweep():
    PROFILER.reset()
    _device_sweep()
    assert PROFILER.spans() == [] and PROFILER.aggregate() == {}


def test_spans_are_host_events_of_a_profiler_trace(tmp_path):
    """Each program span is a ``TraceAnnotation``: a ``jax.profiler``
    trace holds one host event per span, with the same name, nested
    as the spans are, on the trace's own clock."""
    import collections
    import glob

    import jax
    from jax.profiler import ProfileData

    _device_sweep()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    PROFILER.enable(reset=True)
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _device_sweep()
    finally:
        jax.profiler.stop_trace()
        PROFILER.disable()
    want = collections.Counter(n for n, *_ in PROFILER.spans())
    pb = sorted(glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                              / "*.xplane.pb")))[-1]
    events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
              for plane in ProfileData.from_file(pb).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name in want]
    assert collections.Counter(n for n, _, _ in events) == want
    at = {n: (s, e) for n, s, e in events}
    for child, parent in DEVICE_SPAN_PARENTS.items():
        assert at[parent][0] <= at[child][0] <= at[child][1] \
            <= at[parent][1], (child, parent)


def test_span_profiler_merge_folds_worker_aggregates():
    prof = SpanProfiler()
    prof.enable()
    with prof.span("p"):
        pass
    prof.disable()
    prof.merge({"p": {"count": 2, "total_s": 1.5},
                "q": {"count": 1, "total_s": 0.25}})
    agg = prof.aggregate()
    assert agg["p"]["count"] == 3 and agg["q"]["count"] == 1
    # merged phases carry no span events of their own
    assert [n for n, *_ in prof.spans()] == ["p"]


def test_logger_namespacing_and_verbosity():
    assert get_logger("sweep").name == "repro.sweep"
    assert get_logger("repro.sweep").name == "repro.sweep"
    root = configure(verbosity=-1)
    try:
        assert root.level == logging.WARNING
        assert configure(verbosity=0).level == logging.INFO
        assert configure(verbosity=2).level == logging.DEBUG
        # idempotent: reconfiguring replaces rather than stacks
        configure(verbosity=0)
        assert len(root.handlers) == 1
    finally:
        for h in list(root.handlers):
            root.removeHandler(h)


def test_logger_color_follows_no_color_and_tty(monkeypatch):
    import io

    from repro.obs.log import _ColorFormatter, _use_color

    plain = io.StringIO()                       # not a tty
    monkeypatch.delenv("NO_COLOR", raising=False)
    assert not _use_color(plain)

    class _Tty(io.StringIO):
        def isatty(self):
            return True

    assert _use_color(_Tty())
    monkeypatch.setenv("NO_COLOR", "1")         # NO_COLOR beats tty
    assert not _use_color(_Tty())
    monkeypatch.delenv("NO_COLOR", raising=False)

    # redirected streams get a plain formatter end to end
    root = configure(verbosity=0, stream=plain)
    try:
        get_logger("sweep").warning("beware")
        assert "beware" in plain.getvalue()
        assert "\x1b[" not in plain.getvalue()
    finally:
        for h in list(root.handlers):
            root.removeHandler(h)

    # the color formatter wraps WARNING+ and leaves INFO bare
    fmt = _ColorFormatter("%(message)s")
    rec = logging.LogRecord("repro", logging.WARNING, __file__, 0,
                            "boom", None, None)
    assert fmt.format(rec) == "\x1b[33mboom\x1b[0m"
    rec.levelno = logging.INFO
    assert fmt.format(rec) == "boom"


def test_sweep_summary_reports_peak_rss():
    scenarios = SWEEPS["fig1"].build(True, n_requests=8)
    _, stats = SweepRunner(cache=None, mode="event_loop").run(scenarios)
    assert stats.peak_rss_mb > 0.0              # Linux: ru_maxrss in KB
    assert "peak RSS" in stats.summary()
    assert f"{stats.peak_rss_mb:.0f} MB" in stats.summary()


def test_probe_base_hooks_are_noops():
    p = Probe()
    p.on_run_begin("tag")
    p.on_stage(0.0, 0.1, 0, 0, None, 0, 0, 0)
    p.on_complete(0.0, 0, 0, [])
    p.on_route(0.0, 0, 0)
    p.on_scale(0.0, 0, 1, 0, "up")
    p.on_requests([], [])
    p.on_epoch_eval(0, None)


# ---------------------------------------------------------------------------
# (d) cache effectiveness counters
# ---------------------------------------------------------------------------

def test_sweep_stats_report_cache_effectiveness(tmp_path):
    scenarios = SWEEPS["fig1"].build(True, n_requests=16)
    cache = ResultCache(tmp_path / "cache")
    _, cold = SweepRunner(cache=cache, mode="event_loop").run(scenarios)
    assert cold.cache_attached
    assert cold.cache_miss == len(scenarios) and cold.cache_memo == 0
    _, warm = SweepRunner(cache=cache, mode="event_loop").run(scenarios)
    assert warm.cache_memo == len(scenarios) and warm.cache_miss == 0
    assert f"cache {len(scenarios)} memo / 0 disk / 0 miss" \
        in warm.summary()
    # a fresh process-equivalent (empty memo) serves off disk
    disk_cache = ResultCache(tmp_path / "cache")
    _, disk = SweepRunner(cache=disk_cache,
                          mode="event_loop").run(scenarios)
    assert disk.cache_disk == len(scenarios) and disk.cache_miss == 0
    _, bare = SweepRunner(cache=None, mode="event_loop").run(scenarios)
    assert not bare.cache_attached and "memo" not in bare.summary()


# ---------------------------------------------------------------------------
# flight-recorder CLI
# ---------------------------------------------------------------------------

def test_obs_cli_list_and_record(tmp_path, capsys):
    from repro.obs.__main__ import main

    assert main(["list", "--smoke"]) == 0
    assert "fig1" in capsys.readouterr().out

    out = tmp_path / "fig1.trace.json"
    rc = main(["--quiet", "record", "fig1", "--smoke",
               "--n-requests", "8", "--resolution", "30",
               "--out", str(out), "--csv-dir", str(tmp_path / "csv")])
    assert rc == 0
    summary = json.loads(capsys.readouterr().out)
    assert summary["stage_events"] > 0
    assert summary["trace_events"] > 0 and out.exists()
    assert (tmp_path / "csv" / "stages.csv").exists()


def test_obs_cli_unknown_sweep_fails(capsys):
    from repro.obs.__main__ import main

    assert main(["--quiet", "record", "nope"]) == 2
    assert "unknown sweep" in capsys.readouterr().err
