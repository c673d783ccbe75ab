"""First-divergence explainer pins (``repro.obs.diff``).

(a) **dependency-order localization** — the first reported cell is the
    earliest broken link in the composition → roofline → power →
    energy → carbon → latency chain, not its downstream fallout;
(b) **tolerance-contract classification** — a device-mode run of a
    tier-1 grid diffs against the event loop entirely within
    ``DEVICE_MODE_RTOL`` (no cell is a ``regression``), and goldens
    gate bit-exact;
(c) **single-cell property** (hypothesis) — perturbing exactly one
    (row, column) cell of a stage table yields exactly that cell as
    the first divergence, classified by its true relative error;
(d) **CLI + artifacts** — ``python -m repro.obs diff`` exit semantics,
    the pinned report path ``results/obs/divergence/<name>.{md,json}``
    and the report JSON schema CI consumes.
"""
import json
import math

import numpy as np
import pytest
from _hypothesis_support import given, settings, st

from repro.obs.diff import (DIVERGENCE_DIR, REPORT_SCHEMA, _rel,
                            assert_golden, classify, column_phase,
                            diff_golden, diff_records,
                            diff_stage_tables, tolerance_contracts,
                            write_report)
from repro.sweep import SWEEPS, SweepRunner

MAIN = None  # populated lazily: repro.obs.__main__.main


def _cli(argv):
    global MAIN
    if MAIN is None:
        from repro.obs.__main__ import main as MAIN  # noqa: N806
    return MAIN(argv)


# ---------------------------------------------------------------------------
# (a) dependency order + phase mapping
# ---------------------------------------------------------------------------

def test_column_phase_mapping():
    assert column_phase("n_stages") == "composition"
    assert column_phase("avg_batch") == "composition"
    assert column_phase("duration_s") == "roofline"
    assert column_phase("throughput_qps") == "roofline"
    assert column_phase("avg_power_w") == "power"
    assert column_phase("energy_wh") == "energy"
    assert column_phase("carbon_total_g") == "carbon"
    assert column_phase("grid_ci_g_per_kwh") == "carbon"
    assert column_phase("ttft_p99_s") == "latency"
    assert column_phase("zzz") == "other"


def test_first_divergence_follows_dependency_order():
    a = {"ttft_p50_s": 1.0, "carbon_total_g": 5.0, "avg_power_w": 100.0,
         "n_stages": 10}
    b = dict(a, ttft_p50_s=9.0, carbon_total_g=50.0, n_stages=11)
    r = diff_golden(a, b)
    # composition breaks before carbon breaks before latency
    assert [c.column for c in r.cells] == \
        ["n_stages", "carbon_total_g", "ttft_p50_s"]
    assert r.first.column == "n_stages"
    assert r.first.phase == "composition"


def test_earlier_phase_wins_even_when_later_cell_diverges_more():
    a = {"avg_power_w": 100.0, "carbon_total_g": 5.0}
    b = {"avg_power_w": 101.0, "carbon_total_g": 500.0}  # 1% vs 100x
    r = diff_golden(a, b)
    assert r.first.column == "avg_power_w" and r.first.phase == "power"


# ---------------------------------------------------------------------------
# (b) classification + golden semantics
# ---------------------------------------------------------------------------

def test_tolerance_ladder_is_tightest_first():
    ladder = tolerance_contracts()
    assert [name for name, _ in ladder] == \
        ["host-bitwise", "DEVICE_MODE_RTOL", "DAY_FLUID_RTOL",
         "regression"]
    rtols = [r for _, r in ladder]
    assert rtols == sorted(rtols)
    assert rtols[0] == 0.0 and math.isinf(rtols[-1])


def test_classify_against_named_contracts():
    assert classify(0.0) == "host-bitwise"
    assert classify(1e-6) == "DEVICE_MODE_RTOL"
    assert classify(5e-6) == "DEVICE_MODE_RTOL"
    assert classify(8e-6) == "DAY_FLUID_RTOL"
    assert classify(5e-3) == "DAY_FLUID_RTOL"
    assert classify(0.5) == "regression"
    assert classify(math.inf) == "regression"


def test_rel_handles_non_numeric_and_nan():
    assert _rel(1.0, 1.0) == 0.0
    assert _rel(float("nan"), float("nan")) == 0.0
    assert _rel("a100", "a100") == 0.0
    assert math.isinf(_rel("a100", "h100"))
    assert math.isinf(_rel(1.0, float("nan")))
    assert _rel(True, False) == math.inf     # bools compare by equality
    assert _rel(100.0, 101.0) == pytest.approx(1.0 / 101.0)


def test_device_mode_diff_all_within_device_rtol():
    scenarios = SWEEPS["fig1"].build(True, n_requests=12)
    ev, _ = SweepRunner(cache=None, mode="event_loop").run(scenarios)
    dv, _ = SweepRunner(cache=None, mode="device").run(scenarios)
    r = diff_records(ev, dv, label_a="event_loop", label_b="device")
    assert r.n_scenarios == len(scenarios)
    assert not r.has_regression, r.summary()
    # every divergent cell is absorbed by the device-mode contract
    assert set(r.by_contract()) <= {"DEVICE_MODE_RTOL"}, r.summary()
    assert r.worst_contract in ("host-bitwise", "DEVICE_MODE_RTOL")


def test_diff_records_aligns_by_key_and_reports_unmatched():
    ra = [{"scenario": "s0", "key": "k0", "metrics": {"energy_wh": 1.0}},
          {"scenario": "s1", "key": "k1", "metrics": {"energy_wh": 2.0}}]
    rb = [{"scenario": "s1x", "key": "k1",
           "metrics": {"energy_wh": 2.0}},
          {"scenario": "s2", "key": "k2", "metrics": {"energy_wh": 3.0}}]
    r = diff_records(ra, rb)
    assert r.n_scenarios == 1 and not r.cells
    assert r.only_a == ["s0"] and r.only_b == ["s2"]
    assert r.has_regression        # unmatched scenarios are drift


def test_diff_golden_walks_only_pinned_keys():
    metrics = {"energy_wh": 1.0, "extra_metric": 42.0,
               "avg_power_w": 10.0}
    golden = {"energy_wh": 1.0, "avg_power_w": 10.0}
    assert diff_golden(metrics, golden).identical
    # a pinned key the run no longer produces is an inf divergence
    r = diff_golden({"energy_wh": 1.0}, golden)
    assert not r.identical and r.first.column == "avg_power_w"
    assert math.isinf(r.first.rel) and r.first.contract == "regression"


def test_assert_golden_raises_through_explainer(tmp_path):
    golden = {"avg_power_w": 100.0, "carbon_total_g": 5.0}
    run = {"avg_power_w": 101.0, "carbon_total_g": 5.0}
    with pytest.raises(AssertionError) as ei:
        assert_golden(run, golden, "demo_golden", outdir=tmp_path)
    msg = str(ei.value)
    assert "golden drift in demo_golden" in msg
    assert "avg_power_w" in msg
    assert str(tmp_path / "demo_golden.md") in msg
    assert (tmp_path / "demo_golden.json").exists()
    # a clean run neither writes nor raises
    res = assert_golden(dict(golden), golden, "clean", outdir=tmp_path)
    assert res.identical and not (tmp_path / "clean.md").exists()


# ---------------------------------------------------------------------------
# (c) stage tables + the single-cell property
# ---------------------------------------------------------------------------

def _table(rows=6):
    base = np.arange(1.0, rows + 1.0)
    return {"t_s": base * 0.5, "dur_s": np.full(rows, 0.25),
            "batch_size": base + 4.0, "kv_tokens": base * 128.0}


def test_stage_table_reports_first_divergent_row_per_column():
    a, b = _table(), _table()
    b["t_s"] = b["t_s"].copy()
    b["t_s"][[2, 4]] += 1.0          # two breaks: row 2 surfaces, 4 not
    r = diff_stage_tables(a, b)
    assert len(r.cells) == 1
    assert (r.first.column, r.first.stage) == ("t_s", 2)


def test_stage_table_row_count_mismatch_is_drift():
    a, b = _table(6), _table(5)
    r = diff_stage_tables(a, b)
    assert not r.cells               # shared prefix identical
    assert r.has_regression and r.only_a == ["rows[5:6]"]


def test_stage_table_nan_rows_are_equal():
    a, b = _table(), _table()
    a["dur_s"] = a["dur_s"].copy()
    b["dur_s"] = b["dur_s"].copy()
    a["dur_s"][3] = b["dur_s"][3] = float("nan")
    assert diff_stage_tables(a, b).identical


_COLS = ("t_s", "dur_s", "batch_size", "kv_tokens")


@settings(max_examples=30, deadline=None)
@given(col=st.integers(min_value=0, max_value=len(_COLS) - 1),
       row=st.integers(min_value=0, max_value=5),
       eps=st.sampled_from([1e-7, 3e-6, 8e-6, 3e-3, 0.5]))
def test_single_perturbed_cell_is_the_first_divergence(col, row, eps):
    a, b = _table(), _table()
    name = _COLS[col]
    b[name] = b[name].copy()
    b[name][row] = a[name][row] * (1.0 + eps)
    r = diff_stage_tables(a, b)
    assert len(r.cells) == 1         # exactly the perturbed cell
    cell = r.first
    assert (cell.column, cell.stage) == (name, row)
    assert cell.phase == column_phase(name)
    expected_rel = _rel(float(a[name][row]), float(b[name][row]))
    assert cell.rel == expected_rel
    assert cell.contract == classify(expected_rel)
    assert not r.only_a and not r.only_b


# ---------------------------------------------------------------------------
# (d) CLI exit semantics, pinned artifact path + report schema
# ---------------------------------------------------------------------------

def _records_payload(scale=1.0):
    return {"records": [
        {"scenario": "s0", "key": "k0", "params": {},
         "metrics": {"energy_wh": 10.0 * scale, "avg_power_w": 100.0,
                     "n_stages": 5}}], "derived": ""}


def test_cli_diff_records_exit_semantics(tmp_path):
    a = tmp_path / "a.json"
    b = tmp_path / "b.json"
    a.write_text(json.dumps(_records_payload()))
    b.write_text(json.dumps(_records_payload()))
    rd = tmp_path / "reports"
    assert _cli(["diff", str(a), str(b),
                 "--report-dir", str(rd)]) == 0
    # a divergence within a named contract still exits 0 ...
    b.write_text(json.dumps(_records_payload(scale=1.0 + 1e-6)))
    assert _cli(["diff", str(a), str(b),
                 "--report-dir", str(rd)]) == 0
    # ... a regression exits 1, and the same drift under --golden too
    b.write_text(json.dumps(_records_payload(scale=2.0)))
    assert _cli(["diff", str(a), str(b),
                 "--report-dir", str(rd)]) == 1


def test_cli_diff_golden_gate_is_bit_exact(tmp_path):
    run = tmp_path / "run.json"
    golden = tmp_path / "golden.json"
    run.write_text(json.dumps(_records_payload()))
    golden.write_text(json.dumps({"energy_wh": 10.0,
                                  "avg_power_w": 100.0}))
    rd = tmp_path / "reports"
    assert _cli(["diff", str(run), str(golden), "--golden",
                 "--report-dir", str(rd)]) == 0
    # ulp-level drift is a golden failure even though DEVICE_MODE_RTOL
    # would absorb it in a records diff
    run.write_text(json.dumps(_records_payload(scale=1.0 + 1e-6)))
    assert _cli(["diff", str(run), str(golden), "--golden",
                 "--report-dir", str(rd)]) == 1


def test_cli_diff_stage_table_csv(tmp_path):
    header = "t_s,dur_s,batch_size\n"
    rows_a = "".join(f"{i * 0.5},0.25,{i + 4}\n" for i in range(4))
    a = tmp_path / "a.csv"
    b = tmp_path / "b.csv"
    a.write_text(header + rows_a)
    b.write_text(header + rows_a.replace("1.5,0.25,7", "1.5,0.25,9"))
    rd = tmp_path / "reports"
    rc = _cli(["diff", str(a), str(b), "--name", "csvdiff",
               "--report-dir", str(rd)])
    assert rc == 1                   # 7 -> 9 is far outside every rtol
    r = json.loads((rd / "csvdiff.json").read_text())
    assert r["kind"] == "stage-table"
    assert r["first"]["column"] == "batch_size"
    assert r["first"]["stage"] == 3


def test_cli_diff_mixed_kinds_rejected(tmp_path):
    a = tmp_path / "a.csv"
    a.write_text("t_s\n1.0\n")
    b = tmp_path / "b.json"
    b.write_text(json.dumps(_records_payload()))
    assert _cli(["diff", str(a), str(b)]) == 2


def test_cli_perturbed_fixture_pins_artifact_path_and_schema(
        tmp_path, monkeypatch, capsys):
    """The CI failure artifact: a perturbed run diffed with default
    settings must land at ``results/obs/divergence/<name>.{md,json}``
    with the schema the workflow's inline checks consume."""
    monkeypatch.chdir(tmp_path)
    a = tmp_path / "a.json"
    b = tmp_path / "b.json"
    a.write_text(json.dumps(_records_payload()))
    b.write_text(json.dumps(_records_payload(scale=1.5)))
    rc = _cli(["diff", str(a), str(b), "--name", "pinned"])
    assert rc == 1
    assert str(DIVERGENCE_DIR) == "results/obs/divergence"
    md = tmp_path / DIVERGENCE_DIR / "pinned.md"
    js = tmp_path / DIVERGENCE_DIR / "pinned.json"
    assert md.exists() and js.exists()
    out = capsys.readouterr().out
    assert str(md.relative_to(tmp_path)) in out

    r = json.loads(js.read_text())
    assert r["schema"] == REPORT_SCHEMA == 1
    assert set(r) == {"schema", "kind", "a", "b", "identical",
                      "has_regression", "worst_contract", "n_compared",
                      "n_scenarios", "by_contract", "first", "cells",
                      "only_a", "only_b"}
    assert r["kind"] == "records" and r["has_regression"] is True
    assert r["worst_contract"] == "regression"
    assert r["first"]["column"] == "energy_wh"
    assert r["first"]["contract"] == "regression"
    assert r["by_contract"] == {"regression": 1}
    md_text = md.read_text()
    assert "# Divergence report (records)" in md_text
    assert "## Tolerance ladder" in md_text


def test_write_report_returns_both_paths(tmp_path):
    r = diff_golden({"energy_wh": 1.0}, {"energy_wh": 1.0})
    paths = write_report(r, "ok", outdir=tmp_path)
    assert paths["md"].read_text().startswith("# Divergence report")
    assert json.loads(paths["json"].read_text())["identical"] is True
