"""Every name in BENCHMARK.json resolves to its own file, found by name."""
import importlib.util
import json

from conftest import BENCH, ROOT, load_run


def manifest():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_cells_find_their_deployment_and_mix():
    run = load_run()
    m = manifest()
    for cell in m["workloads"]:
        _, c, dep, mix = run.load_cell(ROOT, cell["name"])
        assert c is not None and dep["name"] == cell["config"]
        assert mix["name"] == cell["traffic"]
        assert (BENCH / "traffic" / f"{cell['traffic']}.json").is_file()
    for c in m["configs"]:
        assert (ROOT / c["file"]).is_file()


def test_every_per_layer_metric_has_a_reader_that_reads_nothing_empty():
    run = load_run()
    import argparse
    empty = argparse.Namespace(sweeps=[], trace=None, compiles=0, peak={})
    for metric in manifest()["per_layer"]:
        read = run.metric_reader(metric["name"])
        v = read(empty)
        assert v is None or v == 0, metric["name"]


def test_metrics_per_cell():
    run = load_run()
    m = manifest()
    for cell in m["workloads"]:
        e2e = {x["name"] for x in run.cell_metrics(m, cell, "end_to_end")}
        assert {"scenarios_per_s", "setup_s"} <= e2e
        layer = run.cell_metrics(m, cell, "per_layer")
        assert layer and all(x["moves"] in e2e for x in layer)


def test_families_and_hardware_found_by_name():
    import reference
    assert reference.family("dense").param_count is not None
    assert set(reference.hardware()) >= {"a100", "h100"}
    spec = importlib.util.find_spec("traffic")
    assert spec is not None


def test_unknown_cell_refused():
    import pytest
    run = load_run()
    with pytest.raises(KeyError):
        run.load_cell(ROOT, "no.such_cell")
