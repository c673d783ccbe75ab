"""Shared set-up of the benchmark's own tests: run them with

    JAX_PLATFORMS=cpu python -m pytest bench/tests

They import the harness modules by name and the program from ``src``.
"""
import importlib.util
import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH / "harness")):
    if p not in sys.path:
        sys.path.insert(0, p)


def load_run():
    """``bench/run.py`` as a module (it is a script, not a package)."""
    spec = importlib.util.spec_from_file_location("bench_run",
                                                  BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


#: a small mix of each kind, with every field the generator reads
TINY = {
    "qps": {"points": {"workload.qps": [1.0, 6.45, 12.6]},
            "report": {"pue": [1.2], "grid_ci": [250.0]},
            "seeding": "per_point", "check_groups": 3, "n": 24,
            "arrival": "poisson", "lens": (128, 1024)},
    "plane": {"points": {"device": ["a100", "h100"], "tp_factor": [1, 2]},
              "report": {"pue": [1.0, 1.5], "grid_ci": [25.0, 700.0]},
              "seeding": "shared", "check_groups": 4, "n": 16,
              "arrival": "uniform", "lens": (64, 256)},
}


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout root holding BENCHMARK.json with one small cell of
    each mix kind on the phi-2 deployment."""
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    shutil.copy(BENCH / "configs" / "phi2-a100.json",
                tmp_path / "bench" / "configs")
    base = json.loads((BENCH / "traffic" / "qps_sweep.json").read_text())
    cells = []
    for name, t in TINY.items():
        mix = dict(base, name=name, points=t["points"], report=t["report"],
                   seeding=t["seeding"], check_groups=t["check_groups"])
        mix["workload"] = dict(base["workload"], n_requests=t["n"],
                               arrival=t["arrival"], qps=0.5,
                               min_len=t["lens"][0], max_len=t["lens"][1])
        (tmp_path / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
        cells.append({"name": f"tiny.{name}", "config": "phi2-a100",
                      "traffic": name, "chips": 1, "why": "test"})
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    manifest["workloads"] = cells
    for m in manifest["per_layer"]:
        m["workloads"] = [c["name"] for c in cells]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    return tmp_path


@pytest.fixture
def run_on_cpu(monkeypatch):
    """The harness with its look for a chip skipped and a stand-in peak
    for the CPU, so the rest of a run can be driven here."""
    run = load_run()
    import jax
    monkeypatch.setattr(run, "require_accelerator",
                        lambda chips: jax.devices())
    load = run.reference.load_json

    def with_cpu_peak(path):
        d = load(path)
        if Path(path).name == "peaks.json":
            d["devices"]["cpu"] = {"hbm_bytes_per_s": 819e9}
        return d
    monkeypatch.setattr(run.reference, "load_json", with_cpu_peak)
    return run


def result_line(capsys) -> dict:
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])
