"""Benchmark of the sweep's device path on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a deployment (``bench/configs/``) and
a traffic mix (``bench/traffic/``). A run builds sweeps from the two and
the seed, warms up every padded ``(G, S, K)`` bucket the mix reaches
(set-up), then runs whole sweeps back to back through
``SweepRunner(cache=None, mode="device")`` until their summed wall time
passes ``--seconds``, each sweep on fresh workload seeds. Afterwards a
plain host reference (``bench/harness/reference.py``) recomputes a
sample of the window's trace groups drawn from the seed (the run keeps
the records of these alone), and the comparison
(``bench/harness/compare.py``) decides ``correct``.

``--trace 0`` reports the cell's end-to-end metrics. ``--trace 1``
enables the program's wall-clock spans, records a JAX profiler trace of
the first sweeps of the window, and reports the per-layer metrics, each
read by ``bench/metrics/<metric>.py``.

The last line on stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, and with ``--trace 1``
``breakdown``). A run that finds no TPU, or fewer chips than the cell
asks for, exits with status 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                           # noqa: E402
import importlib.util                                     # noqa: E402
import json                                               # noqa: E402
import math                                               # noqa: E402
import os                                                 # noqa: E402
import shutil                                             # noqa: E402
import sys                                                # noqa: E402
import tempfile                                           # noqa: E402
from pathlib import Path                                  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH / "harness"))

import compare                                            # noqa: E402
import reference                                          # noqa: E402
import traffic                                            # noqa: E402

#: set-up warms every power-of-two stage bucket within this factor of
#: the warm-up sweep's largest trace: the window's seeds move the
#: largest trace a few percent (PERF.md, "Cells"), so a bucket edge
#: nearby is reached on some seeds and not on others
BUCKET_REACH = 1.5
#: run seed of the warm-up sweep: the same warm-up in every run, so that
#: set-up is the same work whatever ``--seed`` is; its role ("warm")
#: keeps its workload seeds apart from the window's
WARM_SEED = 0
#: traced sweeps: at least this many, and at least this much wall time
TRACE_MIN_SWEEPS, TRACE_MIN_S = 2, 2.0
ANNOTATION = "bench.sweep"
KERNEL = "_group_kernel"


class NoChip(RuntimeError):
    """The machine has no TPU, or fewer chips than the cell asks for."""


def next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (int(n) - 1).bit_length()


# ------------------------------------------------------------- manifest ---

def load_cell(root: Path, name: str):
    """(manifest, cell, deployment, mix) for cell ``name``."""
    manifest = reference.load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    dep = reference.load_json(root / configs[cell["config"]]["file"])
    mix = reference.load_json(root / "bench" / "traffic"
                              / f"{cell['traffic']}.json")
    return manifest, cell, dep, mix


def cell_metrics(manifest: dict, cell: dict, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` metrics reported in ``cell``.
    An end-to-end metric without ``workloads`` is reported in every
    cell; a per-layer metric lists the cells it is read in."""
    if kind == "end_to_end":
        return [m for m in manifest["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])]
    return [m for m in manifest["per_layer"]
            if cell["name"] in m["workloads"]]


def metric_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------------------- device ---

def require_accelerator(chips: int):
    """JAX's devices, which must be ``chips`` TPUs or more."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs


class CompileCounter:
    """Counts executables that JAX builds (a backend compile) or loads
    from its persistent cache, from its monitoring events."""
    EVENTS = ("/jax/core/compile/backend_compile_duration",)
    HITS = ("/jax/compilation_cache/cache_hits",)

    def __init__(self):
        import jax.monitoring as mon
        self.count = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, name, secs, **kw):
        if name in self.EVENTS:
            self.count += 1

    def _event(self, name, **kw):
        if name in self.HITS:
            self.count += 1


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks or [0]))


# ---------------------------------------------------------------- sweeps ---

def group_slices(groups):
    out, i = [], 0
    for g in groups:
        out.append((i, i + len(g.scenarios)))
        i += len(g.scenarios)
    return out


def run_sweep(runner, groups):
    scs = traffic.to_program(groups)
    t0 = time.perf_counter()
    recs, stats = runner.run(scs)
    return recs, stats, time.perf_counter() - t0


def stage_counts(groups, recs):
    return [int(recs[a]["metrics"]["n_stages"]) if a < len(recs) else 0
            for a, _ in group_slices(groups)]


def record_faults(groups, recs) -> int:
    """Records of a sweep that are missing, extra or out of order."""
    tags = [s["tag"] for g in groups for s in g.scenarios]
    got = [r.get("scenario") for r in recs]
    if got == tags:
        return 0
    return max(len(set(tags) ^ set(got)) + abs(len(tags) - len(got)), 1)


def setup(runner, dep, mix) -> list:
    """Warm the program at every bucket the window can reach; returns
    the buckets as (G, S, K)."""
    warm = traffic.plan_sweep(dep, mix, WARM_SEED, "warm", 0)
    recs, _, warm_s = run_sweep(runner, warm)
    pad_s = 0.0
    rows = max(stage_counts(warm, recs))
    n_groups = len(warm)
    k = max(len(g.scenarios) for g in warm)
    seen = next_pow2(rows)
    lo = next_pow2(math.ceil(rows / BUCKET_REACH))
    hi = next_pow2(math.floor(rows * BUCKET_REACH))
    buckets, b = [], lo
    while b <= hi:
        buckets.append((next_pow2(n_groups), b, next_pow2(k)))
        if b != seen:
            want = 3 * b // 4
            if not b // 2 < traffic.pad_rows(dep, want) <= b:
                raise ValueError(f"no pad sweep fits bucket {b}")
            pad_s += run_sweep(runner, traffic.plan_pad_sweep(
                dep, n_groups, k, want, b))[2]
        b *= 2
    print(f"bench: set-up sweeps: warm-up {warm_s:.3f} s, bucket pads "
          f"{pad_s:.3f} s", file=sys.stderr)
    return buckets


# ----------------------------------------------------------------- check ---

class Sample:
    """The trace groups of the window that the reference recomputes: the
    one with the largest trace, and ``k`` of the others drawn uniformly
    from the seed as they come (reservoir sampling), so that a run holds
    the records of these alone."""

    def __init__(self, k: int, seed: int):
        import numpy as np
        self.k, self.seen = k, 0
        self.rng = np.random.default_rng(traffic.derive_seed(seed, "check"))
        self.largest, self.kept = None, []

    def offer(self, rows: int, item) -> None:
        if self.largest is not None and rows <= self.largest[0]:
            self._keep(item)
            return
        if self.largest is not None:
            self._keep(self.largest[1])
        self.largest = (rows, item)

    def _keep(self, item) -> None:
        self.seen += 1
        if len(self.kept) < self.k:
            self.kept.append(item)
        else:
            j = int(self.rng.integers(self.seen))
            if j < self.k:
                self.kept[j] = item

    def items(self) -> list:
        return ([self.largest[1]] if self.largest else []) + self.kept


def check(sample: Sample, faults: int):
    """The reference over the sampled trace groups, and the structural
    faults found in every sweep. Returns (numbers, failed scenarios)."""
    failed, readings = faults, []
    for group, recs in sample.items():
        r = compare.gaps(reference.group_records(group), recs)
        readings.append(r)
        if not compare.passes(r):
            failed += len(group.scenarios)
    numbers = compare.worst(readings)
    numbers["assembly_faults"] += faults
    return numbers, failed


# ------------------------------------------------------------------ main ---

def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def environment(root: Path) -> None:
    """JAX's compile cache at a fixed path inside the checkout, and the
    CPU backend beside the chip for the program's host paths."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / "bench" / ".cache"
                                                  / "jax")
    plat = os.environ.get("JAX_PLATFORMS")
    if plat and "cpu" not in plat.split(","):
        os.environ["JAX_PLATFORMS"] = plat + ",cpu"
    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def main(argv=None, root: Path = ROOT) -> int:
    args = parse(argv)
    environment(root)
    manifest, cell, dep, mix = load_cell(root, args.workload)
    try:
        devs = require_accelerator(cell["chips"])
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    t_backend = time.perf_counter() - T_START

    import jax
    from repro.obs.spans import PROFILER
    from repro.sweep import SweepRunner
    print(f"bench: set-up: backend up at {t_backend:.3f} s, program "
          f"imported at {time.perf_counter() - T_START:.3f} s",
          file=sys.stderr)

    counter = CompileCounter()
    runner = SweepRunner(cache=None, mode="device")
    buckets = setup(runner, dep, mix)
    setup_s = time.perf_counter() - T_START

    counter.count = 0
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace \
        else None
    tracing = False
    offsets, spans = [], []
    sweeps, wall = [], 0.0
    stats = None
    sample, faults = Sample(mix["check_groups"] - 1, args.seed), 0
    try:
        if args.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tracing = True
        while wall < args.seconds:
            groups = traffic.plan_sweep(dep, mix, args.seed, "window",
                                        len(sweeps))
            if args.trace:
                PROFILER.enable(reset=True)
            if tracing:
                with jax.profiler.TraceAnnotation(ANNOTATION):
                    pc = time.perf_counter()
                    recs, stats, dt = run_sweep(runner, groups)
                offsets.append(pc)
                spans += [(n, s + PROFILER.t_origin, d, dep_)
                          for n, s, d, dep_ in PROFILER.spans()]
            else:
                recs, stats, dt = run_sweep(runner, groups)
            agg = {k: v["total_s"] for k, v in PROFILER.aggregate().items()} \
                if args.trace else {}
            stages = stage_counts(groups, recs)
            faults += record_faults(groups, recs)
            for g, (a, b), rows in zip(groups, group_slices(groups),
                                       stages):
                sample.offer(rows, (g, recs[a:b]))
            sweeps.append({"wall_s": dt, "stages": stages,
                           "scenarios": sum(len(g.scenarios)
                                            for g in groups),
                           "ks": [len(g.scenarios) for g in groups],
                           "spans": agg, "traced": tracing})
            del groups, recs
            wall += dt
            n_traced = sum(s["traced"] for s in sweeps)
            if tracing and n_traced >= TRACE_MIN_SWEEPS and sum(
                    s["wall_s"] for s in sweeps if s["traced"]) \
                    >= TRACE_MIN_S:
                jax.profiler.stop_trace()
                tracing = False
    finally:
        if tracing:
            jax.profiler.stop_trace()
        PROFILER.disable()
    compiles = counter.count
    mem = memory_peak(jax.local_devices())
    n_scen = sum(sw["scenarios"] for sw in sweeps)

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    result = {}
    if args.trace:
        import devtrace
        loaded = devtrace.load(trace_dir, ANNOTATION)
        shutil.rmtree(trace_dir, ignore_errors=True)
        offset = 0.0
        if loaded["marks"] and offsets:
            offset = loaded["marks"][0][0] - offsets[0]
        red = devtrace.reduce(loaded, spans, offset, KERNEL)
        peaks = reference.load_json(BENCH / "peaks.json")["devices"]
        if devs[0].device_kind not in peaks:
            raise KeyError(f"no peaks for {devs[0].device_kind!r} in "
                           "bench/peaks.json")
        ctx = argparse.Namespace(sweeps=sweeps, trace=red,
                                 compiles=compiles,
                                 peak=peaks[devs[0].device_kind])
        metrics = {}
        for m in cell_metrics(manifest, cell, "per_layer"):
            v = metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
    else:
        values = {"scenarios_per_s": n_scen / wall, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell_metrics(manifest, cell, "end_to_end")}

    numbers, failed = check(sample, faults)
    correct = compare.passes(numbers)
    print(f"bench: {len(sweeps)} sweeps, {n_scen} scenarios in "
          f"{wall:.3f} s; set-up {setup_s:.3f} s; buckets {buckets}; "
          f"window compiles {compiles}; last sweep: "
          f"{stats.summary() if stats else '-'}", file=sys.stderr)
    for k, lim in compare.LIMITS.items():
        print(f"check {k} {numbers[k]!r} limit {lim!r}", file=sys.stderr)
    result.update({
        "correct": correct, "attempted": n_scen, "failed": failed,
        "metrics": metrics, "device": device,
        "check": {k: {"value": numbers[k], "limit": lim}
                  for k, lim in compare.LIMITS.items()}})
    order = ["correct", "attempted", "failed", "metrics", "device",
             "breakdown", "check"]
    print(json.dumps({k: result[k] for k in order if k in result}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
