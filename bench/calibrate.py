"""Readings that the limits of ``bench/harness/compare.py`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --control 3

On the chip, in one process: set-up as a run makes it, then for each
seed one whole sweep of the cell at its own size through the timed
path, compared with the plain reference over every trace group (the
program's readings, the lower ends of the limits). For the first
``--control`` seeds each control of ``reference.controls()`` (the
reference one precision step below the program in one place) takes the
program's place in turn; the control keyed by a number gives that
number's upper end. Prints one JSON line per seed and one summary line.
The benchmark's own runs never run the controls.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run                                                # noqa: E402
from run import compare, reference, traffic               # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--control", type=int, default=3,
                    help="how many of the seeds also read the control")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    run.environment(run.ROOT)
    _, cell, dep, mix = run.load_cell(run.ROOT, args.workload)
    try:
        devs = run.require_accelerator(cell["chips"])
    except run.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    from repro.sweep import SweepRunner
    runner = SweepRunner(cache=None, mode="device")
    run.setup(runner, dep, mix)

    prog, ctl = [], {k: [] for k in reference.controls()}
    for i, seed in enumerate(seeds):
        groups = traffic.plan_sweep(dep, mix, seed, "window", 0)
        recs, stats, wall = run.run_sweep(runner, groups)
        row = {"seed": seed, "wall_s": wall, "bucket_rows":
               max(run.stage_counts(groups, recs)),
               "event_loops": stats.event_loops, "replayed": stats.replayed}
        ref = [r for g in groups for r in reference.group_records(g)]
        p = compare.gaps(ref, recs)
        prog.append(p)
        row["program"] = p
        if i < args.control:
            row["control"] = {}
            for number in ctl:
                low = [r for g in groups
                       for r in reference.control_records(g, number)]
                c = compare.gaps(ref, low)
                ctl[number].append(c)
                row["control"][number] = c
        print(json.dumps(row), flush=True)
    summary = {
        "workload": args.workload, "device": devs[0].device_kind,
        "seeds": len(seeds),
        "program_max": compare.worst(prog),
        "control_min": {k: min(c[k] for c in cs)
                        for k, cs in ctl.items() if cs},
        "limits": compare.LIMITS}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
