"""Smoke run of the sweep's device path on a TPU, checked against the
host reference.

    python chip_smoke.py            # one chip: fig1, fig3, fig4, exp5, perf
    python chip_smoke.py --chips 4  # four chips: the perf grid, sharded

Each grid runs at full size through the sweep CLI in ``--mode device``.
Its records are then held to the device-mode contract against an
``event_loop`` run of the same scenarios in this process: the columns
the device program computes (``DEVICE_COLS``) within
``DEVICE_MODE_RTOL``, every other column and the ``derived`` string
equal. The one-chip run also checks that the host reference reproduces
the fig1 golden record bit for bit, and that a two-process vectorized
sweep (whose children start JAX on the CPU only) runs beside this
process, which holds the chip, and equals the serial records.

Everything that touches the chip runs in this one process. The script
exits non-zero, without the result line, when JAX's first device is not
a TPU. Its last line on success is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "tests")]

ONE_CHIP_GRIDS = ("fig1", "fig3", "fig4", "exp5", "perf")


def require(ok: bool, *what) -> None:
    """Fail the run (exit 1, no result line); unlike ``assert`` it
    holds under ``python -O``."""
    if not ok:
        raise SystemExit(f"chip_smoke FAILED: {what}")


def check_contract(ref, dev) -> dict:
    """Raise unless ``dev`` meets the device-mode contract against the
    host records ``ref``; return the largest relative error of each
    device-computed column."""
    from repro.sweep.device import DEVICE_COLS, DEVICE_MODE_RTOL
    require(len(ref) == len(dev), "record count", len(ref), len(dev))
    worst = dict.fromkeys(sorted(DEVICE_COLS), 0.0)
    for a, b in zip(ref, dev):
        require((a["scenario"], a["key"], a["params"])
                == (b["scenario"], b["key"], b["params"]), a["scenario"])
        require(a["metrics"].keys() == b["metrics"].keys(), a["scenario"])
        for col, va in a["metrics"].items():
            vb = b["metrics"][col]
            if col in DEVICE_COLS:
                rel = abs(va - vb) / max(abs(va), abs(vb), 1e-300)
                require(rel <= DEVICE_MODE_RTOL, a["scenario"], col, va, vb)
                worst[col] = max(worst[col], rel)
            else:
                require(va == vb, a["scenario"], col, va, vb)
    return worst


def run_cli(argv):
    """One sweep through the CLI; returns its records, derived string
    and the wall-clock phases it spent."""
    from repro.obs.spans import PROFILER
    from repro.sweep import cli

    with tempfile.TemporaryDirectory() as out:
        PROFILER.enable(reset=True)
        try:
            rc = cli.main(argv + ["--no-cache", "--quiet", "--out", out])
        finally:
            PROFILER.disable()
        require(rc == 0, "sweep CLI", argv, "returned", rc)
        payload = json.loads((Path(out) / f"{argv[0]}.json").read_text())
    return payload["records"], payload.get("derived"), PROFILER.aggregate()


def device_grid(name: str, devices: int) -> dict:
    """Run ``name`` in device mode, hold it to the contract, then run
    the device program again at the same bucket to time it warm."""
    from repro.obs.spans import PROFILER
    from repro.sweep import SWEEPS
    from repro.sweep.device import execute_device_grid
    from repro.sweep.scenarios import run_sweep

    recs, derived, phases = run_cli([name, "--mode", "device"])
    ref, _, ref_derived = run_sweep(name, mode="event_loop")
    errs = check_contract(ref, recs)
    require(derived == ref_derived, name, derived, ref_derived)
    first = {k: v["total_s"] for k, v in phases.items()
             if k in ("device.jit_compile_and_execute", "device.execute")}

    scenarios = SWEEPS[name].build(False)
    PROFILER.enable(reset=True)
    try:
        again, stats = execute_device_grid(scenarios)
    finally:
        PROFILER.disable()
    require(stats.platform == "tpu" and stats.devices == devices, stats)
    require([r["metrics"] for r in again] == [r["metrics"] for r in recs],
            name, "device records differ between two runs")
    row = {"grid": name, "scenarios": len(scenarios),
           "trace_groups": stats.trace_groups, "bucket": stats.bucket,
           "devices": stats.devices, "first_call_s": first,
           "warm_dispatch_s": PROFILER.aggregate()["device.execute"][
               "total_s"],
           "max_rel_err": max(errs.values()), "rel_err_by_col": errs}
    print(json.dumps(row), flush=True)
    return {"records": recs, "reference": ref, "scenarios": scenarios}


def one_chip() -> None:
    from test_day import GOLDEN_FIG1_QPS645

    from repro.sweep import SWEEPS, execute_scenario

    runs = {name: device_grid(name, devices=1) for name in ONE_CHIP_GRIDS}

    golden = execute_scenario(SWEEPS["fig1"].build(True)[1])["metrics"]
    bad = [k for k in GOLDEN_FIG1_QPS645 if golden[k] != GOLDEN_FIG1_QPS645[k]]
    require(not bad, "fig1 golden drifted on the host reference", bad)
    print("fig1 golden: host reference bitwise equal", flush=True)

    t0 = time.perf_counter()
    par, _, _ = run_cli(["fig3", "--mode", "vectorized", "--workers", "2"])
    ser = runs["fig3"]["reference"]
    require([r["metrics"] for r in par] == [r["metrics"] for r in ser],
            "fig3 --workers 2 differs from the serial records")
    print(f"fig3 --workers 2 vectorized beside the chip: {len(par)} "
          f"records bitwise equal to serial "
          f"({time.perf_counter() - t0:.2f}s)", flush=True)


def four_chips() -> None:
    import jax

    from repro.sweep.device import execute_device_grid

    n = jax.local_device_count()
    require(n >= 4, "--chips 4 needs four local TPU devices, found", n)
    run = device_grid("perf", devices=4)
    one, stats = execute_device_grid(run["scenarios"], max_devices=1)
    require(stats.devices == 1 and stats.platform == "tpu", stats)
    err = max(check_contract(run["reference"], one).values())
    sharded_vs_one = max(check_contract(one, run["records"]).values())
    bitwise = [r["metrics"] for r in one] == [r["metrics"]
                                             for r in run["records"]]
    print(json.dumps({"grid": "perf", "one_device_max_rel_err": err,
                      "sharded_vs_one_device_max_rel_err": sharded_vs_one,
                      "sharded_equals_one_device_bitwise": bitwise}),
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1 (default): every grid on one chip; 4: the "
                         "perf grid sharded over four chips")
    args = ap.parse_args(argv)

    # the host reference computes on JAX's CPU backend beside the chip
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"jax {jax.__version__}: {len(devices)} x {dev.platform} "
          f"({dev.device_kind})", flush=True)
    if dev.platform != "tpu":
        print(f"no TPU: JAX's first device is {dev.platform!r}",
              file=sys.stderr)
        return 1

    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
