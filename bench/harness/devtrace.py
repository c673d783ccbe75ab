"""Reduction of a JAX profiler trace to device metrics.

Input is the ``.xplane.pb`` that ``jax.profiler`` writes; everything
below works on plain tuples so that the tests can feed it a small
recorded trace.

- Device planes are the planes named ``/device:TPU:<n>`` (or any
  ``/device:`` plane that is not the host's). On each, the ``XLA Ops``
  line holds one event per operation; where a plane has no such line,
  all of its events count.
- Busy time is the union of the operation intervals on one device,
  clipped to the traced window, averaged over the devices used.
- The window runs from the start of the first to the end of the last
  host annotation that the harness wraps around each traced sweep.
- Kernel time is the summed duration of the ``XLA Modules`` events
  whose name contains the kernel's name, averaged over the devices.
- Each idle gap between busy intervals is named after the innermost
  host span open at its midpoint.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_s, end_s)

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def op_name(hlo: str) -> str:
    """An operation's name without its HLO text: ``%fusion.3 = f32[...]
    fusion(...)`` becomes ``%fusion.3``."""
    return hlo.split(" = ", 1)[0][:120]


def load(trace_dir: str, annotation: str) -> dict:
    """Read the newest xplane under ``trace_dir`` into plain data:
    per device its op events and module events, and the host
    annotations named ``annotation`` — all as seconds on the trace's
    clock."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    devices: Dict[str, dict] = {}
    marks: List[Interval] = []
    for plane in pd.planes:
        name = plane.name
        is_dev = name.startswith("/device:") and "CPU" not in name \
            and "CUSTOM" not in name
        if is_dev:
            lines = {ln.name: ln for ln in plane.lines}
            op_lines = [lines[OPS_LINE]] if OPS_LINE in lines else \
                [ln for ln in plane.lines if ln.name != MODULES_LINE]
            ops = [(op_name(ev.name), ev.start_ns * 1e-9,
                    (ev.start_ns + ev.duration_ns) * 1e-9)
                   for ln in op_lines for ev in ln.events]
            mods = [(ev.name, ev.start_ns * 1e-9,
                     (ev.start_ns + ev.duration_ns) * 1e-9)
                    for ev in (lines[MODULES_LINE].events
                               if MODULES_LINE in lines else ())]
            devices[name] = {"ops": ops, "modules": mods}
        elif name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name == annotation:
                        marks.append((ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9))
    marks.sort()
    return {"devices": devices, "marks": marks}


def union(intervals: Sequence[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """Merged, sorted intervals, clipped to [lo, hi]."""
    out: List[list] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi] between merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def span_at(spans: Sequence[Tuple[str, float, float, int]], t: float
            ) -> str:
    """Name of the innermost host span (name, start_s, dur_s, depth)
    open at ``t``, or ``harness`` where none is."""
    best, depth = "harness", -1
    for name, s, d, dep in spans:
        if s <= t <= s + d and dep > depth:
            best, depth = name, dep
    return best


def reduce(trace: dict, spans: Sequence[Tuple[str, float, float, int]],
           offset_s: float, kernel: str, top: int = 10) -> Optional[dict]:
    """Device metrics of a loaded trace. ``spans`` are host spans on
    the host clock; ``offset_s`` is trace clock minus host clock.
    Returns None when the trace holds no device operation."""
    if not trace["marks"] or not trace["devices"]:
        return None
    lo, hi = trace["marks"][0][0], max(e for _, e in trace["marks"])
    window = hi - lo
    n = len(trace["devices"])
    busy_total = kernel_total = 0.0
    op_time: Dict[str, float] = {}
    idle: List[Tuple[str, float]] = []
    shifted = [(nm, s + offset_s, d, dep) for nm, s, d, dep in spans]
    any_op = False
    for dev in trace["devices"].values():
        busy = union([(s, e) for _, s, e in dev["ops"]], lo, hi)
        any_op = any_op or bool(busy)
        busy_total += sum(e - s for s, e in busy)
        for nm, s, e in dev["ops"]:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                op_time[nm] = op_time.get(nm, 0.0) + (e - s)
        kernel_total += sum(min(e, hi) - max(s, lo)
                            for nm, s, e in dev["modules"]
                            if kernel in nm and min(e, hi) > max(s, lo))
        for s, e in gaps(busy, lo, hi):
            idle.append((span_at(shifted, 0.5 * (s + e)), e - s))
    if not any_op:
        return None
    idle.sort(key=lambda x: -x[1])
    ops = sorted(op_time.items(), key=lambda x: -x[1])
    return {
        "busy_s": busy_total / n,
        "window_s": window,
        "kernel_s": kernel_total / n,
        "devices": n,
        "device_ops": [[k, v / n] for k, v in ops[:top]],
        "idle_gaps": [[k, v] for k, v in idle[:top]],
    }
