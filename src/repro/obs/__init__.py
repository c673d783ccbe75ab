"""Dual-clock observability: sim-time flight recorder + wall-clock
sweep profiler, online physics-invariant auditing, and a
first-divergence explainer (``python -m repro.obs`` for the
record/diff CLI).

Two clocks, one contract:

* **sim-time** — the opt-in ``Probe`` protocol threaded through the
  event loop and the fleet/day drivers; ``FlightRecorder`` logs queue
  depth, batch occupancy, KV usage, routing, autoscaling, epoch
  evaluations and per-bin Eq. 1-5 power/CI/carbon timelines. Probe-off
  runs are bitwise identical to un-instrumented ones (neutrality,
  pinned by tests/test_obs.py).
* **wall-clock** — the ``SpanProfiler`` (module-global ``PROFILER``)
  over the sweep pipeline: cache lookups and key digests, trace
  grouping, event-loop runs, stacked passes, the device mode's
  pad/stack, host-device transfers, program run and record assembly,
  worker fan-out. Enabled, each span is also a
  ``jax.profiler.TraceAnnotation``, on the device trace's clock.

On top of the probe layer:

* ``AuditProbe`` (``repro.obs.audit``) streams conservation-law and
  sanity checks — request/token conservation, Eq. 2-3 and Eq. 4-5
  closure, KV-budget/monotonic-clock invariants, power-range,
  autoscaler legality — into a structured ``AuditReport``; stack it
  with a recorder via ``MultiProbe``.
* ``repro.obs.diff`` localizes the *first* divergent (scenario,
  stage, column) cell between two runs — sweep records, golden
  records or flight traces — and classifies every divergence against
  the repo's named tolerance contracts.

Traces serialize to Perfetto-viewable Chrome trace-event JSON and tidy
CSV (``repro.obs.chrometrace``); divergence reports to markdown + JSON
under ``results/obs/divergence/``.
"""
from repro.obs.audit import (AuditError, AuditProbe, AuditReport,
                             AuditViolation)
from repro.obs.chrometrace import (chrome_trace_events, write_chrome_trace,
                                   write_csvs)
from repro.obs.diff import (DiffResult, DivergentCell, assert_golden,
                            diff_golden, diff_records, diff_stage_tables,
                            write_report)
from repro.obs.log import configure as configure_logging
from repro.obs.log import get_logger
from repro.obs.probe import (NULL_PROBE, MultiProbe, NullProbe, Probe,
                             SiteIndexProbe)
from repro.obs.recorder import ColumnBuilder, FlightRecorder
from repro.obs.spans import PROFILER, SpanProfiler

__all__ = [
    "Probe", "NullProbe", "NULL_PROBE", "MultiProbe", "SiteIndexProbe",
    "FlightRecorder", "ColumnBuilder",
    "AuditProbe", "AuditReport", "AuditViolation", "AuditError",
    "DiffResult", "DivergentCell", "diff_records", "diff_golden",
    "diff_stage_tables", "assert_golden", "write_report",
    "SpanProfiler", "PROFILER",
    "chrome_trace_events", "write_chrome_trace", "write_csvs",
    "get_logger", "configure_logging",
]
