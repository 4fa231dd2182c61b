"""repro.analysis — the Examiner-style analytics layer over Memento results.

Results used to dead-end at ``ResultSet.pivot()/to_csv()``; this package is
the insight layer the related work converges on (mlrunner's Examiner,
MLXP's result queries, NSML's live monitoring):

* :mod:`repro.analysis.metrics` — declarative metric extraction: pull named
  metrics out of ``ResultSet`` rows, file-queue ``done/`` records, and raw
  log text via regex/callable :class:`MetricSpec`\\ s, normalized into typed
  :class:`MetricFrame` records (metric, value, params, host, timestamp,
  commit).
* :mod:`repro.analysis.tables` — grouped comparison tables over sweep
  results: ``compare(frame, rows=..., cols=..., agg=..., baseline=...)``
  with delta/ratio columns and markdown/CSV renderers, plus
  ``compare_frames`` for cross-run A/B diffs (one column per run).
* :mod:`repro.analysis.dash` — a stdlib-only live dashboard
  (:class:`Dashboard`, ``http.server`` + JSON/SSE endpoints) fed by
  :class:`AnalysisNotificationProvider`, which tees ``Memento.stream`` /
  ``queue_progress`` events into a JSONL journal and live aggregates
  (per-host throughput, queue depth, ETA, failure drill-down with real
  tracebacks).

CLI: ``python -m repro.analysis {table,dash}``.
"""
from .dash import AnalysisNotificationProvider, Dashboard
from .metrics import Examiner, MetricFrame, MetricRecord, MetricSpec
from .tables import Table, compare, compare_frames

__all__ = [
    "AnalysisNotificationProvider",
    "Dashboard",
    "Examiner",
    "MetricFrame",
    "MetricRecord",
    "MetricSpec",
    "Table",
    "compare",
    "compare_frames",
]
