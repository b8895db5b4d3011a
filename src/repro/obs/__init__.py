"""Observability: aggregation forensics, metrics schema, tracing, export.

The telemetry layer of the Byzantine runtime (see
docs/observability.md).  Four pieces, all importable from here:

* ``repro.obs.buffer`` — the jit-compatible :class:`MetricsBuffer`
  forensics ring carried in ``AggState.obs`` and its host-side
  :func:`drain`;
* ``repro.obs.forensics`` — the ``obs-<base>`` registry family
  (:func:`make_obs`) recording one :class:`AggDiagnostics` row per
  aggregation call with the base rule's data path bitwise untouched;
* ``repro.obs.detect`` — host-side attack detectors (selection-entropy
  collapse, suspicion ranking, ε-margin trajectory);
* ``repro.obs.schema`` / ``repro.obs.trace`` / ``repro.obs.export`` —
  the shared train-metrics schema, the tracing hooks (in-graph named
  scopes, host spans on the profiler's clock, compile counts), and
  JSONL/CSV writers.

Enable end to end with ``AggSpec(..., telemetry=True)`` — every train /
async / serve step then aggregates through ``spec.effective_gar``
(``obs-<gar>``) and the carried state's ring is drained by the
trainers' / engine's ``telemetry()`` methods.
"""
from repro.obs.buffer import (DEFAULT_OBS_CAPACITY, AggDiagnostics,
                              MetricsBuffer, drain, init_metrics_buffer,
                              push_record)
from repro.obs.detect import (margin_trajectory, selection_collapsed,
                              selection_entropy, suspicion_scores)
from repro.obs.export import (read_jsonl, to_jsonable, write_csv,
                              write_jsonl)
from repro.obs.forensics import (dense_diagnostics, make_obs, obs_name,
                                 tree_diagnostics)
from repro.obs.schema import (METRIC_SCHEMA, async_extras, core_metrics,
                              global_norm, selection_weight)
from repro.obs.trace import count_compiles, host_span, named_span

__all__ = [
    "AggDiagnostics",
    "DEFAULT_OBS_CAPACITY",
    "METRIC_SCHEMA",
    "MetricsBuffer",
    "async_extras",
    "core_metrics",
    "count_compiles",
    "dense_diagnostics",
    "drain",
    "global_norm",
    "host_span",
    "init_metrics_buffer",
    "make_obs",
    "margin_trajectory",
    "named_span",
    "obs_name",
    "push_record",
    "read_jsonl",
    "selection_collapsed",
    "selection_entropy",
    "selection_weight",
    "suspicion_scores",
    "to_jsonable",
    "tree_diagnostics",
    "write_csv",
    "write_jsonl",
]
