"""Tracing hooks: in-graph named scopes, host spans and compile counts.

Everything lands on the profiler's own clock (``jax.profiler.trace``), so
one trace lines up the device's ops with the host code that issued them:

* :func:`named_span` — a zero-cost ``jax.named_scope`` wrapper the hot
  paths wear around their phases (``train/grads``, ``agg/gram``,
  ``agg/select``, ``agg/coordinate``, ``serve/verify``, ``kernel/fused``),
  so device ops and HLO op names carry readable phase names.
  Metadata-only: it never changes the computation.
* :func:`host_span` — a ``jax.profiler.TraceAnnotation`` around host code
  (``serve/admit``, ``serve/step/sample``, ...), recorded in the same
  trace as the device ops; it costs under a microsecond when no profiler
  is running.
* :func:`count_compiles` — adds the JAX compile work done inside a block
  to a dict of plain counters (``compiles``, ``compile_s``).

Span names are ``layer/phase``; a host span's keyword metadata (such as
``rid``) is kept as statistics of its trace event.
"""
from __future__ import annotations

import contextlib
from typing import Dict, Iterator

import jax

__all__ = ["count_compiles", "host_span", "named_span"]

#: the ``jax.monitoring`` events :func:`count_compiles` listens to: tracing
#: to a jaxpr, lowering to MLIR, and the backend compile (which holds the
#: persistent compilation cache's retrieval when the executable is found
#: there, ``/jax/compilation_cache/cache_retrieval_time_sec``)
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_BACKEND_COMPILE = _COMPILE_EVENTS[2]


def named_span(name: str):
    """Profiler/HLO phase annotation (``jax.named_scope`` passthrough).

    Purely metadata: operations traced under the returned context keep
    bitwise-identical lowering, they just carry ``name`` in profiler
    timelines and HLO op names.

    Args:
      name: phase label, conventionally ``layer/phase`` (e.g.
        ``"agg/gram"``).

    Returns:
      A context manager usable inside or outside traced code.
    """
    return jax.named_scope(name)


def host_span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    """A span of host code on the profiler's timeline.

    Args:
      name: ``layer/phase`` label; a child span extends its parent's
        label (``serve/admit`` holds ``serve/admit/prefill``).
      **meta: statistics recorded with the span (``rid=`` for the spans
        of one request).

    Returns:
      A ``jax.profiler.TraceAnnotation`` context manager: recorded when a
      profiler is running, a no-op otherwise.
    """
    return jax.profiler.TraceAnnotation(name, **meta)


def _union_s(spans) -> float:
    """Seconds covered by the union of ``(start, end)`` spans."""
    total, end = 0.0, None
    for s, e in sorted(spans):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


@contextlib.contextmanager
def count_compiles(counters: Dict[str, float]) -> Iterator[Dict[str, float]]:
    """Add the compile work done inside the block to ``counters``.

    ``counters["compiles"]`` grows by the executables built or loaded
    from the persistent compilation cache (one backend compile event
    each), ``counters["compile_s"]`` by the seconds spent tracing,
    lowering and compiling.  Nested events (a jaxpr traced while another
    is traced, a cache retrieval inside its backend compile) are counted
    once: the seconds are the union of the events' time spans.  The
    listener is removed when the block exits, also on an exception.  Do
    not nest two blocks over one dict: each would count the same work.

    Args:
      counters: dict holding ``"compiles"`` and ``"compile_s"``, updated
        in place.

    Returns:
      A context manager yielding ``counters``.
    """
    spans = []

    def on_span(event: str, start: float, end: float, **_kw) -> None:
        if event in _COMPILE_EVENTS:
            spans.append((start, end))
            if event == _BACKEND_COMPILE:
                counters["compiles"] += 1

    jax.monitoring.register_event_time_span_listener(on_span)
    try:
        yield counters
    finally:
        jax.monitoring.unregister_event_time_span_listener(on_span)
        counters["compile_s"] += _union_s(spans)
