"""Profiler spans of the serving path.

A span is a ``jax.profiler.TraceAnnotation``: it lands in the profiler's
own trace (``jax.profiler.trace`` / ``start_trace``, or a capture through
``jax.profiler.start_server``), on the same clock as the device ops, and
costs one enabled-check when no profiler records.  Counts ride along as
the span's arguments; a list of ids becomes one ``;``-joined string.
Arguments are formatted only while a profiler records.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation


def _arg(value):
    if isinstance(value, (list, tuple)):
        return ";".join(str(v) for v in value)
    return value


def span(name: str, **args) -> TraceAnnotation:
    """A ``TraceAnnotation`` named ``name`` carrying ``args``."""
    if args and TraceAnnotation.is_enabled():
        return TraceAnnotation(name, **{k: _arg(v) for k, v in args.items()})
    return TraceAnnotation(name)
