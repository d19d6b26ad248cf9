"""Observability subsystem: tracing, tasks, profiling, slow logs.

One substrate, four consumers:

- ``tracer``   — monotonic-clock spans with parent/child links,
                 self time and thread CPU, contextvar propagation
                 (pool workers included), a profiler annotation per
                 open span, Chrome-trace dump
                 (``GET /_nodes/_local/trace``). Deep layers open
                 spans with the module-level :func:`span`, which is a
                 shared no-op when the flow has no active span.
- ``tasks``    — node-level task registry with cooperative cancellation
                 and cross-node parent links (``GET/POST /_tasks``).
- ``profiler`` — ``?profile=true`` per-shard phase timings splitting
                 device compile from device execute via jit trace counts.
- ``slowlog``  — ``index.search.slowlog.threshold.*``-driven slow logs.

This module owns the COMBINED wire context: :func:`wire_context`
captures the active span + task as one JSON-safe header dict that the
TCP transport attaches to every frame (utils/wire.py::attach_ctx), and
:func:`adopt_wire_context` restores both on the receiving node — so a
coordinator search yields one trace spanning every remote shard owner,
and cancelling a coordinator task reaches its remote children.

Import cost: no jax, no numpy — safe for the transport layer.
"""
from __future__ import annotations

from contextlib import contextmanager
from typing import Iterator, Optional

from elasticsearch_tpu.tracing import tasks as _tasks
from elasticsearch_tpu.tracing import tracer as _tracer
from elasticsearch_tpu.tracing.tasks import (TaskCancelledException,
                                             TaskRegistry, check_cancelled,
                                             current_task)
from elasticsearch_tpu.tracing.tracer import Span, Tracer, span

__all__ = [
    "Tracer", "Span", "span", "TaskRegistry", "TaskCancelledException",
    "check_cancelled", "current_task", "wire_context",
    "adopt_wire_context",
]


def wire_context() -> Optional[dict]:
    """The active span + task as one wire-header dict (None when the
    current flow is untraced and untasked)."""
    out = {}
    trace = _tracer.trace_header()
    if trace:
        out["trace"] = trace
    task = _tasks.task_header()
    if task:
        out["task"] = task
    return out or None


@contextmanager
def adopt_wire_context(ctx: Optional[dict]) -> Iterator[None]:
    """Adopt a received wire context for the duration of a handler:
    spans join the sender's trace, registered tasks become children of
    the sender's task."""
    if not ctx:
        yield
        return
    with _tracer.adopt(ctx.get("trace")):
        with _tasks.adopt_parent(ctx.get("task")):
            yield
