"""Span tracer: monotonic-clock spans with parent/child links.

Reference: there is no tracer in ES 2.x — the closest ancestors are the
search Profile API's timing tree (search/profile/Profiler.java) and the
task manager's start-time accounting. This module is the shared
substrate both ride here: every instrumented layer (the REST socket,
the pool hop, coordinator scatter, transport send/handle, shard
query/fetch phases, device dispatch and pulls) opens a span; the
profiler and the slow logs read the same clocks.

Two ways to open one:

- ``tracer.span(name, **tags)`` on a node's :class:`Tracer` — the roots
  (a REST request, a coalesced batch, a transport handler);
- the module-level :func:`span` — a child of the flow's active span on
  THAT span's tracer, and one shared no-op when no span is active, so
  deep layers (search, executor) need no ``node`` and library-embedded
  use pays a contextvar read.

What a span measures: wall time (``time.perf_counter()``), the CPU its
own thread burned while it was open (``time.thread_time()``), and the
wall and CPU of its direct children, so that **self = own − children**
for both. A child's wall always counts to its parent (the parent waits
while the child runs on a pool worker); a child's CPU counts only when
it ran on the parent's thread, because only then is it part of the
parent's own reading — so self CPU summed over all spans counts every
thread-second once. Who reads the CPU clock: the spans opened on a
tracer (roots and containers: a few readings a request) always; the
phases opened with :func:`span` only while a profiler session is on.
The clock is a system call (6 µs a reading on the v5e's host against
0.3 µs on bare metal: thirty readings a search moved ``search_p50_ms``
by a sixth there), and without a phase's own reading its CPU stays in
its container's self CPU, so the sum is exact either way.

The profiler's clock: while a ``jax.profiler`` session is on, an open
span also holds a ``jax.profiler.TraceAnnotation(name, t=<trace id>)``
on its own thread, so the session's ``.xplane.pb`` carries the
program's spans beside the device planes, on one clock. With no session
it is a flag test (``TraceAnnotation.is_enabled()``). This module never
imports jax: the class is looked up in ``sys.modules`` once jax is there.

Clock discipline (tpulint R007): span *durations* come from
``time.perf_counter()`` — wall clock (``time.time()``) steps under NTP
adjustments and would corrupt durations; it is used only for the
epoch-millis display timestamp a span carries for humans.

Propagation is ``contextvars``-based: it follows the request across
``FixedThreadPool`` workers (utils/threadpool.py runs each work item in
the submitter's context) and crosses the TCP transport as a wire header
(utils/wire.py::attach_ctx — the counterpart of the reference's
ThreadContext headers riding every transport message).
"""
from __future__ import annotations

import contextvars
import itertools
import os
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Any, Dict, Iterator, List, Optional, Union


@dataclass(frozen=True)
class SpanContext:
    """The propagated identity of a REMOTE parent span (a wire header)."""

    trace_id: str
    span_id: str


# the active span for THIS logical flow of execution: the open local
# Span (which knows its Tracer), or a remote SpanContext adopted from a
# wire header; restored when the span closes
_ACTIVE: contextvars.ContextVar[Union["Span", SpanContext, None]] = \
    contextvars.ContextVar("estpu-active-span", default=None)

# ids: a per-process random prefix and a process-wide counter (two
# nodes in one process must not mint the same id into a shared trace)
_PREFIX = os.urandom(3).hex()
_IDS = itertools.count(1)


def _new_id() -> str:
    return "%s%010x" % (_PREFIX, next(_IDS))


# epoch seconds at perf_counter 0, taken once: a span's display
# timestamp is its start on this scale (never fed into a duration)
_WALL_OFFSET = time.time() - time.perf_counter()  # tpulint: allow[R007]

_annotation_cls = None


def _session():
    """``jax.profiler.TraceAnnotation`` while a profiler session is on,
    else None. The class is resolved from ``sys.modules`` (never an
    import: tracing/ stays loadable by the transport layer without jax)
    and cached once found; the session test is a flag read."""
    global _annotation_cls
    cls = _annotation_cls
    if cls is None:
        jax = sys.modules.get("jax")
        cls = getattr(getattr(jax, "profiler", None), "TraceAnnotation",
                      None)
        if cls is None:  # jax absent, or still half-imported
            return None
        _annotation_cls = cls
    return cls if cls.is_enabled() else None


class Span:
    """One timed interval; its own context manager (``with`` opens it on
    the current thread and closes it there)."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "node",
                 "start", "duration", "cpu", "child_wall", "child_cpu",
                 "thread", "tags", "error", "tracer",
                 "_parent", "_token", "_cpu0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, tags: Dict[str, Any],
                 parent: Union["Span", SpanContext, None],
                 reads_cpu: bool = True):
        self.tracer = tracer
        self.name = name
        self.tags = tags
        self.node = tracer.node_id
        if parent is None:
            self.trace_id = _new_id()
            self.parent_id = None
        else:
            self.trace_id = parent.trace_id
            self.parent_id = parent.span_id or None
        # only a local open span accumulates its children
        self._parent = parent if parent.__class__ is Span else None
        self.span_id = _new_id()
        self.error: Optional[str] = None
        # perf_counter seconds at open; duration filled at close
        self.start = 0.0
        self.duration = 0.0
        # CPU seconds of the opening thread while open (thread_time);
        # 0.0 for a phase opened outside a profiler session
        self.cpu = 0.0
        self.child_wall = 0.0
        self.child_cpu = 0.0
        self.thread = 0
        self._token = None
        self._ann = None
        # thread_time at open; None = this span does not read the clock
        self._cpu0 = 0.0 if reads_cpu else None

    # -- derived ---------------------------------------------------------------

    @property
    def timestamp_ms(self) -> int:
        """Wall-clock display timestamp (epoch millis) of the open — NOT
        used for any duration math."""
        return int((self.start + _WALL_OFFSET) * 1000)

    @property
    def self_wall(self) -> float:
        """Wall seconds no direct child covers (never below 0: children
        that overlap one another can out-sum their parent)."""
        return max(0.0, self.duration - self.child_wall)

    @property
    def self_cpu(self) -> float:
        """CPU seconds of this span's thread that no same-thread child
        accounts for."""
        return max(0.0, self.cpu - self.child_cpu)

    def tag(self, **tags: Any) -> None:
        """Add tags learned while the span is open (bounded values only:
        an outcome, a size — never a body or a query)."""
        self.tags.update(tags)

    # -- context manager ---------------------------------------------------------

    def __enter__(self) -> "Span":
        next(self.tracer._started)
        self.thread = threading.get_ident()
        self._token = _ACTIVE.set(self)
        session = _session()
        if session is not None:
            # the event carries its trace id as a stat: a reader of the
            # profile can tell one request's spans from another's
            self._ann = ann = session(self.name, t=self.trace_id)
            ann.__enter__()
        # the wall interval encloses the CPU one, so cpu <= duration
        self.start = time.perf_counter()
        if self._cpu0 is not None or session is not None:
            self._cpu0 = time.thread_time()
        return self

    def __exit__(self, etype, evalue, tb) -> bool:
        if self._cpu0 is not None:
            self.cpu = time.thread_time() - self._cpu0
        self.duration = time.perf_counter() - self.start
        if self._ann is not None:
            self._ann.__exit__(etype, evalue, tb)
            self._ann = None
        _ACTIVE.reset(self._token)
        self._token = None
        if evalue is not None and self.error is None:
            self.error = f"{etype.__name__}: {evalue}"
        self.tracer._finish(self)
        return False

    def to_json(self) -> dict:
        out = {
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "node": self.node,
            "timestamp_ms": self.timestamp_ms,
            "duration_nanos": int(self.duration * 1e9),
            "self_nanos": int(self.self_wall * 1e9),
            "cpu_nanos": int(self.cpu * 1e9),
        }
        if self.tags:
            out["tags"] = dict(self.tags)
        if self.error:
            out["error"] = self.error
        return out


class _NoopSpan:
    """What :func:`span` hands out when the flow has no active span: one
    shared object, nothing allocated, nothing recorded."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, etype, evalue, tb) -> bool:
        return False

    def tag(self, **tags: Any) -> None:
        pass


NOOP = _NoopSpan()


def span(name: str, **tags: Any) -> Union[Span, _NoopSpan]:
    """A child of the flow's active span, on that span's tracer; the
    shared :data:`NOOP` when no local span is open (library-embedded
    use, most unit tests)."""
    parent = _ACTIVE.get()
    if parent.__class__ is Span:
        return Span(parent.tracer, name, tags, parent, reads_cpu=False)
    return NOOP


def record(name: str, start: float, duration: float, **tags: Any) -> None:
    """File an interval that already ended (``start`` in perf_counter
    seconds) as a finished child of the flow's active span — for a wait
    whose two ends lie on two threads, where no ``with`` block fits.
    Nothing happens when no local span is open."""
    parent = _ACTIVE.get()
    if parent.__class__ is Span:
        parent.tracer.record(name, start, duration, **tags)


def tag_active(**tags: Any) -> None:
    """Tag the flow's active local span, if there is one."""
    sp = _ACTIVE.get()
    if sp.__class__ is Span:
        sp.tags.update(tags)


def current_context() -> Union[Span, SpanContext, None]:
    """The active span (``.trace_id`` / ``.span_id``), local or remote."""
    return _ACTIVE.get()


def trace_header() -> Optional[dict]:
    """The active span as a wire-header dict (None when untraced)."""
    ctx = _ACTIVE.get()
    if ctx is None:
        return None
    return {"trace_id": ctx.trace_id, "span_id": ctx.span_id}


@contextmanager
def adopt(header: Optional[dict]) -> Iterator[None]:
    """Adopt a remote parent span from a wire header: spans opened inside
    join the remote trace as children of the sender's span."""
    if not header or not header.get("trace_id"):
        yield
        return
    token = _ACTIVE.set(SpanContext(str(header["trace_id"]),
                                    str(header.get("span_id") or "")))
    try:
        yield
    finally:
        _ACTIVE.reset(token)


class Tracer:
    """Per-node span recorder with a bounded finished-span ring.

    The ring bounds memory the way the translog-recovery event ring does
    (monitor/stats.py): counters stay exact forever, per-span detail is
    last-N. 4096 spans ≈ a few hundred requests of full detail — enough
    for the flamegraph dump to show the recent past.
    """

    def __init__(self, node_id: str = "", max_spans: int = 4096):
        self.node_id = node_id
        self._lock = threading.Lock()
        self._spans: deque = deque(maxlen=max_spans)
        # opens are counted without the lock (``next`` on a count is one
        # C call); stats() reads the count by drawing from it and
        # remembers how many draws were its own
        self._started = itertools.count()
        self._stats_draws = 0
        self.finished_total = 0
        # optional finished-span sink (monitor/metrics.py::span_sink):
        # every close also lands in a latency histogram, so the span
        # substrate doubles as continuous time-series without
        # re-instrumenting call sites
        self._sink = None

    def set_sink(self, sink) -> None:
        """``sink(span)`` called after every span close (outside the
        ring lock). It must be cheap and must not raise; a sink failure
        is swallowed — dropping one metric sample must never fail the
        request the span measured."""
        self._sink = sink

    def span(self, name: str, **tags: Any) -> Span:
        """A span on this tracer: a child of the flow's active span
        (local or adopted from the wire), else the root of a new trace.
        Use as ``with tracer.span(...) as sp:``."""
        return Span(self, name, tags, _ACTIVE.get())

    def record(self, name: str, start: float, duration: float,
               **tags: Any) -> Span:
        """A finished span from ``start`` (perf_counter seconds) lasting
        ``duration``, child of the flow's active span. It burned no CPU
        of its own (a wait) and has no profiler annotation."""
        sp = Span(self, name, tags, _ACTIVE.get(), reads_cpu=False)
        next(self._started)
        sp.thread = threading.get_ident()
        sp.start = start
        sp.duration = max(0.0, duration)
        self._finish(sp)
        return sp

    def _finish(self, sp: Span) -> None:
        parent = sp._parent
        sp._parent = None  # a ring of spans must not pin their ancestors
        with self._lock:  # the one acquisition a span costs
            self.finished_total += 1
            self._spans.append(sp)
            if parent is not None:
                parent.child_wall += sp.duration
                if parent.thread == sp.thread:
                    parent.child_cpu += sp.cpu
        sink = self._sink
        if sink is not None:
            try:
                sink(sp)
            except Exception:
                pass  # a metrics failure must never fail the request

    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._spans)

    def stats(self) -> dict:
        with self._lock:
            started = next(self._started) - self._stats_draws
            self._stats_draws += 1
            return {"started_total": started,
                    "finished_total": self.finished_total,
                    "retained": len(self._spans)}

    def chrome_trace(self) -> dict:
        """The finished-span ring in Chrome trace-event format (chrome://
        tracing, Perfetto, speedscope all read it): complete events
        ("ph": "X"), one row per originating thread. ``ts``/``dur`` are
        microseconds on THIS PROCESS's ``time.perf_counter()`` timebase —
        not the profiler's: a ``jax.profiler`` session carries the same
        spans as annotations on its own clock (ns from the session's
        start), and only there do they line up with device events."""
        events = []
        pid = os.getpid()
        for sp in self.spans():
            args = {"trace_id": sp.trace_id, "span_id": sp.span_id,
                    "node": sp.node,
                    "self_us": int(sp.self_wall * 1e6),
                    "cpu_us": int(sp.cpu * 1e6)}
            if sp.parent_id:
                args["parent_id"] = sp.parent_id
            args.update({k: v for k, v in sp.tags.items()
                         if isinstance(v, (str, int, float, bool))})
            if sp.error:
                args["error"] = sp.error
            events.append({
                "name": sp.name, "cat": "estpu", "ph": "X",
                "ts": int(sp.start * 1e6),
                "dur": max(1, int(sp.duration * 1e6)),
                "pid": pid, "tid": sp.thread, "args": args,
            })
        return {"traceEvents": events,
                "displayTimeUnit": "ms",
                "otherData": {"node": self.node_id}}
