"""Runtime retrace auditor — tpulint's dynamic counterpart.

Static analysis (R001) catches the *patterns* that cause recompile storms;
this module catches the storms themselves: it wraps ``jax.jit`` so every
(re)trace of a jitted callable increments a counter, letting benches and
tests assert "steady state traces nothing" instead of inferring it from
latency jitter.

How counting works: ``jax.jit(f)`` executes ``f``'s Python body exactly
once per trace (cache miss), so a counting shim around ``f`` *is* a trace
counter. Each ``jax.jit(...)`` construction gets its own key
(``qualname#seq``) — a cached program re-called with known shapes counts
nothing; a new shape class counts one; the R001 jit-in-loop bug shows up
as an ever-growing key population. Callables jitted *inside* an outer
trace (e.g. a jitted helper vmapped by another jitted fn) count once per
outer trace; that inflation is deterministic and disappears in
steady-state deltas, which is what the assertions use.

Install order matters: the codebase binds ``jax.jit`` at import time
(``@partial(jax.jit, static_argnames=...)``), so call ``install()``
*before* importing ``elasticsearch_tpu``/``bench`` (as
``elasticsearch_tpu/tracing/retrace.py`` does from the package inits),
or use the ``trace_audit()`` context manager around code that builds its
programs inside (program factories, tests).
"""
from __future__ import annotations

import functools
import itertools
import threading
from contextlib import contextmanager
from typing import Dict, List, Optional


class TraceBudgetExceeded(AssertionError):
    """A jitted callable retraced more often than the declared bound."""


class TraceAuditor:
    """Per-program trace counters with snapshot/delta helpers."""

    def __init__(self, max_traces: Optional[int] = None):
        self.max_traces = max_traces
        self._lock = threading.Lock()
        self._counts: Dict[str, int] = {}
        # optional per-trace observer: called OUTSIDE the lock as
        # reporter(key, args, kwargs) with the traced call's abstract
        # arguments, so a registry can attribute the compile to a
        # (program, shapes) key (elasticsearch_tpu tracing/retrace.py
        # wires this into the device-program observatory). A reporter
        # failure must never break tracing — exceptions are swallowed.
        self._reporter = None
        # per-thread totals: tracing runs synchronously on the calling
        # thread, so this attributes each trace to the request that paid
        # it — the profiler's compile/execute split reads it to stay
        # correct under concurrent searches (a neighbor thread's
        # first-call compile must not misclassify THIS thread's cached
        # execution). LRU-bounded: a thread-per-connection server would
        # otherwise grow one entry per thread that ever traced, forever.
        # Eviction (and ident reuse) is safe for the snapshot/delta
        # pattern because both reads happen on the SAME live thread
        # within one request.
        from collections import OrderedDict

        self._thread_counts: "OrderedDict[int, int]" = OrderedDict()

    _THREAD_CAP = 512

    def set_reporter(self, fn) -> None:
        """Install the per-trace observer (None to remove)."""
        self._reporter = fn

    def _record(self, key: str, args: tuple = (),
                kwargs: Optional[dict] = None) -> None:
        tid = threading.get_ident()
        with self._lock:
            n = self._counts.get(key, 0) + 1
            self._counts[key] = n
            self._thread_counts[tid] = self._thread_counts.get(tid, 0) + 1
            self._thread_counts.move_to_end(tid)
            while len(self._thread_counts) > self._THREAD_CAP:
                self._thread_counts.popitem(last=False)
        rep = self._reporter
        if rep is not None:
            try:
                rep(key, args, kwargs or {})
            except Exception:
                pass  # observability must never fail the traced program
        if self.max_traces is not None and n > self.max_traces:
            raise TraceBudgetExceeded(
                f"jitted `{key}` traced {n} times "
                f"(budget {self.max_traces}) — recompilation storm; check "
                "static_argnames cardinality and argument shape bucketing")

    def counts(self) -> Dict[str, int]:
        """Per-program trace counts (key = `qualname#construction-seq`)."""
        with self._lock:
            return dict(self._counts)

    def total(self) -> int:
        with self._lock:
            return sum(self._counts.values())

    def thread_total(self) -> int:
        """Traces recorded on the CALLING thread (exact: jit tracing is
        synchronous in the caller)."""
        with self._lock:
            return self._thread_counts.get(threading.get_ident(), 0)

    def snapshot(self) -> Dict[str, int]:
        return self.counts()

    def traces_since(self, snap: Dict[str, int]) -> Dict[str, int]:
        now = self.counts()
        return {k: n - snap.get(k, 0) for k, n in now.items()
                if n - snap.get(k, 0) > 0}

    def assert_max(self, max_traces: int) -> None:
        worst = max(self.counts().values(), default=0)
        if worst > max_traces:
            offenders = [k for k, n in self.counts().items()
                         if n > max_traces]
            raise TraceBudgetExceeded(
                f"{len(offenders)} jitted callable(s) exceeded the "
                f"{max_traces}-trace budget: {sorted(offenders)[:5]}")

    def assert_no_new_traces_since(self, snap: Dict[str, int]) -> None:
        delta = self.traces_since(snap)
        if delta:
            raise TraceBudgetExceeded(
                "steady state retraced: " + ", ".join(
                    f"{k}×{n}" for k, n in sorted(delta.items())[:8]))


_active: List[TraceAuditor] = []
_orig_jit = None
_seq = itertools.count()


def _counting_jit(orig_jit):
    def jit(fun=None, **kwargs):
        if fun is None:  # jax.jit(static_argnames=...) decorator form
            return lambda f: jit(f, **kwargs)
        if not callable(fun):
            return orig_jit(fun, **kwargs)
        key = f"{getattr(fun, '__qualname__', repr(fun))}#{next(_seq)}"

        @functools.wraps(fun)
        def counted(*args, **kw):
            # args are abstract values here (the body runs under trace):
            # reporters read only .shape/.dtype, never concrete data
            for auditor in list(_active):
                auditor._record(key, args, kw)
            return fun(*args, **kw)

        return orig_jit(counted, **kwargs)

    jit.__tpulint_counting__ = True
    return jit


def install(max_traces: Optional[int] = None) -> TraceAuditor:
    """Patch ``jax.jit`` process-wide and return the auditor. Call before
    importing modules that bind jax.jit at import time. Nested installs
    share one patch; each gets its own auditor."""
    global _orig_jit
    import jax

    if not getattr(jax.jit, "__tpulint_counting__", False):
        _orig_jit = jax.jit
        jax.jit = _counting_jit(_orig_jit)
    auditor = TraceAuditor(max_traces=max_traces)
    _active.append(auditor)
    return auditor


def uninstall(auditor: Optional[TraceAuditor] = None) -> None:
    """Detach ``auditor`` (or the most recent). Restores the pristine
    ``jax.jit`` once no auditor is active — already-wrapped callables keep
    working, they just stop counting."""
    global _orig_jit
    import jax

    if auditor is None and _active:
        auditor = _active[-1]
    if auditor in _active:
        _active.remove(auditor)
    if not _active and _orig_jit is not None:
        jax.jit = _orig_jit
        _orig_jit = None


@contextmanager
def trace_audit(max_traces: Optional[int] = None):
    """Context manager: count every trace of jits *constructed inside*,
    optionally enforcing a per-program budget at trace time.

        with trace_audit(max_traces=1) as audit:
            prog = jax.jit(f)
            prog(x); prog(x)          # 1 trace — fine
        audit.counts()                # {'f#0': 1}
    """
    auditor = install(max_traces=max_traces)
    try:
        yield auditor
    finally:
        uninstall(auditor)
