"""Process-global retrace auditor hookup for the search profiler.

The profiler splits device time into COMPILE vs EXECUTE by watching
``jax.jit`` trace counts around each device call: a call whose trace
count moved paid tracing+compilation; a steady call ran a cached
program. The counter is tools.tpulint.trace_audit's auditor, installed
process-wide.

Install-order constraint (see trace_audit's module docstring): the
codebase binds ``jax.jit`` at import time, so the auditor must patch
``jax.jit`` first. The ``__init__`` of every jit-binding package
(``ops/``, ``models/``, ``parallel/``) calls :func:`ensure_installed` —
parent packages initialize before their submodules, so the patch lands
before any ``@jax.jit`` binds, while the ROOT package import stays
jax-free (a Client-only import pays nothing). ``ESTPU_NO_TRACE_AUDIT=1``
opts out — then profiles report ``retraces: null`` and bench deltas
``jit_compiles: null`` (unavailable as a typed absence; the in-process
``traces_since`` sentinel stays -1 for cheap comparisons, but it must
never leak into a serialized envelope or a sum).
"""
from __future__ import annotations

import os
import threading
from typing import Optional

_LOCK = threading.Lock()
_AUDITOR = None
_TRIED = False


def ensure_installed():
    """Install the global auditor once; None when unavailable (no jax,
    no tools package, or explicitly disabled)."""
    global _AUDITOR, _TRIED
    with _LOCK:
        if _TRIED:
            return _AUDITOR
        _TRIED = True
        if os.environ.get("ESTPU_NO_TRACE_AUDIT"):
            return None
        try:
            from tools.tpulint import trace_audit

            _AUDITOR = trace_audit.install()
            # device-program observatory feed: every (re)trace reports
            # the traced callable's identity + abstract arg shapes into
            # monitor/programs.py, so compiles are attributed to stable
            # (program, shapes, backend) keys instead of only bumping a
            # per-thread counter. The `#seq` construction suffix is
            # stripped: it depends on import order, the qualname does not
            # (the census's cross-process stability contract).
            _AUDITOR.set_reporter(_report_trace)
        except Exception:
            # tools/ not importable (installed-package context) or jax
            # missing: the profiler degrades to retraces unknown
            _AUDITOR = None
        return _AUDITOR


def _report_trace(key: str, args: tuple, kwargs: dict) -> None:
    """Trace-auditor reporter → program registry (lazy import: the
    registry pulls monitor/metrics, which this module must not load for
    auditor-less processes)."""
    from elasticsearch_tpu.monitor import programs

    program = key.rpartition("#")[0] or key
    programs.REGISTRY.record_compile(program,
                                     programs.shape_sig(args, kwargs))


def auditor():
    """The installed auditor, or None (never installs as a side effect —
    a late install would miss every import-time-bound program and report
    a misleading 0)."""
    return _AUDITOR


def snapshot() -> Optional[int]:
    """Per-THREAD trace count at this instant (tracing runs
    synchronously on the calling thread, so thread attribution is
    exact). A global count would misclassify: a neighbor request's
    first-call compile on another thread must not turn this thread's
    cached execution into device_compile."""
    a = _AUDITOR
    return a.thread_total() if a is not None else None


def traces_since(snap: Optional[int]) -> int:
    """New traces ON THIS THREAD since ``snap``; -1 when the auditor is
    unavailable (unknown must stay distinguishable from zero)."""
    a = _AUDITOR
    if a is None or snap is None:
        return -1
    return a.thread_total() - snap
