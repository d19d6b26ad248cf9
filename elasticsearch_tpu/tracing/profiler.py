"""Search profiler: per-shard phase timings with a TPU phase breakdown.

Reference: org/elasticsearch/search/profile/ — Profiler.java /
ProfileResult (the ``?profile=true`` response tree). The reference
times Lucene Weight/Scorer stages; a TPU shard has different phases, so
the per-shard profile here keeps the reference's envelope (``profile.
shards[].searches[].query[]``) and adds a ``tpu`` section with the
phases that actually decide latency on this engine:

  rewrite         query parse + join/MLT prepare (host)
  executor_build  SegmentContext construction, program selection (host)
  device_compile  time inside device calls whose jit trace count moved
                  (tracing + XLA compilation; first shape class only)
  device_execute  time inside device calls running cached programs
  topk            top-k selection + result packing (device)
  host_sync       device→host pulls of packed results
  aggs            aggregation partials (device + host reduce)
  rehydrate       fielddata-tier device copies re-placed after eviction
                  (resources/residency.py — the `tpu.rehydrate` tracer
                  span's time, attributed via the attached() contextvar)

``retraces`` counts the jit traces the request triggered
(tools.tpulint.trace_audit via tracing/retrace.py); null = auditor
unavailable (``ESTPU_NO_TRACE_AUDIT`` / tools package missing — a typed
absence, never a sentinel that could leak into arithmetic).
Separating compile from execute is the point: BM25S-style
eager scoring (PAPERS.md) makes steady-state ``device_execute`` the
tuning signal, while a nonzero steady ``device_compile`` means shape
bucketing is broken (tpulint R001 territory).

Clock discipline (tpulint R007): all durations from
``time.perf_counter()``.
"""
from __future__ import annotations

import contextvars
import time
from contextlib import contextmanager, nullcontext
from typing import Any, Callable, Dict, Iterator, Optional

from elasticsearch_tpu.tracing import retrace
from elasticsearch_tpu.tracing.tracer import NOOP

PHASES = ("rewrite", "executor_build", "device_compile", "device_execute",
          "topk", "host_sync", "aggs", "rehydrate", "fuse", "rerank")

# the PhaseTimer of the profiled query phase running on THIS logical
# flow — lets out-of-band instrumentation (residency rehydration) file
# time without threading the timer through every layer. Explicitly
# scoped by attached(): a stale pointer must never absorb a later
# request's rehydrates into an already-serialized profile.
_ACTIVE_TIMER: contextvars.ContextVar[Optional["PhaseTimer"]] = \
    contextvars.ContextVar("estpu-active-phase-timer", default=None)


def attached(timer: Optional["PhaseTimer"]):
    """Context manager scoping ``timer`` as the flow's rehydrate sink
    (no-op for None — unprofiled requests pay nothing)."""
    if timer is None:
        return nullcontext()

    @contextmanager
    def _cm():
        tok = _ACTIVE_TIMER.set(timer)
        try:
            yield
        finally:
            _ACTIVE_TIMER.reset(tok)

    return _cm()


def record_rehydrate(ns: int) -> None:
    """File ``ns`` under the attached timer's `rehydrate` phase (called
    by resources/residency.py; dropped when no profile is active)."""
    t = _ACTIVE_TIMER.get()
    if t is not None:
        t.nanos["rehydrate"] = t.nanos.get("rehydrate", 0) + int(ns)


def _block(out: Any) -> None:
    """Wait for device work referenced by ``out`` (tolerates host values,
    tuples, None — profiling must never change results, only timing)."""
    try:
        import jax

        jax.block_until_ready(out)
    except Exception:
        pass  # host-only value / jax unavailable: nothing to wait for


class PhaseTimer:
    """Accumulates named phase durations (nanos) for ONE shard's query
    phase. Not thread-safe — one per query_phase call."""

    def __init__(self):
        self.nanos: Dict[str, int] = {p: 0 for p in PHASES}
        self.retraces = 0
        self._unknown_retraces = retrace.auditor() is None
        self.device_calls = 0
        self.segments = 0
        self._t0 = time.perf_counter()

    @contextmanager
    def phase(self, name: str) -> Iterator[None]:
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.nanos[name] = self.nanos.get(name, 0) + int(
                (time.perf_counter() - t0) * 1e9)

    @contextmanager
    def span_phase(self, sp, name: str) -> Iterator[None]:
        """Open the tracer span ``sp`` and file ITS duration under phase
        ``name``: one pair of clock reads serves the span and the
        profile. With the no-op span (no tracer on this flow) the timer
        reads its own clock."""
        if sp is NOOP:
            with self.phase(name):
                yield
            return
        try:
            with sp:
                yield
        finally:
            self.nanos[name] = self.nanos.get(name, 0) + int(
                sp.duration * 1e9)

    def device_call(self, fn: Callable[[], Any],
                    bucket: Optional[str] = None) -> Any:
        """Run a device call, block for its results, and attribute its
        wall time to device_compile (trace count moved) or
        device_execute (cached program). ``bucket`` additionally files
        the time under a named phase (e.g. "topk")."""
        snap = retrace.snapshot()
        t0 = time.perf_counter()
        out = fn()
        _block(out)
        ns = int((time.perf_counter() - t0) * 1e9)
        delta = retrace.traces_since(snap)
        self.device_calls += 1
        if delta > 0:
            self.retraces += delta
            self.nanos["device_compile"] += ns
        else:
            self.nanos["device_execute"] += ns
        if bucket is not None:
            self.nanos[bucket] = self.nanos.get(bucket, 0) + ns
        return out

    def to_json(self) -> dict:
        return {
            "phases": {f"{k}_nanos": v for k, v in self.nanos.items()},
            # measured wall time since the timer opened — NOT a phase
            # sum: the named ``bucket`` buckets (topk) deliberately
            # double-file time also counted under device_compile/
            # device_execute, so summing phases over-reports
            "query_total_nanos": int(
                (time.perf_counter() - self._t0) * 1e9),
            # null = auditor unavailable (unknown, NOT zero): the typed
            # absence keeps consumers from mixing a sentinel into sums —
            # the same convention bench metrics_delta uses
            "retraces": None if self._unknown_retraces else self.retraces,
            "device_calls": self.device_calls,
            "segments": self.segments,
        }


def shard_profile_entry(shard_label: str, query_nanos: int,
                        tpu: Optional[dict],
                        description: str = "whole-segment score/mask "
                                           "program") -> dict:
    """One ``profile.shards[]`` element: reference envelope + tpu extras."""
    out: Dict[str, Any] = {
        "id": shard_label,
        "searches": [{
            "query": [{
                "type": "CompiledSegmentProgram",
                "description": description,
                "time_in_nanos": int(query_nanos),
            }],
            "rewrite_time": (tpu or {}).get("phases", {}).get(
                "rewrite_nanos", 0),
            "collector": [{
                "name": "TopKMaskCollector",
                "reason": "search_top_hits",
                "time_in_nanos": (tpu or {}).get("phases", {}).get(
                    "topk_nanos", 0),
            }],
        }],
    }
    if tpu is not None:
        out["tpu"] = tpu
    return out
