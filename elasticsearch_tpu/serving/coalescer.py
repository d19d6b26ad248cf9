"""Cross-request adaptive micro-batching: the query coalescer.

Reference: there is no coalescer in ES 2.x — searches execute one
program each. Here the engine's single biggest measured lever is
batching (an explicit ``_msearch`` body runs its whole batch as one
vmapped device program, search/batch.py), so the serving front-end
converts *concurrent independent* single-search requests into the same
amortized shape: each eligible request parks briefly in a micro-batch
queue keyed by ``(index, query-shape bucket)``; a drain thread flushes
the bucket as ONE fused batch (``execute_batch``) and fans each
request's top-k back to its parked thread. Hybrid retrieval bodies
(search/hybrid.py) coalesce too, under their own
``(fusion method, lexical field, vector field)`` bucket — per-request
fusion weights ride as traced batch rows, so weight diversity never
fragments the bucket (the solo-bypass contract is unchanged).

Blocking discipline: tpulint R010 forbids unbounded waits while holding
a lock in this package, and R013 generalizes the same hazard — plus
lock-order cycle detection — to every module interprocedurally; waits
here are timeout-bounded and parking happens OUTSIDE the coalescer
lock.

Drain policy (adaptive):

- **solo bypass** — when no other eligible search is in flight and no
  batch is forming, the request runs the normal path untouched: a lone
  request pays ~zero added latency (``mode=adaptive``, the default).
- **full** — a bucket reaching ``max_batch`` flushes immediately.
- **deadline** — a forming batch flushes ``wait window`` after its
  first entry; the window adapts to the observed arrival rate (EWMA of
  inter-arrival gaps, clamped to ``max_wait``) so dense bursts hold
  just long enough to fill.
- **idle** — no new arrivals for ``idle_gap`` flushes early: the burst
  is over, waiting out the deadline would only add latency.

Integration with the production substrate (PRs 3–7):

- a parked request's time is two tracer spans on its own thread,
  children of its ``search`` span: ``serving.queue_wait`` (park → claim)
  and ``serving.batch_wait`` (claim → done); the drain thread's fused
  execution is a ``serving.batch`` span, a root of its own that names
  its first member's trace (its members' ``batch_wait`` already covers
  the same interval); plus a ``coalescer`` section under
  ``?profile=true``;
- every parked request registers a *pending* TaskRegistry child task —
  ``POST /_tasks/{id}/_cancel`` evicts it from the queue before it ever
  reaches the device;
- ``estpu_coalescer_*`` metric families (batch-size histogram,
  queue-wait histogram, flush-reason / bypass-reason counters) ride the
  node registry;
- admission happens upstream in REST dispatch through the per-tenant
  QoS layer (serving/qos.py) over the ``in_flight_requests`` breaker.

Ineligible bodies (aggs, sort, scroll, scripts, non-uniform query
shapes) bypass the queue unchanged.

Lock discipline (tpulint R010): every ``Condition.wait``/``Event.wait``
in this module is timeout-bounded — an unbounded wait while holding a
lock would wedge the drain path behind one lost notify.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from elasticsearch_tpu.tracing.tracer import current_context, span

#: body keys a parked request may carry; `profile` parks too (its queue
#: wait must be attributed honestly) but executes sequentially at flush
PARK_KEYS = frozenset({"query", "size", "from", "_source", "profile"})

#: sentinel result: the waiter executes its own body on its own thread
#: (sequential remainder of a flush — profile bodies, fused-tier refusals)
RUN_SELF = object()


class _Entry:
    """One parked request."""

    __slots__ = ("svc", "body", "query", "claimed", "done",
                 "result", "error", "task", "enqueued", "claimed_at",
                 "batch_size", "flush_reason", "trace_id")

    def __init__(self, svc, body: dict, query):
        self.svc = svc
        self.body = body
        self.query = query
        self.claimed = threading.Event()  # left the queue (exec started)
        self.done = threading.Event()     # result/error available
        self.result: Any = None
        self.error: Optional[BaseException] = None
        self.task = None
        self.enqueued = time.perf_counter()
        self.claimed_at: Optional[float] = None
        self.batch_size = 0
        self.flush_reason = ""
        # the parking request's trace, for the batch span that serves it
        ctx = current_context()
        self.trace_id = ctx.trace_id if ctx is not None else ""

    def resolve(self, result: Any = None,
                error: Optional[BaseException] = None) -> None:
        self.result = result
        self.error = error
        if self.claimed_at is None:
            self.claimed_at = time.perf_counter()
        self.claimed.set()
        self.done.set()


def _parse_duration_s(v, default: float) -> float:
    if v is None:
        return default
    from elasticsearch_tpu.search.service import _parse_timeout

    out = _parse_timeout(v)
    return default if out is None else float(out)


class QueryCoalescer:
    """Micro-batch queue between REST dispatch and the search executor."""

    #: EWMA smoothing for the inter-arrival gap estimate
    _ALPHA = 0.2
    #: wait window = this many estimated gaps (room for several joiners)
    _GAP_FACTOR = 4.0
    #: floor so a dense burst still holds long enough to fill a batch
    _MIN_WINDOW_S = 2e-4

    def __init__(self, node):
        self.node = node
        self._cv = threading.Condition()
        # (index name, shape bucket) -> forming batch
        self._queues: Dict[Tuple[str, str], List[_Entry]] = {}
        self._flush_at: Dict[Tuple[str, str], float] = {}
        self._last_arrival: Optional[float] = None
        self._ewma_gap: Optional[float] = None
        self._active = 0  # bypassed eligible searches currently executing
        self._outstanding = 0  # parked entries not yet fully served
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        # -- config (env default + dynamic serving.coalescer.* settings)
        env = os.environ.get("ESTPU_COALESCER", "1").lower()
        self.enabled = env not in ("0", "false", "off")
        self.mode = "adaptive"  # adaptive | always | off
        self.max_batch = 256
        self.max_wait_s = 0.004
        self.idle_gap_s = 0.001
        # -- metrics (node registry; estpu_coalescer_* families)
        m = node.metrics
        self._m_batch = m.histogram(
            "estpu_coalescer_batch_size",
            "Requests per coalesced device batch",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512))
        self._m_wait = m.histogram(
            "estpu_coalescer_queue_wait_seconds",
            "Time a request spent parked in the micro-batch queue")
        self._m_flush = m.counter(
            "estpu_coalescer_flush_total",
            "Batch flushes by drain reason (full/deadline/idle/close)",
            ("reason",))
        self._m_bypass = m.counter(
            "estpu_coalescer_bypass_total",
            "Searches that bypassed the queue, by reason", ("reason",))

    # -- settings ------------------------------------------------------------

    def apply_cluster_settings(self, flat: Dict[str, object]) -> None:
        """Idempotent from the merged map (absent key = default) — the
        breaker-settings discipline."""
        with self._cv:
            v = flat.get("serving.coalescer.enabled")
            env = os.environ.get("ESTPU_COALESCER", "1").lower()
            self.enabled = (str(v).lower() not in ("false", "0", "off")
                            if v is not None
                            else env not in ("0", "false", "off"))
            v = flat.get("serving.coalescer.mode")
            self.mode = (str(v) if v in ("adaptive", "always", "off")
                         else "adaptive")
            v = flat.get("serving.coalescer.max_batch")
            self.max_batch = max(2, int(v)) if v is not None else 256
            self.max_wait_s = _parse_duration_s(
                flat.get("serving.coalescer.max_wait"), 0.004)
            self.idle_gap_s = _parse_duration_s(
                flat.get("serving.coalescer.idle_gap"), 0.001)
            self._cv.notify_all()

    # -- submission ----------------------------------------------------------

    def execute(self, svc, body: dict, run) -> Optional[dict]:
        """The serving front door for one single-index search. Returns
        the response (coalesced or via ``run()``, the caller's normal
        sequential path), or None when the body is ineligible and the
        caller must run its own path (parse errors keep their typed
        surface there)."""
        if (not self.enabled or self.mode == "off" or self._closed
                or not isinstance(body, dict) or set(body) - PARK_KEYS):
            return None
        try:
            frm, size = int(body.get("from", 0)), int(body.get("size", 10))
        except (TypeError, ValueError):
            return None
        if frm + size < 1 or frm + size > 10_000:
            return None
        now = time.perf_counter()
        with self._cv:
            window = self._note_arrival(now)
            park = (self.mode == "always" or self._active > 0
                    or bool(self._queues))
            if not park:
                # solo: the normal path untouched — a lone request pays
                # zero added latency; _active marks the overlap window
                # so a concurrent burst starts coalescing immediately
                self._active += 1
        if not park:
            try:
                self._m_bypass.labels("solo").inc()
                return run()
            finally:
                with self._cv:
                    self._active -= 1
                    self._cv.notify_all()  # close() may be draining
        # coalescing is warranted: now pay for shape analysis
        made = self._make_entry(svc, body)
        if made is None:
            self._m_bypass.labels("shape").inc()
            return None
        entry, field = made
        return self._park(entry, field, window, run)

    def _make_entry(self, svc,
                    body: dict) -> Optional[Tuple[_Entry, str]]:
        from elasticsearch_tpu.search.batch import batch_field
        from elasticsearch_tpu.search.queries import parse_query

        try:
            with span("search.rewrite"):
                query = parse_query(body.get("query"))
        except Exception:
            return None  # the normal path reports the typed error
        with span("search.plan"):
            field = batch_field(svc, query)
        if field is None:
            return None
        return _Entry(svc, body, query), field

    def _park(self, entry: _Entry, field: str, window: float, run) -> dict:
        key = (entry.svc.name, field)
        with self._cv:
            self._outstanding += 1
        # pending child task: visible in /_tasks, cancellable while
        # parked — on_cancel evicts before the device ever sees it
        entry.task = self.node.tasks.register(
            "indices:data/read/search[coalesced]",
            description=f"indices[{entry.svc.name}] queued[{field}]",
            status="pending",
            on_cancel=lambda t, e=entry: self._evict(e))
        try:
            with self._cv:
                if entry.error is None:  # not born-cancelled
                    q = self._queues.get(key)
                    if q is None:
                        q = self._queues[key] = []
                        self._flush_at[key] = entry.enqueued + window
                    q.append(entry)
                    self._ensure_thread()
                    self._cv.notify_all()
            # queue wait as a span: child of the request's search span
            # (same thread of execution), closed at CLAIM — what follows
            # is the batch's execution, waited out under batch_wait
            with self.node.tracer.span("serving.queue_wait",
                                       index=entry.svc.name, bucket=field):
                while not entry.claimed.wait(timeout=0.05):
                    with self._cv:
                        dead = (self._thread is None
                                or not self._thread.is_alive())
                    if dead and self._reclaim(entry, key):
                        break
            with self.node.tracer.span("serving.batch_wait"):
                while not entry.done.wait(timeout=0.05):
                    pass
            queue_s = ((entry.claimed_at or entry.enqueued)
                       - entry.enqueued)
            self._m_wait.observe(queue_s)
            if entry.error is not None:
                raise entry.error
            if entry.result is RUN_SELF:
                resp = run()
            else:
                resp = entry.result
            if isinstance(resp, dict):
                queue_ms = int(queue_s * 1000)
                if "took" in resp:
                    resp["took"] = int(resp["took"]) + queue_ms
                if "profile" in resp and isinstance(resp["profile"], dict):
                    resp["profile"]["coalescer"] = {
                        "queue_wait_nanos": int(queue_s * 1e9),
                        "batch_size": entry.batch_size,
                        "flush_reason": entry.flush_reason or "self",
                    }
            return resp
        finally:
            self.node.tasks.unregister(entry.task)
            with self._cv:
                self._outstanding -= 1
                self._cv.notify_all()  # close() may be draining

    def _note_arrival(self, now: float) -> float:
        """Caller holds _cv. Update the EWMA inter-arrival estimate and
        return the adaptive wait window for a batch formed now."""
        if self._last_arrival is not None:
            gap = min(now - self._last_arrival, 1.0)
            self._ewma_gap = (gap if self._ewma_gap is None
                              else (1 - self._ALPHA) * self._ewma_gap
                              + self._ALPHA * gap)
        self._last_arrival = now
        if self.mode == "always":
            return self.max_wait_s
        if self._ewma_gap is None:
            return self._MIN_WINDOW_S
        return min(self.max_wait_s,
                   max(self._ewma_gap * self._GAP_FACTOR,
                       self._MIN_WINDOW_S))

    # -- eviction / reclaim --------------------------------------------------

    def _evict(self, entry: _Entry) -> None:
        """on_cancel hook (cancelling thread): remove a still-parked
        entry from its queue and fail it with the task's typed error —
        it never reaches the device. A claimed entry is past eviction;
        its flush resolves it normally."""
        from elasticsearch_tpu.tracing import TaskCancelledException

        with self._cv:
            for key, q in list(self._queues.items()):
                if entry in q:
                    q.remove(entry)
                    if not q:
                        self._queues.pop(key, None)
                        self._flush_at.pop(key, None)
                    break
            if not entry.claimed.is_set():
                task = entry.task
                reason = (task.cancel_reason if task is not None
                          else None) or "by user request"
                tid = task.tagged_id if task is not None else "?"
                entry.resolve(error=TaskCancelledException(
                    f"task [{tid}] (indices:data/read/search[coalesced]) "
                    f"was cancelled [{reason}] while queued"))
            self._cv.notify_all()

    def _reclaim(self, entry: _Entry, key) -> bool:
        """Dead drain thread: pull the entry back and run it ourselves
        (never wedge a client on a crashed drain loop)."""
        with self._cv:
            q = self._queues.get(key)
            if q is not None and entry in q:
                q.remove(entry)
                if not q:
                    self._queues.pop(key, None)
                    self._flush_at.pop(key, None)
                entry.resolve(result=RUN_SELF)
                return True
            return entry.done.is_set()

    # -- drain thread --------------------------------------------------------

    def _ensure_thread(self) -> None:
        """Caller holds _cv. Lazy drain thread (library-embedded Nodes
        that never coalesce don't pay for one)."""
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._drain_loop, name="estpu-coalescer",
                daemon=True)
            self._thread.start()

    def _due(self, now: float) -> Optional[Tuple[Tuple[str, str], str]]:
        """Caller holds _cv. The first bucket due to flush, with reason."""
        for key, q in self._queues.items():
            if not q:
                continue
            if len(q) >= self.max_batch:
                return key, "full"
            if now >= self._flush_at.get(key, now):
                return key, "deadline"
            if (self._last_arrival is not None
                    and now - self._last_arrival >= self.idle_gap_s):
                return key, "idle"
        return None

    def _next_wakeup(self, now: float) -> float:
        """Caller holds _cv. Seconds until the earliest possible flush."""
        t = 0.5  # idle heartbeat: re-check config/close periodically
        if self._queues:
            for key in self._queues:
                t = min(t, self._flush_at.get(key, now) - now)
            if self._last_arrival is not None:
                t = min(t, self._last_arrival + self.idle_gap_s - now)
        return max(t, 1e-4)

    def _drain_loop(self) -> None:
        while True:
            batch: List[_Entry] = []
            reason = ""
            with self._cv:
                while True:
                    if self._closed:
                        for q in self._queues.values():
                            for e in q:
                                e.resolve(result=RUN_SELF)
                        self._queues.clear()
                        self._flush_at.clear()
                        return
                    now = time.perf_counter()
                    due = self._due(now)
                    if due is not None:
                        key, reason = due
                        q = self._queues.pop(key, [])
                        self._flush_at.pop(key, None)
                        batch = q[: self.max_batch]
                        rest = q[self.max_batch:]
                        if rest:
                            self._queues[key] = rest
                            self._flush_at[key] = now
                        break
                    self._cv.wait(timeout=self._next_wakeup(now))
            if batch:
                try:
                    self._flush(batch, reason)
                except Exception:
                    # the sequential path is always correct — a drain bug
                    # must degrade to per-request execution, not wedge
                    # parked clients (counted, never silent)
                    self._m_bypass.labels("drain_error").inc()
                    for e in batch:
                        if not e.done.is_set():
                            e.resolve(result=RUN_SELF)

    def _flush(self, batch: List[_Entry], reason: str) -> None:
        from elasticsearch_tpu.search.batch import execute_batch

        # cancelled-while-claiming entries resolve with their typed error
        live: List[_Entry] = []
        for e in batch:
            if e.done.is_set():
                continue
            if e.task is not None and e.task.cancelled:
                self._evict(e)
                continue
            live.append(e)
        if not live:
            return
        self._m_flush.labels(reason).inc()
        # profile bodies pay the queue wait like everyone (that is the
        # honest number) but execute sequentially: a fused batch cannot
        # attribute per-phase device time to one request
        fused = [e for e in live if "profile" not in e.body]
        rest = [e for e in live if "profile" in e.body]
        now = time.perf_counter()
        for e in live:
            e.claimed_at = now
            e.batch_size = len(fused) if e in fused else 1
            e.flush_reason = reason
            e.claimed.set()
        # the sequential remainder has no dependency on the fused batch:
        # release those waiters BEFORE the device execution, not after —
        # they run on their own threads in parallel with the batch
        for e in rest:
            e.resolve(result=RUN_SELF)
        responses = None
        if len(fused) >= 2:
            svc = fused[0].svc
            try:
                with self.node.tracer.span(
                        "serving.batch", batch_size=len(fused),
                        flush_reason=reason,
                        first_trace_id=fused[0].trace_id):
                    responses = execute_batch(
                        svc, [e.body for e in fused],
                        queries=[e.query for e in fused], pad_pow2=True)
            except Exception:
                responses = None  # sequential fallback below
                self._m_bypass.labels("batch_error").inc()
        if responses is not None:
            self._m_batch.observe(len(fused))
            q_ms = (time.perf_counter() - now) * 1000
            for e, r in zip(fused, responses):
                try:  # slow log sees coalesced searches too (honest cost:
                    # this request's share is queue wait + batch execute)
                    e.svc.slowlog.on_search(
                        q_ms + (e.claimed_at - e.enqueued) * 1000,
                        e.body, r)
                except Exception:
                    pass  # logging must never fail the batch
                e.resolve(result=r)
        else:
            for e in fused:
                e.resolve(result=RUN_SELF)

    # -- lifecycle -----------------------------------------------------------

    def oldest_queue_age(self) -> Optional[float]:
        """Age in seconds of the oldest still-PARKED request across
        every forming bucket, or None when nothing is parked. Normal
        waits are sub-millisecond (the adaptive window); an age orders
        of magnitude past ``max_wait`` means the drain thread is wedged
        or dead — the watchdog's coalescer_drain signal."""
        with self._cv:
            oldest = min((e.enqueued for q in self._queues.values()
                          for e in q), default=None)
        if oldest is None:
            return None
        return time.perf_counter() - oldest

    def stats(self) -> dict:
        with self._cv:
            return {
                "enabled": self.enabled,
                "mode": self.mode,
                "queued": sum(len(q) for q in self._queues.values()),
                "buckets": len(self._queues),
                "max_batch": self.max_batch,
                "max_wait_ms": self.max_wait_s * 1000,
            }

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()
            th = self._thread
        if th is not None and th.is_alive():
            th.join(timeout=2.0)
        # parked waiters resolved RUN_SELF (and solo bypasses) still
        # EXECUTE on their own threads — wait them out (bounded) so the
        # caller can tear indices down without racing live searches
        deadline = time.perf_counter() + 5.0
        with self._cv:
            while (self._outstanding > 0 or self._active > 0) \
                    and time.perf_counter() < deadline:
                self._cv.wait(timeout=0.05)
