"""Tiered HBM residency: one choke point for device-resident allocations.

The reference keeps fielddata in an IndicesFieldDataCache whose entries
load lazily, count against the fielddata breaker, and evict under
pressure (org/elasticsearch/index/fielddata/ + indices/fielddata/cache/).
Here the device-resident structures play that role: doc-value columns,
vector slabs and dense impact blocks are *evictable* — the registry keeps
the host mirror, drops the device copy LRU-first when a reservation
can't fit, and transparently rehydrates on the next touch (a
``tpu.rehydrate`` tracer span + profiler phase, so the latency cost of
running over-HBM is visible, never silent).

Three entry points, one accounting surface:

- :meth:`ResidencyRegistry.put_array` — an EVICTABLE device copy of a
  host array (handle keeps the mirror; ``handle.get()`` returns the
  device array, rehydrating if evicted). Charges the tier's breaker;
  under pressure evicts LRU handles before tripping.
- :meth:`ResidencyRegistry.track` — a pinned charge for device memory
  owned elsewhere (executor data/prepared-query caches, IVF device
  lists): force-charged (never trips — the owners have their own LRU
  caps) and released when the token dies with its cache entry.
- :meth:`ResidencyRegistry.device_put` — the accounting wrapper around
  ``jax.device_put`` for always-resident placements (postings, live
  masks, nested-join arrays). Counts placements/bytes per tier so
  ``/_nodes`` shows where HBM goes; admission control for these is the
  engine's per-segment ``segments``-breaker charge at freeze.

tpulint R008 flags raw ``jax.device_put`` in ``elasticsearch_tpu/`` that
bypasses these entry points (``# tpulint: offbudget`` is the justified
escape hatch for transient per-call uploads).

Fault point ``resources.reserve`` (utils/faults.py) fires before every
breaker reservation — the chaos suite uses it to prove a tripped
fielddata breaker degrades to partial shard results.
"""
from __future__ import annotations

import threading
import time
import weakref
from collections import OrderedDict
from typing import Any, Dict, List, Optional

import numpy as np

from elasticsearch_tpu.resources.breakers import CircuitBreakerService
from elasticsearch_tpu.utils.errors import CircuitBreakingException
from elasticsearch_tpu.utils.faults import FAULTS

#: residency tiers — each maps to the breaker of the same name
TIERS = ("fielddata", "segments", "request")


def _jax_device_put(x, *args, **kw):
    import jax

    return jax.device_put(x, *args, **kw)


class ResidentArray:
    """Handle for one evictable device-resident array.

    ``get()`` is the only consumer API: it returns the device array,
    touching LRU recency, and rehydrates (reserve → device_put → span)
    when the device copy was evicted. The host mirror is authoritative
    and immutable (segments are frozen), so evict→rehydrate is exact.

    Note eviction drops the REGISTRY's reference; XLA frees the buffer
    once in-flight consumers drop theirs too (normal refcounting — same
    lifecycle as a merged-away segment's arrays).
    """

    def __init__(self, registry: "ResidencyRegistry", host: np.ndarray,
                 label: str, tier: str, dtype: Any = None,
                 device: Any = None):
        try:  # device dtype decides the footprint (bf16 halves it)
            itemsize = (np.dtype(dtype).itemsize if dtype is not None
                        else host.dtype.itemsize)
        except TypeError:
            itemsize = host.dtype.itemsize
        self.label = label
        self.tier = tier
        self.nbytes = int(host.size * itemsize)
        self.evictions = 0
        self.rehydrations = 0
        self._host = host
        self._dtype = dtype
        # the owning shard's chip; None = the default device. A
        # rehydration goes back where the first placement went.
        self._device = device
        self._dev: Any = None
        self._lock = threading.Lock()
        self._registry = registry
        # shared state cell: the weakref.finalize callback releases the
        # breaker charge for a handle GC'd while resident (segment
        # merged away / index closed) without resurrecting the handle
        self._cell = {"resident": False, "nbytes": self.nbytes,
                      "tier": tier, "key": id(self)}
        registry._adopt(self)

    @property
    def resident(self) -> bool:
        return self._dev is not None

    def _place(self):
        host = self._host
        if self._dtype is not None:
            import jax.numpy as jnp

            host = jnp.asarray(host, dtype=self._dtype)
        return _jax_device_put(host, self._device)

    def get(self):
        with self._lock:
            dev = self._dev
        if dev is not None:
            self._registry._touch(self)
            return dev
        return self._rehydrate()

    def _rehydrate(self):
        reg = self._registry
        t0 = time.perf_counter()
        reg._reserve(self.nbytes, self.tier, self.label, exclude=self)
        try:
            tracer = reg._tracer
            if tracer is not None:
                with tracer.span("tpu.rehydrate", label=self.label,
                                 tier=self.tier, bytes=self.nbytes):
                    dev = self._place()
            else:
                dev = self._place()
        except Exception:
            # the reservation must not leak when the placement itself
            # fails (device OOM / transfer error) — repeated transient
            # failures would otherwise ratchet `used` into permanent
            # spurious trips
            reg._release(self.nbytes, self.tier)
            raise
        ns = int((time.perf_counter() - t0) * 1e9)
        with self._lock:
            if self._dev is None:
                self._dev = dev
                fresh = True
            else:  # lost a rehydrate race: keep the winner's copy
                dev = self._dev
                fresh = False
        if fresh:
            self.rehydrations += 1
            self._cell["resident"] = True
            reg._on_rehydrated(self, ns)
        else:
            reg._release(self.nbytes, self.tier)
        return dev

    def evict(self) -> bool:
        """Drop the device copy (host mirror retained); False when
        already evicted. Next ``get()`` rehydrates."""
        with self._lock:
            if self._dev is None:
                return False
            self._dev = None
        self.evictions += 1
        self._cell["resident"] = False
        self._registry._on_evicted(self)
        return True


class PinnedToken:
    """A pinned byte charge tied to a cache entry's lifetime: close()
    (or GC) releases it."""

    def __init__(self, registry: "ResidencyRegistry", nbytes: int,
                 label: str, tier: str):
        self.nbytes = int(nbytes)
        self.label = label
        self.tier = tier
        self._registry = registry
        self._closed = False

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._registry._untrack(self)

    def __del__(self):  # cache entry dropped without explicit close
        try:
            self.close()
        except Exception:
            pass


class ResidencyRegistry:
    """Per-node registry of device-resident allocations (one per
    process by default — the device is process-shared, so admission
    control must be too; in-process multi-node tests share it the same
    way they share the device)."""

    def __init__(self, breakers: CircuitBreakerService):
        self.breakers = breakers
        self._lock = threading.Lock()
        # id(handle) -> weakref; insertion order IS the LRU order
        self._lru: "OrderedDict[int, weakref.ref]" = OrderedDict()
        self._tracer = None
        self._tiers: Dict[str, Dict[str, int]] = {
            t: {"resident_bytes": 0, "handles": 0, "loads": 0,
                "evictions": 0, "rehydrations": 0,
                "rehydrate_time_in_nanos": 0}
            for t in TIERS}
        self._pinned_bytes = 0
        self._pinned_tokens = 0
        self._placements = 0
        self._placed_bytes_total = 0

    def set_tracer(self, tracer) -> None:
        """Adopt a node's tracer so rehydration spans land in its ring
        (in-process multi-node: last registration wins — rehydrates are
        process-wide events, same note as the shared registry)."""
        self._tracer = tracer

    # -- evictable handles --------------------------------------------------

    def put_array(self, host: np.ndarray, *, label: str,
                  tier: str = "fielddata", dtype: Any = None,
                  best_effort: bool = False,
                  device: Any = None) -> Optional[ResidentArray]:
        """Register ``host`` and place its device copy, charging the
        tier's breaker (evicting LRU peers under pressure). Raises
        CircuitBreakingException when nothing evictable covers the
        reservation — or returns None with ``best_effort=True`` (for
        pure accelerations like dense impact blocks, where the caller
        has a slower but correct path)."""
        handle = ResidentArray(self, host, label, tier, dtype=dtype,
                               device=device)
        try:
            self._reserve(handle.nbytes, tier, label, exclude=handle)
        except CircuitBreakingException:
            self._drop(handle)
            if best_effort:
                return None
            raise
        try:
            dev = handle._place()
        except Exception:
            # reservation-leak guard, same as _rehydrate: a failed
            # allocation must release its breaker charge
            self._release(handle.nbytes, tier)
            self._drop(handle)
            raise
        with handle._lock:
            handle._dev = dev
        handle._cell["resident"] = True
        with self._lock:
            self._tiers[tier]["resident_bytes"] += handle.nbytes
            self._tiers[tier]["loads"] += 1
        return handle

    def _adopt(self, handle: ResidentArray) -> None:
        with self._lock:
            self._lru[id(handle)] = weakref.ref(handle)
            self._tiers[handle.tier]["handles"] += 1
        weakref.finalize(handle, self._on_gc, handle._cell)

    def _drop(self, handle: ResidentArray) -> None:
        # LRU removal only — the handle-count decrement stays with the
        # weakref.finalize callback (_on_gc), which fires exactly once
        with self._lock:
            self._lru.pop(handle._cell["key"], None)

    def _on_gc(self, cell: dict) -> None:
        with self._lock:
            self._lru.pop(cell["key"], None)
            t = self._tiers[cell["tier"]]
            t["handles"] -= 1
            if cell["resident"]:
                t["resident_bytes"] -= cell["nbytes"]
        if cell["resident"]:
            self.breakers.breaker(cell["tier"]).release(cell["nbytes"])

    def _touch(self, handle: ResidentArray) -> None:
        with self._lock:
            if id(handle) in self._lru:
                self._lru.move_to_end(id(handle))

    def _reserve(self, n: int, tier: str, label: str,
                 exclude: Optional[ResidentArray] = None) -> None:
        """Charge ``n`` against the tier's breaker, evicting LRU
        handles (any tier — they all share the parent) until it fits;
        raises the ES-shaped CircuitBreakingException when it can't."""
        FAULTS.check("resources.reserve", tier=tier, label=label, nbytes=n)
        br = self.breakers.breaker(tier)
        if br.reserve(n, count_trip=False):
            return
        for victim in self._victims(exclude):
            victim.evict()
            if br.reserve(n, count_trip=False):
                return
        br.break_or_reserve(n, label)  # counts the trip and raises

    def _victims(self, exclude: Optional[ResidentArray]) -> List[ResidentArray]:
        with self._lock:
            refs = list(self._lru.values())
        out = []
        for r in refs:  # oldest first
            h = r()
            if h is not None and h is not exclude and h.resident:
                out.append(h)
        return out

    def _release(self, n: int, tier: str) -> None:
        self.breakers.breaker(tier).release(n)

    def _on_evicted(self, handle: ResidentArray) -> None:
        self.breakers.breaker(handle.tier).release(handle.nbytes)
        with self._lock:
            t = self._tiers[handle.tier]
            t["resident_bytes"] -= handle.nbytes
            t["evictions"] += 1

    def _on_rehydrated(self, handle: ResidentArray, ns: int) -> None:
        with self._lock:
            t = self._tiers[handle.tier]
            t["resident_bytes"] += handle.nbytes
            t["rehydrations"] += 1
            t["rehydrate_time_in_nanos"] += ns
        from elasticsearch_tpu.tracing import profiler

        profiler.record_rehydrate(ns)

    def evict_all(self, tier: Optional[str] = None) -> int:
        """Force-evict every evictable handle (of ``tier``, or all) —
        operational pressure valve + the evict/rehydrate parity tests."""
        n = 0
        for h in self._victims(None):
            if tier is None or h.tier == tier:
                n += bool(h.evict())
        return n

    # -- pinned charges -----------------------------------------------------

    def track(self, nbytes: int, label: str,
              tier: str = "request") -> PinnedToken:
        self.breakers.breaker(tier).force(int(nbytes))
        tok = PinnedToken(self, nbytes, label, tier)
        with self._lock:
            self._pinned_bytes += tok.nbytes
            self._pinned_tokens += 1
        return tok

    def _untrack(self, tok: PinnedToken) -> None:
        self.breakers.breaker(tok.tier).release(tok.nbytes)
        with self._lock:
            self._pinned_bytes -= tok.nbytes
            self._pinned_tokens -= 1

    # -- accounted placement choke point ------------------------------------

    def device_put(self, x, *args, label: str = "", tier: str = "segments",
                   **kw):
        """``jax.device_put`` with placement accounting (cumulative —
        these arrays live exactly as long as their owners; the byte
        ceiling for them is the engine's per-segment breaker charge)."""
        dev = _jax_device_put(x, *args, **kw)
        n = int(getattr(dev, "nbytes", getattr(x, "nbytes", 0)) or 0)
        with self._lock:
            self._placements += 1
            self._placed_bytes_total += n
        return dev

    # -- stats --------------------------------------------------------------

    def stats(self) -> dict:
        with self._lock:
            return {
                "tiers": {t: dict(c) for t, c in self._tiers.items()},
                "pinned": {"bytes": self._pinned_bytes,
                           "tokens": self._pinned_tokens},
                "device_put": {"placements": self._placements,
                               "bytes_total": self._placed_bytes_total},
            }
