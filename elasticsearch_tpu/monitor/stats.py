"""Monitoring: process/OS/device stats and search-phase counters.

Reference: org/elasticsearch/monitor/ — process/ProcessService.java,
os/OsService.java, jvm/JvmService.java feeding _nodes/stats, and
index/search/stats/SearchStats.java (query/fetch counts + cumulative
times per shard).

TPU adaptation: the "jvm" section maps to the Python process + the jax
device (HBM bytes in use via device memory stats when the backend exposes
them); search stats count compiled-program executions rather than Lucene
collector invocations, but the response shape matches the reference so
dashboards keep working.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, Optional


class SearchStats:
    """Per-shard-ish search counters (reference: SearchStats.Stats)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.query_total = 0
        self.query_time_ms = 0.0
        self.fetch_total = 0
        self.fetch_time_ms = 0.0
        self.suggest_total = 0
        self.scroll_total = 0
        # per-group counters for requests tagged with body `stats: [...]`
        # (reference: SearchStats groupStats / the `groups` scope of _stats)
        self.groups: Dict[str, Dict[str, int]] = {}

    def _group(self, g: str) -> Dict[str, int]:
        return self.groups.setdefault(g, {
            "query_total": 0, "query_time_in_millis": 0,
            "fetch_total": 0, "fetch_time_in_millis": 0})

    def on_query(self, ms: float, n: int = 1, groups=None):
        """n > 1: a batched execution serving n requests at once (msearch
        fast path) — counters must match the sequential path's totals."""
        with self._lock:
            self.query_total += n
            self.query_time_ms += ms
            for g in groups or ():
                gs = self._group(str(g))
                gs["query_total"] += n
                gs["query_time_in_millis"] += int(ms)

    def on_fetch(self, ms: float, n: int = 1, groups=None):
        with self._lock:
            self.fetch_total += n
            self.fetch_time_ms += ms
            for g in groups or ():
                gs = self._group(str(g))
                gs["fetch_total"] += n
                gs["fetch_time_in_millis"] += int(ms)

    def on_suggest(self):
        with self._lock:
            self.suggest_total += 1

    def on_scroll(self):
        with self._lock:
            self.scroll_total += 1

    def to_json(self) -> dict:
        out = {
            "query_total": self.query_total,
            "query_time_in_millis": int(self.query_time_ms),
            "fetch_total": self.fetch_total,
            "fetch_time_in_millis": int(self.fetch_time_ms),
            "suggest_total": self.suggest_total,
            "scroll_total": self.scroll_total,
        }
        if self.groups:
            out["groups"] = {g: dict(gs) for g, gs in self.groups.items()}
        return out


class TranslogRecoveryStats:
    """Process-wide accounting of translog replay damage: every corrupt
    tail a replay stopped at (reference: the recovery stats surfaced by
    TranslogService + the TranslogCorruptedException logging — here the
    frames/bytes dropped are COUNTED so operators see data loss instead
    of inferring it from doc counts)."""

    def __init__(self, max_events: int = 64):
        from collections import deque

        self._lock = threading.Lock()
        self.frames_skipped = 0
        self.bytes_dropped = 0
        # counters stay exact; the per-event detail ring is bounded so a
        # node that keeps reopening damaged translogs can't grow its own
        # monitoring payload without limit
        self.events = deque(maxlen=max_events)

    def record(self, path: str, bytes_dropped: int, reason: str) -> None:
        with self._lock:
            self.frames_skipped += 1
            self.bytes_dropped += int(bytes_dropped)
            self.events.append({
                "path": path,
                "bytes_dropped": int(bytes_dropped),
                "reason": reason,
                "timestamp": int(time.time() * 1000),
            })

    def reset(self) -> None:
        with self._lock:
            self.frames_skipped = 0
            self.bytes_dropped = 0
            self.events.clear()

    def to_json(self) -> dict:
        with self._lock:
            return {
                "corrupt_tail_frames_skipped": self.frames_skipped,
                "corrupt_tail_bytes_dropped": self.bytes_dropped,
                "events": list(self.events),
            }


#: process-global sink — translog replay (index/translog.py) reports here
TRANSLOG_RECOVERY = TranslogRecoveryStats()


def record_corrupt_tail(path: str, bytes_dropped: int, reason: str) -> None:
    TRANSLOG_RECOVERY.record(path, bytes_dropped, reason)


def aggregate_slowlog(index_services) -> dict:
    """Node-wide slow-operation gauge for ``/_nodes``, aggregated from
    THIS node's own indices' slow-log rings (tracing/slowlog.py). NOT a
    process-global singleton: several in-process nodes (the multi-host
    test harness, embedded setups) must each report only their own slow
    ops — the same per-node discipline translog_recovery follows. The
    per-entry detail (source, took, level) stays in the per-index
    rings; this is the one-glance number a dashboard polls to notice an
    index going slow before digging into which one."""
    search_total = indexing_total = 0
    for svc in index_services:
        sl = getattr(svc, "slowlog", None)
        if sl is None:
            continue
        search_total += sl.query.total
        indexing_total += sl.index.total
    return {"search_slow_total": search_total,
            "indexing_slow_total": indexing_total}


def aggregate_recovery(index_services) -> dict:
    """Per-NODE recovery gauges aggregated from the node's own indices'
    RecoveryRegistry entries (index/recovery.py) — the same per-node
    discipline translog_recovery and slowlog follow. ``incremental``
    counts ops-mode (checkpoint-based) recoveries; ``full_copies`` the
    fallback streams — the ratio is the replication-safety win made
    visible (reference: RecoveryStats current_as_source/target)."""
    out = {"current_as_source": 0, "current_as_target": 0,
           "total": 0, "incremental": 0, "full_copies": 0,
           "ops_replayed": 0, "docs_copied": 0}
    for svc in index_services:
        reg = getattr(svc, "recoveries", None)
        if reg is None:
            continue
        out["current_as_source"] += getattr(reg, "source_active", 0)
        for e in reg.entries():
            out["total"] += 1
            if e["stage"] not in ("done", "failed"):
                out["current_as_target"] += 1
            if e.get("mode") == "ops":
                out["incremental"] += 1
            elif e.get("mode") == "full":
                out["full_copies"] += 1
            out["ops_replayed"] += e.get("ops_replayed", 0)
            out["docs_copied"] += e.get("docs_copied", 0)
    return out


def process_stats() -> dict:
    """Process-level stats (reference: ProcessService → _nodes/stats.process)."""
    import resource

    ru = resource.getrusage(resource.RUSAGE_SELF)
    out: Dict[str, Any] = {
        "timestamp": int(time.time() * 1000),
        "open_file_descriptors": _count_fds(),
        "cpu": {"total_in_millis": int((ru.ru_utime + ru.ru_stime) * 1000)},
        "mem": {
            # CURRENT resident set (dashboards treat this as live memory);
            # peak kept under its honest name
            "resident_in_bytes": _current_rss() or ru.ru_maxrss * 1024,
            "peak_resident_in_bytes": ru.ru_maxrss * 1024,
        },
    }
    return out


def _current_rss() -> int:
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return 0


def _count_fds() -> int:
    try:
        return len(os.listdir("/proc/self/fd"))
    except OSError:
        return -1


def os_stats() -> dict:
    """Host stats (reference: OsService → _nodes/stats.os)."""
    out: Dict[str, Any] = {"timestamp": int(time.time() * 1000)}
    try:
        load1, load5, load15 = os.getloadavg()
        out["cpu"] = {"load_average": {"1m": load1, "5m": load5, "15m": load15}}
    except OSError:
        pass
    try:
        with open("/proc/meminfo") as f:
            mem = {}
            for line in f:
                parts = line.split()
                if parts[0] in ("MemTotal:", "MemFree:", "MemAvailable:"):
                    mem[parts[0][:-1]] = int(parts[1]) * 1024
        out["mem"] = {
            "total_in_bytes": mem.get("MemTotal", 0),
            "free_in_bytes": mem.get("MemFree", 0),
            "available_in_bytes": mem.get("MemAvailable", 0),
        }
    except OSError:
        pass
    return out


def device_label() -> dict:
    """This process's devices as JAX reports them — what every benchmark
    record and smoke verdict names. Local devices: in a multi-process
    world ``jax.devices()`` also lists the other ranks' chips."""
    import jax

    devs = jax.local_devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def device_stats() -> dict:
    """Accelerator stats — the TPU-native analogue of the reference's JVM
    heap section: platform, device kind and count as JAX reports them, and
    per-device HBM usage where the backend exposes it (XLA:CPU does not).
    Every device of THIS process (``jax.local_devices()``: a remote rank's
    chip is not addressable and has no memory stats here — each node
    reports its own). ``hbm`` sums over them; ``devices`` carries one row
    per chip, so the four-chip host shows where each shard's bytes
    actually sit."""
    import jax

    devs = jax.local_devices()
    rows = []
    for dev in devs:
        stats = dev.memory_stats() or {}
        rows.append({
            "id": dev.id,
            "bytes_in_use": stats.get("bytes_in_use", 0),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use", 0),
            "bytes_limit": stats.get("bytes_limit", 0),
        })
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
        "hbm": {
            "bytes_in_use": sum(r["bytes_in_use"] for r in rows),
            "bytes_limit": sum(r["bytes_limit"] for r in rows),
        },
        "devices": rows,
    }
