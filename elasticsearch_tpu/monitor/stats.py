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
import re
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from elasticsearch_tpu.monitor.metrics import OVERFLOW_LABEL, SHARED


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


# -- per-thread CPU account (estpu_thread_cpu_seconds_total) ----------------

#: the closed label set of the family's ``group``: a metric file can only
#: select exact label values, so the set never grows
THREAD_GROUPS = ("request", "runtime", "background", "other")
#: named ``(group, thread)`` series kept of the names the product does not
#: give (a runtime thread's ``comm``, a foreign Python thread's name); a
#: later one folds into ``{group, thread="_other_"}`` (the registry's
#: overflow convention). The ``request`` and ``background`` names are a set
#: the product's code fixes, and are never folded: on the four-chip host
#: 645 threads carry 22 runtime names, and they would otherwise take the
#: room before the first search starts the pools
THREAD_SERIES_CAP = 32
_NAMED_BY_CODE = ("request", "background")
#: CPU that no live or remembered thread accounts for: what threads that
#: ended burned after their last reading
EXITED = ("other", "exited")

# the threads a search runs on besides the pools' (rest/server.py names
# the accept loop and its connection threads; cluster/transport.py a
# connection's thread that runs a search phase for another node)
_REQUEST = ("rest.server", "rest.connection", "transport.search",
            "estpu-coalescer")
# daemons the product starts off a search's path (watchdog, watcher,
# warm-up, transport, allocator, recovery, fault detection)
_BACKGROUND = ("estpu-watchdog", "estpu-warmup", "resource-watcher", "tpu-")
_DIGITS = re.compile(r"\d+")


def classify_thread(name: Optional[str], comm: str) -> Tuple[str, str]:
    """``(group, thread)`` of one OS thread of this process: ``name`` is
    the name of the Python thread that owns it (None where none does),
    ``comm`` its ``/proc`` name. ``thread`` drops per-thread indexes, so a
    pool's workers share one series."""
    if name is None:  # the XLA / PjRt / TPU runtime, the profiler
        return "runtime", _DIGITS.sub("", comm) or "?"
    if name.startswith("tpu["):  # FixedThreadPool worker tpu[<pool>][i]
        return "request", name.rpartition("[")[0]
    if name in _REQUEST:
        return "request", name
    if name.startswith(_BACKGROUND):
        return "background", _DIGITS.sub("", name.partition("[")[0])
    return "other", _DIGITS.sub("", name)


def thread_cpu_seconds(tid: int) -> Optional[float]:
    """On-CPU seconds of thread ``tid`` of this process, None once it has
    ended: the thread's own CPU clock (the kernel's ``sum_exec_runtime``,
    brought up to date for a thread that is running; a syscall that keeps
    the interpreter's lock)."""
    try:
        # glibc's encoding of a thread's CPU clock: ~tid << 3 | PERTHREAD
        # | SCHED — what pthread_getcpuclockid returns for that thread
        return time.clock_gettime(((~tid) << 3) | 6)
    except OSError:
        return None


def task_ids() -> List[int]:
    return [int(t) for t in os.listdir("/proc/self/task")]


def thread_comm(tid: int) -> str:
    try:
        with open(f"/proc/self/task/{tid}/comm") as fh:
            return fh.read().strip()
    except OSError:
        return ""


def python_threads() -> Dict[int, threading.Thread]:
    """{native thread id: the Python thread that owns it}; a foreign
    thread's ``_DummyThread`` record owns nothing."""
    return {t.native_id: t for t in threading.enumerate()
            if t.native_id is not None
            and not isinstance(t, threading._DummyThread)}


class _Thread:
    """One live OS thread's place in the account: its series, the reading
    its series started from, its last reading, and its names."""

    __slots__ = ("key", "base", "last", "name", "comm")

    def __init__(self, key: Tuple[str, str], name: Optional[str], comm: str):
        self.key = key
        self.base = self.last = 0.0
        self.name = name
        self.comm = comm


class ThreadCpuAccount:
    """Every CPU-second of the process by ``(group, thread)``, read from
    the kernel at scrape time (nothing is recorded per request).

    Monotone and closed: a thread that ends, or whose series changes (a
    rename), leaves its counted seconds in its old series; retired seconds
    are kept by series, never by thread id, so memory stays bounded by the
    series cap. What no live or remembered thread accounts for — threads
    that ended after their last reading — is ``{group="other",
    thread="exited"}``: the process clock, read after every thread, minus
    the threads. So the family summed is the process CPU at each scrape."""

    def __init__(self):
        self._lock = threading.Lock()
        self._keys: set = set()
        self._live: Dict[int, _Thread] = {}
        self._retired: Dict[Tuple[str, str], float] = {}
        self._exited = 0.0
        # threads that filed themselves are dropped once they are gone at
        # the next scrape, or here past this many (a server nobody scrapes
        # still opens and closes connections)
        self._prune_at = 64

    def _series(self, name: Optional[str], comm: str) -> Tuple[str, str]:
        key = classify_thread(name, comm)
        if key[0] in _NAMED_BY_CODE or key in self._keys:
            return key
        if len(self._keys) < THREAD_SERIES_CAP:
            self._keys.add(key)
            return key
        return key[0], OVERFLOW_LABEL

    def _retire(self, t: _Thread) -> None:
        self._retired[t.key] = self._retired.get(t.key, 0.0) \
            + t.last - t.base

    def _observe(self, tid: int, secs: float,
                 owner: Optional[threading.Thread]) -> None:
        name = owner.name if owner is not None else None
        t = self._live.get(tid)
        if t is not None and secs < t.last:  # the id went to a new thread
            self._retire(t)
            t = None
        if t is None:
            comm = thread_comm(tid) if name is None else ""
            t = self._live[tid] = _Thread(self._series(name, comm), name,
                                          comm)
        elif name is not None and name != t.name:
            # renamed, or its Python thread came up after the OS thread
            # (one that is going down keeps its series to the end)
            t.name = name
            key = self._series(name, t.comm)
            if key != t.key:
                self._retire(t)
                t.key, t.base = key, t.last
        t.last = secs

    def _retire_ended(self, alive: set) -> None:
        for tid in [tid for tid in self._live if tid not in alive]:
            self._retire(self._live.pop(tid))

    def observe_current(self) -> None:
        """File the calling thread's CPU now. A thread that ends between
        two scrapes calls it last, so what it burned stays in its series
        and not in ``exited`` (one reading a thread, never one a request)."""
        with self._lock:
            self._observe(threading.get_native_id(), time.thread_time(),
                          threading.current_thread())
            if len(self._live) > self._prune_at:
                self._retire_ended(set(task_ids()))
                self._prune_at = 2 * len(self._live) + 64

    def collect(self) -> List[Tuple[Tuple[str, str], float]]:
        """``[((group, thread), seconds), ...]`` as of now."""
        with self._lock:
            owners = python_threads()
            me = threading.get_native_id()
            seen = set()
            for tid in task_ids():
                if tid == me:
                    continue
                secs = thread_cpu_seconds(tid)
                if secs is not None:  # None: it ended since the listing
                    seen.add(tid)
                    self._observe(tid, secs, owners.get(tid))
            # the scraping thread last, the process clock after it: the
            # process then holds every thread's reading
            self._observe(me, time.thread_time(), owners.get(me))
            seen.add(me)
            process = time.process_time()
            self._retire_ended(seen)
            totals = dict(self._retired)
            for t in self._live.values():
                totals[t.key] = totals.get(t.key, 0.0) + t.last - t.base
            # never falls: a thread read a few µs before the process clock
            # can run on between the two readings of one scrape
            self._exited = max(self._exited,
                               process - sum(totals.values()))
            totals[EXITED] = totals.get(EXITED, 0.0) + self._exited
            return sorted(totals.items())


#: the process's account (the process, like the device, is one for every
#: node in it)
THREAD_CPU = ThreadCpuAccount()
SHARED.collector(
    "estpu_thread_cpu_seconds_total",
    "On-CPU seconds of the process's threads by group (request, runtime, "
    "background, other) and thread; summed, the process CPU at the scrape",
    ("group", "thread"), THREAD_CPU.collect, kind="counter")
