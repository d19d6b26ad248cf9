"""The process's per-thread CPU account (monitor/stats.py), the family
``estpu_thread_cpu_seconds_total{group, thread}`` on every node's
``/_prometheus/metrics``: a thread's CPU lands in its series, a series
never falls, and the family summed is the process CPU at the scrape.
Every assertion is a sum, a bound or a count of CPU clocks — never a ratio
to wall time, which a loaded test machine moves."""
import threading
import time

import pytest

from benchmarks.metrics import counters
from elasticsearch_tpu.monitor import metrics, stats
from elasticsearch_tpu.monitor.metrics import OVERFLOW_LABEL

FAMILY = "estpu_thread_cpu_seconds_total"


def _scrape() -> dict:
    """{(group, thread): seconds} from the shared registry's exposition."""
    rows = counters.parse(metrics.SHARED.expose()).get(FAMILY, {})
    out = {}
    for labels, v in rows.items():
        got = counters._labels(labels)
        out[(got["group"], got["thread"])] = v
    return out


class _Burner:
    """A thread that burns ``cpu_s`` of its own CPU clock, records it, and
    stays alive until released."""

    def __init__(self, name: str, cpu_s: float):
        self.cpu_s = cpu_s
        self.burned = None
        self.done = threading.Event()
        self.release = threading.Event()
        self.thread = threading.Thread(target=self._run, name=name,
                                       daemon=True)

    def _run(self):
        end = time.thread_time() + self.cpu_s
        x = 0
        while time.thread_time() < end:
            x += 1
        self.burned = time.thread_time()
        self.done.set()
        self.release.wait(30)

    def __enter__(self):
        self.thread.start()
        assert self.done.wait(60)
        return self

    def __exit__(self, *exc):
        self.release.set()
        self.thread.join(30)
        assert not self.thread.is_alive()


def test_a_pool_workers_cpu_rises_in_its_request_series():
    before = _scrape()
    with _Burner("tpu[cpuprobe][3]", 0.08) as b:
        after = _scrape()
    key = ("request", "tpu[cpuprobe]")
    rise = after[key] - before.get(key, 0.0)
    assert rise >= 0.8 * b.burned


def test_an_ended_thread_keeps_its_reading_and_no_series_falls():
    acct = stats.ThreadCpuAccount()
    seen = [dict(acct.collect())]
    with _Burner("tpu[ending][0]", 0.03) as b:
        seen.append(dict(acct.collect()))
    key = ("request", "tpu[ending]")
    # ended: its tid is gone from /proc, its seconds stay in its series
    for _ in range(2):
        seen.append(dict(acct.collect()))
        assert seen[-1][key] >= b.burned
    with _Burner("tpu[ending][1]", 0.02):  # the same series, a new thread
        seen.append(dict(acct.collect()))
    seen.append(dict(acct.collect()))
    assert seen[-1][key] >= b.burned + 0.02
    for older, newer in zip(seen, seen[1:]):
        for k, v in older.items():
            assert newer[k] >= v, k


def test_the_family_summed_is_the_process_cpu_at_the_scrape():
    with _Burner("tpu[sumprobe][0]", 0.02), \
            _Burner("estpu-watchdog", 0.02):
        for _ in range(3):
            lo = time.process_time()
            total = sum(_scrape().values())
            hi = time.process_time()
            assert lo <= total <= hi


def test_the_groups_are_the_closed_four():
    with _Burner("tpu[groupprobe][0]", 0.01):
        got = _scrape()
    assert {g for g, _ in got} <= set(stats.THREAD_GROUPS)
    assert stats.EXITED in got


def test_the_watchdogs_counter_walk_never_reads_the_threads():
    assert not [k for k in metrics.process_counters()
                if "estpu_thread_cpu" in k]
    assert not [k for k in metrics.SHARED.counter_values()
                if "estpu_thread_cpu" in k]


def test_past_the_cap_new_series_fold_into_other():
    """Names the product does not give fold past the cap; a pool that
    starts after them keeps its own series."""
    acct = stats.ThreadCpuAccount()
    release = threading.Event()
    # digits are stripped from a series name: letters keep them apart
    names = [f"probe-{chr(97 + i // 26)}{chr(97 + i % 26)}"
             for i in range(stats.THREAD_SERIES_CAP + 8)]
    threads = [threading.Thread(target=release.wait, args=(30,), name=n,
                                daemon=True) for n in names]
    for t in threads:
        t.start()
    try:
        acct.collect()
        with _Burner("tpu[latepool][0]", 0.01):
            got = dict(acct.collect())
    finally:
        release.set()
        for t in threads:
            t.join(30)
    assert got[("request", "tpu[latepool]")] > 0.0
    named = [k for k in got if k[1] != OVERFLOW_LABEL and k != stats.EXITED
             and k[0] in ("runtime", "other")]
    assert len(named) == stats.THREAD_SERIES_CAP
    assert ("other", OVERFLOW_LABEL) in got
    assert len([k for k in named if k[1].startswith("probe-")]) < len(names)
    lo = time.process_time()
    total = sum(dict(acct.collect()).values())
    hi = time.process_time()
    assert lo <= total <= hi


def test_threads_that_file_themselves_as_they_end_stay_bounded():
    """A connection's thread files its CPU as it ends; a server nobody
    scrapes keeps no record of every connection it ever had."""
    acct = stats.ThreadCpuAccount()
    before = dict(acct.collect())
    for _ in range(300):
        t = threading.Thread(target=acct.observe_current,
                             name="rest.connection", daemon=True)
        t.start()
        t.join(30)
        assert not t.is_alive()
    assert len(acct._live) <= 2 * len(stats.task_ids()) + 65
    lo = time.process_time()
    after = dict(acct.collect())
    hi = time.process_time()
    assert lo <= sum(after.values()) <= hi
    key = ("request", "rest.connection")
    assert after[key] > before.get(key, 0.0)


@pytest.mark.parametrize("name,comm,expected", [
    ("tpu[search][3]", "", ("request", "tpu[search]")),
    ("rest.connection", "", ("request", "rest.connection")),
    ("rest.server", "", ("request", "rest.server")),
    ("estpu-coalescer", "", ("request", "estpu-coalescer")),
    ("transport.search", "", ("request", "transport.search")),
    ("estpu-watchdog", "", ("background", "estpu-watchdog")),
    ("tpu-transport[connection]", "", ("background", "tpu-transport")),
    ("tpu-relocate[logs-7][2]", "", ("background", "tpu-relocate")),
    ("resource-watcher", "", ("background", "resource-watcher")),
    ("MainThread", "", ("other", "MainThread")),
    ("Thread-12 (run)", "", ("other", "Thread- (run)")),
    (None, "tpu_runtime_12", ("runtime", "tpu_runtime_")),
])
def test_a_thread_is_filed_by_its_name(name, comm, expected):
    assert stats.classify_thread(name, comm) == expected


def test_a_threads_clock_reads_what_the_thread_reads_itself():
    tid = threading.get_native_id()
    lo = time.thread_time()
    got = stats.thread_cpu_seconds(tid)
    hi = time.thread_time()
    assert lo <= got <= hi


def test_an_ended_threads_clock_reads_none():
    t = threading.Thread(target=lambda: None, name="ends-now")
    t.start()
    t.join(30)
    # join returns once Python lets go of it; the OS thread ends just after
    for _ in range(3000):
        if t.native_id not in stats.task_ids():
            break
        time.sleep(0.001)
    assert t.native_id not in stats.task_ids()
    assert stats.thread_cpu_seconds(t.native_id) is None


def test_a_remote_search_phase_is_filed_as_request_cpu():
    """A two-node cluster: the query and fetch phases a node runs for
    another node's search run on a transport connection's thread, which
    files its CPU as a search's (and as it ends, not as ``exited``)."""
    import socket

    from elasticsearch_tpu.cluster.bootstrap import MultiHostCluster
    from elasticsearch_tpu.cluster.transport import handler_thread_name
    from elasticsearch_tpu.node import Node

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    node0, node1 = Node(name="cpu-rank0"), Node(name="cpu-rank1")
    c0 = MultiHostCluster(node0, rank=0, world=2, transport_port=port,
                          ping_interval=0)
    c1 = MultiHostCluster(node1, rank=1, world=2, transport_port=port,
                          ping_interval=0)
    try:
        c0.data.create_index("cpu2", {"settings": {
            "number_of_shards": 2, "number_of_replicas": 0}})
        for i in range(8):
            c0.data.index_doc("cpu2", str(i), {"title": f"fox {i}"})
        c0.data.refresh("cpu2")
        before = _scrape()
        for _ in range(3):
            r = c0.node.search("cpu2", {"query": {"match": {"title": "fox"}},
                                        "size": 20})
            assert r["hits"]["total"] == 8
        after = _scrape()
    finally:
        try:
            c1.close()
        finally:
            c0.close()
            node1.close()
            node0.close()
    assert handler_thread_name("indices:data/read/search[phase/query]") \
        == "transport.search"
    assert handler_thread_name("cluster:publish") \
        == "tpu-transport[connection]"
    key = ("request", "transport.search")
    assert after.get(key, 0.0) > before.get(key, 0.0)
