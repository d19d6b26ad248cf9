"""Continuous metrics substrate (monitor/metrics.py + consumers).

Covers the ISSUE-7 acceptance surface: histogram bucket/percentile math,
Prometheus text-exposition well-formedness (parsed by a strict
mini-parser, label escaping round-trip), the tracer-sink span→histogram
flow, a mixed search+index workload scrape containing the required
families, /_cluster/stats fan-out over an in-process 2-node cluster, the
per-node scrape after a distributed search, hot-threads sampling
semantics, _cat/thread_pool h=/largest, and the bench metrics-delta
helpers.
"""
import json
import re
import socket
import threading
import time

import pytest

from elasticsearch_tpu.monitor.metrics import (DEFAULT_LATENCY_BUCKETS,
                                               Histogram, MetricsRegistry,
                                               OVERFLOW_LABEL, SHARED,
                                               counters_delta,
                                               escape_label_value,
                                               process_counters, span_sink)
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.rest.server import RestController


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ---------------------------------------------------------------------------
# a strict exposition-format parser (the round-trip the acceptance demands)
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})? (\+Inf|-?[0-9][0-9.e+-]*)$")
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _unescape(v: str) -> str:
    return v.replace("\\n", "\n").replace('\\"', '"').replace("\\\\", "\\")


def parse_exposition(text: str):
    """(types, helps, samples) or raise — every line must be a comment,
    blank, or a well-formed sample; every sample's base family must have
    a preceding # TYPE."""
    types, helps = {}, {}
    samples = []  # (name, labels dict, float value)
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            assert kind in ("counter", "gauge", "histogram"), line
            types[name] = kind
            continue
        if line.startswith("# HELP "):
            _, _, name, h = line.split(" ", 3)
            helps[name] = h
            continue
        assert not line.startswith("#"), f"unknown comment: {line}"
        m = _SAMPLE_RE.match(line)
        assert m, f"malformed sample line: {line!r}"
        name, rawlabels, value = m.groups()
        labels = {}
        if rawlabels:
            consumed = 0
            for lm in _LABEL_RE.finditer(rawlabels):
                labels[lm.group(1)] = _unescape(lm.group(2))
                consumed = lm.end()
            leftover = rawlabels[consumed:].strip(", ")
            assert not leftover, f"unparsed labels {leftover!r} in {line!r}"
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        assert base in types or name in types, \
            f"sample {name} has no # TYPE"
        samples.append((name, labels,
                        float("inf") if value == "+Inf" else float(value)))
    return types, helps, samples


def sample_value(samples, name, **labels):
    for n, ls, v in samples:
        if n == name and all(ls.get(k) == str(w) for k, w in labels.items()):
            return v
    return None


# ---------------------------------------------------------------------------
# histogram math
# ---------------------------------------------------------------------------

class TestHistogram:
    def test_bucketing_and_counts(self):
        h = Histogram(DEFAULT_LATENCY_BUCKETS)
        for _ in range(50):
            h.observe(0.001)
        for _ in range(40):
            h.observe(0.01)
        for _ in range(10):
            h.observe(0.1)
        assert h.count == 100
        assert abs(h.sum - (50 * 0.001 + 40 * 0.01 + 10 * 0.1)) < 1e-9
        assert h.max == pytest.approx(0.1)

    def test_percentiles_interpolate_within_bucket(self):
        h = Histogram(DEFAULT_LATENCY_BUCKETS)
        for _ in range(50):
            h.observe(0.001)
        for _ in range(40):
            h.observe(0.01)
        for _ in range(10):
            h.observe(0.1)
        # p50 falls in 0.001's bucket (bounds 0.0008 .. 0.0016)
        assert 0.0008 <= h.percentile(50) <= 0.0016
        # p99 falls in 0.1's bucket, clamped by the exact max
        assert 0.05 <= h.percentile(99) <= 0.1
        assert h.percentile(100) == pytest.approx(0.1)

    def test_all_zero_observations_clamp_to_max(self):
        # p50 interpolating inside bucket 0 must not exceed the exact
        # max of 0.0 (the "estimate never exceeds max" invariant)
        h = Histogram(DEFAULT_LATENCY_BUCKETS)
        for _ in range(3):
            h.observe(0.0)
        assert h.percentile(50) == 0.0
        s = h.summary()
        assert s["p50_seconds"] <= s["max_seconds"] == 0.0

    def test_empty_and_single(self):
        h = Histogram(DEFAULT_LATENCY_BUCKETS)
        assert h.percentile(99) == 0.0
        h.observe(0.0042)
        assert 0.0 < h.percentile(50) <= 0.0064
        s = h.summary()
        assert s["count"] == 1 and s["max_seconds"] == pytest.approx(0.0042)

    def test_overflow_bucket_beyond_top_bound(self):
        h = Histogram((0.001, 0.01))
        h.observe(5.0)  # past every finite bound
        assert h.counts[-1] == 1
        # estimated inside the (top bound, exact max] overflow bucket
        assert 0.01 < h.percentile(99) <= 5.0
        assert h.percentile(100) == pytest.approx(5.0)


class TestRegistry:
    def test_counter_gauge_and_labels(self):
        r = MetricsRegistry()
        c = r.counter("t_total", "help", ("k",))
        c.labels("a").inc()
        c.labels("a").inc(2)
        c.labels("b").inc()
        g = r.gauge("t_gauge", "help")
        g.set(42)
        vals = r.counter_values()
        assert vals['t_total{k="a"}'] == 3
        assert vals['t_total{k="b"}'] == 1

    def test_family_is_idempotent_by_name(self):
        r = MetricsRegistry()
        a = r.counter("x_total", "h", ("k",))
        b = r.counter("x_total", "different help ignored", ("k",))
        assert a is b

    def test_label_cardinality_cap_collapses_to_overflow(self):
        r = MetricsRegistry()
        c = r.counter("capped_total", "h", ("k",), max_series=2)
        for i in range(6):
            c.labels(f"v{i}").inc()
        series = c.series()
        assert len(series) <= 3  # 2 real + the overflow bucket
        assert any(lv == (OVERFLOW_LABEL,) for lv, _ in series)
        # no count lost: everything past the cap landed in _other_
        assert sum(ch.value for _, ch in series) == 6


# ---------------------------------------------------------------------------
# exposition well-formedness
# ---------------------------------------------------------------------------

class TestExposition:
    def test_roundtrip_counter_gauge_histogram(self):
        r = MetricsRegistry()
        r.counter("a_total", "counts a", ("k",)).labels("x").inc(3)
        r.gauge("b_bytes", "bytes of b").set(1.5)
        h = r.histogram("c_seconds", "latency of c", ("op",))
        h.labels("read").observe(0.003)
        h.labels("read").observe(0.3)
        types, helps, samples = parse_exposition(r.expose())
        assert types == {"a_total": "counter", "b_bytes": "gauge",
                         "c_seconds": "histogram"}
        assert helps["a_total"] == "counts a"
        assert sample_value(samples, "a_total", k="x") == 3
        assert sample_value(samples, "b_bytes") == 1.5
        assert sample_value(samples, "c_seconds_count", op="read") == 2
        assert sample_value(
            samples, "c_seconds_sum", op="read") == pytest.approx(0.303)
        # bucket lines are CUMULATIVE and end at +Inf == count
        buckets = [(ls["le"], v) for n, ls, v in samples
                   if n == "c_seconds_bucket"]
        assert buckets[-1][0] == "+Inf" and buckets[-1][1] == 2
        cum = [v for _, v in buckets]
        assert cum == sorted(cum), "bucket counts must be cumulative"

    def test_label_escaping_roundtrip(self):
        ugly = 'a"b\\c\nd'
        assert escape_label_value(ugly) == 'a\\"b\\\\c\\nd'
        r = MetricsRegistry()
        r.counter("esc_total", "h", ("k",)).labels(ugly).inc()
        _, _, samples = parse_exposition(r.expose())
        assert sample_value(samples, "esc_total", k=ugly) == 1

    def test_help_newline_escaped(self):
        r = MetricsRegistry()
        r.counter("nl_total", "line1\nline2").inc()
        text = r.expose()
        assert "# HELP nl_total line1\\nline2" in text
        parse_exposition(text)  # single-line HELP parses


# ---------------------------------------------------------------------------
# tracer sink
# ---------------------------------------------------------------------------

class TestSpanSink:
    def test_finished_spans_land_in_histogram(self):
        from elasticsearch_tpu.tracing import Tracer

        r = MetricsRegistry()
        t = Tracer("n1")
        t.set_sink(span_sink(r))
        with t.span("phase.alpha"):
            pass
        with t.span("phase.alpha"):
            with t.span("phase.beta"):
                pass
        _, _, samples = parse_exposition(r.expose())
        assert sample_value(samples, "estpu_span_duration_seconds_count",
                            span="phase.alpha") == 2
        assert sample_value(samples, "estpu_span_duration_seconds_count",
                            span="phase.beta") == 1

    def test_error_spans_counted_and_sink_failure_is_swallowed(self):
        from elasticsearch_tpu.tracing import Tracer

        r = MetricsRegistry()
        t = Tracer("n1")
        t.set_sink(span_sink(r))
        with pytest.raises(ValueError):
            with t.span("phase.err"):
                raise ValueError("boom")
        _, _, samples = parse_exposition(r.expose())
        assert sample_value(samples, "estpu_span_errors_total",
                            span="phase.err") == 1
        # a broken sink must not break spans
        t.set_sink(lambda sp: 1 / 0)
        with t.span("phase.ok"):
            pass
        assert t.stats()["finished_total"] == 2


# ---------------------------------------------------------------------------
# the acceptance scrape: mixed search+index workload
# ---------------------------------------------------------------------------

@pytest.fixture()
def workload_node(tmp_path):
    n = Node(name="metrics-node", data_path=str(tmp_path))
    n.create_index("logs", {
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {"msg": {"type": "string"},
                                    "v": {"type": "integer"}}}})
    rc = RestController(n)
    for i in range(8):
        s, _ = rc.dispatch("PUT", f"/logs/_doc/{i}", {},
                           json.dumps({"msg": "hello world", "v": i}).encode())
        assert s in (200, 201)
    s, _ = rc.dispatch("POST", "/logs/_refresh", {}, b"")
    assert s == 200
    body = b'{"query": {"match": {"msg": "hello"}}}'
    for _ in range(4):
        s, r = rc.dispatch("POST", "/logs/_search", {}, body)
        assert s == 200 and r["hits"]["total"] == 8
    yield n, rc
    n.close()


class TestScrape:
    def test_wellformed_and_required_families(self, workload_node):
        n, rc = workload_node
        s, text = rc.dispatch("GET", "/_prometheus/metrics", {}, b"")
        assert s == 200 and isinstance(text, str)
        types, _, samples = parse_exposition(text)

        # search-latency histogram with populated buckets
        assert types["estpu_rest_request_duration_seconds"] == "histogram"
        inf = sample_value(samples,
                           "estpu_rest_request_duration_seconds_bucket",
                           endpoint="/{index}/_search", method="POST",
                           le="+Inf")
        assert inf == 4
        # per-endpoint request counters with status class
        assert types["estpu_rest_requests_total"] == "counter"
        assert sample_value(samples, "estpu_rest_requests_total",
                            endpoint="/{index}/_search", method="POST",
                            status="2xx") == 4
        assert sample_value(samples, "estpu_rest_requests_total",
                            endpoint="/{index}/_doc/{id}", method="PUT",
                            status="2xx") == 8
        # breaker used-bytes gauges (all five breakers)
        assert types["estpu_breaker_used_bytes"] == "gauge"
        for br in ("parent", "fielddata", "request", "in_flight_requests",
                   "segments"):
            assert sample_value(samples, "estpu_breaker_used_bytes",
                                breaker=br) is not None, br
        # threadpool queue + rejected counters
        assert sample_value(samples, "estpu_threadpool_queue_depth",
                            pool="search") is not None
        assert types["estpu_threadpool_rejected_total"] == "counter"
        assert sample_value(samples, "estpu_threadpool_rejected_total",
                            pool="search") is not None
        # jit compile counter
        assert types["estpu_jit_traces_total"] == "counter"
        assert sample_value(samples, "estpu_jit_traces_total") >= 0
        # span histogram fed by the tracer sink (search spans exist)
        assert sample_value(samples, "estpu_span_duration_seconds_count",
                            span="search") >= 4
        # write path: indexing ops + translog fsync (disk-backed index)
        assert sample_value(samples, "estpu_indexing_operations_total",
                            op="index") == 8
        assert sample_value(samples,
                            "estpu_translog_fsyncs_total") >= 8

    def test_nodes_stats_carries_percentile_summaries(self, workload_node):
        n, rc = workload_node
        s, st = rc.dispatch("GET", "/_nodes/stats", {}, b"")
        assert s == 200
        mets = st["nodes"][n.node_id]["metrics"]
        fam = mets["estpu_rest_request_duration_seconds"]
        row = next(r for r in fam
                   if r["labels"]["endpoint"] == "/{index}/_search")
        assert row["count"] == 4
        assert 0 < row["p50_seconds"] <= row["p99_seconds"]
        assert row["p99_seconds"] <= row["max_seconds"] * 1.0001

    def test_status_classes_split(self, workload_node):
        n, rc = workload_node
        s, _ = rc.dispatch("GET", "/nope/_doc/1", {}, b"")
        assert s == 404
        s, text = rc.dispatch("GET", "/_prometheus/metrics", {}, b"")
        _, _, samples = parse_exposition(text)
        assert sample_value(samples, "estpu_rest_requests_total",
                            endpoint="/{index}/_doc/{id}", method="GET",
                            status="4xx") == 1


# ---------------------------------------------------------------------------
# cluster stats fan-out + per-node scrape over a real 2-node cluster
# ---------------------------------------------------------------------------

@pytest.fixture()
def two_node_cluster():
    """Two MultiHostClusters in-process over real TCP (the
    test_observability/test_faults harness): rank 0 is the
    master+coordinator, rank 1 owns half the shards."""
    from elasticsearch_tpu.cluster.bootstrap import MultiHostCluster

    port = _free_port()
    node0 = Node(name="rank0")
    c0 = MultiHostCluster(node0, rank=0, world=2, transport_port=port,
                          ping_interval=0)
    node1 = Node(name="rank1")
    c1 = MultiHostCluster(node1, rank=1, world=2, transport_port=port)
    c0.data.create_index("evt", {
        "settings": {"number_of_shards": 2},
        "mappings": {"properties": {"n": {"type": "integer"}}}})
    assig = c0.dist_indices["evt"]["assignment"]
    assert len({o[0] for o in assig.values()}) == 2, assig
    for i in range(24):
        c0.data.index_doc("evt", str(i), {"n": i})
    c0.data.refresh("evt")
    yield c0, c1
    try:
        c1.close()
    finally:
        c0.close()
        node1.close()
        node0.close()


class TestClusterStats:
    def test_single_node_shape(self):
        n = Node(name="cs1")
        n.create_index("a", {"settings": {"number_of_shards": 1}})
        n.indices["a"].index_doc("1", {"x": 1})
        n.indices["a"].refresh()
        rc = RestController(n)
        s, cs = rc.dispatch("GET", "/_cluster/stats", {}, b"")
        assert s == 200
        assert cs["indices"]["count"] == 1
        assert cs["indices"]["docs"]["count"] == 1
        assert cs["indices"]["segments"]["count"] >= 1
        assert cs["nodes"]["count"]["total"] == 1
        assert cs["nodes"]["process"]["mem"]["resident_in_bytes"] > 0
        assert cs["status"] in ("green", "yellow", "red")
        assert "_index_names" not in cs
        n.close()

    def test_docs_count_primaries_only(self):
        # replicas hold the same documents: docs.count must not inflate
        # by the replication factor (store/segments DO count every copy)
        n = Node(name="cs-repl")
        n.create_index("r", {"settings": {"number_of_shards": 1,
                                          "number_of_replicas": 1}})
        for i in range(3):
            n.indices["r"].index_doc(str(i), {"x": i})
        n.indices["r"].refresh()
        rc = RestController(n)
        s, cs = rc.dispatch("GET", "/_cluster/stats", {}, b"")
        assert s == 200
        assert cs["indices"]["docs"]["count"] == 3
        assert cs["indices"]["shards"]["primaries"] == 1
        assert cs["indices"]["shards"]["total"] == 2
        n.close()

    def test_fanout_aggregates_both_members(self, two_node_cluster):
        c0, c1 = two_node_cluster
        r = c0.data.search("evt", {"size": 24})
        assert r["hits"]["total"] == 24
        # an index that exists ONLY on the remote member must still be
        # counted by the coordinator's index-name union
        c1.node.create_index("only1", {"settings": {"number_of_shards": 1}})
        c1.node.indices["only1"].index_doc("1", {"z": 1})
        c1.node.indices["only1"].refresh()
        rc = RestController(c0.node)
        s, cs = rc.dispatch("GET", "/_cluster/stats", {}, b"")
        assert s == 200
        # both members counted; the distributed index counted ONCE, the
        # remote-only local index counted too
        assert cs["nodes"]["count"]["total"] == 2
        assert cs["indices"]["count"] == 2
        # docs live on their owner processes; the fan-out sums them all
        assert cs["indices"]["docs"]["count"] == 25
        # shards from both owners
        assert cs["indices"]["shards"]["total"] >= 3
        assert cs["nodes"]["thread_pool"]["completed"] >= 0
        assert "_index_names" not in cs

    def test_each_member_scrape_reflects_the_distributed_search(
            self, two_node_cluster):
        c0, c1 = two_node_cluster
        r = c0.data.search("evt", {"size": 24})
        assert r["hits"]["total"] == 24
        # coordinator side: its scrape shows the coordinate span + tx bytes
        _, _, s0 = parse_exposition(
            RestController(c0.node).dispatch(
                "GET", "/_prometheus/metrics", {}, b"")[1])
        assert sample_value(s0, "estpu_span_duration_seconds_count",
                            span="search.coordinate") >= 1
        assert sample_value(s0, "estpu_transport_bytes_total",
                            direction="tx") > 0
        # remote owner side: ITS scrape shows the shard query work it
        # served and the frames it received — per-node registries stay
        # per-node even in-process
        _, _, s1 = parse_exposition(
            RestController(c1.node).dispatch(
                "GET", "/_prometheus/metrics", {}, b"")[1])
        assert sample_value(s1, "estpu_span_duration_seconds_count",
                            span="shard.query_phase") >= 1
        assert sample_value(s1, "estpu_span_duration_seconds_count",
                            span="transport.handle") >= 1
        assert sample_value(s1, "estpu_transport_bytes_total",
                            direction="rx") > 0
        # per-action transport latency recorded on the coordinator
        q_act = "indices:data/read/search[phase/query]"
        assert sample_value(
            s0, "estpu_transport_action_duration_seconds_count",
            action=q_act) >= 1


# ---------------------------------------------------------------------------
# hot threads sampling + _cat/thread_pool satellites
# ---------------------------------------------------------------------------

class TestHotThreads:
    def test_sampling_collates_stacks_busiest_first(self):
        n = Node(name="ht-node")
        rc = RestController(n)
        stop = threading.Event()

        def burn():
            x = 0
            while not stop.is_set():
                x += 1
            return x

        t = threading.Thread(target=burn, name="busy-burner", daemon=True)
        t.start()
        try:
            s, text = rc.dispatch(
                "GET", "/_nodes/hot_threads",
                {"interval": "10ms", "snapshots": "4", "threads": "8"}, b"")
        finally:
            stop.set()
            t.join(timeout=2)
            n.close()
        assert not t.is_alive()
        assert s == 200
        assert text.startswith(f"::: {{{n.name}}}")
        assert "snapshots=4" in text and "type=cpu" in text
        # collation lines: M/N snapshots sharing following K elements
        m = re.search(r"(\d+)/4 snapshots sharing following (\d+) elements",
                      text)
        assert m and 1 <= int(m.group(1)) <= 4
        # the reference's form, ranked by CPU burned: the burner first
        rows = re.findall(r"([\d.]+)% \(([\d.]+)ms out of (\d+)ms\) cpu "
                          r"usage by thread '([^']*)'", text)
        assert rows and rows[0][3] == "busy-burner"
        burned = [float(r[1]) for r in rows]
        assert burned[0] > 0.0 and burned == sorted(burned, reverse=True)
        # a thread runs on one core at a time: Y never passes Z (Z is
        # printed to the ms)
        assert all(float(r[1]) <= float(r[2]) + 1.0 for r in rows)
        # the false claim is gone: the clock is read, not guessed
        assert "snapshots non-idle" not in text

    def test_idle_threads_filtered_unless_asked(self):
        n = Node(name="ht2-node")
        rc = RestController(n)
        try:
            _, with_idle = rc.dispatch(
                "GET", "/_nodes/hot_threads",
                {"interval": "5ms", "snapshots": "2", "threads": "1000",
                 "ignore_idle_threads": "false"}, b"")
            _, without = rc.dispatch(
                "GET", "/_nodes/hot_threads",
                {"interval": "5ms", "snapshots": "2", "threads": "1000"}, b"")
            s_wait, wait = rc.dispatch(
                "GET", "/_nodes/hot_threads",
                {"interval": "5ms", "snapshots": "2", "type": "wait"}, b"")
            s_bad, _ = rc.dispatch("GET", "/_nodes/hot_threads",
                                   {"type": "bogus"}, b"")
        finally:
            n.close()
        # pool workers parked in queue.get burn no CPU: reported only when
        # ignore_idle_threads=false
        assert with_idle.count("usage by thread") > \
            without.count("usage by thread")
        assert "(0.0ms out of" in with_idle
        # the reference's other types are answered with the CPU ranking,
        # and say so; a type the reference does not know is a 400
        assert s_wait == 200 and "type=cpu (asked for wait" in wait
        assert s_bad == 400


class TestCatThreadPool:
    def test_pool_rows_include_largest_and_h_selection(self):
        from elasticsearch_tpu.rest.server import _cat_json_rows, _cat_table

        n = Node(name="ctp-node")
        rc = RestController(n)
        try:
            s, rows = rc.dispatch("GET", "/_cat/thread_pool",
                                  {"pools": "true"}, b"")
            assert s == 200
            by_name = {r["name"]: r for r in rows}
            assert "largest" in by_name["search"]
            assert "queue_size" in by_name["search"]
            assert by_name["management"]["largest"] >= 1  # ran this request
            # format=json keeps the full declared column set (threads/
            # queue_size must not vanish for existing consumers)
            json_rows = _cat_json_rows(rows, {})
            assert {"name", "threads", "queue_size", "largest",
                    "completed"} <= set(json_rows[0])
            # h= selects columns through the one serialization layer
            # (the same path every other _cat endpoint uses over HTTP)
            sel = _cat_json_rows(rows, {"h": "name,largest"})
            assert all(set(r.keys()) == {"name", "largest"} for r in sel)
            # unknown h columns silently drop (RestTable semantics)
            sel2 = _cat_json_rows(rows, {"h": "name,frobnicate"})
            assert all(set(r.keys()) == {"name"} for r in sel2)
            # text table form honors h= too
            table = _cat_table(rows, {"h": "name,largest", "v": "true"})
            assert table.splitlines()[0].split() == ["name", "largest"]
        finally:
            n.close()


# ---------------------------------------------------------------------------
# bench delta helpers
# ---------------------------------------------------------------------------

class TestBenchDelta:
    def test_process_counters_and_delta(self):
        from elasticsearch_tpu.monitor import kernels

        before = process_counters()
        assert "kernels.executor_prep_hit" in before
        assert "jit.traces_total" in before
        kernels.record("executor_prep_hit")
        kernels.record("executor_prep_miss", 2)
        after = process_counters()
        d = counters_delta(before, after)
        assert d["kernels.executor_prep_hit"] == 1
        assert d["kernels.executor_prep_miss"] == 2

    def test_unknown_sentinel_becomes_typed_null(self):
        # the -1 snapshot sentinel (trace auditor absent) must surface
        # as None (JSON null) in the delta — unavailable, never a number
        # a consumer could mix into arithmetic (and never a fake 0)
        d = counters_delta({"jit.traces_total": -1.0},
                           {"jit.traces_total": -1.0})
        assert d["jit.traces_total"] is None
        d = counters_delta({"jit.traces_total": -1.0},
                           {"jit.traces_total": 5.0})
        assert d["jit.traces_total"] is None
        d = counters_delta({"a": None}, {"a": 3.0})
        assert d["a"] is None

    def test_shared_registry_counters_in_snapshot(self):
        SHARED.counter("estpu_test_shared_total", "t").inc(3)
        snap = process_counters()
        assert snap.get("estpu_test_shared_total") >= 3
