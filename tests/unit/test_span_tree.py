"""One request, one span tree, socket to socket (tracing/tracer.py).

A REST ``_search`` through a real ``RestServer`` socket yields one trace
rooted at ``rest.request`` whose leaves are the phase spans in order, with
parent links that resolve across the pool hop and durations that add up;
``_msearch`` bodies each get a ``search`` span; the coalescer's batch is a
span of its own; every span is also an event on the profiler's clock.
"""
import contextvars
import functools
import json
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from elasticsearch_tpu.node import Node
from elasticsearch_tpu.tracing import Span, Tracer, span
from elasticsearch_tpu.tracing import tracer as tracer_mod

LEAVES = {"rest.pool_wait", "search.body_json", "serving.queue_wait",
          "serving.batch_wait", "search.rewrite", "search.plan",
          "device.dispatch", "device.wait", "search.fetch", "rest.respond"}
CONTAINERS = {"rest.request", "search", "serving.batch",
              "msearch.batch_attempt"}
HEAD = ["alpha", "beta", "gamma", "delta"]


@pytest.fixture(scope="module")
def node():
    from elasticsearch_tpu.index import segment as segmod

    # the small corpus builds a dense block, so the fused batch tiers are
    # reachable (the test_serving / test_msearch_batch knob)
    orig = segmod.build_dense_impact
    segmod.build_dense_impact = functools.partial(orig, df_threshold=8)
    n = Node()
    # the host tiers (search_shards -> query_phase), pinned: the cells'
    # text shard is served by them
    n.create_index("tr", {"settings": {"index": {
        "number_of_shards": 1, "search": {"mesh": "false"}}},
        "mappings": {"properties": {"body": {"type": "text"}}}})
    svc = n.indices["tr"]
    rng = np.random.default_rng(7)
    for i in range(96):
        words = list(rng.choice(HEAD, size=5)) + [f"rare{i % 19}"]
        svc.index_doc(str(i), {"body": " ".join(words)})
    svc.refresh()
    yield n
    segmod.build_dense_impact = orig
    n.close()


@pytest.fixture(scope="module")
def server(node):
    from elasticsearch_tpu.rest.server import RestServer

    srv = RestServer(node, host="127.0.0.1", port=0)
    srv.start(background=True)
    yield srv
    srv.stop()


def _post(srv, path, body, ndjson=False):
    data = (("\n".join(json.dumps(x) for x in body) + "\n").encode()
            if ndjson else json.dumps(body).encode())
    rq = urllib.request.Request(f"http://127.0.0.1:{srv.port}{path}",
                                data=data, method="POST")
    with urllib.request.urlopen(rq) as resp:
        return json.loads(resp.read())


def _trace_of(node, n_before, root_name="rest.request", wait_s=5.0):
    """The spans of the newest trace rooted at ``root_name`` that
    finished after ``n_before`` spans had."""
    deadline = time.monotonic() + wait_s
    while time.monotonic() < deadline:  # the root closes after the reply
        new = [s for s in node.tracer.spans()
               if s.name == root_name and s.parent_id is None]
        if len(new) > n_before:
            break
        time.sleep(0.005)
    root = new[-1]
    return root, [s for s in node.tracer.spans()
                  if s.trace_id == root.trace_id]


def _roots(node, name="rest.request"):
    return len([s for s in node.tracer.spans()
                if s.name == name and s.parent_id is None])


MATCH = {"query": {"match": {"body": "alpha rare3"}}, "size": 5}


def test_one_rest_search_is_one_span_tree(node, server):
    _post(server, "/tr/_search", MATCH)  # compile outside the reading
    before = _roots(node)
    _post(server, "/tr/_search", MATCH)
    root, spans = _trace_of(node, before)
    assert root.name == "rest.request" and root.parent_id is None
    assert root.tags["endpoint"] == "/{index}/_search"
    assert root.tags["method"] == "POST" and root.tags["status"] == 200
    assert root.tags["bytes_in"] > 0 and root.tags["bytes_out"] > 0
    by_id = {s.span_id: s for s in spans}
    # every parent link resolves inside the trace, across the pool hop
    for s in spans:
        assert s is root or s.parent_id in by_id, s.name
    search = [s for s in spans if s.name == "search"]
    assert len(search) == 1 and search[0].parent_id == root.span_id
    assert search[0].thread != root.thread  # opened on the pool's worker
    assert search[0].tags["index"] == "tr"
    # nothing but the vocabulary
    assert {s.name for s in spans} <= LEAVES | CONTAINERS
    # the leaves, in the order the work happens
    order = [s.name for s in sorted(spans, key=lambda s: s.start)
             if s.name in LEAVES]
    firsts = list(dict.fromkeys(order))
    assert firsts == ["rest.pool_wait", "search.body_json",
                      "search.rewrite", "search.plan", "device.dispatch",
                      "device.wait", "search.fetch", "rest.respond"]
    assert order[-1] == "rest.respond"
    # leaves never nest in one another: each hangs off a container
    for s in spans:
        if s.name in LEAVES:
            assert by_id[s.parent_id].name in CONTAINERS
    # sum of leaves + containers' self = root, within 5%
    total = (sum(s.duration for s in spans if s.name in LEAVES)
             + sum(s.self_wall for s in spans if s.name in CONTAINERS))
    assert total == pytest.approx(root.duration, rel=0.05)
    wait = [s for s in spans if s.name == "device.wait"]
    assert wait and all(s.tags["bytes"] > 0 for s in wait)


def test_self_cpu_of_a_request_fits_the_cpu_measured_round_it(node, server):
    before = _roots(node)
    cpu0 = time.process_time()
    _post(server, "/tr/_search", MATCH)
    root, spans = _trace_of(node, before)
    cpu = time.process_time() - cpu0
    self_cpu = sum(s.self_cpu for s in spans)
    assert 0 < self_cpu <= cpu
    for s in spans:
        assert 0 <= s.self_cpu <= s.cpu <= s.duration + 1e-4


def test_msearch_bodies_each_get_a_search_span(node, server):
    # aggs keep every body off the fused tiers: four single searches
    bodies = []
    for q in ("alpha", "beta", "gamma rare2", "delta"):
        bodies += [{"index": "tr"},
                   {"query": {"match": {"body": q}}, "size": 3,
                    "aggs": {"n": {"value_count": {"field": "body"}}}}]
    before = _roots(node)
    out = _post(server, "/_msearch", bodies, ndjson=True)
    assert len(out["responses"]) == 4
    root, spans = _trace_of(node, before)
    assert root.tags["endpoint"] == "/_msearch"
    searches = [s for s in spans if s.name == "search"]
    assert len(searches) == 4
    assert all(s.parent_id == root.span_id for s in searches)
    attempt = [s for s in spans if s.name == "msearch.batch_attempt"]
    assert len(attempt) == 1
    assert attempt[0].tags["outcome"] in ("fused", "declined")
    assert attempt[0].error is None


def test_fused_msearch_is_tagged_fused(node, server):
    bodies = []
    for q in ("alpha", "beta", "gamma", "delta"):
        bodies += [{"index": "tr"},
                   {"query": {"match": {"body": q}}, "size": 3}]
    before = _roots(node)
    _post(server, "/_msearch", bodies, ndjson=True)
    root, spans = _trace_of(node, before)
    attempt = [s for s in spans if s.name == "msearch.batch_attempt"]
    assert [s.tags["outcome"] for s in attempt] == ["fused"]
    # the fused tiers' phases hang off the attempt, not off a search
    assert not [s for s in spans if s.name == "search"]
    kids = {s.name for s in spans if s.parent_id == attempt[0].span_id}
    assert {"search.plan", "device.dispatch", "device.wait",
            "search.fetch"} <= kids


def test_swallowed_batch_error_is_counted_and_logged(node, server,
                                                     monkeypatch, caplog):
    from elasticsearch_tpu.search import batch

    def boom(svc, bodies, min_batch=2):
        raise RuntimeError("planted")

    monkeypatch.setattr(batch, "try_batched_msearch", boom)
    bodies = []
    for q in ("alpha", "beta"):
        bodies += [{"index": "tr"},
                   {"query": {"match": {"body": q}}, "size": 3}]
    before = _roots(node)
    with caplog.at_level("WARNING", logger="elasticsearch_tpu.node"):
        out = _post(server, "/_msearch", bodies, ndjson=True)
        out = _post(server, "/_msearch", bodies, ndjson=True)
    # the sequential loop still answers
    assert [len(r["hits"]["hits"]) for r in out["responses"]] == [3, 3]
    root, spans = _trace_of(node, before + 1)
    attempt = [s for s in spans if s.name == "msearch.batch_attempt"][0]
    assert attempt.tags["outcome"] == "error"
    assert attempt.error == "RuntimeError: planted"
    assert len([s for s in spans if s.name == "search"]) == 2
    text = node.metrics.expose()
    line = [ln for ln in text.splitlines() if ln.startswith(
        'estpu_span_errors_total{span="msearch.batch_attempt"}')]
    assert line and float(line[0].rsplit(" ", 1)[1]) >= 2
    # once a process for the type, however often it happens
    logged = [r for r in caplog.records if "RuntimeError" in r.getMessage()]
    assert len(logged) <= 1


def test_coalesced_pair_has_a_batch_span_and_two_batch_waits(node):
    node.serving.apply_cluster_settings({
        "serving.coalescer.mode": "always",
        "serving.coalescer.max_wait": "250ms",
        "serving.coalescer.idle_gap": "100ms"})
    try:
        n_batches = _roots(node, "serving.batch")
        barrier = threading.Barrier(2)
        out = [None, None]

        def one(i, q):
            barrier.wait()
            out[i] = node.search("tr", {"query": {"match": {"body": q}},
                                        "size": 4})

        threads = [threading.Thread(target=one, args=(i, q))
                   for i, q in enumerate(("alpha", "beta gamma"))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert all(not t.is_alive() for t in threads)
        assert all(r and r["hits"]["hits"] for r in out)
        batches = [s for s in node.tracer.spans()
                   if s.name == "serving.batch" and s.parent_id is None]
        assert len(batches) == n_batches + 1
        batch = batches[-1]
        assert batch.tags["batch_size"] == 2
        assert batch.tags["flush_reason"] in ("deadline", "idle", "full")
        spans = node.tracer.spans()
        searches = [s for s in spans if s.name == "search"][-2:]
        # the batch names its first member's trace
        assert batch.tags["first_trace_id"] in {s.trace_id
                                                for s in searches}
        for s in searches:
            kids = [c.name for c in spans if c.parent_id == s.span_id]
            assert "serving.queue_wait" in kids
            assert "serving.batch_wait" in kids
        # the fused execution's phases hang off the batch span
        kids = {c.name for c in spans if c.parent_id == batch.span_id}
        assert {"search.plan", "device.dispatch", "device.wait",
                "search.fetch"} <= kids
    finally:
        node.serving.apply_cluster_settings({})


def test_profile_phases_are_the_spans_durations(node, server):
    before = _roots(node)
    out = _post(server, "/tr/_search?profile=true", MATCH)
    root, spans = _trace_of(node, before)
    phases = out["profile"]["shards"][0]["tpu"]["phases"]

    def nanos(name):
        return sum(int(s.duration * 1e9) for s in spans if s.name == name)

    assert phases["rewrite_nanos"] == nanos("search.rewrite") > 0
    assert phases["host_sync_nanos"] == nanos("device.wait") > 0
    # executor_build is query_phase's own plan span (SegmentContext); the
    # term-group planning below it is a search.plan too
    assert 0 < phases["executor_build_nanos"] <= nanos("search.plan")


def test_spans_are_events_on_the_profilers_clock(node, server, tmp_path):
    import jax

    from benchmarks.trace import host_spans
    from benchmarks.trace import reduce as trace_reduce

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            before = _roots(node)
            _post(server, "/tr/_search", MATCH)
            root, spans = _trace_of(node, before)
    finally:
        jax.profiler.stop_trace()
    found = trace_reduce.find_trace(str(tmp_path))
    events = host_spans.read_host_events(found)
    window = [e for e in events if e[0] == trace_reduce.WINDOW]
    assert len(window) == 1
    lo, hi = window[0][1], window[0][1] + window[0][2]
    mine = [e for e in events if e[3] == root.trace_id]
    req = [e for e in mine if e[0] == "rest.request"]
    plan = [e for e in mine if e[0] == "search.plan"]
    assert len(req) == 1 and plan
    r0, r1 = req[0][1], req[0][1] + req[0][2]
    assert lo <= r0 < r1 <= hi
    for _, s, d, _t in plan:
        assert r0 <= s and s + d <= r1
    # every span of the trace but the recorded pool wait has its event
    want = sorted(s.name for s in spans if s.name != "rest.pool_wait")
    assert sorted(e[0] for e in mine) == want
    # and the derived pool wait is the head of the request
    derived = [e for e in host_spans.span_events(
        events, host_spans.load_names()) if e[3] == root.trace_id
        and e[0] == "rest.pool_wait"]
    assert len(derived) == 1 and derived[0][1] == r0 < derived[0][2] < r1


# -- the substrate --------------------------------------------------------------

def test_span_without_an_active_span_is_the_shared_noop(monkeypatch):
    made = []
    orig = Span.__init__

    def counting(self, *a, **k):
        made.append(1)
        orig(self, *a, **k)

    monkeypatch.setattr(Span, "__init__", counting)
    a, b = span("search.plan"), span("device.dispatch", program="x")
    assert a is b is tracer_mod.NOOP and not made
    with span("device.wait") as sp:
        sp.tag(bytes=1)  # a no-op takes tags and drops them
    tracer_mod.record("rest.pool_wait", 0.0, 1.0)  # nowhere to file it
    tr = Tracer("n")
    with tr.span("root"):
        with span("child") as c:
            pass
    assert len(made) == 2 and isinstance(c, Span)
    assert [s.name for s in tr.spans()] == ["child", "root"]


def test_self_is_own_minus_children_even_across_threads():
    tr = Tracer("n")
    with tr.span("root") as root:
        with tr.span("same_thread") as a:  # on a tracer: reads the CPU
            time.sleep(0.01)
        ctx = contextvars.copy_context()
        box = {}

        def other():
            with tr.span("other_thread") as b:
                x = 0
                for i in range(200_000):  # burn CPU on the other thread
                    x += i
                box["b"] = b

        th = threading.Thread(target=lambda: ctx.run(other))
        th.start()
        th.join(timeout=30)
        assert not th.is_alive()
        t0 = time.perf_counter()
        time.sleep(0.005)
        waited_s = time.perf_counter() - t0
        tracer_mod.record("waited", t0, waited_s)
    b = box["b"]
    assert b.parent_id == root.span_id and b.thread != root.thread
    assert root.child_wall == pytest.approx(
        a.duration + b.duration + waited_s)
    assert root.self_wall == pytest.approx(
        root.duration - root.child_wall, abs=1e-9)
    # another thread's CPU is not part of this thread's reading
    assert root.child_cpu == pytest.approx(a.cpu)
    assert b.cpu > 0 and root.self_cpu <= root.cpu
    waited = [s for s in tr.spans() if s.name == "waited"][0]
    assert waited.parent_id == root.span_id and waited.cpu == 0.0
    # overlapping children can out-sum the parent: self stops at 0
    root.child_wall = root.duration + 1.0
    assert root.self_wall == 0.0


def test_ids_are_unique_and_counts_exact_under_threads():
    tr = Tracer("n")
    seen = []

    def work():
        for _ in range(200):
            with tr.span("a") as sp:
                seen.append((sp.trace_id, sp.span_id))

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert all(not t.is_alive() for t in threads)
    ids = [i for pair in seen for i in pair]
    assert len(set(ids)) == len(ids) == 3200
    assert all(len(i) == 16 for i in ids)
    for _ in range(3):  # reading the count does not move it
        st = tr.stats()
        assert st["started_total"] == st["finished_total"] == 1600


def test_pool_carries_the_submitters_context_and_files_the_wait():
    from elasticsearch_tpu.utils.threadpool import FixedThreadPool

    pool = FixedThreadPool("search", 1, 4)
    tr = Tracer("n")
    try:
        release = threading.Event()
        blocker = threading.Thread(
            target=lambda: pool.execute(release.wait, 5.0))
        blocker.start()
        time.sleep(0.05)  # the one worker is busy now

        def handler():
            with span("inside") as sp:
                return sp

        threading.Timer(0.05, release.set).start()
        with tr.span("rest.request") as root:
            inner = pool.execute(handler)
        blocker.join(timeout=10)
        assert not blocker.is_alive()
        assert isinstance(inner, Span)
        assert inner.parent_id == root.span_id
        assert inner.thread != root.thread
        wait = [s for s in tr.spans() if s.name == "rest.pool_wait"]
        assert len(wait) == 1 and wait[0].parent_id == root.span_id
        assert wait[0].tags == {"pool": "search"}
        assert 0.02 < wait[0].duration < root.duration
        assert wait[0].start + wait[0].duration <= inner.start + 1e-6
    finally:
        pool.shutdown()


def test_sink_feeds_the_self_and_cpu_families():
    from benchmarks.metrics import counters
    from elasticsearch_tpu.monitor.metrics import MetricsRegistry, span_sink

    reg = MetricsRegistry()
    tr = Tracer("n")
    tr.set_sink(span_sink(reg))
    with tr.span("rest.request") as root:
        with span("search.plan") as leaf:
            time.sleep(0.005)
    snap = counters.parse(reg.expose())  # as the span_ms.* metrics read

    def val(family, name):
        return counters.total(snap, [{"family": family,
                                      "labels": {"span": name}}])

    assert val("estpu_span_duration_seconds_sum", "rest.request") == \
        pytest.approx(root.duration)
    assert val("estpu_span_self_seconds_total", "rest.request") == \
        pytest.approx(root.duration - leaf.duration)
    assert val("estpu_span_self_seconds_total", "search.plan") == \
        pytest.approx(leaf.duration)
    assert val("estpu_span_cpu_seconds_total", "rest.request") == \
        pytest.approx(root.self_cpu)
    total_cpu = (val("estpu_span_cpu_seconds_total", "rest.request")
                 + val("estpu_span_cpu_seconds_total", "search.plan"))
    assert total_cpu == pytest.approx(root.cpu)  # no second counted twice


def test_tracing_imports_without_jax_and_annotates_once_it_is_there():
    code = (
        "import sys\n"
        "import elasticsearch_tpu.tracing as t\n"
        "from elasticsearch_tpu.tracing import tracer\n"
        "assert 'jax' not in sys.modules, 'tracing imported jax'\n"
        "tr = t.Tracer('n')\n"
        "with tr.span('a'):\n"
        "    with t.span('b'):\n"
        "        pass\n"
        "assert 'jax' not in sys.modules\n"
        "assert tracer._annotation_cls is None\n"
        "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr
    import jax  # loaded here: a session's spans now hold annotations

    tr = Tracer("n")
    with tr.span("a") as sp:
        assert sp._ann is None  # no profiler session: a flag test
    assert tracer_mod._annotation_cls is jax.profiler.TraceAnnotation


def test_a_phase_reads_the_cpu_clock_only_under_a_profiler_session(
        tmp_path):
    import jax

    def burn():
        x = 0
        for i in range(100_000):
            x += i

    tr = Tracer("n")
    with tr.span("search") as outer:
        with span("search.plan") as leaf:
            burn()
    # the container read the clock, the phase did not: its CPU is in the
    # container's self CPU, so the sum over spans is still the thread's
    assert leaf.cpu == 0.0 and leaf._ann is None
    assert outer.cpu > 0 and outer.self_cpu == outer.cpu
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with tr.span("search") as outer:
            with span("search.plan") as leaf:
                burn()
                assert leaf._ann is not None
    finally:
        jax.profiler.stop_trace()
    assert 0 < leaf.cpu <= leaf.duration
    assert outer.self_cpu == pytest.approx(outer.cpu - leaf.cpu)
