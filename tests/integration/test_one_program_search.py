"""One enqueue a search segment on the host loop (search/service.py
``query_phase``): a plain search over a pure disjunctive term group runs
ONE device program a segment, fed by one packed argument
(``bm25_term_group_topk``); every other plain search runs its score program
and ONE finishing program (``finish_topk``). Counts and equality only,
never a time."""
import functools

import numpy as np
import pytest

from elasticsearch_tpu.monitor import kernels
from elasticsearch_tpu.node import Node

HEAD = ["alpha", "beta", "gamma", "delta"]
N_SEGMENTS = 3


@pytest.fixture(scope="module")
def node():
    from elasticsearch_tpu.index import segment as segmod

    # small segments build a dense block, so hybrid and all-dense groups
    # exist beside scatter-only ones (the test_span_tree knob)
    orig = segmod.build_dense_impact
    segmod.build_dense_impact = functools.partial(orig, df_threshold=8)
    n = Node()
    # pinned to the host tiers (search_shards -> query_phase), which serve
    # a deployment-sized text shard
    n.create_index("op", {"settings": {"index": {
        "number_of_shards": 1, "search": {"mesh": "false"}}},
        "mappings": {"properties": {
            "body": {"type": "text"}, "n": {"type": "long"},
            "tag": {"type": "keyword"}}}})
    n.create_index("nest", {"settings": {"index": {
        "number_of_shards": 1, "search": {"mesh": "false"}}},
        "mappings": {"properties": {
            "body": {"type": "text"},
            "comments": {"type": "nested", "properties": {
                "text": {"type": "text"}}}}}})
    rng = np.random.default_rng(7)
    svc, nest = n.indices["op"], n.indices["nest"]
    for s in range(N_SEGMENTS):
        for i in range(s * 64, (s + 1) * 64):
            words = list(rng.choice(HEAD, size=5)) + [f"rare{i % 19}"]
            svc.index_doc(str(i), {"body": " ".join(words), "n": i,
                                   "tag": f"t{i % 3}"})
            nest.index_doc(str(i), {
                "body": " ".join(words),
                "comments": [{"text": "alpha child"},
                             {"text": f"rare{i % 19} child"}]})
        svc.refresh()
        nest.refresh()
    yield n
    segmod.build_dense_impact = orig
    n.close()


def _segments(node, index):
    (group,) = node.indices[index].groups
    return group.copies[0].engine.segments


def _search(node, index, body):
    """(response, the search's spans, rise of the bm25_one_program
    count) of one search through Node.search."""
    seen = {s.span_id for s in node.tracer.spans()}
    before = kernels.snapshot().get("bm25_one_program", 0)
    out = node.search(index, body)
    spans = [s for s in node.tracer.spans() if s.span_id not in seen]
    (root,) = [s for s in spans if s.name == "search"]
    spans = [s for s in spans if s.trace_id == root.trace_id]
    return out, spans, kernels.snapshot().get("bm25_one_program", 0) - before


def _hits(out):
    return [(h["_id"], h["_score"]) for h in out["hits"]["hits"]]


def _programs(spans):
    return sorted(s.tags["program"] for s in spans
                  if s.name == "device.dispatch")


def test_the_index_has_several_segments(node):
    assert len(_segments(node, "op")) == N_SEGMENTS
    assert len(_segments(node, "nest")) == N_SEGMENTS
    assert all(s.has_nested for s in _segments(node, "nest"))


TERM_GROUPS = {
    # every present term has a dense row: the tail is empty (T 1, lens 0)
    "all_dense": {"match": {"body": "alpha beta"}},
    "hybrid": {"match": {"body": "alpha rare3"}},
    "hybrid_three_terms": {"match": {"body": "beta rare3 rare7"}},
    "scatter_only": {"match": {"body": "rare3 rare5"}},
    "term": {"term": {"body": "rare4"}},
    "boosted": {"match": {"body": {"query": "gamma rare11", "boost": 2.5}}},
    "absent_term": {"match": {"body": "alpha rare6 nosuchword"}},
    "nothing_matches": {"match": {"body": "nosuchword"}},
}


@pytest.mark.parametrize("name", sorted(TERM_GROUPS))
def test_a_term_group_is_one_dispatch_and_one_pull_a_segment(node, name):
    body = {"query": TERM_GROUPS[name], "size": 7}
    out, spans, rise = _search(node, "op", body)
    assert _programs(spans) == ["bm25_term_group_topk"] * N_SEGMENTS
    waits = [s for s in spans if s.name == "device.wait"]
    assert len(waits) == N_SEGMENTS
    assert all(s.tags["bytes"] == 4 * (2 * 7 + 1) for s in waits)
    assert rise == N_SEGMENTS
    # planned once: query_phase's own span and the term group's, a segment
    assert len([s for s in spans if s.name == "search.plan"]) \
        == 2 * N_SEGMENTS
    # the staged path (a min_score every hit passes leaves the fused
    # shapes: query tree -> finish_topk) and the sorted branch's eager
    # code return the same documents with the same scores, bit for bit
    staged, s_spans, s_rise = _search(
        node, "op", dict(body, min_score=0.0))
    assert s_rise == 0 and "finish_topk" in _programs(s_spans)
    assert _hits(out) == _hits(staged)
    assert out["hits"]["total"] == staged["hits"]["total"]
    if name == "nothing_matches":
        assert out["hits"]["total"] == 0 and not out["hits"]["hits"]
    else:
        assert out["hits"]["total"] > 0 and len(out["hits"]["hits"]) == 7
    by_n, _sp, _r = _search(node, "op", dict(
        body, size=200, sort=[{"n": "asc"}], track_scores=True))
    assert by_n["hits"]["total"] == out["hits"]["total"]
    assert {h["_id"] for h in out["hits"]["hits"]} \
        <= {h["_id"] for h in by_n["hits"]["hits"]}


@pytest.mark.parametrize("index,name", [("op", "hybrid"),
                                        ("op", "all_dense"),
                                        ("nest", "all_dense")])
def test_the_counter_is_the_prometheus_series(node, index, name):
    # (the series appears with its first count: make sure it has one)
    _search(node, index, {"query": TERM_GROUPS[name]})
    text = node.metrics.expose()
    before = [ln for ln in text.splitlines() if ln.startswith(
        'estpu_kernel_dispatch_total{kernel="bm25_one_program"}')]
    _search(node, index, {"query": TERM_GROUPS[name]})
    after = [ln for ln in node.metrics.expose().splitlines()
             if ln.startswith(
                 'estpu_kernel_dispatch_total{kernel="bm25_one_program"}')]
    assert len(before) == len(after) == 1
    assert float(after[0].rsplit(" ", 1)[1]) \
        - float(before[0].rsplit(" ", 1)[1]) == N_SEGMENTS


OTHER_SHAPES = {
    # name: (body, programs a segment)
    "bool": ({"query": {"bool": {
        "must": [{"match": {"body": "alpha"}}],
        "filter": [{"range": {"n": {"gte": 10}}}]}}},
        ["bm25_hybrid", "finish_topk"]),
    "match_and": ({"query": {"match": {"body": {
        "query": "alpha rare3", "operator": "and"}}}},
        ["bm25_hybrid", "finish_topk"]),
    "min_score": ({"query": {"match": {"body": "alpha rare3"}},
                   "min_score": 1.0},
                  ["bm25_hybrid", "finish_topk"]),
    "aggregated": ({"query": {"match": {"body": "alpha rare3"}},
                    "aggs": {"tags": {"terms": {"field": "tag"}}}},
                   ["bm25_hybrid", "finish_topk"]),
    "match_all": ({"query": {"match_all": {}}}, ["finish_topk"]),
    "sorted": ({"query": {"match": {"body": "alpha rare3"}},
                "sort": [{"n": "desc"}]},
               ["bm25_hybrid", "mask_ops"]),
}


@pytest.mark.parametrize("name", sorted(OTHER_SHAPES))
def test_other_shapes_do_not_count_as_one_program(node, name):
    body, per_segment = OTHER_SHAPES[name]
    out, spans, rise = _search(node, "op", body)
    assert rise == 0
    assert out["hits"]["total"] > 0
    assert _programs(spans) == sorted(per_segment * N_SEGMENTS)
    if name == "aggregated":
        buckets = out["aggregations"]["tags"]["buckets"]
        assert sum(b["doc_count"] for b in buckets) == out["hits"]["total"]
    if name == "min_score":
        assert all(h["_score"] >= 1.0 for h in out["hits"]["hits"])
        loose, _s, _r = _search(node, "op", {"query": body["query"]})
        assert out["hits"]["total"] < loose["hits"]["total"]


@pytest.mark.parametrize("name", ["all_dense", "hybrid", "scatter_only"])
def test_nested_segments_take_the_one_program_with_their_roots(node, name):
    body = {"query": TERM_GROUPS[name], "size": 9}
    out, spans, rise = _search(node, "nest", body)
    assert _programs(spans) == ["bm25_term_group_topk"] * N_SEGMENTS
    assert rise == N_SEGMENTS
    staged, _s, s_rise = _search(node, "nest", dict(body, min_score=0.0))
    assert s_rise == 0
    assert _hits(out) == _hits(staged)
    flat, _s, _r = _search(node, "op", body)
    # the children (two a document) are hidden: the same roots as the
    # flat index holds, document for document
    assert out["hits"]["total"] == staged["hits"]["total"] \
        == flat["hits"]["total"]


def test_a_child_field_term_group_finds_no_root(node):
    out, spans, rise = _search(
        node, "nest", {"query": {"match": {"comments.text": "child"}}})
    assert rise == N_SEGMENTS and out["hits"]["total"] == 0


@pytest.mark.parametrize("text", ["gamma rare2", "gamma delta"],
                         ids=["hybrid", "all_dense"])
def test_a_deleted_document_leaves_the_one_program_result(node, text):
    body = {"query": {"match": {"body": text}}, "size": 5}
    out, _s, _r = _search(node, "op", body)
    top = out["hits"]["hits"][0]["_id"]
    svc = node.indices["op"]
    src = svc.get_doc(top)["_source"]
    svc.delete_doc(top)
    svc.refresh()
    try:
        after, spans, rise = _search(node, "op", body)
        assert after["hits"]["total"] == out["hits"]["total"] - 1
        assert top not in [h["_id"] for h in after["hits"]["hits"]]
        assert "bm25_term_group_topk" in _programs(spans)
        assert rise == len(_segments(node, "op"))
    finally:
        svc.index_doc(top, src)
        svc.refresh()


def test_profile_files_the_one_program_under_topk(node):
    out, spans, rise = _search(node, "op", {
        "query": TERM_GROUPS["hybrid"], "profile": True})
    assert rise == len(_segments(node, "op"))
    tpu = out["profile"]["shards"][0]["tpu"]
    assert tpu["phases"]["topk_nanos"] > 0
    assert tpu["phases"]["host_sync_nanos"] > 0
    assert tpu["device_calls"] == tpu["segments"]


def test_repeating_a_shape_compiles_nothing(node):
    from elasticsearch_tpu.tracing import retrace

    _search(node, "op", {"query": {"match": {"body": "beta rare8"}}})
    snap = retrace.snapshot()
    _search(node, "op", {"query": {"match": {"body": "delta rare9"}}})
    assert retrace.traces_since(snap) == 0


@pytest.mark.parametrize("name", ["all_dense", "hybrid", "scatter_only"])
def test_the_one_program_takes_one_host_argument(node, name, monkeypatch):
    """Everything but the packed word buffer is already on the device:
    one host→device copy a search segment (five before)."""
    import jax

    from elasticsearch_tpu.ops import scoring

    calls = []
    real = scoring.bm25_term_group_topk

    def spy(*args, **statics):
        calls.append((args, statics))
        return real(*args, **statics)

    monkeypatch.setattr(scoring, "bm25_term_group_topk", spy)
    _search(node, "op", {"query": TERM_GROUPS[name]})
    # (an earlier test's delete and re-index may have added a segment)
    assert len(calls) == len(_segments(node, "op"))
    for args, statics in calls:
        host = [a for a in args if isinstance(a, np.ndarray)]
        assert len(host) == 1 and host[0].dtype == np.int32
        R, T = statics["R"], statics["T"]
        assert host[0].shape == (2 * R + 3 * T,)
        if name == "all_dense" and R:
            assert T == 1 and not host[0][2 * R + T: 2 * R + 2 * T].any()
        assert all(a is None or isinstance(a, jax.Array)
                   for a in args if a is not host[0])
    # (such a one-document segment has no dense block)
    dense = sum(statics["R"] > 0 for _a, statics in calls)
    assert dense == (0 if name == "scatter_only" else N_SEGMENTS)


def test_no_switch_chooses_the_arithmetic():
    """A plain term-group search has one arithmetic: the two environment
    variables that chose a less exact one are read nowhere in the
    package, and ops/pallas_kernels.py holds no BM25 kernel whose use an
    environment variable could decide."""
    import os
    import re

    import elasticsearch_tpu

    root = os.path.dirname(elasticsearch_tpu.__file__)
    gone = re.compile("ESTPU_BM25_BATCH_KERNEL|ESTPU_IMPACT_PRECISION")
    found = []
    for dirpath, _dirs, files in os.walk(root):
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(dirpath, f)
                with open(path) as fh:
                    found += [(path, n) for n, line in enumerate(fh, 1)
                              if gone.search(line)]
    assert not found
    with open(os.path.join(root, "ops", "pallas_kernels.py")) as fh:
        text = fh.read()
    assert "bm25" not in text.lower()
    reads = re.findall(r"os\.environ[^\n]*", text)
    assert reads and all("ESTPU_MAXSIM_KERNEL" in r for r in reads)
