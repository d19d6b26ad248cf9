"""One sharded program a search (parallel/term_group_sharded.py): a plain
term-group search over a four-shard index whose shards each hold their one
segment on a device of their own runs every shard's
``bm25_term_group_topk`` under ``shard_map``, merges on the device and
pulls once — and answers what ``search_shards``' shard-after-shard loop
answers, bit for bit. 4 of the 8 forced host devices; counts, placement and
equality only, never a time."""
import gc
import math

import numpy as np
import pytest

from elasticsearch_tpu.monitor import kernels
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.parallel import term_group_sharded

S = 4
COMMON = [f"c{i}" for i in range(6)]
TAIL = [f"t{i}" for i in range(200)]
K1, B = 1.2, 0.75
# the four-shard cell's limits (benchmarks/cells/…4shard.match-steady.json)
SCORE_ERR, RANK_GAP = 5e-05, 1e-05


def _index(n, name, shards, mesh, docs):
    settings = {"number_of_shards": shards}
    if not mesh:
        settings["search"] = {"mesh": "false"}
    n.create_index(name, {"settings": {"index": settings}, "mappings": {
        "properties": {"body": {"type": "text"}, "n": {"type": "long"},
                       "tag": {"type": "keyword"}}}})
    svc = n.indices[name]
    for doc_id, toks in docs:
        svc.index_doc(doc_id, {"body": " ".join(toks), "n": int(doc_id),
                               "tag": f"g{int(doc_id) % 3}"})
    svc.refresh()
    return svc


@pytest.fixture(scope="module")
def world():
    """node, the corpus as (id, tokens) a document, and which shard of
    ``ix`` holds which document."""
    rng = np.random.default_rng(33)
    n = Node()
    n.create_index("probe", {"settings": {"index": {"number_of_shards": S}}})
    route = n.indices["probe"].route
    docs = []
    for i in range(2400):
        sid = route(str(i)).shard_id
        toks = (list(rng.choice(COMMON, 3, replace=False))
                + list(rng.choice(TAIL, int(rng.integers(2, 12)))))
        # dense (df >= 128) on shard 0 only
        if rng.random() < (0.4 if sid == 0 else 0.1):
            toks.append("mid")
        if sid == 0 and rng.random() < 0.05:
            toks.append("only0")  # absent from shards 1-3
        if i % 7 == 0:
            toks = ["c0", "t1"]  # identical documents: ties across shards
        docs.append((str(i), toks))
    _index(n, "ix", S, False, docs)          # the sharded route's index
    # every shard the same documents under other ids: the same statistics,
    # so a document scores the same to the bit on all four shards
    ids = {s: [] for s in range(S)}
    i = 10_000
    while any(len(v) < 150 for v in ids.values()):
        sid = route(str(i)).shard_id
        if len(ids[sid]) < 150:
            ids[sid].append(str(i))
        i += 1
    _index(n, "ties", S, False, [(doc_id, docs[j][1]) for s in range(S)
                                 for j, doc_id in enumerate(ids[s])])
    _index(n, "meshy", S, True, docs[:400])  # the mesh DSL path's
    _index(n, "one", 1, False, docs[:400])   # never enters the route
    yield n, docs
    n.close()


def _searchers(n, index):
    return [g.reader(None).searcher for g in n.indices[index].groups]


def _search(n, index, body):
    """(reply, spans of the search, rise of every kernel counter)."""
    seen = {s.span_id for s in n.tracer.spans()}
    before = kernels.snapshot()
    out = n.search(index, body)
    after = kernels.snapshot()
    spans = [s for s in n.tracer.spans() if s.span_id not in seen]
    (root,) = [s for s in spans if s.name == "search"]
    rise = {k: v - before.get(k, 0) for k, v in after.items()
            if v - before.get(k, 0)}
    return out, [s for s in spans if s.trace_id == root.trace_id], rise


def _shard_after_shard(n, index, body, monkeypatch):
    """The same search with the route switched off: search_shards' loop."""
    with monkeypatch.context() as m:
        m.setattr(term_group_sharded, "query_phase", lambda *a, **k: None)
        return n.search(index, body)


def _bits(out):
    return [(h["_id"], np.float32(h["_score"]).tobytes())
            for h in out["hits"]["hits"]]


def _queries():
    rng = np.random.default_rng(1611)
    words = COMMON + TAIL + ["mid", "only0"]
    p = np.array([8.0] * 6 + [1.0] * 200 + [3.0, 1.0])
    p /= p.sum()
    named = {
        "tie_across_shards": ("c0 t1", 10),
        "term_absent_from_three_shards": ("only0 t3", 10),
        "term_dense_in_one_shard_only": ("mid t5 t6", 10),
        "k_larger_than_a_shards_matches": ("only0", 200),
        "nothing_matches": ("nosuchword", 10),
        "all_dense": ("c1 c2", 10),
        "scatter_only": ("t7 t8 t9", 10),
    }
    for i in range(50):
        named[f"seeded_{i:02d}"] = (
            " ".join(rng.choice(words, int(rng.integers(2, 11)), p=p)), 10)
    return named


QUERIES = _queries()


def _reference(docs, n, query: str):
    """float64 BM25, each shard under its own statistics: {doc id: score}
    of every matching document."""
    route = n.indices["ix"].route
    by_shard = {}
    for doc_id, toks in docs:
        by_shard.setdefault(route(doc_id).shard_id, []).append((doc_id, toks))
    weights = {}
    for t in query.split():
        weights[t] = weights.get(t, 0) + 1
    scores = {}
    for shard_docs in by_shard.values():
        N = len(shard_docs)
        avg = sum(len(t) for _, t in shard_docs) / N
        for term, times in weights.items():
            df = sum(term in toks for _, toks in shard_docs)
            if not df:
                continue
            idf = math.log(1.0 + (N - df + 0.5) / (df + 0.5))
            for doc_id, toks in shard_docs:
                tf = toks.count(term)
                if tf:
                    scores[doc_id] = scores.get(doc_id, 0.0) + times * idf * (
                        tf * (K1 + 1.0) / (tf + K1 * (
                            1.0 - B + B * len(toks) / avg)))
    return scores


# ---- (a) the answer ---------------------------------------------------------

@pytest.mark.parametrize("name", sorted(QUERIES))
def test_the_sharded_reply_is_search_shards_reply_bit_for_bit(
        world, monkeypatch, name):
    n, docs = world
    text, size = QUERIES[name]
    body = {"query": {"match": {"body": text}}, "size": size}
    out, _spans, rise = _search(n, "ix", body)
    assert rise.get("bm25_sharded_program") == 1
    old = _shard_after_shard(n, "ix", body, monkeypatch)
    assert _bits(out) == _bits(old)
    assert out["hits"]["total"] == old["hits"]["total"]
    assert out["hits"]["max_score"] == old["hits"]["max_score"]
    assert out["_shards"] == old["_shards"]
    # and the float64 reference, within the cell's limits
    ref = _reference(docs, n, text)
    assert out["hits"]["total"] == len(ref)
    got = {h["_id"]: h["_score"] for h in out["hits"]["hits"]}
    assert len(got) == min(size, len(ref))
    for doc_id, score in got.items():
        assert abs(score - ref[doc_id]) <= SCORE_ERR * ref[doc_id]
    if got and len(ref) > len(got):
        left_out = max(v for d, v in ref.items() if d not in got)
        last = min(ref[d] for d in got)
        assert left_out - last <= RANK_GAP * last


def test_the_shards_plans_differ_in_class_and_are_padded_to_one(world):
    """Among the queries the shards plan different (R, T, P) — a term
    dense here and not there, longer runs on one shard — so the common
    class is really padded to."""
    from elasticsearch_tpu.search.context import SegmentContext
    from elasticsearch_tpu.search.queries import (build_term_group_plan,
                                                  parse_query)

    n, _docs = world
    differing = 0
    for text, _size in QUERIES.values():
        classes = set()
        for s in _searchers(n, "ix"):
            ctx = SegmentContext(s.segments[0], s.mappings, s.analysis)
            plan = build_term_group_plan(
                ctx, parse_query({"match": {"body": text}}))
            classes.add((0 if plan.impact is None else plan.qrows.shape[0],
                         plan.starts.shape[0], plan.P))
        differing += len(classes) > 1
    assert differing >= 5


@pytest.mark.parametrize("text,size", [("c0 t1", 40), ("c3 c4 mid", 7),
                                       ("t11 t12 c5", 23)])
def test_a_tie_across_shards_is_ordered_by_shard_then_local_doc(
        world, monkeypatch, text, size):
    n, _docs = world
    body = {"query": {"match": {"body": text}}, "size": size}
    out, _spans, rise = _search(n, "ties", body)
    assert rise.get("bm25_sharded_program") == 1
    hits = out["hits"]["hits"]
    assert len(hits) == size
    route = n.indices["ties"].route
    shard_of = [route(h["_id"]).shard_id for h in hits]
    # every document has its three twins on the other shards: equal
    # scores come lower shard first
    crossings = 0
    for a, b, sa, sb in zip(hits, hits[1:], shard_of, shard_of[1:]):
        assert a["_score"] > b["_score"] or (
            a["_score"] == b["_score"] and sa <= sb)
        crossings += a["_score"] == b["_score"] and sa < sb
    assert crossings >= 1
    old = _shard_after_shard(n, "ties", body, monkeypatch)
    assert _bits(out) == _bits(old)
    assert out["hits"]["total"] == old["hits"]["total"]


# ---- (b) placement ------------------------------------------------------------

def _device_arrays(seg):
    inv = seg.inverted["body"]
    out = {"live": seg.live, "doc_ids": inv.doc_ids, "tf": inv.tf,
           "tfnorm": inv.tfnorm, "term_ids": inv.term_ids,
           "field_lengths": seg.field_lengths["body"]}
    block = inv.dense_block()
    if block is not None:
        out["dense_impact"] = block[1]
    return out


def test_every_array_of_shard_s_is_on_device_s(world):
    import jax

    n, _docs = world
    n.search("ix", {"query": {"match": {"body": "c0 mid t4"}}})
    # a sorted and an aggregated search place the columns too
    n.search("ix", {"query": {"match": {"body": "c1"}},
                    "sort": [{"n": "asc"}],
                    "aggs": {"tags": {"terms": {"field": "tag"}}}})
    devices = jax.devices()
    for s, searcher in enumerate(_searchers(n, "ix")):
        (seg,) = searcher.segments
        assert seg.device == devices[s]
        arrays = _device_arrays(seg)
        assert "dense_impact" in arrays
        arrays["n.values"] = seg.numerics["n"].values
        arrays["tag.ords"] = seg.keywords["tag"].ords
        for name, a in arrays.items():
            assert a.devices() == {devices[s]}, (s, name)


def test_a_one_shard_index_keeps_the_default_device(world):
    n, _docs = world
    (searcher,) = _searchers(n, "one")
    (seg,) = searcher.segments
    assert seg.device is None
    assert not seg.live.committed
    assert not seg.inverted["body"].doc_ids.committed


def test_a_shard_its_chip_holds_whole_is_not_split_in_place(world):
    from elasticsearch_tpu.parallel import postings_shard

    n, _docs = world
    for searcher in _searchers(n, "ix"):
        inv = searcher.segments[0].inverted["body"]
        assert inv.postings_split() is None and inv._pshard is None
    # the deployment's shard: 99.5M postings are 2 GiB padded, a chip
    # holds them whole — and still too big to stack a second copy of
    assert postings_shard.chip_holds_whole(99_471_968)
    assert postings_shard.declines_stacked_copy(99_471_968)
    assert not postings_shard.declines_stacked_copy(1 << 20)
    # a field a quarter of the chip's memory does not hold is split
    assert not postings_shard.chip_holds_whole((1 << 28) + 1)


def test_a_field_that_is_not_resident_whole_still_splits(monkeypatch):
    from elasticsearch_tpu.parallel import postings_shard

    monkeypatch.setattr(postings_shard, "POSTINGS_SHARD_NNZ", 1)
    n = Node()
    svc = _index(n, "big", 1, False,
                 [(str(i), ["alpha", f"w{i % 5}"]) for i in range(40)])
    inv = svc.shards[0].segments[0].inverted["body"]
    assert isinstance(inv.__dict__["_doc_ids_raw"], np.ndarray)
    assert inv.wants_postings_shard()
    assert inv.postings_split() is not None
    n.close()


def test_a_search_leaves_no_second_copy_of_the_postings(world):
    import jax

    n, _docs = world
    body = {"query": {"match": {"body": "c2 mid t9"}}}
    n.search("ix", body)
    inv0 = _searchers(n, "ix")[0].segments[0].inverted["body"]
    big = inv0.doc_ids.nbytes

    def census():
        gc.collect()
        return sorted((str(next(iter(a.devices()))), a.shape, str(a.dtype))
                      for a in jax.live_arrays()
                      if a.nbytes >= big and len(a.devices()) == 1)

    before = census()
    for _ in range(3):
        n.search("ix", body)
    assert census() == before
    assert not n.indices["ix"]._mesh_executor


# ---- (c) routing --------------------------------------------------------------

def test_with_the_mesh_on_a_small_index_is_served_by_the_mesh_path(world):
    n, _docs = world
    out, _spans, rise = _search(
        n, "meshy", {"query": {"match": {"body": "c0 t1"}}})
    assert out["hits"]["total"] > 0
    assert rise.get("mesh_search") == 1
    assert "bm25_sharded_program" not in rise


def test_a_one_shard_index_never_enters_the_route(world):
    n, _docs = world
    out, spans, rise = _search(
        n, "one", {"query": {"match": {"body": "c0 t1"}}})
    assert out["hits"]["total"] > 0
    assert "bm25_sharded_program" not in rise
    assert rise.get("bm25_one_program") == 1
    assert [s.tags["program"] for s in spans
            if s.name == "device.dispatch"] == ["bm25_term_group_topk"]


DECLINED = {
    "bool_query": {"query": {"bool": {"must": [
        {"match": {"body": "c0"}}, {"match": {"body": "t1"}}]}}},
    "operator_and": {"query": {"match": {"body": {
        "query": "c0 t1", "operator": "and"}}}},
    "sort": {"query": {"match": {"body": "c0 t1"}}, "sort": [{"n": "asc"}]},
    "aggs": {"query": {"match": {"body": "c0 t1"}},
             "aggs": {"tags": {"terms": {"field": "tag"}}}},
    "min_score": {"query": {"match": {"body": "c0 t1"}}, "min_score": 0.0},
    "profile": {"query": {"match": {"body": "c0 t1"}}, "profile": True},
    "terminate_after": {"query": {"match": {"body": "c0 t1"}},
                        "terminate_after": 5},
    "timeout": {"query": {"match": {"body": "c0 t1"}}, "timeout": "10s"},
    "rescore": {"query": {"match": {"body": "c0 t1"}}, "rescore": {
        "window_size": 5, "query": {"rescore_query": {
            "match": {"body": "t2"}}}}},
    "match_all": {"query": {"match_all": {}}},
}


@pytest.mark.parametrize("name", sorted(DECLINED))
def test_a_request_the_route_does_not_take_runs_search_shards(world, name):
    n, _docs = world
    out, spans, rise = _search(n, "ix", DECLINED[name])
    assert "bm25_sharded_program" not in rise
    assert term_group_sharded.PROGRAM not in [
        s.tags.get("program") for s in spans]
    assert out["_shards"]["failed"] == 0 and out["hits"]["total"] > 0


def test_a_scroll_runs_search_shards(world):
    n, _docs = world
    before = kernels.snapshot().get("bm25_sharded_program", 0)
    out = n.search("ix", {"query": {"match": {"body": "c0 t1"}},
                          "scroll": "1m"})
    assert out["hits"]["total"] > 0 and "_scroll_id" in out
    assert kernels.snapshot().get("bm25_sharded_program", 0) == before


def test_a_shard_of_two_segments_or_with_nested_docs_is_declined(world):
    n, docs = world
    svc = _index(n, "twoseg", S, False, docs[:400])
    for doc_id, toks in docs[400:600]:
        svc.index_doc(doc_id, {"body": " ".join(toks), "n": int(doc_id),
                               "tag": "g0"})
    svc.refresh()
    assert max(len(s.segments) for s in _searchers(n, "twoseg")) == 2
    out, _spans, rise = _search(
        n, "twoseg", {"query": {"match": {"body": "c0 t1"}}})
    assert out["hits"]["total"] > 0 and "bm25_sharded_program" not in rise

    n.create_index("nest", {"settings": {"index": {
        "number_of_shards": S, "search": {"mesh": "false"}}},
        "mappings": {"properties": {
            "body": {"type": "text"},
            "comments": {"type": "nested", "properties": {
                "text": {"type": "text"}}}}}})
    nest = n.indices["nest"]
    for doc_id, toks in docs[:200]:
        nest.index_doc(doc_id, {"body": " ".join(toks),
                                "comments": [{"text": "c0 child"}]})
    nest.refresh()
    out, _spans, rise = _search(
        n, "nest", {"query": {"match": {"body": "c0 t1"}}})
    assert out["hits"]["total"] > 0 and "bm25_sharded_program" not in rise


# ---- (d) spans and counters ---------------------------------------------------

def test_one_dispatch_one_wait_and_the_counters_of_a_sharded_search(world):
    from elasticsearch_tpu.search.context import SegmentContext
    from elasticsearch_tpu.search.queries import (build_term_group_plan,
                                                  parse_query)

    n, _docs = world
    text, size = "c0 mid t4 t5", 7
    out, spans, rise = _search(
        n, "ix", {"query": {"match": {"body": text}}, "size": size})
    assert len(out["hits"]["hits"]) == size
    (dispatch,) = [s for s in spans if s.name == "device.dispatch"]
    assert dispatch.tags["program"] == "bm25_term_group_topk_sharded"
    assert dispatch.tags["shards"] == S
    (wait,) = [s for s in spans if s.name == "device.wait"]
    assert wait.tags["bytes"] == 4 * (3 * size + S)
    assert len([s for s in spans if s.name == "search.plan"]) == 1
    assert len([s for s in spans if s.name == "search.rewrite"]) == 1
    plans = []
    for s in _searchers(n, "ix"):
        ctx = SegmentContext(s.segments[0], s.mappings, s.analysis)
        plans.append(build_term_group_plan(
            ctx, parse_query({"match": {"body": text}})))
    T = max(p.starts.shape[0] for p in plans)
    P = max(p.P for p in plans)
    assert rise["bm25_sharded_program"] == 1
    assert rise["shard_exchange_bytes"] == S * (2 * size + 1) * 4
    assert rise["tail_window_slots"] == S * T * P
    assert rise["tail_window_postings"] == sum(
        int(p.lens.sum()) for p in plans)
    assert rise["bm25_hybrid"] == S
    assert "bm25_one_program" not in rise
    assert "bm25_postings_sharded" not in rise
