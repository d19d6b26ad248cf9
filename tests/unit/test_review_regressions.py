"""Regression tests for code-review findings."""
import numpy as np
import pytest

from elasticsearch_tpu.analysis.char_filters import html_strip
from elasticsearch_tpu.analysis.analyzer import build_custom_analyzer
from elasticsearch_tpu.analysis.registry import AnalysisRegistry
from elasticsearch_tpu.index.doc_parser import DocumentParser
from elasticsearch_tpu.index.mappings import Mappings
from elasticsearch_tpu.index.segment import SegmentBuilder
from elasticsearch_tpu.utils.dates import parse_date
from elasticsearch_tpu.utils.errors import MapperParsingException


def test_token_count_counts_tokens():
    m = Mappings({"properties": {"nc": {"type": "token_count", "analyzer": "standard"}}})
    parser = DocumentParser(m, AnalysisRegistry())
    p = parser.parse("1", {"nc": "New York City"})
    assert p.doc_values["nc"] == [3]


def test_ipv6_rejected_cleanly():
    m = Mappings({"properties": {"addr": {"type": "ip"}}})
    parser = DocumentParser(m, AnalysisRegistry())
    with pytest.raises(MapperParsingException):
        parser.parse("1", {"addr": "2001:db8::1"})
    p = parser.parse("2", {"addr": "192.168.0.1"})
    assert p.doc_values["addr"] == [(192 << 24) + (168 << 16) + 1]


def test_multiword_synonym():
    an = build_custom_analyzer(
        "syn",
        {"tokenizer": "whitespace", "filter": ["lowercase", "s"]},
        {"filter": {"s": {"type": "synonym", "synonyms": ["united states, usa => america"]}}},
    )
    assert an.tokens("the united states rules") == ["the", "america", "rules"]
    assert an.tokens("usa rules") == ["america", "rules"]
    assert an.tokens("united kingdom") == ["united", "kingdom"]


def test_multiword_synonym_output_splits_tokens():
    an = build_custom_analyzer(
        "syn",
        {"tokenizer": "whitespace", "filter": ["lowercase", "s"]},
        {"filter": {"s": {"type": "synonym", "synonyms": ["nyc => new york"]}}},
    )
    assert an.analyze("nyc rules") == [("new", 0), ("york", 1), ("rules", 1)]


def test_html_strip_no_double_decode():
    assert html_strip("&amp;lt;b&amp;gt;") == "&lt;b&gt;"


def test_date_hour_only():
    assert parse_date("2015-01-01T12") == parse_date("2015-01-01") + 12 * 3600 * 1000


def test_date_column_offset_precision():
    m = Mappings({"properties": {"ts": {"type": "date"}}})
    parser = DocumentParser(m, AnalysisRegistry())
    b = SegmentBuilder(m)
    base = parse_date("2026-07-29T00:00:00Z")
    for i in range(4):
        b.add(parser.parse(str(i), {"ts": base + i * 1000}))  # 1s apart
    seg = b.freeze()
    col = seg.numerics["ts"]
    # f32 channel must resolve 1s differences (raw millis f32 could not);
    # consumers add offset back in f64 space
    rel = np.asarray(col.values)[:4].astype(np.float64)
    assert np.diff(rel).tolist() == [1000.0, 1000.0, 1000.0]
    assert rel[2] + col.offset == base + 2000
    assert col.exact[2] == base + 2000


def test_lazy_live_mask_refresh():
    m = Mappings({"properties": {"t": {"type": "text"}}})
    parser = DocumentParser(m, AnalysisRegistry())
    b = SegmentBuilder(m)
    for i in range(3):
        b.add(parser.parse(str(i), {"t": "x"}))
    seg = b.freeze()
    seg.delete_local(0)
    seg.delete_local(2)
    live = np.asarray(seg.live)
    assert live[:3].tolist() == [False, True, False]


def _mini_ctx(docs, mapping):
    from elasticsearch_tpu.search.context import SegmentContext

    m = Mappings(mapping)
    reg = AnalysisRegistry()
    parser = DocumentParser(m, reg)
    b = SegmentBuilder(m)
    for i, d in enumerate(docs):
        b.add(parser.parse(str(i), d))
    return SegmentContext(b.freeze(), m, reg)


def test_chunked_slices_p_covers_full_chunks(monkeypatch):
    import elasticsearch_tpu.search.context as C

    monkeypatch.setattr(C, "TAIL_W", 4)
    docs = [{"t": "x"} for _ in range(10)]  # term "x" in 10 docs -> runs of 4,4,2
    ctx = _mini_ctx(docs, {"properties": {"t": {"type": "text"}}})
    inv = ctx.inv("t")
    starts, lens, ws, P, n = ctx.chunked_slices(inv, ["x"], [1.0])
    # the cap's width, whatever the run's tail of 2; three chunks are a
    # bucket of their own (3), not padded to 4
    assert P == 4 and lens.tolist() == [4, 4, 2]
    assert (starts[1:] - starts[:-1]).tolist() == [4, 4]
    from elasticsearch_tpu.ops.scoring import match_count_segment

    counts = np.asarray(match_count_segment(inv.doc_ids, starts, lens, P=P, D=ctx.D))
    assert counts[:10].tolist() == [1] * 10


@pytest.mark.parametrize("n_ids,absent", [(1000, 0), (600, 400), (40, 0)])
def test_a_terms_filter_of_short_runs_keeps_a_narrow_window(n_ids, absent):
    """A terms filter over a keyword field's ids (df 1 each) cuts a
    [T, 8] window, as it did before the tail's width was capped at
    TAIL_W — not a [T, TAIL_W] one — and still finds every document."""
    import elasticsearch_tpu.search.context as C
    from elasticsearch_tpu.search.queries import parse_query
    from elasticsearch_tpu.utils.shapes import pow2_bucket

    docs = [{"id": f"k{i:05d}", "t": "x"} for i in range(n_ids)]
    ctx = _mini_ctx(docs, {"properties": {"id": {"type": "keyword"},
                                          "t": {"type": "text"}}})
    ids = [f"k{i:05d}" for i in range(n_ids)] + [
        f"none{i}" for i in range(absent)]
    inv = ctx.inv("id")
    assert inv.nnz_pad > 8
    starts, lens, _ws, P, n_present = ctx.chunked_slices(
        inv, ids, [1.0] * len(ids))
    assert P == 8 < C.TAIL_W and n_present == n_ids
    # no more slots than the pow2 table of 8-wide chunks it was
    assert starts.shape[0] * P <= pow2_bucket(len(ids)) * 8
    assert int(lens.sum()) == n_ids
    _scores, mask = parse_query({"terms": {"id": ids}}).execute(ctx)
    assert int(np.asarray(mask).sum()) == n_ids


def test_match_phrase_prefix_mixed_empty_expansion():
    ctx = _mini_ctx(
        [{"t": "quick broke it"}, {"t": "brown alone"}],
        {"properties": {"t": {"type": "text"}}},
    )
    from elasticsearch_tpu.search.queries import parse_query

    s, m = parse_query({"match_phrase_prefix": {"t": "quick bro"}}).execute(ctx)
    assert np.nonzero(np.asarray(m)[:2])[0].tolist() == [0]


def test_cardinality_double_field():
    from elasticsearch_tpu.search.aggregations import parse_aggs, run_aggs, reduce_aggs
    import jax.numpy as jnp

    ctx = _mini_ctx(
        [{"p": 1.5}, {"p": 2.5}, {"p": 1.5}],
        {"properties": {"p": {"type": "double"}}},
    )
    aggs = parse_aggs({"c": {"cardinality": {"field": "p"}}})
    mask = (jnp.arange(ctx.D) < ctx.segment.num_docs)
    out = reduce_aggs(aggs, [run_aggs(aggs, ctx, mask)])
    assert out["c"]["value"] == 2


def test_cardinality_multivalued_keyword_and_cross_segment_merge():
    from elasticsearch_tpu.search.aggregations import parse_aggs, run_aggs, reduce_aggs
    import jax.numpy as jnp

    mapping = {"properties": {"tag": {"type": "keyword"}}}
    ctx1 = _mini_ctx([{"tag": ["a", "b"]}, {"tag": ["c", "d"]}], mapping)
    ctx2 = _mini_ctx([{"tag": ["c", "e"]}], mapping)  # c overlaps segment 1
    aggs = parse_aggs({"c": {"cardinality": {"field": "tag"}}})
    p1 = run_aggs(aggs, ctx1, jnp.arange(ctx1.D) < ctx1.segment.num_docs)
    p2 = run_aggs(aggs, ctx2, jnp.arange(ctx2.D) < ctx2.segment.num_docs)
    out = reduce_aggs(aggs, [p1, p2])
    assert out["c"]["value"] == 5  # a b c d e — ords would double-count c


def test_function_score_sum_with_filtered_function():
    from elasticsearch_tpu.search.queries import parse_query

    ctx = _mini_ctx(
        [{"t": "hit", "p": 1.0}, {"t": "hit", "p": 2.0}],
        {"properties": {"t": {"type": "text"}, "p": {"type": "double"}}},
    )
    dsl = {"function_score": {
        "query": {"match": {"t": "hit"}},
        "functions": [
            {"filter": {"range": {"p": {"gte": 2}}}, "weight": 10},
        ],
        "score_mode": "sum", "boost_mode": "replace"}}
    s, m = parse_query(dsl).execute(ctx)
    s = np.asarray(s)
    assert s[1] == 10.0  # matches filter -> weight
    assert s[0] == 1.0  # matches NO function -> neutral factor 1, not 0/1-inflated


def test_fuzzy_and_operator_groups_expansions():
    ctx = _mini_ctx(
        [{"t": "quick dog"}, {"t": "quirk dog"}, {"t": "slow cat"}],
        {"properties": {"t": {"type": "text"}}},
    )
    from elasticsearch_tpu.search.queries import parse_query

    dsl = {"match": {"t": {"query": "quik dog", "operator": "and", "fuzziness": "AUTO"}}}
    _, m = parse_query(dsl).execute(ctx)
    # 'quik' expands to {quick, quirk}: both docs 0 and 1 must match (OR within group)
    assert np.nonzero(np.asarray(m)[:3])[0].tolist() == [0, 1]


def test_msm_not_capped_by_absent_terms():
    ctx = _mini_ctx(
        [{"t": "quick fox"}, {"t": "quick dog"}],
        {"properties": {"t": {"type": "text"}}},
    )
    from elasticsearch_tpu.search.queries import parse_query

    dsl = {"match": {"t": {"query": "quick zzzz", "minimum_should_match": 2}}}
    _, m = parse_query(dsl).execute(ctx)
    assert int(np.asarray(m).sum()) == 0  # absent term can never satisfy msm=2


def test_histogram_zero_interval_rejected():
    from elasticsearch_tpu.search.aggregations import parse_aggs
    from elasticsearch_tpu.utils.errors import SearchParseException

    aggs = parse_aggs({"h": {"histogram": {"field": "p", "interval": 0}}})
    ctx = _mini_ctx([{"p": 1.0}], {"properties": {"p": {"type": "double"}}})
    import jax.numpy as jnp

    with pytest.raises(SearchParseException):
        aggs[0].collect(ctx, jnp.ones(ctx.D, dtype=bool))


def test_nested_ternary_script():
    from elasticsearch_tpu.search.scripting import compile_script
    import jax.numpy as jnp

    cs = compile_script("doc['p'].value > 10 ? 2.0 : doc['p'].value > 5 ? 1.0 : 0.5")
    from elasticsearch_tpu.search.scripting import _DocField

    vals = jnp.asarray(np.array([20.0, 7.0, 1.0], np.float32))
    out = cs.run(lambda f: _DocField(vals, jnp.ones(3, bool)))
    assert np.asarray(out).tolist() == [2.0, 1.0, 0.5]


def test_query_string_negated_phrase():
    ctx = _mini_ctx(
        [{"t": "quick brown fox"}, {"t": "brown bear"}, {"t": "red fish"}],
        {"properties": {"t": {"type": "text"}}},
    )
    from elasticsearch_tpu.search.queries import parse_query

    dsl = {"query_string": {"query": '-"quick brown" bear', "default_field": "t"}}
    _, m = parse_query(dsl).execute(ctx)
    # doc 0 excluded by the negated phrase; doc 1 matches 'bear'
    assert np.nonzero(np.asarray(m)[:3])[0].tolist() == [1]


def test_terms_order_by_subagg():
    from elasticsearch_tpu.search.aggregations import parse_aggs, run_aggs, reduce_aggs
    import jax.numpy as jnp

    ctx = _mini_ctx(
        [{"tag": "a", "p": 1.0}, {"tag": "b", "p": 9.0}, {"tag": "c", "p": 5.0}],
        {"properties": {"tag": {"type": "keyword"}, "p": {"type": "double"}}},
    )
    aggs = parse_aggs({"t": {"terms": {"field": "tag", "order": {"mp": "desc"}},
                             "aggs": {"mp": {"max": {"field": "p"}}}}})
    mask = jnp.arange(ctx.D) < ctx.segment.num_docs
    out = reduce_aggs(aggs, [run_aggs(aggs, ctx, mask)])
    assert [b["key"] for b in out["t"]["buckets"]] == ["b", "c", "a"]


def test_scatter_free_failure_falls_back_to_scatter(monkeypatch):
    """The executor's insurance: when the candidate-set program fails
    (first real-TPU run risk), the search re-executes on the scatter
    form, the gauge ticks, and same-shape queries go straight to the
    rebuilt program."""
    import elasticsearch_tpu.ops.scoring as S
    from elasticsearch_tpu.monitor import kernels
    from elasticsearch_tpu.node import Node

    monkeypatch.setenv("ESTPU_TAIL_MODE", "candidates")
    boom = {"count": 0}

    def exploding(*a, **kw):
        boom["count"] += 1
        raise RuntimeError("simulated backend failure")

    monkeypatch.setattr(S, "bm25_hybrid_candidates_topk", exploding)
    n = Node()
    n.create_index("ins", {"mappings": {"properties": {
        "t": {"type": "text"}}}})
    svc = n.indices["ins"]
    # enough docs that "common" crosses the dense-impact df threshold
    # (max(128, D/256)) — the candidates fast path needs a hybrid group
    for i in range(300):
        svc.index_doc(str(i), {"t": f"common word{i % 5}"})
    svc.refresh()
    assert svc.shards[0].segments[0].inverted["t"].dense_block() is not None
    kernels.reset()
    r = n.search("ins", {"query": {"match": {"t": "common"}}})
    assert r["hits"]["total"] == 300  # served via the scatter fallback
    assert boom["count"] >= 1
    snap = kernels.snapshot()
    assert snap.get("tail_scatter_free_failed", 0) >= 1
    # same shape again: no new explosion (the rebuilt program is cached)
    before = boom["count"]
    r2 = n.search("ins", {"query": {"match": {"t": "common"}}})
    assert r2["hits"]["total"] == 300 and boom["count"] == before


def test_prepared_query_memo_invalidation():
    """The prepared-query memo reuses compile/build/transfer for repeated
    identical requests but must ALWAYS re-execute and must invalidate on
    any write: delete (tombstone), new doc + refresh (new segments)."""
    from elasticsearch_tpu.node import Node

    n = Node()
    n.create_index("memo", {"mappings": {"properties": {
        "t": {"type": "text"}, "v": {"type": "long"}}}})
    svc = n.indices["memo"]
    for i in range(30):
        svc.index_doc(str(i), {"t": "common", "v": i})
    svc.refresh()
    body = {"query": {"match": {"t": "common"}}, "size": 3}
    r1 = n.search("memo", dict(body))
    r2 = n.search("memo", dict(body))  # memo hit
    assert r1["hits"]["total"] == r2["hits"]["total"] == 30
    ex = svc.mesh_executor()
    assert ex is not None and len(ex._prep) >= 1
    # delete invalidates via the tombstone count in the key
    svc.delete_doc(r1["hits"]["hits"][0]["_id"])
    r3 = n.search("memo", dict(body))
    assert r3["hits"]["total"] == 29
    assert r3["hits"]["hits"][0]["_id"] != r1["hits"]["hits"][0]["_id"]
    # new doc + refresh → new segment objects → fresh entry
    svc.index_doc("x", {"t": "common", "v": 99})
    svc.refresh()
    r4 = n.search("memo", dict(body))
    assert r4["hits"]["total"] == 30
    # different body → different memo entry (no collision)
    r5 = n.search("memo", {"query": {"match": {"t": "common"}}, "size": 1})
    assert len(r5["hits"]["hits"]) == 1


def test_groovy_param_name_inside_string_literal_untouched():
    """A string literal textually equal to a param name must never be
    rewritten (the bare-param binding is AST-level, not textual)."""
    from elasticsearch_tpu.node import Node

    n = Node()
    n.create_index("lit", {})
    svc = n.indices["lit"]
    svc.index_doc("1", {"tag": "init"})
    svc.update_doc("1", {"script": "ctx._source.tag = 'beta'",
                         "params": {"beta": 2}, "lang": "groovy"})
    assert svc.get_doc("1")["_source"]["tag"] == "beta"
    # and the bare param still binds when actually referenced
    svc.update_doc("1", {"script": "ctx._source.tag = beta",
                         "params": {"beta": 7}, "lang": "groovy"})
    assert svc.get_doc("1")["_source"]["tag"] == 7
