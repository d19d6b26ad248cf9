import math

import numpy as np
import pytest

from elasticsearch_tpu.analysis.registry import AnalysisRegistry
from elasticsearch_tpu.index.doc_parser import DocumentParser
from elasticsearch_tpu.index.mappings import Mappings
from elasticsearch_tpu.index.segment import SegmentBuilder, K1, B, split_i64
from elasticsearch_tpu.utils.shapes import half_step_bucket, pow2_bucket

DOCS = [
    "the quick brown fox jumps over the lazy dog",
    "quick brown foxes leap over lazy dogs in summer",
    "the rain in spain stays mainly in the plain",
    "quick wit beats slow brawn",
    "dogs and cats living together",
]


def build_segment(docs=DOCS, analyzer="standard"):
    mappings = Mappings({"properties": {"body": {"type": "text", "analyzer": analyzer}}})
    reg = AnalysisRegistry()
    parser = DocumentParser(mappings, reg)
    builder = SegmentBuilder(mappings)
    for i, text in enumerate(docs):
        builder.add(parser.parse(str(i), {"body": text}))
    return builder.freeze(), reg


def bm25_oracle(docs, query_terms, analyzer_tokens):
    """Independent BM25 (Lucene 5 formula) in pure python."""
    toks = [analyzer_tokens(d) for d in docs]
    N = len(docs)
    avg = sum(len(t) for t in toks) / N
    scores = [0.0] * N
    for term in query_terms:
        df = sum(1 for t in toks if term in t)
        if df == 0:
            continue
        idf = math.log(1 + (N - df + 0.5) / (df + 0.5))
        for i, t in enumerate(toks):
            tf = t.count(term)
            if tf == 0:
                continue
            tfn = tf * (K1 + 1) / (tf + K1 * (1 - B + B * len(t) / avg))
            scores[i] += idf * tfn
    return scores


def test_segment_structure():
    seg, _ = build_segment()
    assert seg.num_docs == 5
    assert seg.max_docs == 64
    inv = seg.inverted["body"]
    assert inv.vocab["quick"] >= 0
    assert int(inv.df[inv.vocab["quick"]]) == 3
    assert int(inv.df[inv.vocab["the"]]) == 2
    start, ln = inv.term_slice("quick")
    docs = np.asarray(inv.doc_ids)[start : start + ln]
    assert sorted(docs.tolist()) == [0, 1, 3]


def test_bm25_matches_oracle():
    from elasticsearch_tpu.ops.scoring import bm25_score_segment

    seg, reg = build_segment()
    inv = seg.inverted["body"]
    an = reg.get("standard")
    qterms = ["quick", "dogs"]
    starts, lens, weights = [], [], []
    for t in qterms:
        s, ln = inv.term_slice(t)
        starts.append(s)
        lens.append(ln)
        weights.append(inv.idf(t))
    P = pow2_bucket(max(lens))
    scores = bm25_score_segment(
        inv.doc_ids,
        inv.tfnorm,
        np.array(starts, np.int32),
        np.array(lens, np.int32),
        np.array(weights, np.float32),
        P=P,
        D=seg.max_docs,
    )
    got = np.asarray(scores)[: seg.num_docs]
    want = bm25_oracle(DOCS, qterms, lambda d: an.tokens(d))
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_bm25_chunk_splitting_equivalence():
    """A term split into 2 chunks must score identically to 1 chunk."""
    from elasticsearch_tpu.ops.scoring import bm25_score_segment

    seg, _ = build_segment()
    inv = seg.inverted["body"]
    s, ln = inv.term_slice("quick")
    assert ln == 3
    w = inv.idf("quick")
    one = bm25_score_segment(
        inv.doc_ids, inv.tfnorm,
        np.array([s], np.int32), np.array([ln], np.int32), np.array([w], np.float32),
        P=4, D=seg.max_docs,
    )
    two = bm25_score_segment(
        inv.doc_ids, inv.tfnorm,
        np.array([s, s + 2], np.int32), np.array([2, 1], np.int32),
        np.array([w, w], np.float32),
        P=2, D=seg.max_docs,
    )
    np.testing.assert_allclose(np.asarray(one), np.asarray(two), rtol=1e-6)


def test_term_mask_and_topk():
    from elasticsearch_tpu.ops.scoring import term_mask, topk_with_mask, bm25_score_segment

    seg, _ = build_segment()
    inv = seg.inverted["body"]
    s, ln = inv.term_slice("dogs")
    mask = term_mask(
        inv.doc_ids, np.array([s], np.int32), np.array([ln], np.int32), P=8, D=seg.max_docs
    )
    m = np.asarray(mask)
    assert m[[1, 4]].all() and m.sum() == 2

    s2, l2 = inv.term_slice("quick")
    scores = bm25_score_segment(
        inv.doc_ids, inv.tfnorm,
        np.array([s2], np.int32), np.array([l2], np.int32),
        np.array([1.0], np.float32), P=8, D=seg.max_docs,
    )
    vals, idx = topk_with_mask(scores, mask & seg.live, k=3)
    vals, idx = np.asarray(vals), np.asarray(idx)
    assert idx[0] == 1 and vals[0] > 0
    assert vals[1] == 0.0 and idx[1] == 4  # filter-only match scores 0
    assert not np.isfinite(vals[2])  # no third match


def test_delete_updates_live_mask():
    seg, _ = build_segment()
    assert seg.delete_local(1)
    assert not seg.delete_local(1)
    assert seg.live_docs == 4
    assert not np.asarray(seg.live)[1]


def test_split_i64_order():
    vals = np.array([-(2**62), -1, 0, 1, 2**31, 2**62], dtype=np.int64)
    hi, lo = split_i64(vals)
    packed = list(zip(hi.tolist(), lo.tolist()))
    assert packed == sorted(packed)


def test_keyword_and_numeric_columns():
    mappings = Mappings(
        {
            "properties": {
                "tag": {"type": "keyword"},
                "n": {"type": "long"},
                "price": {"type": "double"},
            }
        }
    )
    reg = AnalysisRegistry()
    parser = DocumentParser(mappings, reg)
    b = SegmentBuilder(mappings)
    rows = [
        {"tag": "red", "n": 10, "price": 1.5},
        {"tag": "blue", "n": 2**40, "price": 2.5},
        {"tag": ["red", "green"], "n": -5},
    ]
    for i, r in enumerate(rows):
        b.add(parser.parse(str(i), r))
    seg = b.freeze()
    kw = seg.keywords["tag"]
    inv = seg.inverted["tag"]
    assert inv.terms == ["blue", "green", "red"]
    s, ln = inv.term_slice("red")
    assert sorted(np.asarray(inv.doc_ids)[s : s + ln].tolist()) == [0, 2]
    assert np.asarray(kw.ords)[1] == 0  # "blue"
    col = seg.numerics["n"]
    assert col.exact[1] == 2**40
    assert col.hi is not None
    pr = seg.numerics["price"]
    assert np.asarray(pr.exists)[:3].tolist() == [True, True, False]


def test_knn_ops_match_numpy():
    from elasticsearch_tpu.ops.knn import (knn_row_terms, knn_topk_chunked,
                                           knn_topk_stored)

    rng = np.random.default_rng(0)
    D, dims, Q, k = 256, 32, 4, 5
    vecs = rng.standard_normal((D, dims)).astype(np.float32)
    queries = rng.standard_normal((Q, dims)).astype(np.float32)
    mask = np.ones(D, dtype=bool)

    terms = knn_row_terms(vecs, metric="cosine")
    vals, idx = knn_topk_stored(queries, vecs, terms, mask, k=k,
                                metric="cosine", use_bf16=False)
    qn = queries / np.linalg.norm(queries, axis=1, keepdims=True)
    vn = vecs / np.linalg.norm(vecs, axis=1, keepdims=True)
    sim = (1 + qn @ vn.T) / 2
    want_idx = np.argsort(-sim, axis=1)[:, :k]
    assert (np.asarray(idx) == want_idx).mean() > 0.95  # ties may reorder

    cvals, cidx = knn_topk_chunked(queries, vecs, terms, mask, k=k,
                                   metric="cosine", chunk=64, use_bf16=False)
    np.testing.assert_allclose(np.sort(np.asarray(cvals)), np.sort(np.asarray(vals)), rtol=1e-5)


def test_knn_l2_and_dot():
    from elasticsearch_tpu.ops.knn import knn_row_terms, knn_scores

    rng = np.random.default_rng(1)
    vecs = rng.standard_normal((16, 8)).astype(np.float32)
    q = rng.standard_normal((2, 8)).astype(np.float32)
    s = np.asarray(knn_scores(q, vecs, knn_row_terms(vecs, metric="l2_norm"),
                              metric="l2_norm", use_bf16=False))
    d2 = ((q[:, None, :] - vecs[None, :, :]) ** 2).sum(-1)
    np.testing.assert_allclose(s, 1 / (1 + d2), rtol=2e-3, atol=1e-4)
    assert knn_row_terms(vecs, metric="dot_product") is None
    sd = np.asarray(knn_scores(q, vecs, None, metric="dot_product",
                               use_bf16=False))
    np.testing.assert_allclose(sd, (1 + q @ vecs.T) / 2, rtol=1e-4)


def test_hybrid_dense_sparse_matches_pure_scatter():
    """Hybrid (dense rows / dense matmul + scatter tail) == pure scatter ==
    numpy oracle on a synthetic corpus large enough to produce dense rows."""
    from elasticsearch_tpu.index.segment import build_dense_impact
    from elasticsearch_tpu.ops.scoring import (
        bm25_score_hybrid_batch,
        bm25_score_hybrid_gather,
        bm25_score_segment,
        match_count_hybrid_gather,
        pack_dense_rows,
        term_mask,
        term_mask_hybrid_gather,
    )

    rng = np.random.default_rng(7)
    n_docs, vocab = 512, 64
    D = pow2_bucket(n_docs)
    # zipf-ish postings: term t appears in ~n_docs/(t+1) docs
    doc_lists = [
        np.sort(rng.choice(n_docs, size=max(1, n_docs // (t + 1)), replace=False))
        for t in range(vocab)
    ]
    df = np.array([len(d) for d in doc_lists], np.int32)
    offsets = np.zeros(vocab + 1, np.int64)
    offsets[1:] = np.cumsum(df)
    nnz = int(df.sum())
    u_doc = np.concatenate(doc_lists).astype(np.int32)
    tfn = rng.random(nnz).astype(np.float32) + 0.5

    block = build_dense_impact(u_doc, tfn, offsets, df, D, df_threshold=64)
    assert block is not None
    dense_rows, impact = block
    assert (dense_rows >= 0).sum() > 0 and (dense_rows < 0).sum() > 0

    nnz_pad = pow2_bucket(nnz)
    d_doc = np.full(nnz_pad, D, np.int32)
    d_doc[:nnz] = u_doc
    d_tfn = np.zeros(nnz_pad, np.float32)
    d_tfn[:nnz] = tfn

    qterms = [0, 1, 40, 63]  # mix of dense (frequent) + sparse (rare) terms
    weights = [1.5, 0.7, 2.0, 1.1]
    F = impact.shape[0]
    qw = np.zeros(F, np.float32)
    row_w = {}
    runs = []
    for t, w in zip(qterms, weights):
        row = int(dense_rows[t])
        if row >= 0:
            qw[row] += w
            row_w[row] = row_w.get(row, 0.0) + w
        else:
            runs.append((int(offsets[t]), int(df[t]), w))
    qrows, qrw = pack_dense_rows(row_w)
    P = pow2_bucket(max((ln for _, ln, _ in runs), default=1))
    T = pow2_bucket(max(len(runs), 1))
    starts = np.zeros(T, np.int32)
    lens = np.zeros(T, np.int32)
    ws = np.zeros(T, np.float32)
    for i, (s, ln, w) in enumerate(runs):
        starts[i], lens[i], ws[i] = s, ln, w

    # oracle
    want = np.zeros(D, np.float32)
    for t, w in zip(qterms, weights):
        s, e = int(offsets[t]), int(offsets[t + 1])
        want[u_doc[s:e]] += w * tfn[s:e]

    got_h = bm25_score_hybrid_gather(
        impact, qrows, qrw, d_doc, d_tfn, starts, lens, ws, P=P, D=D)
    counts = match_count_hybrid_gather(impact, qrows, d_doc, starts, lens,
                                       P=P, D=D)
    np.testing.assert_allclose(np.asarray(got_h), want, rtol=1e-5, atol=1e-5)

    got_b = bm25_score_hybrid_batch(
        impact, qw[None], d_doc, d_tfn, starts[None], lens[None], ws[None], P=P, D=D)
    np.testing.assert_allclose(np.asarray(got_b)[0], want, rtol=1e-5, atol=1e-5)

    # pure scatter path on the same query (all terms as runs)
    all_runs = [(int(offsets[t]), int(df[t]), w) for t, w in zip(qterms, weights)]
    P2 = pow2_bucket(max(ln for _, ln, _ in all_runs))
    st2 = np.array([r[0] for r in all_runs], np.int32)
    ln2 = np.array([r[1] for r in all_runs], np.int32)
    ws2 = np.array([r[2] for r in all_runs], np.float32)
    got_s = bm25_score_segment(d_doc, d_tfn, st2, ln2, ws2, P=P2, D=D)
    np.testing.assert_allclose(np.asarray(got_s), want, rtol=1e-5, atol=1e-5)

    # matched-term counts
    want_counts = np.zeros(D, np.int64)
    for t in qterms:
        s, e = int(offsets[t]), int(offsets[t + 1])
        want_counts[u_doc[s:e]] += 1
    np.testing.assert_array_equal(np.asarray(counts), want_counts)

    # any-of mask
    got_m = term_mask_hybrid_gather(impact, qrows, d_doc, starts, lens,
                                    P=P, D=D)
    np.testing.assert_array_equal(np.asarray(got_m), want_counts > 0)
    got_m2 = term_mask(d_doc, st2, ln2, P=P2, D=D)
    np.testing.assert_array_equal(np.asarray(got_m2), want_counts > 0)


def test_segment_dense_block_lazy():
    """Small segments have no qualifying terms -> dense_block() is None and
    cached as absent; query path falls back to pure scatter."""
    seg, _ = build_segment()
    inv = seg.inverted["body"]
    assert inv.dense_block() is None
    assert inv._dense is False


def test_exact_topk_matches_lax_including_ties():
    """Blocked two-stage top-k must be bit-identical to lax.top_k —
    values AND indices — including tie resolution (lowest index wins),
    1-D and batched, with non-finite entries present."""
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from elasticsearch_tpu.ops.scoring import exact_topk

    rng = np.random.default_rng(11)
    for shape in ((8192,), (4, 8192)):
        # quantized values force many exact ties across blocks
        x = np.round(rng.standard_normal(shape) * 3).astype(np.float32)
        x[..., :7] = -np.inf  # masked entries
        xj = jnp.asarray(x)
        for k in (1, 10, 64):
            gv, gi = exact_topk(xj, k, block=1024)
            lv, li = lax.top_k(xj, k)
            assert np.array_equal(np.asarray(gv), np.asarray(lv)), (shape, k)
            assert np.array_equal(np.asarray(gi), np.asarray(li)), (shape, k)
    # fallback shapes route to plain lax.top_k
    x = jnp.asarray(rng.standard_normal(100).astype(np.float32))
    gv, gi = exact_topk(x, 5, block=1024)
    lv, li = lax.top_k(x, 5)
    assert np.array_equal(np.asarray(gv), np.asarray(lv))
    assert np.array_equal(np.asarray(gi), np.asarray(li))


def test_blocked_topk_env_product_equivalence(monkeypatch):
    """ESTPU_BLOCKED_TOPK must leave product search results identical —
    it only re-stages the top-k selection. A SMALL block (64) with a
    600-doc corpus (padded D=1024 >= 2*block, divisible) guarantees the
    blocked path actually executes, and the block is a STATIC part of
    every program/jit cache key, so flag-on and flag-off runs can share
    one process without stale-program contamination."""
    import random

    from elasticsearch_tpu.node import Node
    from elasticsearch_tpu.ops.scoring import topk_block_config

    rng = random.Random(5)
    words = ["alpha", "beta", "gamma", "delta"]
    docs = {str(i): {"body": " ".join(rng.choices(words, k=5))}
            for i in range(600)}

    def run():
        n = Node()
        try:
            n.create_index("bt", {"settings": {"number_of_shards": 1},
                                  "mappings": {"properties": {
                                      "body": {"type": "text"}}}})
            for i, src in docs.items():
                n.indices["bt"].index_doc(i, src)
            n.indices["bt"].refresh()
            seg = n.indices["bt"].shards[0].engine.segments[0]
            assert seg.max_docs >= 2 * 64  # the blocked path really runs
            return n.search("bt", {"query": {"match": {"body": "alpha"}},
                                   "size": 10})
        finally:
            n.close()

    monkeypatch.setenv("ESTPU_BLOCKED_TOPK", "64")
    assert topk_block_config() == 64
    r1 = run()
    monkeypatch.delenv("ESTPU_BLOCKED_TOPK")
    assert topk_block_config() == 0
    r2 = run()
    assert r1["hits"]["total"] == r2["hits"]["total"] > 0
    assert [(h["_id"], round(h["_score"], 5)) for h in r1["hits"]["hits"]] \
        == [(h["_id"], round(h["_score"], 5)) for h in r2["hits"]["hits"]]


def test_gather_hybrid_matches_scatter_over_all_terms():
    """The row-read single-query forms (bm25_score_hybrid_gather /
    match_count_hybrid_gather / term_mask_hybrid_gather) produce the same
    scores/counts/masks as the scatter forms run over ALL the query's
    terms' postings — a reference that never touches the dense block."""
    from elasticsearch_tpu.index.segment import build_dense_impact
    from elasticsearch_tpu.ops.scoring import (
        bm25_score_hybrid_gather, bm25_score_segment,
        match_count_hybrid_gather, match_count_segment, pack_dense_rows,
        term_mask, term_mask_hybrid_gather)

    rng = np.random.default_rng(11)
    n_docs, vocab = 512, 64
    D = pow2_bucket(n_docs)
    doc_lists = [
        np.sort(rng.choice(n_docs, size=max(1, n_docs // (t + 1)),
                           replace=False))
        for t in range(vocab)
    ]
    df = np.array([len(d) for d in doc_lists], np.int32)
    offsets = np.zeros(vocab + 1, np.int64)
    offsets[1:] = np.cumsum(df)
    nnz = int(df.sum())
    u_doc = np.concatenate(doc_lists).astype(np.int32)
    tfn = rng.random(nnz).astype(np.float32) + 0.5
    block = build_dense_impact(u_doc, tfn, offsets, df, D, df_threshold=64)
    dense_rows, impact = block
    nnz_pad = pow2_bucket(nnz)
    d_doc = np.full(nnz_pad, D, np.int32)
    d_doc[:nnz] = u_doc
    d_tfn = np.zeros(nnz_pad, np.float32)
    d_tfn[:nnz] = tfn

    qterms = [0, 1, 2, 40, 63]
    weights = [1.5, 0.7, 0.9, 2.0, 1.1]
    row_w = {}
    runs = []
    for t, w in zip(qterms, weights):
        row = int(dense_rows[t])
        if row >= 0:
            row_w[row] = row_w.get(row, 0.0) + w
        else:
            runs.append((int(offsets[t]), int(df[t]), w))
    assert row_w and runs  # the query must exercise BOTH halves
    qrows, qrw = pack_dense_rows(row_w)
    assert qrows.shape[0] >= 8 and (qrows < 0).any()  # padded
    P = pow2_bucket(max(ln for _, ln, _ in runs))
    T = pow2_bucket(len(runs))
    starts = np.zeros(T, np.int32)
    lens = np.zeros(T, np.int32)
    ws = np.zeros(T, np.float32)
    for i, (s, ln, w) in enumerate(runs):
        starts[i], lens[i], ws[i] = s, ln, w

    # the reference: every term, dense or not, as a postings run
    st2 = np.array([offsets[t] for t in qterms], np.int32)
    ln2 = np.array([df[t] for t in qterms], np.int32)
    ws2 = np.array(weights, np.float32)
    P2 = pow2_bucket(int(ln2.max()))

    want = np.asarray(bm25_score_segment(d_doc, d_tfn, st2, ln2, ws2,
                                         P=P2, D=D))
    got = np.asarray(bm25_score_hybrid_gather(
        impact, qrows, qrw, d_doc, d_tfn, starts, lens, ws, P=P, D=D))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    want_c = np.asarray(match_count_segment(d_doc, st2, ln2, P=P2, D=D))
    got_c = np.asarray(match_count_hybrid_gather(
        impact, qrows, d_doc, starts, lens, P=P, D=D))
    np.testing.assert_array_equal(got_c, want_c)

    want_m = np.asarray(term_mask(d_doc, st2, ln2, P=P2, D=D))
    got_m = np.asarray(term_mask_hybrid_gather(
        impact, qrows, d_doc, starts, lens, P=P, D=D))
    np.testing.assert_array_equal(got_m, want_m)


@pytest.mark.parametrize("block_dtype", ["float32", "bfloat16"])
def test_dense_topk_batch_matches_numpy(block_dtype):
    """The batched tier's all-dense top-k (qw[Q, F] @ impact[F, D] → live
    mask → top-k, Q swept in chunks) against a float64 numpy product:
    same ids in the same order, dead docs never returned, every chunk
    (the last one ragged) in its place. A bf16-stored block multiplies in
    bf16 with f32 sums, like every other reader of it."""
    import jax.numpy as jnp

    from elasticsearch_tpu.ops.scoring import dense_topk_batch

    rng = np.random.default_rng(6)
    Q, F, D, k = 5, 16, 512, 5
    impact = jnp.asarray(rng.random((F, D)).astype(np.float32),
                         dtype=block_dtype)
    qw = rng.random((Q, F)).astype(np.float32)
    live = rng.random(D) > 0.1
    vals, idx = dense_topk_batch(jnp.asarray(qw), impact, jnp.asarray(live),
                                 k=k, chunk_q=2)
    vals, idx = np.asarray(vals), np.asarray(idx)
    assert vals.shape == idx.shape == (Q, k) and idx.dtype == np.int32
    lhs = qw if block_dtype == "float32" else np.asarray(
        jnp.asarray(qw).astype(jnp.bfloat16).astype(jnp.float32))
    exact = lhs.astype(np.float64) @ np.asarray(
        impact.astype(jnp.float32)).astype(np.float64)
    exact = np.where(live[None, :], exact, -np.inf)
    want = np.argsort(-exact, axis=1, kind="stable")[:, :k]
    np.testing.assert_array_equal(idx, want)
    np.testing.assert_allclose(vals, np.take_along_axis(exact, want, axis=1),
                               rtol=1e-6)
    assert live[idx].all()


def test_candidates_topk_matches_scatter_path():
    """bm25_hybrid_candidates_topk (scatter-free) == dense scatter path
    (score vector + masked top-k + count) — across duplicate tail docs,
    dense/tail overlap, dead docs, chunk-split runs, and exact ties."""
    import jax.numpy as jnp

    from elasticsearch_tpu.index.segment import build_dense_impact
    from elasticsearch_tpu.ops.scoring import (
        bm25_hybrid_candidates_topk, bm25_score_hybrid_gather,
        pack_dense_rows, topk_with_mask)

    rng = np.random.default_rng(23)
    n_docs, vocab, k = 512, 64, 10
    D = pow2_bucket(n_docs)
    doc_lists = [
        np.sort(rng.choice(n_docs, size=max(1, n_docs // (t + 1)),
                           replace=False))
        for t in range(vocab)
    ]
    df = np.array([len(d) for d in doc_lists], np.int32)
    offsets = np.zeros(vocab + 1, np.int64)
    offsets[1:] = np.cumsum(df)
    nnz = int(df.sum())
    u_doc = np.concatenate(doc_lists).astype(np.int32)
    tfn = rng.random(nnz).astype(np.float32) + 0.5
    tfn = (tfn * 8).round() / 8  # quantize -> exact ties exist
    block = build_dense_impact(u_doc, tfn, offsets, df, D, df_threshold=64)
    dense_rows, impact = block
    nnz_pad = pow2_bucket(nnz)
    d_doc = np.full(nnz_pad, D, np.int32)
    d_doc[:nnz] = u_doc
    d_tfn = np.zeros(nnz_pad, np.float32)
    d_tfn[:nnz] = tfn
    live = np.ones(D, bool)
    live[n_docs:] = False
    live[rng.choice(n_docs, 40, replace=False)] = False  # dead docs

    for trial, qterms in enumerate([[0, 1, 40, 41, 63],  # overlap-heavy
                                    [50, 60, 63],        # tail-only
                                    [0, 1],              # dense-only
                                    [0, 30, 31, 32, 60, 61, 62, 63]]):
        weights = [float(1.0 + 0.5 * i) for i in range(len(qterms))]
        row_w = {}
        runs = []
        for t, w in zip(qterms, weights):
            row = int(dense_rows[t])
            if row >= 0:
                row_w[row] = row_w.get(row, 0.0) + w
            else:
                runs.append((int(offsets[t]), int(df[t]), w))
        if not row_w:
            continue  # hybrid paths require >= 1 dense term
        qrows, qrw = pack_dense_rows(row_w)
        from elasticsearch_tpu.search.context import chunk_table
        P = 128  # narrower than the long runs: they split into chunks
        starts, lens, ws = chunk_table(runs, P)

        # reference: full scatter score vector -> masked topk + count
        scores = np.asarray(bm25_score_hybrid_gather(
            impact, qrows, qrw, d_doc, d_tfn, starts, lens, ws, P=P, D=D))
        m = (scores > 0) & live
        wv, wi = topk_with_mask(jnp.asarray(scores),
                                jnp.asarray(m), k=k)
        want_total = int(m.sum())

        gv, gi, gt = bm25_hybrid_candidates_topk(
            impact, qrows, qrw, d_doc, d_tfn, starts, lens, ws,
            jnp.asarray(live), P=P, D=D, k=k, topk_block=0)
        np.testing.assert_allclose(np.asarray(gv), np.asarray(wv),
                                   rtol=2e-5, atol=2e-5,
                                   err_msg=f"trial {trial} vals")
        finite = np.isfinite(np.asarray(wv))
        np.testing.assert_array_equal(np.asarray(gi)[finite],
                                      np.asarray(wi)[finite],
                                      err_msg=f"trial {trial} ids")
        assert int(gt) == want_total, (trial, int(gt), want_total)


def test_candidates_topk_batch_matches_scatter_batch():
    """bm25_hybrid_candidates_topk_batch == bm25_hybrid_topk_batch across
    a mixed batch (per-query different dense/tail splits, ties, dupes)."""
    import jax.numpy as jnp

    from elasticsearch_tpu.index.segment import build_dense_impact
    from elasticsearch_tpu.ops.scoring import (
        bm25_hybrid_candidates_topk_batch, bm25_hybrid_topk_batch)
    from elasticsearch_tpu.search.context import split_runs

    rng = np.random.default_rng(31)
    n_docs, vocab, k = 512, 64, 10
    D = pow2_bucket(n_docs)
    doc_lists = [
        np.sort(rng.choice(n_docs, size=max(1, n_docs // (t + 1)),
                           replace=False))
        for t in range(vocab)
    ]
    df = np.array([len(d) for d in doc_lists], np.int32)
    offsets = np.zeros(vocab + 1, np.int64)
    offsets[1:] = np.cumsum(df)
    nnz = int(df.sum())
    u_doc = np.concatenate(doc_lists).astype(np.int32)
    tfn = ((rng.random(nnz) + 0.5) * 8).round().astype(np.float32) / 8
    block = build_dense_impact(u_doc, tfn, offsets, df, D, df_threshold=64)
    dense_rows, impact = block
    F = impact.shape[0]
    nnz_pad = pow2_bucket(nnz)
    d_doc = np.full(nnz_pad, D, np.int32)
    d_doc[:nnz] = u_doc
    d_tfn = np.zeros(nnz_pad, np.float32)
    d_tfn[:nnz] = tfn
    live = np.ones(D, bool)
    live[n_docs:] = False
    live[rng.choice(n_docs, 30, replace=False)] = False

    batches = [[0, 1, 40, 63], [0, 50, 60], [1, 2], [30, 31, 62, 63],
               [0, 1, 2, 3, 60, 61]]
    qw = np.zeros((len(batches), F), np.float32)
    all_runs = []
    Pmax, Tmax = 128, 1  # narrower than the long runs: they split
    for qi, qterms in enumerate(batches):
        runs = []
        for i, t in enumerate(qterms):
            w = 1.0 + 0.3 * i
            row = int(dense_rows[t])
            if row >= 0:
                qw[qi, row] += w
            else:
                runs.append((int(offsets[t]), int(df[t]), w))
        st, ln, ws_ = split_runs(runs, Pmax)
        Tmax = max(Tmax, len(st))
        all_runs.append((st, ln, ws_))
    T = half_step_bucket(Tmax)
    starts = np.zeros((len(batches), T), np.int32)
    lens = np.zeros((len(batches), T), np.int32)
    ws = np.zeros((len(batches), T), np.float32)
    for qi, (st, ln, ws_) in enumerate(all_runs):
        starts[qi, :len(st)] = st
        lens[qi, :len(ln)] = ln
        ws[qi, :len(ws_)] = ws_

    wv, wi, wt = bm25_hybrid_topk_batch(
        impact, jnp.asarray(qw), d_doc, d_tfn, jnp.asarray(starts),
        jnp.asarray(lens), jnp.asarray(ws), jnp.asarray(live),
        P=Pmax, D=D, k=k, topk_block=0)
    gv, gi, gt = bm25_hybrid_candidates_topk_batch(
        impact, jnp.asarray(qw), d_doc, d_tfn, jnp.asarray(starts),
        jnp.asarray(lens), jnp.asarray(ws), jnp.asarray(live),
        P=Pmax, D=D, k=k, topk_block=0)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(wv),
                               rtol=2e-5, atol=2e-5)
    finite = np.isfinite(np.asarray(wv))
    np.testing.assert_array_equal(np.asarray(gi)[finite],
                                  np.asarray(wi)[finite])
    np.testing.assert_array_equal(np.asarray(gt), np.asarray(wt))


def test_lookup_tail_matches_scatter_forms():
    """The scatter-free lookup forms produce identical [D] vectors to the
    scatter kernels (scores/counts/masks), including duplicate docs
    across terms and chunk-split runs."""
    from elasticsearch_tpu.ops.scoring import (
        bm25_score_segment, bm25_score_segment_lookup,
        match_count_segment, match_count_segment_lookup, term_mask,
        term_mask_lookup)
    from elasticsearch_tpu.search.context import chunk_table

    rng = np.random.default_rng(41)
    n_docs, vocab = 512, 32
    D = pow2_bucket(n_docs)
    doc_lists = [
        np.sort(rng.choice(n_docs, size=max(1, n_docs // (t + 1)),
                           replace=False))
        for t in range(vocab)
    ]
    df = np.array([len(d) for d in doc_lists], np.int32)
    offsets = np.zeros(vocab + 1, np.int64)
    offsets[1:] = np.cumsum(df)
    nnz = int(df.sum())
    u_doc = np.concatenate(doc_lists).astype(np.int32)
    tfn = rng.random(nnz).astype(np.float32) + 0.5
    nnz_pad = pow2_bucket(nnz)
    d_doc = np.full(nnz_pad, D, np.int32)
    d_doc[:nnz] = u_doc
    d_tfn = np.zeros(nnz_pad, np.float32)
    d_tfn[:nnz] = tfn

    for qterms in ([0, 1, 5, 30], [2], [0, 1, 2, 3, 4, 5, 6, 7]):
        runs = [(int(offsets[t]), int(df[t]), 1.0 + 0.25 * i)
                for i, t in enumerate(qterms)]
        P = 128  # narrower than the long runs: they split into chunks
        starts, lens, ws = chunk_table(runs, P)
        want = np.asarray(bm25_score_segment(
            d_doc, d_tfn, starts, lens, ws, P=P, D=D))
        got = np.asarray(bm25_score_segment_lookup(
            d_doc, d_tfn, starts, lens, ws, P=P, D=D))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
        want_c = np.asarray(match_count_segment(
            d_doc, starts, lens, P=P, D=D))
        got_c = np.asarray(match_count_segment_lookup(
            d_doc, starts, lens, P=P, D=D))
        np.testing.assert_array_equal(got_c, want_c)
        want_m = np.asarray(term_mask(d_doc, starts, lens, P=P, D=D))
        got_m = np.asarray(term_mask_lookup(d_doc, starts, lens, P=P, D=D))
        np.testing.assert_array_equal(got_m, want_m)


def test_hybrid_lookup_matches_hybrid_gather():
    """The *_hybrid_lookup forms (scatter-free tail) == *_hybrid_gather
    (scatter tail) for scores, counts, and masks."""
    from elasticsearch_tpu.index.segment import build_dense_impact
    from elasticsearch_tpu.ops.scoring import (
        bm25_score_hybrid_gather, bm25_score_hybrid_lookup,
        match_count_hybrid_gather, match_count_hybrid_lookup,
        pack_dense_rows, term_mask_hybrid_gather, term_mask_hybrid_lookup)
    from elasticsearch_tpu.search.context import chunk_table

    rng = np.random.default_rng(47)
    n_docs, vocab = 512, 64
    D = pow2_bucket(n_docs)
    doc_lists = [
        np.sort(rng.choice(n_docs, size=max(1, n_docs // (t + 1)),
                           replace=False))
        for t in range(vocab)
    ]
    df = np.array([len(d) for d in doc_lists], np.int32)
    offsets = np.zeros(vocab + 1, np.int64)
    offsets[1:] = np.cumsum(df)
    nnz = int(df.sum())
    u_doc = np.concatenate(doc_lists).astype(np.int32)
    tfn = rng.random(nnz).astype(np.float32) + 0.5
    block = build_dense_impact(u_doc, tfn, offsets, df, D, df_threshold=64)
    dense_rows, impact = block
    nnz_pad = pow2_bucket(nnz)
    d_doc = np.full(nnz_pad, D, np.int32)
    d_doc[:nnz] = u_doc
    d_tfn = np.zeros(nnz_pad, np.float32)
    d_tfn[:nnz] = tfn

    qterms = [0, 1, 2, 40, 63]
    row_w = {}
    runs = []
    for i, t in enumerate(qterms):
        w = 1.0 + 0.5 * i
        row = int(dense_rows[t])
        if row >= 0:
            row_w[row] = row_w.get(row, 0.0) + w
        else:
            runs.append((int(offsets[t]), int(df[t]), w))
    assert row_w and runs
    qrows, qrw = pack_dense_rows(row_w)
    P = 128  # narrower than the long runs: they split into chunks
    starts, lens, ws = chunk_table(runs, P)

    want = np.asarray(bm25_score_hybrid_gather(
        impact, qrows, qrw, d_doc, d_tfn, starts, lens, ws, P=P, D=D))
    got = np.asarray(bm25_score_hybrid_lookup(
        impact, qrows, qrw, d_doc, d_tfn, starts, lens, ws, P=P, D=D))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    want_c = np.asarray(match_count_hybrid_gather(
        impact, qrows, d_doc, starts, lens, P=P, D=D))
    got_c = np.asarray(match_count_hybrid_lookup(
        impact, qrows, d_doc, starts, lens, P=P, D=D))
    np.testing.assert_array_equal(got_c, want_c)
    want_m = np.asarray(term_mask_hybrid_gather(
        impact, qrows, d_doc, starts, lens, P=P, D=D))
    got_m = np.asarray(term_mask_hybrid_lookup(
        impact, qrows, d_doc, starts, lens, P=P, D=D))
    np.testing.assert_array_equal(got_m, want_m)


# -- one program a search segment (finish_topk, bm25_term_group_topk) --------

def _term_group_corpus(ties: bool):
    """A synthetic segment with dense rows and a CSR tail; with ``ties``
    every posting carries the same tf norm, so whole runs of documents tie
    on score and the top-k's tie order shows."""
    from elasticsearch_tpu.index.segment import build_dense_impact

    rng = np.random.default_rng(53)
    n_docs, vocab = 512, 64
    D = pow2_bucket(n_docs)
    doc_lists = [
        np.sort(rng.choice(n_docs, size=max(1, n_docs // (t + 1)),
                           replace=False))
        for t in range(vocab)
    ]
    df = np.array([len(d) for d in doc_lists], np.int32)
    offsets = np.zeros(vocab + 1, np.int64)
    offsets[1:] = np.cumsum(df)
    nnz = int(df.sum())
    u_doc = np.concatenate(doc_lists).astype(np.int32)
    tfn = (np.ones(nnz, np.float32) if ties
           else rng.random(nnz).astype(np.float32) + 0.5)
    dense_rows, impact = build_dense_impact(u_doc, tfn, offsets, df, D,
                                            df_threshold=64)
    nnz_pad = pow2_bucket(nnz)
    d_doc = np.full(nnz_pad, D, np.int32)
    d_doc[:nnz] = u_doc
    d_tfn = np.zeros(nnz_pad, np.float32)
    d_tfn[:nnz] = tfn
    return dict(D=D, n_docs=n_docs, dense_rows=dense_rows, impact=impact,
                offsets=offsets, df=df, d_doc=d_doc, d_tfn=d_tfn)


def _term_group_tables(c, qterms, dense: bool):
    """(qrows, qrw, starts, lens, ws, P) as context.hybrid_slices /
    chunked_slices build them; ``dense`` False sends every term down the
    scatter tail (qrows, qrw None)."""
    from elasticsearch_tpu.ops.scoring import pack_dense_rows
    from elasticsearch_tpu.search.context import chunk_table

    row_w, runs = {}, []
    for i, t in enumerate(qterms):
        w = 1.0 + 0.5 * i
        row = int(c["dense_rows"][t]) if dense else -1
        if row >= 0:
            row_w[row] = row_w.get(row, 0.0) + w
        else:
            runs.append((int(c["offsets"][t]), int(c["df"][t]), w))
    P = 128  # narrower than the long runs: they split into chunks
    starts, lens, ws = chunk_table(runs, P)
    qrows, qrw = pack_dense_rows(row_w) if row_w else (None, None)
    return qrows, qrw, starts, lens, ws, P


TERM_GROUP_CASES = {
    # name: (query terms, dense rows used, k, deleted docs, nested roots,
    #        tied scores)
    "hybrid": ([0, 1, 2, 40, 63], True, 10, 0, False, False),
    "scatter_only": ([30, 40, 63], False, 10, 0, False, False),
    "all_dense_empty_tail": ([0, 1], True, 10, 0, False, False),
    "k_larger_than_the_matches": ([60, 63], False, 64, 0, False, False),
    "deleted_documents": ([0, 1, 40, 63], True, 10, 200, False, False),
    "nested_documents": ([0, 2, 40, 50], True, 10, 40, True, False),
    "ties": ([0, 1, 40, 63], True, 32, 0, False, True),
    "ties_scatter_only": ([5, 40, 63], False, 32, 17, False, True),
}


@pytest.mark.parametrize("case", sorted(TERM_GROUP_CASES))
def test_term_group_topk_is_bitwise_the_staged_sequence(case):
    """bm25_term_group_topk (one program, one packed argument) returns
    the very words of the staged sequence: score program → ``> 0`` →
    ``& live`` (``& roots``) → sum → topk_with_mask → pack_topk_result."""
    import jax.numpy as jnp

    from elasticsearch_tpu.ops.scoring import (
        bm25_score_hybrid_gather, bm25_score_segment, bm25_term_group_topk,
        pack_term_group_words, pack_topk_result, topk_block_config,
        topk_with_mask, unpack_topk_result)

    qterms, dense, k, n_deleted, nested, ties = TERM_GROUP_CASES[case]
    c = _term_group_corpus(ties)
    D = c["D"]
    qrows, qrw, starts, lens, ws, P = _term_group_tables(c, qterms, dense)
    rng = np.random.default_rng(59)
    live = np.zeros(D, bool)
    live[:c["n_docs"]] = True
    live[rng.choice(c["n_docs"], size=n_deleted, replace=False)] = False
    roots = None
    if nested:
        roots = np.zeros(D, bool)
        roots[:c["n_docs"]:3] = True  # two children behind every root
    live_dev = jnp.asarray(live)
    roots_dev = None if roots is None else jnp.asarray(roots)

    if qrows is not None:
        scores = bm25_score_hybrid_gather(
            c["impact"], qrows, qrw, c["d_doc"], c["d_tfn"], starts, lens,
            ws, P=P, D=D)
    else:
        scores = bm25_score_segment(c["d_doc"], c["d_tfn"], starts, lens,
                                    ws, P=P, D=D)
    mask = (scores > 0) & live_dev
    if roots_dev is not None:
        mask = mask & roots_dev
    tot = jnp.sum(mask.astype(jnp.int32))
    vals, idx = topk_with_mask(scores, mask, k=k)
    want = np.asarray(pack_topk_result(vals, idx, tot))

    words = pack_term_group_words(qrows, qrw, starts, lens, ws)
    R = 0 if qrows is None else qrows.shape[0]
    assert words.dtype == np.int32 and words.shape == (2 * R + 3 * len(ws),)
    got = np.asarray(bm25_term_group_topk(
        c["impact"] if qrows is not None else None, c["d_doc"], c["d_tfn"],
        live_dev, roots_dev, words, R=R, T=len(ws), P=P, D=D, k=k,
        topk_block=topk_block_config()))
    np.testing.assert_array_equal(got, want)  # i32 words: bitwise

    # the case is what its name says
    v, i, total = unpack_topk_result(got, k)
    hits = np.isfinite(v)
    assert total == int(np.asarray(mask).sum()) > 0
    assert hits.sum() == min(k, total)
    assert live[i[hits]].all()
    if case == "k_larger_than_the_matches":
        assert total < k and not hits.all()
    if nested:
        assert roots[i[hits]].all()
        assert total < int(np.asarray((scores > 0) & live_dev).sum())
    if n_deleted:
        assert total < int(np.asarray(scores > 0).sum())
    if ties:
        # tied scores come back lowest document first
        for a in range(k - 1):
            if hits[a + 1] and v[a] == v[a + 1]:
                assert i[a] < i[a + 1]
        assert len(set(v[hits].tolist())) < hits.sum()


@pytest.mark.parametrize("with_min_score", [False, True],
                         ids=["no_min_score", "min_score"])
@pytest.mark.parametrize("with_roots", [False, True],
                         ids=["no_roots", "roots"])
def test_finish_topk_equals_the_eager_sequence(with_roots, with_min_score):
    """finish_topk = mask & live (& roots) (& scores >= min_score) → sum →
    topk_with_mask → pack_topk_result, -inf for what is masked out, and
    the final mask when asked for."""
    import jax.numpy as jnp

    from elasticsearch_tpu.ops.scoring import (
        finish_topk, pack_topk_result, topk_block_config, topk_with_mask,
        unpack_topk_result)

    rng = np.random.default_rng(61)
    D, k = 256, 16
    # few distinct values: ties, zeros (filter-only hits) and negatives
    scores = jnp.asarray(rng.integers(-2, 6, size=D).astype(np.float32) / 2)
    mask = jnp.asarray(rng.random(D) < 0.6)
    live = jnp.asarray(rng.random(D) < 0.9)
    roots = jnp.asarray(rng.random(D) < 0.5) if with_roots else None
    min_score = 0.5 if with_min_score else None

    m = mask & live
    if roots is not None:
        m = m & roots
    if min_score is not None:
        m = m & (scores >= float(min_score))
    tot = jnp.sum(m.astype(jnp.int32))
    vals, idx = topk_with_mask(scores, m, k=k)
    want = np.asarray(pack_topk_result(vals, idx, tot))

    packed, out_mask = finish_topk(scores, mask, live, roots, min_score,
                                   k=k, topk_block=topk_block_config(),
                                   with_mask=True)
    np.testing.assert_array_equal(np.asarray(packed), want)
    np.testing.assert_array_equal(np.asarray(out_mask), np.asarray(m))
    packed2, no_mask = finish_topk(scores, mask, live, roots, min_score,
                                   k=k, topk_block=topk_block_config())
    assert no_mask is None
    np.testing.assert_array_equal(np.asarray(packed2), want)
    v, i, total = unpack_topk_result(np.asarray(packed), k)
    assert total == int(np.asarray(m).sum())
    assert np.asarray(m)[i[np.isfinite(v)]].all()
    if with_min_score:
        assert (v[np.isfinite(v)] >= 0.5).all()


def test_finish_topk_pads_with_neg_inf_beyond_the_matches():
    import jax.numpy as jnp

    from elasticsearch_tpu.ops.scoring import finish_topk, unpack_topk_result

    scores = jnp.asarray(np.array([0.0, 3.0, 1.0, 2.0], np.float32))
    mask = jnp.asarray(np.array([True, True, False, True]))
    live = jnp.asarray(np.array([True, False, True, True]))
    packed, _ = finish_topk(scores, mask, live, k=4, topk_block=0)
    v, i, total = unpack_topk_result(np.asarray(packed), 4)
    assert total == 2
    assert v.tolist() == [2.0, 0.0, -np.inf, -np.inf]  # 0.0 is a real hit
    assert i[:2].tolist() == [3, 0]
