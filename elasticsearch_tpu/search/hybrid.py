"""Hybrid retrieval: fused lexical+vector scoring as one device pipeline.

Reference: ES 2.0 has no hybrid search; this is the north-star RAG /
semantic-search workload (Anserini's dense+sparse integration,
arXiv:2304.12139). Both engines already emit whole-segment dense score
vectors — BM25 through the dense-impact/scatter programs (ops/scoring.py)
and kNN through the brute MXU sweep (ops/knn.py) — so fusion is an
elementwise combine before a single ``lax.top_k``:

    stage 1   lexical f32[D] ⊕ vector f32[D] → fused f32[D] → top-k
    stage 2   optional MaxSim re-rank of the top-k survivors (multi-vector
              token interaction), gated by a packed bit-vector candidate
              set exactly like the PQ coarse→fine split (ops/bitvec.py)

Fusion methods (weights are TRACED operands — a weight sweep must not
recompile, tpulint R017):

    linear    w_lex * lex + w_vec * vec on each engine's matches
    rrf       reciprocal rank fusion, w_e / (rank_constant + 1 + rank_e);
              ranks are computed ON DEVICE by a double stable argsort, so
              tie discipline ((-score, doc_id)) matches ``lax.top_k``

The fast path (`hybrid_fused_topk`) runs BOTH engines, the fusion, the
top-k, and the total count in ONE jitted program per segment round — the
acceptance contract is byte-identity with a host numpy fusion of the two
engines' exact score vectors. The composable fallback (`HybridQuery.
execute`) keeps the generic (scores, mask) contract so hybrid sub-trees
still work under aggs/sort/bool composition.

Stage-2 cost is charged against the ``request`` circuit breaker
(resources/breakers.py) BEFORE any device work: a fat re-rank degrades to
stage-1-only with a typed partial response (never a 500), mirrored by
``estpu_hybrid_rerank_total{decision=admit|decline}`` counters.
"""
from __future__ import annotations

from collections import Counter
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from elasticsearch_tpu.utils.errors import (CircuitBreakingException,
                                            QueryParsingException)

NEG_INF = float("-inf")

#: jit trace counts per hybrid program — incremented at TRACE time inside
#: the program bodies, so tests can prove (a) stage 1 is ONE program per
#: segment shape class and (b) a fusion-weight sweep never retraces (R017)
TRACE_COUNTS: "Counter[str]" = Counter()


def _jnp():
    import jax.numpy as jnp

    return jnp


# ---------------------------------------------------------------------------
# fusion math (traced helpers shared by the fast path and the fallback)
# ---------------------------------------------------------------------------

def _rrf_contrib(scores, mask, rank_constant):
    """Per-engine RRF contribution 1/(rank_constant + 1 + rank) over the
    engine's matches; rank is 0-based position in (-score, doc_id) order
    among ALL docs (non-matches sink to -inf so matches occupy the rank
    prefix — restricting to the match set cannot change a match's rank).
    Double stable argsort = inverse permutation without a device scatter.
    """
    jnp = _jnp()
    key = jnp.where(mask, scores, NEG_INF)
    order = jnp.argsort(-key, stable=True)
    rank = jnp.argsort(order, stable=True)
    return jnp.where(
        mask, 1.0 / (rank_constant + 1.0 + rank.astype(jnp.float32)), 0.0)


def _fuse_math(lex_s, lex_m, vec_s, vec_m, weights, rank_constant, *,
               method: str):
    """(fused f32[D], mask bool[D]) from the two engines' dense score
    vectors. ``weights`` f32[2] and ``rank_constant`` f32 are traced."""
    jnp = _jnp()
    if method == "linear":
        fused = (weights[0] * jnp.where(lex_m, lex_s, 0.0)
                 + weights[1] * jnp.where(vec_m, vec_s, 0.0))
    elif method == "rrf":
        fused = (weights[0] * _rrf_contrib(lex_s, lex_m, rank_constant)
                 + weights[1] * _rrf_contrib(vec_s, vec_m, rank_constant))
    else:  # parse_hybrid validates; unreachable from the DSL
        raise ValueError(f"unknown fusion method [{method}]")
    return fused, lex_m | vec_m


def _vector_side(qvec, vecs, vterms, vmask, kc, vboost, *, metric: str):
    """Brute-force vector engine inside the fused program: f32 scores for
    every doc + the top-``kc`` candidate mask (ES knn-query semantics:
    candidates beyond num_candidates are non-matches). The rank that
    implements the cutoff is the same (-score, id) double argsort the RRF
    path uses — ``kc`` stays a TRACED operand so a num_candidates sweep
    never recompiles."""
    jnp = _jnp()
    from elasticsearch_tpu.ops.knn import knn_scores

    vs = knn_scores(qvec[None, :], vecs, vterms, metric=metric,
                    use_bf16=False)[0]
    key = jnp.where(vmask, vs, NEG_INF)
    order = jnp.argsort(-key, stable=True)
    rank = jnp.argsort(order, stable=True)
    vm = vmask & (rank < kc)
    return vs * vboost, vm


def _fuse_select(lex, live, qvec, vecs, vterms, vexists, weights,
                 rank_constant, kc, vboost, *, k: int, method: str,
                 metric: str, topk_block: int):
    """Shared tail of both stage-1 program variants: vector engine →
    fusion → single masked top-k + exact total, packed for ONE host pull."""
    jnp = _jnp()
    from elasticsearch_tpu.ops.scoring import pack_topk_result, topk_auto

    lex_m = (lex > 0) & live
    vec_s, vec_m = _vector_side(qvec, vecs, vterms, vexists & live, kc,
                                vboost, metric=metric)
    fused, mask = _fuse_math(lex, lex_m, vec_s, vec_m, weights,
                             rank_constant, method=method)
    masked = jnp.where(mask, fused, NEG_INF)
    vals, idx = topk_auto(masked, k, topk_block)
    total = jnp.sum(mask.astype(jnp.int32))
    return pack_topk_result(vals, idx, total)


# ---------------------------------------------------------------------------
# stage-1 device programs (module-level jits behind aot.wrap keys)
# ---------------------------------------------------------------------------

def _hybrid_topk_gather(impact, qrows, qrw, doc_ids, tfnorm, starts, lens,
                        ws, live, qvec, vecs, vterms, vexists, weights,
                        rank_constant, kc, vboost, *, P: int, D: int,
                        k: int, method: str, metric: str, topk_block: int):
    """Stage-1, dense-impact lexical form: BM25 gathers only the query's
    dense rows (+ scatter tail), the vector engine sweeps the slab (once:
    ``vterms`` is the column's stored row term), and fusion + top-k +
    total land in the SAME program — one device dispatch and one packed
    i32[2k+1] pull per segment."""
    from elasticsearch_tpu.ops.scoring import bm25_score_hybrid_gather

    TRACE_COUNTS["hybrid_fused_topk"] += 1
    lex = bm25_score_hybrid_gather(impact, qrows, qrw, doc_ids, tfnorm,
                                   starts, lens, ws, P=P, D=D)
    return _fuse_select(lex, live, qvec, vecs, vterms, vexists, weights,
                        rank_constant, kc, vboost, k=k, method=method,
                        metric=metric, topk_block=topk_block)


def _hybrid_topk_scatter(doc_ids, tfnorm, starts, lens, ws, live, qvec,
                         vecs, vterms, vexists, weights, rank_constant, kc,
                         vboost, *, P: int, D: int, k: int, method: str,
                         metric: str, topk_block: int):
    """Stage-1, scatter-only lexical form (segments without a dense
    impact block — small corpora, all-rare term groups)."""
    from elasticsearch_tpu.ops.scoring import bm25_score_segment

    TRACE_COUNTS["hybrid_fused_topk_scatter"] += 1
    lex = bm25_score_segment(doc_ids, tfnorm, starts, lens, ws, P=P, D=D)
    return _fuse_select(lex, live, qvec, vecs, vterms, vexists, weights,
                        rank_constant, kc, vboost, k=k, method=method,
                        metric=metric, topk_block=topk_block)


_JITTED: Dict[str, Any] = {}


def _program(name: str, fn):
    """jit + aot.wrap (factory-key discipline, ROADMAP #6) — memoized so
    every call site shares one program object per name."""
    prog = _JITTED.get(name)
    if prog is None:
        import jax

        from elasticsearch_tpu.search.queries import _tier_program

        statics = ("P", "D", "k", "method", "metric", "topk_block")
        prog = _tier_program(name, partial(jax.jit, static_argnames=statics)(fn))
        _JITTED[name] = prog
    return prog


# ---------------------------------------------------------------------------
# query node + DSL parsing
# ---------------------------------------------------------------------------

from elasticsearch_tpu.search.queries import Query  # noqa: E402  (no cycle:
#   queries.py only imports this module inside its `hybrid` parse branch)


class HybridQuery(Query):
    """``hybrid`` query: lexical sub-query + kNN side + fusion spec.

    Body shape (parse_hybrid)::

        {"hybrid": {
            "query":  {...any lexical DSL subtree...},
            "knn":    {"field": f, "query_vector": [...],
                       "num_candidates": n, "boost": b},
            "fusion": {"method": "rrf"|"linear", "weights": [wl, wv],
                       "rank_constant": 60},
            "rerank": {"query_vectors": [[...], ...], "window_size": w,
                       "pq": true|false}        # optional stage 2
        }}

    The executor prefers the ONE-program fast path (hybrid_fused_topk);
    this node's ``execute`` is the composable fallback that keeps the
    generic (scores, mask) contract for aggs / sort / bool composition —
    both produce identical results (same fusion program, same tie
    discipline)."""

    def __init__(self, lexical, knn, method: str = "rrf",
                 weights: Tuple[float, float] = (1.0, 1.0),
                 rank_constant: float = 60.0,
                 rerank: Optional[dict] = None):
        self.lexical = lexical
        self.knn = knn
        self.method = method
        self.weights = (float(weights[0]), float(weights[1]))
        self.rank_constant = float(rank_constant)
        self.rerank = rerank

    def execute(self, ctx):
        """(fused scores f32[D], mask bool[D]) — composable fallback.

        Each engine runs its OWN program (the exact per-engine scores the
        fast path must reproduce); the fusion combine is one additional
        jitted elementwise program. Liveness folds into both masks BEFORE
        fusion so RRF ranks ignore deleted docs exactly like the fused
        program."""
        jnp = _jnp()
        from elasticsearch_tpu.monitor import kernels

        live = ctx.segment.live
        lex_s, lex_m = self.lexical.score_or_mask(ctx)
        lex_m = lex_m & live
        vec_s, vec_m = self.knn.execute(ctx)
        vec_m = vec_m & live
        fused, mask = _fuse_program(
            lex_s, lex_m, vec_s, vec_m,
            jnp.asarray(np.asarray(self.weights, np.float32)),
            jnp.float32(self.rank_constant), method=self.method)
        kernels.record("hybrid_fuse")
        return fused, mask


def _fuse_program(lex_s, lex_m, vec_s, vec_m, weights, rank_constant, *,
                  method: str):
    fn = _JITTED.get("hybrid_fuse")
    if fn is None:
        import jax

        from elasticsearch_tpu.search.queries import _tier_program

        def _fuse(lex_s, lex_m, vec_s, vec_m, weights, rank_constant, *,
                  method: str):
            TRACE_COUNTS["hybrid_fuse"] += 1
            return _fuse_math(lex_s, lex_m, vec_s, vec_m, weights,
                              rank_constant, method=method)

        fn = _tier_program(
            "hybrid_fuse",
            partial(jax.jit, static_argnames=("method",))(_fuse))
        _JITTED["hybrid_fuse"] = fn
    return fn(lex_s, lex_m, vec_s, vec_m, weights, rank_constant,
              method=method)


def parse_hybrid(body: dict) -> HybridQuery:
    """Parse a ``hybrid`` body; malformed specs raise the typed 400."""
    from elasticsearch_tpu.search.queries import KnnQuery, parse_query

    if not isinstance(body, dict):
        raise QueryParsingException("hybrid query body must be an object")
    lex_body = body.get("query", body.get("lexical"))
    knn_body = body.get("knn", body.get("vector"))
    if lex_body is None or knn_body is None:
        raise QueryParsingException(
            "hybrid query requires both [query] (lexical) and [knn] "
            "(vector) clauses")
    lexical = parse_query(lex_body)
    if not isinstance(knn_body, dict) or "field" not in knn_body:
        raise QueryParsingException("hybrid [knn] clause requires [field]")
    vec = knn_body.get("query_vector", knn_body.get("vector"))
    if vec is None:
        raise QueryParsingException(
            "hybrid [knn] clause requires [query_vector]")
    filt = (parse_query(knn_body["filter"])
            if knn_body.get("filter") is not None else None)
    knn = KnnQuery(
        knn_body["field"], vec, k=int(knn_body.get("k", 10)),
        num_candidates=knn_body.get("num_candidates"),
        filter_=filt, boost=float(knn_body.get("boost", 1.0)),
        ann=knn_body.get("ann"), pq=knn_body.get("pq"))
    if knn.maxsim:
        raise QueryParsingException(
            "hybrid [knn] clause takes a single query_vector; put the "
            "token matrix in [rerank.query_vectors] (stage-2 MaxSim)")
    fusion = body.get("fusion") or {}
    method = str(fusion.get("method", "rrf")).lower()
    if method not in ("rrf", "linear"):
        raise QueryParsingException(
            f"unknown hybrid fusion method [{method}] "
            f"(expected rrf or linear)")
    weights = fusion.get("weights", (1.0, 1.0))
    try:
        wl, wv = (float(weights[0]), float(weights[1]))
    except (TypeError, ValueError, IndexError):
        raise QueryParsingException(
            f"hybrid fusion weights must be [w_lexical, w_vector], "
            f"got {weights!r}")
    if wl < 0 or wv < 0:
        raise QueryParsingException("hybrid fusion weights must be >= 0")
    rank_constant = float(fusion.get("rank_constant",
                                     fusion.get("rrf_k", 60.0)))
    rerank = body.get("rerank")
    if rerank is not None:
        if not isinstance(rerank, dict):
            raise QueryParsingException("hybrid [rerank] must be an object")
        toks = rerank.get("query_vectors", rerank.get("query_vector"))
        if toks is None:
            raise QueryParsingException(
                "hybrid [rerank] requires [query_vectors]")
        try:
            tm = np.asarray(toks, np.float32)
        except (TypeError, ValueError) as e:
            raise QueryParsingException(
                f"malformed hybrid rerank query_vectors: {e}")
        if tm.ndim == 1:
            tm = tm[None, :]
        if tm.ndim != 2:
            raise QueryParsingException(
                "hybrid rerank query_vectors must be a vector or a "
                "list of vectors")
        rerank = {
            "tokens": tm,
            "window_size": int(rerank.get("window_size", 32)),
            "field": rerank.get("field", knn.field),
            "pq": rerank.get("pq"),
        }
        if rerank["window_size"] < 1:
            raise QueryParsingException(
                "hybrid rerank window_size must be >= 1")
    return HybridQuery(lexical, knn, method=method, weights=(wl, wv),
                       rank_constant=rank_constant, rerank=rerank)


# ---------------------------------------------------------------------------
# stage-1 fast path: ONE device program per segment round
# ---------------------------------------------------------------------------

def hybrid_fused_topk(ctx, query: HybridQuery, k: int):
    """Fused stage-1 over one segment: both engines + fusion + top-k +
    total as one device program, one packed pull. Returns
    (vals f32[k], ids i32[k], total int) or None to fall through to the
    composable execute() path (ANN/PQ vector side, a knn filter, a
    postings-sharded field — each has its own orchestration).

    Weights, rank_constant, num_candidates, and the knn boost are traced
    operands: sweeping any of them reuses the compiled program (R017)."""
    from elasticsearch_tpu.monitor import kernels
    from elasticsearch_tpu.ops.scoring import (topk_block_config,
                                               unpack_topk_result)
    from elasticsearch_tpu.search.queries import _fused_eligible_terms

    jnp = _jnp()
    e = _fused_eligible_terms(ctx, query.lexical)
    if e is None:
        return None
    field, (tlist, wlist) = e
    if not all(w > 0 for w in wlist):
        return None  # score>0 must remain exactly 'lexical match'
    knn = query.knn
    if knn.filter is not None or knn.maxsim or knn._use_ann(ctx):
        return None
    vc = ctx.segment.vectors.get(knn.field)
    if vc is None:
        return None
    if knn.tokens.shape[1] != vc.dims:
        raise QueryParsingException(
            f"knn query vector has {knn.tokens.shape[1]} dims but field "
            f"[{knn.field}] is mapped with {vc.dims}")
    inv = ctx.inv(field)
    if inv is None or inv.wants_postings_shard():
        return None
    live = ctx.segment.live
    kk = min(k, ctx.D)
    kc = int(min(max(knn.num_candidates, knn.k), ctx.D))
    blk = topk_block_config()
    common = dict(k=kk, method=query.method, metric=vc.similarity,
                  topk_block=blk)
    weights = jnp.asarray(np.asarray(query.weights, np.float32))
    rank_c = jnp.float32(query.rank_constant)
    qvec = jnp.asarray(knn.tokens[0])
    hyb = ctx.hybrid_slices(inv, tlist, wlist, need_qw=False)
    if hyb is not None:
        impact, _qw, _qind, starts, lens, ws, P, _n, qrows, qrw = hyb
        prog = _program("hybrid_fused_topk", _hybrid_topk_gather)
        packed = prog(impact, jnp.asarray(qrows), jnp.asarray(qrw),
                      inv.doc_ids, inv.tfnorm, starts, lens, ws, live,
                      qvec, vc.vecs, vc.row_terms(), vc.exists, weights,
                      rank_c, jnp.int32(kc), jnp.float32(knn.boost),
                      P=P, D=ctx.D, **common)
    else:
        starts, lens, ws, P, _n = ctx.chunked_slices(inv, tlist, wlist)
        prog = _program("hybrid_fused_topk_scatter", _hybrid_topk_scatter)
        packed = prog(inv.doc_ids, inv.tfnorm, starts, lens, ws, live,
                      qvec, vc.vecs, vc.row_terms(), vc.exists, weights,
                      rank_c, jnp.int32(kc), jnp.float32(knn.boost),
                      P=P, D=ctx.D, **common)
    kernels.record("hybrid_fused_topk")
    # ONE packed pull (i32[2k+1] bitcast) — the fused-path transfer budget
    vals, ids, total = unpack_topk_result(np.asarray(packed), kk)
    return vals, ids, total


# ---------------------------------------------------------------------------
# stage-1 batched tier (msearch / coalescer)
# ---------------------------------------------------------------------------

def _hybrid_topk_batch(impact, qrows, qrw, doc_ids, tfnorm, starts, lens,
                       ws, live, toks, vecs, vterms, vexists, weights,
                       rank_constants, kcs, vboosts, *, P: int, D: int,
                       k: int, method: str, metric: str, topk_block: int):
    """Batched stage-1: per-query dense-row gather lexical scores
    (einsum over each query's R rows — byte-stable vs the single-query
    gather form) + one [Q, dims] @ slab sweep + vmapped fusion + batched
    top-k, all in one program."""
    import jax
    from jax import lax

    jnp = _jnp()
    from elasticsearch_tpu.ops.knn import knn_scores
    from elasticsearch_tpu.ops.scoring import bm25_score_batch, topk_auto

    TRACE_COUNTS["hybrid_fused_topk_batch"] += 1
    rows = impact[jnp.maximum(qrows, 0)]  # [Q, R, D]
    lex = jnp.einsum("qr,qrd->qd", qrw, rows.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    lex = lex + bm25_score_batch(doc_ids, tfnorm, starts, lens, ws,
                                 P=P, D=D)
    lex_m = (lex > 0) & live[None, :]
    vs = knn_scores(toks, vecs, vterms, metric=metric,
                    use_bf16=False)  # [Q, D]
    vmask = (vexists & live)[None, :]
    key = jnp.where(vmask, vs, NEG_INF)
    order = jnp.argsort(-key, axis=1, stable=True)
    rank = jnp.argsort(order, axis=1, stable=True)
    vec_m = vmask & (rank < kcs[:, None])
    vec_s = vs * vboosts[:, None]
    fused, mask = jax.vmap(
        lambda a, b, c, d, w, rc: _fuse_math(a, b, c, d, w, rc,
                                             method=method)
    )(lex, lex_m, vec_s, vec_m, weights, rank_constants)
    masked = jnp.where(mask, fused, NEG_INF)
    vals, idx = topk_auto(masked, k, topk_block)
    totals = jnp.sum(mask.astype(jnp.int32), axis=1)
    return vals, idx.astype(jnp.int32), totals


def hybrid_fused_topk_batch(ctx, queries: List[HybridQuery], k: int):
    """Batched fused stage-1 over ONE segment for a uniform hybrid micro-
    batch (same lexical field with a dense impact block, same vector
    field, same fusion method, brute-force vector side, no filters/
    rerank). Per-query weights/rank_constant/num_candidates/boost ride as
    traced [Q]-rows. Returns (vals [Q, k], ids [Q, k], totals [Q]) —
    the fused_bm25_topk_batch contract — or None (sequential fallback).
    """
    from elasticsearch_tpu.monitor import kernels
    from elasticsearch_tpu.ops.scoring import topk_block_config
    from elasticsearch_tpu.search.queries import _fused_eligible_terms

    jnp = _jnp()
    if not queries or not all(isinstance(q, HybridQuery) for q in queries):
        return None
    q0 = queries[0]
    if any(q.method != q0.method or q.rerank is not None for q in queries):
        return None
    if any(q.knn.field != q0.knn.field or q.knn.filter is not None
           or q.knn.maxsim or q.knn._use_ann(ctx) for q in queries):
        return None
    vc = ctx.segment.vectors.get(q0.knn.field)
    if vc is None or any(q.knn.tokens.shape[1] != vc.dims for q in queries):
        return None
    field = None
    groups = []
    for q in queries:
        e = _fused_eligible_terms(ctx, q.lexical)
        if e is None:
            return None
        f, (tlist, wlist) = e
        if field is None:
            field = f
        elif f != field:
            return None
        if not all(w > 0 for w in wlist):
            return None
        groups.append((tlist, wlist))
    inv = ctx.inv(field) if field is not None else None
    if inv is None or inv.wants_postings_shard():
        return None
    slices = []
    for tlist, wlist in groups:
        h = ctx.hybrid_slices(inv, tlist, wlist, need_qw=False)
        if h is None:
            return None  # no dense block: the sequential path decides
        slices.append(h)
    impact = slices[0][0]
    Q = len(queries)
    P = max(h[6] for h in slices)
    T = max(h[3].shape[0] for h in slices)
    R = max(h[8].shape[0] for h in slices)
    qrows = np.full((Q, R), -1, np.int32)
    qrw = np.zeros((Q, R), np.float32)
    starts = np.zeros((Q, T), np.int32)
    lens = np.zeros((Q, T), np.int32)
    ws = np.zeros((Q, T), np.float32)
    for qi, h in enumerate(slices):
        _i, _qw, _qind, st, ln, w, _p, _n, qr, qwv = h
        qrows[qi, : qr.shape[0]] = qr
        qrw[qi, : qwv.shape[0]] = qwv
        starts[qi, : st.shape[0]] = st
        lens[qi, : ln.shape[0]] = ln
        ws[qi, : w.shape[0]] = w
    toks = np.stack([q.knn.tokens[0] for q in queries])
    kcs = np.asarray([int(min(max(q.knn.num_candidates, q.knn.k), ctx.D))
                      for q in queries], np.int32)
    weights = np.asarray([q.weights for q in queries], np.float32)
    rcs = np.asarray([q.rank_constant for q in queries], np.float32)
    boosts = np.asarray([q.knn.boost for q in queries], np.float32)
    kk = min(k, ctx.D)
    prog = _program("hybrid_fused_topk_batch", _hybrid_topk_batch)
    vals, idx, totals = prog(
        impact, jnp.asarray(qrows), jnp.asarray(qrw), inv.doc_ids,
        inv.tfnorm, jnp.asarray(starts), jnp.asarray(lens),
        jnp.asarray(ws), ctx.segment.live, jnp.asarray(toks), vc.vecs,
        vc.row_terms(), vc.exists, jnp.asarray(weights), jnp.asarray(rcs),
        jnp.asarray(kcs), jnp.asarray(boosts), P=P, D=ctx.D, k=kk,
        method=q0.method, metric=vc.similarity,
        topk_block=topk_block_config())
    kernels.record("hybrid_fused_batch", Q)
    return (np.asarray(vals), np.asarray(idx),
            np.asarray(totals).astype(np.int64))


# ---------------------------------------------------------------------------
# stage 2: MaxSim window re-rank (breaker-gated, bit-vector admissibility)
# ---------------------------------------------------------------------------

_RERANK_COUNTER = [None]


def _rerank_counter():
    if _RERANK_COUNTER[0] is None:
        from elasticsearch_tpu.monitor.metrics import SHARED

        _RERANK_COUNTER[0] = SHARED.counter(
            "estpu_hybrid_rerank_total",
            "Stage-2 MaxSim re-rank admission decisions by the request "
            "breaker", ("decision",))
    return _RERANK_COUNTER[0]


def _rerank_cost_bytes(n: int, T: int, dims: int, pq) -> int:
    """Stage-2 device working set: candidate gather + [T, n] interaction
    (exact form) or code gather + [T, M, K] LUTs (ADC form), with the
    same 2x transient headroom the executor's estimates carry."""
    if pq is not None:
        return 2 * (n * pq.M * 4 + T * pq.M * pq.K * 4 + n * T * 4)
    return 2 * (n * dims * 4 + T * n * 4 + T * dims * 4)


def maxsim_window_scores(ctx, vc, tokens: np.ndarray, local_ids,
                         *, use_pq: Optional[bool] = None,
                         label: str = "hybrid_rerank"):
    """MaxSim scores f32[n] for ``local_ids`` of one segment (stage-2
    device re-rank: gather the window, score every (token, candidate)
    pair, max over tokens). Inadmissible candidates (deleted, no vector —
    tested in-program through a packed bit-vector exactly like the PQ
    coarse→fine pre-filter) come back -inf.

    Cost is charged to the ``request`` breaker FIRST; a denial re-raises
    the typed CircuitBreakingException after ticking
    ``estpu_hybrid_rerank_total{decision=decline}`` — callers catch it
    and keep their stage-1 results (typed partial, never a 500).

    With a built PQ tier (and ``use_pq`` not False) scoring runs the
    tiled Pallas MaxSim-ADC kernel (ops/pallas_kernels.maxsim_adc_auto):
    scores are then ADC ranking proxies, not calibrated similarities —
    the fidelity/cost trade the request opts into via ``rerank.pq``."""
    import jax

    jnp = _jnp()
    from elasticsearch_tpu.ops.bitvec import pack_mask
    from elasticsearch_tpu.resources import BREAKERS

    ids = np.asarray(local_ids, np.int32)
    n = int(ids.size)
    if n == 0:
        return np.empty(0, np.float32)
    toks = np.asarray(tokens, np.float32)
    if toks.ndim == 1:
        toks = toks[None, :]
    if toks.shape[1] != vc.dims:
        raise QueryParsingException(
            f"rerank query vectors have {toks.shape[1]} dims but field "
            f"[{vc.name}] is mapped with {vc.dims}")
    T = toks.shape[0]
    pq = None
    want_pq = use_pq
    if want_pq is None:
        # auto = follow the mapping (KnnQuery._use_pq discipline) — a
        # get_pq probe on an unmapped field would trigger a k-means build
        fm = ctx.mappings.get(vc.name)
        opts = getattr(fm, "index_options", None) if fm is not None else None
        want_pq = bool(opts) and opts.get("type") == "ivf_pq"
    if want_pq:
        pq = vc.get_pq(ctx.segment.max_docs) or None
        # no tier (too few vectors / budget tight): exact path still runs
    breaker = BREAKERS.breaker("request")
    est = _rerank_cost_bytes(n, T, vc.dims, pq)
    try:
        breaker.break_or_reserve(est, label)
    except CircuitBreakingException:
        _rerank_counter().labels("decline").inc()
        raise
    try:
        _rerank_counter().labels("admit").inc()
        words = pack_mask(vc.exists & ctx.segment.live)
        ids_dev = jnp.asarray(ids)
        if pq is not None:
            from elasticsearch_tpu.ops.pallas_kernels import maxsim_adc_auto

            luts = _maxsim_luts(jnp.asarray(toks), pq.codebooks,
                                metric=vc.similarity)
            codes = _gather_codes_program()(pq.codes_dev(), ids_dev)
            scores = maxsim_adc_auto(codes, luts)
            scores = _admissible_program()(scores, words, ids_dev)
        else:
            scores = _maxsim_window_exact(jnp.asarray(toks), vc.vecs,
                                          ids_dev, words,
                                          metric=vc.similarity)
        out = np.asarray(jax.device_get(scores), np.float32)
    finally:
        breaker.release(est)
    from elasticsearch_tpu.monitor import kernels

    kernels.record("hybrid_rerank", n)
    return out


def _maxsim_luts(toks, codebooks, *, metric: str):
    fn = _JITTED.get("hybrid_rerank_luts")
    if fn is None:
        import jax

        from elasticsearch_tpu.search.queries import _tier_program

        def _luts(toks, codebooks, *, metric: str):
            from elasticsearch_tpu.ops.pq import adc_lut

            jnp = _jnp()
            return jax.vmap(
                lambda t: adc_lut(jnp, t, codebooks, metric))(toks)

        fn = _tier_program(
            "hybrid_rerank_luts",
            partial(jax.jit, static_argnames=("metric",))(_luts))
        _JITTED["hybrid_rerank_luts"] = fn
    return fn(toks, codebooks, metric=metric)


def _gather_codes_program():
    fn = _JITTED.get("hybrid_rerank_codes")
    if fn is None:
        import jax

        from elasticsearch_tpu.search.queries import _tier_program

        def _codes(codes, ids):
            return codes[ids].astype(_jnp().int32)

        fn = _tier_program("hybrid_rerank_codes", partial(jax.jit)(_codes))
        _JITTED["hybrid_rerank_codes"] = fn
    return fn


def _admissible_program():
    fn = _JITTED.get("hybrid_rerank_adm")
    if fn is None:
        import jax

        from elasticsearch_tpu.search.queries import _tier_program

        def _adm(scores, words, ids):
            from elasticsearch_tpu.ops.bitvec import test_bits

            return _jnp().where(test_bits(words, ids), scores, NEG_INF)

        fn = _tier_program("hybrid_rerank_adm", partial(jax.jit)(_adm))
        _JITTED["hybrid_rerank_adm"] = fn
    return fn


def _maxsim_window_exact(toks, vecs, ids, words, *, metric: str):
    fn = _JITTED.get("hybrid_rerank_exact")
    if fn is None:
        import jax

        from elasticsearch_tpu.search.queries import _tier_program

        def _exact(toks, vecs, ids, words, *, metric: str):
            from jax import lax

            jnp = _jnp()
            from elasticsearch_tpu.ops.bitvec import test_bits

            TRACE_COUNTS["hybrid_rerank_exact"] += 1
            cand = vecs[ids].astype(jnp.float32)  # [n, dims]
            q = toks.astype(jnp.float32)
            hi = lax.Precision.HIGHEST
            if metric == "cosine":
                qn = q / jnp.maximum(
                    jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
                cn = cand / jnp.maximum(
                    jnp.linalg.norm(cand, axis=-1, keepdims=True), 1e-12)
                s = (1.0 + jnp.matmul(qn, cn.T, precision=hi)) * 0.5
            elif metric in ("dot_product", "dot"):
                s = (1.0 + jnp.matmul(q, cand.T, precision=hi)) * 0.5
            elif metric in ("l2_norm", "l2"):
                d2 = jnp.sum((q[:, None, :] - cand[None, :, :]) ** 2,
                             axis=-1)
                s = 1.0 / (1.0 + d2)
            else:
                raise ValueError(f"unknown knn metric [{metric}]")
            ms = jnp.max(s, axis=0)  # [n] max over tokens
            return jnp.where(test_bits(words, ids), ms, NEG_INF)

        fn = _tier_program(
            "hybrid_rerank_exact",
            partial(jax.jit, static_argnames=("metric",))(_exact))
        _JITTED["hybrid_rerank_exact"] = fn
    return fn(toks, vecs, ids, words, metric=metric)


def apply_hybrid_rerank(docs, query: HybridQuery, mappings, analysis) -> dict:
    """Stage 2 over the merged stage-1 candidates: re-score the top
    ``window_size`` survivors by MaxSim token interaction and re-order
    the window (ties by (seg_id, local_id) — the stage-1 discipline).
    Returns the typed status dict that rides the response's ``hybrid``
    section: ``{"rerank": "applied"|"declined", ...}``. A breaker denial
    leaves every stage-1 score untouched."""
    from elasticsearch_tpu.search.context import SegmentContext

    spec = query.rerank
    window = docs[: min(spec["window_size"], len(docs))]
    if not window:
        return {"rerank": "applied", "window": 0}
    by_seg: Dict[int, list] = {}
    for d in window:
        by_seg.setdefault(id(d.seg), []).append(d)
    new_scores: Dict[int, float] = {}
    try:
        for seg_docs in by_seg.values():
            seg = seg_docs[0].seg
            ctx = SegmentContext(seg, mappings, analysis)
            vc = seg.vectors.get(spec["field"])
            if vc is None:
                continue  # no vectors in this segment: keep stage-1 order
            ids = np.asarray([d.local_id for d in seg_docs], np.int32)
            scores = maxsim_window_scores(ctx, vc, spec["tokens"], ids,
                                          use_pq=spec.get("pq"))
            for d, s in zip(seg_docs, scores):
                if np.isfinite(s):
                    new_scores[id(d)] = float(s)
    except CircuitBreakingException as e:
        return {"rerank": "declined", "degraded_to": "stage1",
                "reason": {"type": e.error_type, "reason": str(e)}}
    for d in window:
        if id(d) in new_scores:
            d.score = new_scores[id(d)]
    window.sort(key=lambda d: (-d.score, d.seg.seg_id, d.local_id))
    docs[: len(window)] = window
    return {"rerank": "applied", "window": len(window)}
