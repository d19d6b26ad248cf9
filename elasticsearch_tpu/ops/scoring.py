"""Core TPU scoring programs.

These replace Lucene's Weight/Scorer doc-at-a-time iterator trees
(reference: Lucene BM25Similarity via org/elasticsearch/index/similarity/
BM25SimilarityProvider.java, and the per-segment search loop in
org/elasticsearch/search/query/QueryPhase.java) with whole-segment dense
programs:

- ``bm25_score_segment``: T query terms × P-wide postings slices →
  scatter-add into an f32[D] score vector. P and T are power-of-two
  buckets; terms with longer postings runs are pre-split into multiple
  (start, len) chunks by the executor, so one compiled program serves all
  queries in a shape class. Weights fold idf × boost; tf-normalization is
  precomputed per posting at index time (impact-style eager scoring).
- ``term_mask``: same slicing, but produces a bool[D] filter mask.
- ``topk_with_mask``: masked top-k (scores → (values, doc_ids)).
- range masks over numeric doc-value columns, including exact 64-bit
  comparison via (hi, lo) int32 pairs.

All functions are jitted with static shape arguments; callers bucket their
inputs (see utils.shapes.pow2_bucket).
"""
from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

NEG_INF = jnp.float32(-jnp.inf)


# ---------------------------------------------------------------------------
# postings slicing
# ---------------------------------------------------------------------------

def _slice_postings(doc_ids, payload, start, length, P: int):
    """Slice a P-wide window of a term's postings run, handling the edge
    clamp: lax.dynamic_slice clamps start to nnz_pad - P, so compute the
    in-window shift and mask accordingly. Returns (docs[P], payload[P], valid[P]).
    """
    nnz_pad = doc_ids.shape[0]
    clamped = jnp.minimum(start, nnz_pad - P)
    shift = start - clamped
    docs = lax.dynamic_slice(doc_ids, (clamped,), (P,))
    pay = lax.dynamic_slice(payload, (clamped,), (P,))
    idx = jnp.arange(P, dtype=jnp.int32)
    valid = (idx >= shift) & (idx < shift + length)
    return docs, pay, valid


@partial(jax.jit, static_argnames=("P", "D"))
def bm25_score_segment(doc_ids, tfnorm, starts, lens, weights, *, P: int, D: int):
    """BM25 score vector for one segment.

    doc_ids: i32[nnz_pad] — postings doc ids (padded entries point at D
        sentinel and carry tfnorm 0, so they contribute nothing).
    tfnorm:  f32[nnz_pad] — precomputed tf*(k1+1)/(tf+k1*(1-b+b*dl/avg)).
    starts, lens: i32[T] — per-chunk postings runs (host-computed, bucketed).
    weights: f32[T] — idf * query boost per chunk.
    Returns f32[D] scores (0 for non-matching docs).
    """

    def per_chunk(start, length, w):
        docs, tfn, valid = _slice_postings(doc_ids, tfnorm, start, length, P)
        return docs, jnp.where(valid, tfn * w, 0.0)

    docs, contrib = jax.vmap(per_chunk)(starts, lens, weights)  # [T, P]
    scores = jnp.zeros(D, dtype=jnp.float32)
    scores = scores.at[docs.reshape(-1)].add(
        contrib.reshape(-1), mode="drop", indices_are_sorted=False
    )
    return scores


@partial(jax.jit, static_argnames=("P", "D"))
def bm25_score_batch(doc_ids, tfnorm, starts, lens, weights, *, P: int, D: int):
    """Batched queries: starts/lens/weights are [Q, T] → f32[Q, D]."""
    f = partial(bm25_score_segment, P=P, D=D)
    return jax.vmap(lambda s, l, w: f(doc_ids, tfnorm, s, l, w))(starts, lens, weights)


# ---------------------------------------------------------------------------
# hybrid dense/sparse scoring (frequent terms on the MXU, tail via scatter)
#
# Each hybrid op = one dense contribution (a matmul against the segment's
# impact[F, D] block, see index.segment.build_dense_impact) composed with the
# corresponding pure-scatter kernel for the short CSR tail. The scatter logic
# lives ONLY in the base kernels; hybrids never re-implement it.
# ---------------------------------------------------------------------------


def topk_block_config() -> int:
    """The blocked-top-k knob, read from ``ESTPU_BLOCKED_TOPK``: unset =
    platform default (8192 on TPU — the two-stage selection measured
    ~9 ms faster than one 1M-wide flat ``lax.top_k`` on a v5e, and
    ``exact_topk`` is tie-exact so there is no accuracy trade; 0 = flat
    elsewhere, where XLA:CPU's top_k is already fine); 0/false = flat;
    1/true = two-stage with the default 8192 block; an integer = that
    block size. MUST be read OUTSIDE jit (at call or program-build time)
    and plumbed through as a static argument, so the choice participates
    in jit/program cache keys — an env read inside traced code would be
    silently frozen by the first trace."""
    v = os.environ.get("ESTPU_BLOCKED_TOPK", "").lower()
    if not v:
        return 8192 if jax.default_backend() == "tpu" else 0
    if v in ("0", "false", "off"):
        return 0
    if v in ("1", "true", "on"):
        return 8192
    try:
        return int(v)
    except ValueError:
        # a typo'd knob must not crash every search request deep in the
        # scoring path — warn once and run the flat top_k
        global _TOPK_WARNED
        if not _TOPK_WARNED:
            import warnings

            warnings.warn(f"ESTPU_BLOCKED_TOPK={v!r} is not an integer; "
                          f"blocked top-k disabled")
            _TOPK_WARNED = True
        return 0


_TOPK_WARNED = False


def exact_topk(x, k: int, block: int = 8192):
    """Exact top-k over the last axis, two-stage: per-block top-k, then
    top-k over the concatenated block winners. Identical results to
    ``lax.top_k`` INCLUDING tie order (ties resolve to the lowest index:
    within a block top_k orders ties by index, and across blocks the
    winner list is block-ordered so the global pass picks the earlier
    block first). Falls back to the flat top_k when blocking can't help
    (small D, huge k, non-divisible shapes). Shapes a large-D top-k into
    row-sized sorts, which some backends execute far better than one
    D-wide selection."""
    D = x.shape[-1]
    if k >= block or D < 2 * block or D % block:
        return lax.top_k(x, k)
    nb = D // block
    xb = x.reshape(x.shape[:-1] + (nb, block))
    bv, bi = lax.top_k(xb, k)  # [..., nb, k]
    bi = bi + (jnp.arange(nb, dtype=bi.dtype) * block)[:, None]
    flatv = bv.reshape(x.shape[:-1] + (nb * k,))
    flati = bi.reshape(x.shape[:-1] + (nb * k,))
    gv, gp = lax.top_k(flatv, k)
    gi = jnp.take_along_axis(flati, gp, axis=-1)
    return gv, gi


def topk_auto(x, k: int, block: int = 0):
    """Product top-k dispatch: blocked two-stage when ``block`` > 0, else
    flat ``lax.top_k``. Pass ``topk_block_config()`` read OUTSIDE jit."""
    return exact_topk(x, k, block) if block else lax.top_k(x, k)


def _dense_dot(qw, dense_impact, prec: str = "highest"):
    """qw @ impact with dtype-aware MXU mapping: an f32 block multiplies at
    HIGHEST precision (the index states float32 BM25; exactness tests rely
    on it); a bf16 block (segment's ESTPU_IMPACT_BF16 storage) takes the
    native bf16 MXU path with f32 accumulation — no upcast copy of the
    block in HBM. ``prec`` admits only "highest", the one precision there
    is: the yardstick's compile test
    (tests/bench_harness/test_bench_tpu_compile.py) passes it by position,
    and only a `benchmark` PR may edit that file."""
    if prec != "highest":
        raise ValueError(f"impact products are f32-exact; got prec={prec!r}")
    if dense_impact.dtype == jnp.bfloat16:
        return jnp.dot(qw.astype(jnp.bfloat16), dense_impact,
                       preferred_element_type=jnp.float32)
    return jnp.dot(qw, dense_impact, precision=lax.Precision.HIGHEST)


def _fold_dense_rows(dense_impact, qrows, init, step):
    """``step(acc, r, row_r)`` folded over the query's dense rows in
    ascending r — the ONE way a single-query program reads
    ``dense_impact[F, D]``. Row r is the ``[D]`` slice of the block at
    ``max(qrows[r], 0)``, read inside the loop that consumes it (no
    ``[R, D]`` copy), and the loop ends behind the last real row:
    ``pack_dense_rows`` sorts the -1 padding to the back, and on a TPU a
    padding row is no cheaper than a real one. (A stray -1 before that
    reads row 0; callers weight it 0 or mask it by ``qrows >= 0``.)

    What a row costs there (v5e, f32, D 2^22; PERF.md §6, PR 28): the
    block is tiled ``(8, 128)``, a one-row slice streams its whole 8-row
    tile group — 134 MB, 0.19 ms — so a query of n rows moves n/8 of a
    64-row block, not n/64. The advanced-index form ``dense_impact[rows]``
    this replaced lowered to a pass over ALL F rows (128 column pieces
    ``f32[64,32768]``, a small gather a piece, 128 update-slices)."""
    R = qrows.shape[0]
    last = jnp.max(jnp.where(qrows >= 0,
                             jnp.arange(1, R + 1, dtype=jnp.int32), 0))

    def body(r, acc):
        row = lax.dynamic_slice_in_dim(dense_impact,
                                       jnp.maximum(qrows[r], 0), 1, axis=0)
        return step(acc, r, row[0])

    return lax.fori_loop(0, last, body, init)


def _dense_row_score(dense_impact, qrows, qrw):
    """``Σ_r qrw[r] · row_r`` in f32, summed in ascending r (a bf16 block
    is upcast a row at a time). f32[D]."""
    return _fold_dense_rows(
        dense_impact, qrows, jnp.zeros(dense_impact.shape[1], jnp.float32),
        lambda acc, r, row: acc + qrw[r] * row.astype(jnp.float32))


def _dense_row_count(dense_impact, qrows):
    """i32[D]: how many of the query's dense rows (padding excluded) have
    a non-zero impact at each doc."""
    return _fold_dense_rows(
        dense_impact, qrows, jnp.zeros(dense_impact.shape[1], jnp.int32),
        lambda acc, r, row: acc + ((row != 0)
                                   & (qrows[r] >= 0)).astype(jnp.int32))


def _dense_row_mask(dense_impact, qrows):
    """bool[D]: docs where any of the query's dense rows is non-zero."""
    return _fold_dense_rows(
        dense_impact, qrows, jnp.zeros(dense_impact.shape[1], bool),
        lambda acc, r, row: acc | ((row != 0) & (qrows[r] >= 0)))


@partial(jax.jit, static_argnames=("P", "D"))
def bm25_score_hybrid_gather(dense_impact, qrows, qrw, doc_ids, tfnorm,
                             starts, lens, weights, *, P: int, D: int):
    """Single-query hybrid BM25 reading ONLY the query's dense rows.

    ``qrows`` i32[R] are the query's dense-row indices (-1 padding),
    ``qrw`` f32[R] the matching idf*boost weights (0 padding). A matmul
    ``qw[F] @ impact[F, D]`` reads the WHOLE block per query — 1 GiB at
    F 64, D 2^22 — where this reads the query's real rows one by one
    (:func:`_fold_dense_rows`: no ``[R, D]`` copy, no
    read for a padding row). Measured on a v5e at that shape (PERF.md
    §6, PR 28): 0.19 ms a real row — its 8-row tile group, 134 MB — so
    ~0.4 ms for the average query's 2 rows, where the
    ``dense_impact[rows]`` + einsum form this replaced took ~3 ms
    whatever the query. The dense part is the in-order f32 sum
    ``Σ_r qrw[r]·row_r`` — plain f32 multiplies and adds, so it agrees
    with the batched matmul's multi-pass emulation to fp rounding."""
    dense = _dense_row_score(dense_impact, qrows, qrw)
    return dense + bm25_score_segment(doc_ids, tfnorm, starts, lens,
                                      weights, P=P, D=D)


DENSE_ROW_PAD = 8  # kernel sublane multiple; pack_dense_rows pads R to it


def pack_dense_rows(row_w: dict):
    """(qrows i32[R], qrw f32[R]) from {dense_row: weight}: sorted rows,
    -1/0 padding, R = pow2(len) >= DENSE_ROW_PAD. ONE definition for the
    host path (context.hybrid_slices) and the mesh prim
    (compiler.HybridTGroupPrim) — the padding sentinel and alignment
    multiple must never diverge between them."""
    from elasticsearch_tpu.utils.shapes import pow2_bucket

    R = pow2_bucket(max(len(row_w), 1), minimum=DENSE_ROW_PAD)
    qrows = np.full(R, -1, np.int32)
    qrw = np.zeros(R, np.float32)
    for i, (row, w) in enumerate(sorted(row_w.items())):
        qrows[i] = row
        qrw[i] = w
    return qrows, qrw


@partial(jax.jit, static_argnames=("P", "D"))
def match_count_hybrid_gather(dense_impact, qrows, doc_ids, starts, lens,
                              *, P: int, D: int):
    """Matched-term count: the query's dense rows with a non-zero impact
    (padding rows are masked by qrows >= 0) + the scatter tail's count.
    Only conjunctive queries (operator:and / minimum_should_match) pay
    for this second pass over the rows — disjunctions derive their mask
    from scores directly."""
    return (_dense_row_count(dense_impact, qrows)
            + match_count_segment(doc_ids, starts, lens, P=P, D=D))


@partial(jax.jit, static_argnames=("P", "D"))
def term_mask_hybrid_gather(dense_impact, qrows, doc_ids, starts, lens,
                            *, P: int, D: int):
    """bool[D] any-of mask across the query's dense rows + CSR tail."""
    return (_dense_row_mask(dense_impact, qrows)
            | term_mask(doc_ids, starts, lens, P=P, D=D))


@partial(jax.jit, static_argnames=("P", "D"))
def bm25_score_hybrid_batch(
    dense_impact, qw, doc_ids, tfnorm, starts, lens, weights, *, P: int,
    D: int
):
    """Batched hybrid BM25: ONE MXU matmul ``qw[Q, F] @ impact[F, D]`` for
    frequent terms (replacing what would be millions of scatter-adds for long
    postings runs) + the scatter kernel on the [Q, T] tail. Returns f32[Q, D]."""
    dense = _dense_dot(qw, dense_impact)
    return dense + bm25_score_batch(doc_ids, tfnorm, starts, lens, weights, P=P, D=D)


@partial(jax.jit, static_argnames=("P", "D", "k", "topk_block"))
def bm25_hybrid_topk_batch(dense_impact, qw, doc_ids, tfnorm, starts, lens,
                           weights, live, *, P: int, D: int, k: int,
                           topk_block: int = 0):
    """Batched hybrid top-k: scores via bm25_score_hybrid_batch, then the
    per-query masked top-k and exact totals in the SAME program, so the
    [Q, D] score block never leaves the device. For all-positive
    disjunctive term groups, score > 0 is exactly 'matched'. Returns
    (vals f32[Q, k], idx i32[Q, k], totals i32[Q])."""
    scores = bm25_score_hybrid_batch(dense_impact, qw, doc_ids, tfnorm,
                                     starts, lens, weights, P=P, D=D)
    m = (scores > 0) & live[None, :]
    masked = jnp.where(m, scores, NEG_INF)
    vals, idx = topk_auto(masked, k, topk_block)
    return vals, idx.astype(jnp.int32), jnp.sum(m.astype(jnp.int32), axis=1)


@partial(jax.jit, static_argnames=("chunk",))
def dense_presence_count_batch(impact, qind, live, *, chunk: int):
    """Batched exact hit counts: i32[Q] docs where any dense query row has
    non-zero impact, ANDed with live. Sweeps D in `chunk`-wide slices so the
    [Q, D] presence matrix never materializes (Q=2048, D=1M would be 8 GB).
    Caller picks chunk with D % chunk == 0."""
    D = impact.shape[1]
    Q = qind.shape[0]

    def body(i, acc):
        sl = lax.dynamic_slice_in_dim(impact, i * chunk, chunk, axis=1)
        lv = lax.dynamic_slice_in_dim(live, i * chunk, chunk)
        pres = (jnp.dot(qind, (sl != 0).astype(jnp.float32),
                        precision=lax.Precision.DEFAULT) > 0) & lv[None, :]
        return acc + jnp.sum(pres.astype(jnp.int32), axis=1)

    return lax.fori_loop(0, D // chunk, body, jnp.zeros(Q, jnp.int32))


@partial(jax.jit, static_argnames=("P", "D"))
def match_count_segment(doc_ids, starts, lens, *, P: int, D: int):
    """Count of matching query *terms* per doc. Each doc id occurs at most
    once in a term's postings run, so even when a term is split into several
    (start, len) chunks a matching doc is counted exactly once for that term
    — the result equals the number of distinct matched terms. Executors
    compare against the number of distinct query terms (operator:and /
    minimum_should_match), NOT against T (the chunk count). Returns i32[D]."""
    ones = jnp.ones_like(starts, dtype=jnp.float32)

    def per_chunk(start, length, w):
        docs, _, valid = _slice_postings(doc_ids, doc_ids.astype(jnp.float32), start, length, P)
        return docs, jnp.where(valid, w, 0.0)

    docs, contrib = jax.vmap(per_chunk)(starts, lens, ones)
    counts = jnp.zeros(D, dtype=jnp.float32)
    counts = counts.at[docs.reshape(-1)].add(contrib.reshape(-1), mode="drop")
    return counts.astype(jnp.int32)


@partial(jax.jit, static_argnames=("P", "D"))
def term_mask(doc_ids, starts, lens, *, P: int, D: int):
    """bool[D] mask of docs containing ANY of the T postings chunks
    (a terms filter; a single term is T=1)."""

    def per_chunk(start, length):
        docs, _, valid = _slice_postings(doc_ids, doc_ids.astype(jnp.float32), start, length, P)
        return docs, valid

    docs, valid = jax.vmap(per_chunk)(starts, lens)
    mask = jnp.zeros(D, dtype=bool)
    mask = mask.at[docs.reshape(-1)].max(valid.reshape(-1), mode="drop")
    return mask


# ---------------------------------------------------------------------------
# scatter-free hybrid top-k (candidate-set tail)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("P", "D", "k", "topk_block"))
def bm25_hybrid_candidates_topk(dense_impact, qrows, qrw, doc_ids, tfnorm,
                                starts, lens, weights, live, *, P: int,
                                D: int, k: int, topk_block: int = 0):
    """Exact hybrid BM25 top-k with NO scatter anywhere.

    The [D]-vector tail construction (`bm25_score_segment`) is a
    scatter-add — on TPU, XLA lowers non-trivial scatters to a
    sequential read-modify-write loop (~2 µs/slot), so a T×P padded
    window costs tens of ms per query regardless of how few postings are
    real. This computes the same top-k Lucene-style instead: only the
    docs the tail actually TOUCHES are scored.

      1. dense[D] = Σ_r qrw[r]·impact[qrows[r]]   (row reads, no scatter)
      2. tail windows → (doc, contrib) pairs [W = T·P], sort by doc
         (vectorized bitonic), segment-sum equal-doc runs via cumsum
      3. tail candidates = run ends; their TOTAL score adds dense[doc]
         via a W-element gather
      4. merge with the dense-only blocked top-k; a doc in both sets
         keeps the tail entry (its total includes the dense part, the
         dense-only entry doesn't) — dedup by id-match mask
      5. exact totals = |dense>0 ∧ live| + |tail runs with dense==0 ∧
         live ∧ contrib>0|

    Tie order matches the scatter path's `lax.top_k` over the dense
    row: the final merge sorts by (-score, doc id). Returns
    (vals f32[k], idx i32[k], total i32).
    """
    # 1. dense scores (the query's rows only), masked
    dense = _dense_row_score(dense_impact, qrows, qrw)
    dense_m = jnp.where(live, dense, 0.0)

    # 2. tail windows → flat (doc, contrib); padding → doc D, contrib 0
    def per_chunk(start, length, w):
        docs, tfn, valid = _slice_postings(doc_ids, tfnorm, start, length, P)
        return jnp.where(valid, docs, D), jnp.where(valid, tfn * w, 0.0)

    T = starts.shape[0]
    dws, contrib = jax.vmap(per_chunk)(starts, lens, weights)
    dws = dws.reshape(-1)
    contrib = contrib.reshape(-1)
    # sort by doc id; padding (doc D) sorts to the tail
    dws, contrib = lax.sort((dws, contrib), num_keys=1)
    # segment-sum runs of equal doc, EXACTLY: a doc appears at most once
    # per tail term (chunk-split slices are disjoint), so run length <= T
    # (static) and T-1 shifted adds sum each run in-order in f32 — no
    # cumsum-difference cancellation across the 32k window
    totals_at = contrib
    for j in range(1, T):
        same = jnp.concatenate([jnp.zeros((j,), bool),
                                dws[j:] == dws[:-j]])
        totals_at = totals_at + jnp.where(
            same, jnp.concatenate([jnp.zeros((j,), contrib.dtype),
                                   contrib[:-j]]), 0.0)
    is_end = jnp.concatenate([dws[1:] != dws[:-1], jnp.ones((1,), bool)])
    valid_end = is_end & (dws < D)
    tail_total = jnp.where(valid_end, totals_at, 0.0)

    # 3. add the dense part + live mask at the touched docs
    docs_c = jnp.minimum(dws, D - 1)
    dense_at = dense_m[docs_c]
    live_at = live[docs_c]
    cand_score = jnp.where(valid_end & live_at, tail_total + dense_at,
                           NEG_INF)

    # 4. dense-only top-k (docs the tail may not touch)
    dmasked = jnp.where(live & (dense > 0), dense, NEG_INF)
    dv, di = topk_auto(dmasked, k, topk_block)
    # drop dense-only entries whose doc also appears as a tail candidate
    # (the tail entry holds the doc's FULL score)
    dup = jnp.any((di[:, None] == docs_c[None, :])
                  & valid_end[None, :], axis=1)
    dv = jnp.where(dup, NEG_INF, dv)
    all_v = jnp.concatenate([dv, cand_score])
    all_i = jnp.concatenate([di, docs_c])
    # positives only (score > 0 is the match contract); exact tie order:
    # sort candidates by id ascending, then a stable value top_k
    all_v = jnp.where(all_v > 0, all_v, NEG_INF)
    order = jnp.argsort(all_i)
    sv = all_v[order]
    si = all_i[order]
    vals, pos = lax.top_k(sv, k)
    idx = si[pos]

    # 5. exact totals
    n_dense = jnp.sum((dense_m > 0).astype(jnp.int32))
    tail_only = valid_end & live_at & (tail_total > 0) & (dense_at <= 0)
    total = n_dense + jnp.sum(tail_only.astype(jnp.int32))
    return vals, idx.astype(jnp.int32), total


# -- scatter-free [D]-vector tail (lookup form) ------------------------------
#
# For COMPOSED queries (bool/filter trees) the emit contract is a dense
# f32[D]/bool[D] — the candidate-set trick can't apply. This builds the
# same vectors without scatter: sort the (doc, contrib) window pairs,
# binary-search the D+1 bin boundaries (vectorized; the window table is
# VMEM-small), and sum each doc's <= T entries with T bounded gathers —
# exact, in-order f32. Counts and masks fall out of the boundary diffs
# directly (window docs are unique per term, so entries-per-doc IS the
# distinct matched-term count).

def _sorted_window_pairs(doc_ids, tfnorm, starts, lens, weights, P, D):
    def per_chunk(start, length, w):
        docs, tfn, valid = _slice_postings(doc_ids, tfnorm, start, length, P)
        return jnp.where(valid, docs, D), jnp.where(valid, tfn * w, 0.0)

    dws, contrib = jax.vmap(per_chunk)(starts, lens, weights)
    return lax.sort((dws.reshape(-1), contrib.reshape(-1)), num_keys=1)


def _tail_bounds(dws, D):
    bounds = jnp.searchsorted(dws, jnp.arange(D + 1, dtype=dws.dtype))
    return bounds[:-1], bounds[1:] - bounds[:-1]  # (lo [D], n [D])


@partial(jax.jit, static_argnames=("P", "D"))
def bm25_score_segment_lookup(doc_ids, tfnorm, starts, lens, weights, *,
                              P: int, D: int):
    """Scatter-free equivalent of bm25_score_segment (same f32[D])."""
    T = starts.shape[0]
    dws, contrib = _sorted_window_pairs(doc_ids, tfnorm, starts, lens,
                                        weights, P, D)
    lo, n = _tail_bounds(dws, D)
    W = dws.shape[0]
    score = jnp.zeros(D, jnp.float32)
    for t in range(T):  # run length <= T terms: exact in-order sums
        score = score + jnp.where(
            t < n, contrib[jnp.clip(lo + t, 0, W - 1)], 0.0)
    return score


def _sorted_window_docs(doc_ids, starts, lens, P, D):
    """Keys-only variant: the sorted window doc ids (no payload sort)."""
    def per_chunk(start, length):
        docs, _pay, valid = _slice_postings(doc_ids, doc_ids, start,
                                            length, P)
        return jnp.where(valid, docs, D)

    dws = jax.vmap(per_chunk)(starts, lens)
    return jnp.sort(dws.reshape(-1))


@partial(jax.jit, static_argnames=("P", "D"))
def match_count_segment_lookup(doc_ids, starts, lens, *, P: int, D: int):
    """Scatter-free distinct matched-term counts (i32[D]): window docs
    are unique per term, so entries-per-doc IS the distinct count."""
    dws = _sorted_window_docs(doc_ids, starts, lens, P, D)
    _, n = _tail_bounds(dws, D)
    return n.astype(jnp.int32)


@partial(jax.jit, static_argnames=("P", "D"))
def term_mask_lookup(doc_ids, starts, lens, *, P: int, D: int):
    """Scatter-free any-term mask (bool[D])."""
    return match_count_segment_lookup(doc_ids, starts, lens, P=P, D=D) > 0


@partial(jax.jit, static_argnames=("P", "D"))
def bm25_score_hybrid_lookup(dense_impact, qrows, qrw, doc_ids, tfnorm,
                             starts, lens, weights, *, P: int, D: int):
    """Dense rows + lookup tail (scatter-free hybrid scores)."""
    dense = _dense_row_score(dense_impact, qrows, qrw)
    return dense + bm25_score_segment_lookup(doc_ids, tfnorm, starts,
                                             lens, weights, P=P, D=D)


@partial(jax.jit, static_argnames=("P", "D"))
def match_count_hybrid_lookup(dense_impact, qrows, doc_ids, starts, lens,
                              *, P: int, D: int):
    """Dense-row presence + lookup tail counts (scatter-free)."""
    return (_dense_row_count(dense_impact, qrows)
            + match_count_segment_lookup(doc_ids, starts, lens, P=P, D=D))


@partial(jax.jit, static_argnames=("P", "D"))
def term_mask_hybrid_lookup(dense_impact, qrows, doc_ids, starts, lens,
                            *, P: int, D: int):
    """Dense-row presence | lookup tail mask (scatter-free)."""
    return (_dense_row_mask(dense_impact, qrows)
            | term_mask_lookup(doc_ids, starts, lens, P=P, D=D))


@partial(jax.jit, static_argnames=("P", "D", "k", "topk_block"))
def bm25_hybrid_candidates_topk_batch(dense_impact, qw, doc_ids, tfnorm,
                                      starts, lens, weights, live, *,
                                      P: int, D: int, k: int,
                                      topk_block: int = 0):
    """Batched hybrid top-k with a scatter-free tail (batch analogue of
    bm25_hybrid_candidates_topk; same contract as bm25_hybrid_topk_batch).

    Dense terms keep the ONE amortized matmul ``qw[Q, F] @ impact[F, D]``
    (a batch reads the block once — the row-gather trick is a
    single-query lever); the tail replaces the vmapped scatter-add —
    which XLA serializes per element on TPU, Q·T·P slots per batch —
    with per-row sort + bounded-window segment-sum + gathers, all
    vectorized. Returns (vals [Q, k], idx [Q, k], totals [Q]).
    """
    Q, T = starts.shape
    dense = _dense_dot(qw, dense_impact)  # [Q, D]
    dense_m = jnp.where(live[None, :], dense, 0.0)

    def window(starts_q, lens_q, ws_q):
        def per_chunk(start, length, w):
            docs, tfn, valid = _slice_postings(doc_ids, tfnorm, start,
                                               length, P)
            return jnp.where(valid, docs, D), jnp.where(valid, tfn * w, 0.0)

        dws, contrib = jax.vmap(per_chunk)(starts_q, lens_q, ws_q)
        return dws.reshape(-1), contrib.reshape(-1)

    dws, contrib = jax.vmap(window)(starts, lens, weights)  # [Q, W]
    dws, contrib = lax.sort((dws, contrib), dimension=1, num_keys=1)
    totals_at = contrib
    for j in range(1, T):  # run length <= T: exact in-order f32 sums
        same = jnp.concatenate(
            [jnp.zeros((Q, j), bool), dws[:, j:] == dws[:, :-j]], axis=1)
        totals_at = totals_at + jnp.where(
            same, jnp.concatenate([jnp.zeros((Q, j), contrib.dtype),
                                   contrib[:, :-j]], axis=1), 0.0)
    is_end = jnp.concatenate([dws[:, 1:] != dws[:, :-1],
                              jnp.ones((Q, 1), bool)], axis=1)
    valid_end = is_end & (dws < D)
    tail_total = jnp.where(valid_end, totals_at, 0.0)
    docs_c = jnp.minimum(dws, D - 1)
    dense_at = jnp.take_along_axis(dense_m, docs_c, axis=1)  # [Q, W]
    live_at = live[docs_c]
    cand_score = jnp.where(valid_end & live_at, tail_total + dense_at,
                           NEG_INF)

    dmasked = jnp.where(live[None, :] & (dense > 0), dense, NEG_INF)
    dv, di = topk_auto(dmasked, k, topk_block)  # [Q, k]
    dup = jnp.any((di[:, :, None] == docs_c[:, None, :])
                  & valid_end[:, None, :], axis=2)
    dv = jnp.where(dup, NEG_INF, dv)
    all_v = jnp.concatenate([dv, cand_score], axis=1)
    all_i = jnp.concatenate([di, docs_c], axis=1)
    all_v = jnp.where(all_v > 0, all_v, NEG_INF)
    order = jnp.argsort(all_i, axis=1)
    sv = jnp.take_along_axis(all_v, order, axis=1)
    si = jnp.take_along_axis(all_i, order, axis=1)
    vals, pos = lax.top_k(sv, k)
    idx = jnp.take_along_axis(si, pos, axis=1)

    n_dense = jnp.sum((dense_m > 0).astype(jnp.int32), axis=1)
    tail_only = valid_end & live_at & (tail_total > 0) & (dense_at <= 0)
    totals = n_dense + jnp.sum(tail_only.astype(jnp.int32), axis=1)
    return vals, idx.astype(jnp.int32), totals


def tail_mode_batch() -> bool:
    """True when batch paths should use the scatter-free candidate tail
    (same ESTPU_TAIL_MODE knob/platform default as the DSL fast path).
    Read eagerly by callers and passed through static dispatch."""
    mode = os.environ.get("ESTPU_TAIL_MODE", "auto").lower()
    if mode in ("candidates", "scatter"):
        return mode == "candidates"
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# doc-value masks
# ---------------------------------------------------------------------------

@jax.jit
def range_mask_f32(values, exists, lo, hi, include_lo, include_hi):
    """Range filter over an f32 column. lo/hi are f32 scalars (±inf for open)."""
    ge = jnp.where(include_lo, values >= lo, values > lo)
    le = jnp.where(include_hi, values <= hi, values < hi)
    return ge & le & exists


@jax.jit
def range_mask_i64pair(hi_col, lo_col, exists, lo_hi, lo_lo, hi_hi, hi_lo, include_lo, include_hi):
    """Exact 64-bit range over (hi, lo) int32 pair columns (lexicographic)."""
    def ge_pair(ah, al, bh, bl):
        return (ah > bh) | ((ah == bh) & (al >= bl))

    def gt_pair(ah, al, bh, bl):
        return (ah > bh) | ((ah == bh) & (al > bl))

    ge = jnp.where(include_lo, ge_pair(hi_col, lo_col, lo_hi, lo_lo), gt_pair(hi_col, lo_col, lo_hi, lo_lo))
    le = jnp.where(include_hi, ge_pair(hi_hi, hi_lo, hi_col, lo_col), gt_pair(hi_hi, hi_lo, hi_col, lo_col))
    return ge & le & exists


# ---------------------------------------------------------------------------
# top-k
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("k", "topk_block"))
def _topk_with_mask_jit(scores, mask, *, k: int, topk_block: int):
    masked = jnp.where(mask, scores, NEG_INF)
    vals, idx = topk_auto(masked, k, topk_block)
    return vals, idx.astype(jnp.int32)


def topk_with_mask(scores, mask, *, k: int):
    """(values f32[k], indices i32[k]) of the top-k masked scores.
    Masked-out docs get -inf; callers treat -inf as 'no hit'. Eager
    wrapper: the blocked-top-k knob is read here, OUTSIDE jit, and enters
    the cache key as a static arg — callers need no plumbing."""
    return _topk_with_mask_jit(scores, mask, k=k,
                               topk_block=topk_block_config())


def topk_batch(scores, mask, *, k: int):
    """Batched: scores [Q, D], mask [D] or [Q, D] → ([Q,k], [Q,k])."""
    return _topk_with_mask_jit(scores, mask, k=k,
                               topk_block=topk_block_config())


def dense_topk_batch(qw, dense_impact, live, *, k: int, chunk_q: int = 256):
    """Top-k of a batch of all-dense term groups: ``qw[Q, F] @
    impact[F, D]`` → live mask → top-k, Q swept in ``chunk_q`` slices so
    the transient [chunk, D] score block stays bounded (Q=2048, D=1M in
    one piece would be 8 GB). Non-live docs carry -inf. Returns
    (vals f32[Q, k], idx i32[Q, k])."""
    outs = [topk_batch(_dense_dot(qw[q0:q0 + chunk_q], dense_impact), live,
                       k=k)
            for q0 in range(0, qw.shape[0], chunk_q)]
    return (jnp.concatenate([v for v, _ in outs], axis=0),
            jnp.concatenate([i for _, i in outs], axis=0))


@jax.jit
def count_mask(mask):
    return jnp.sum(mask.astype(jnp.int32))


@jax.jit
def pack_topk_result(vals, idx, total):
    """Pack (vals f32[k], idx i32[k], total i32) into ONE i32[2k+1] array.

    Device→host pulls pay a fixed per-ARRAY latency (network-attached
    chips: ~5-20 ms each); fetching three tiny arrays costs three round
    trips. Bitcasting the f32 scores into the i32 payload makes the whole
    query result one transfer; hosts un-bitcast with np.view (exact)."""
    return jnp.concatenate([
        lax.bitcast_convert_type(vals, jnp.int32),
        idx.astype(jnp.int32),
        jnp.asarray(total, jnp.int32)[None],
    ])


def unpack_topk_result(packed_np, k: int):
    """np i32[2k+1] → (vals f32[k], idx i32[k], total int)."""
    import numpy as np

    vals = packed_np[:k].view(np.float32)
    idx = packed_np[k: 2 * k]
    return vals, idx, int(packed_np[-1])


# ---------------------------------------------------------------------------
# one program a search segment (the host loop's query phase)
# ---------------------------------------------------------------------------

@jax.jit
def hit_mask(scores, mask, live, roots=None, min_score=None):
    """The query phase's mask rule, written once: ``mask & live (& roots)
    (& scores >= min_score)`` and its exact count.

    ``roots`` (bool[D], a segment with nested documents: top-level hits
    are root docs only — nested children are reachable solely through
    nested queries/aggs, like Lucene's block-join) and ``min_score`` (f32
    scalar) are None when the request has none; being there or not is
    part of the program's key, their values are not. Returns
    (mask bool[D], total i32)."""
    mask = mask & live
    if roots is not None:
        mask = mask & roots
    if min_score is not None:
        mask = mask & (scores >= min_score)
    return mask, jnp.sum(mask.astype(jnp.int32))


@partial(jax.jit, static_argnames=("k", "topk_block", "with_mask"))
def finish_topk(scores, mask, live, roots=None, min_score=None, *, k: int,
                topk_block: int, with_mask: bool = False):
    """Everything the query phase does after a query's score program, as
    ONE program: :func:`hit_mask` → masked top-k → the packed i32[2k+1]
    of :func:`pack_topk_result`. Run eagerly these are five or six
    enqueues a segment, each leaving and re-taking the interpreter's lock.

    Returns (packed, final mask when ``with_mask`` — aggregations collect
    over it — else None). Composed of the staged jits themselves, so
    values, indices and tie order are theirs bit for bit."""
    mask, total = hit_mask(scores, mask, live, roots, min_score)
    vals, idx = _topk_with_mask_jit(scores, mask, k=k, topk_block=topk_block)
    return pack_topk_result(vals, idx, total), (mask if with_mask else None)


def pack_term_group_words(qrows, qrw, starts, lens, ws):
    """A term group's small host tables as ONE i32 word buffer
    ``qrows | qrw | starts | lens | ws`` (floats reinterpreted, all
    4-byte): one host→device copy a search where five numpy arguments
    were five. ``qrows``/``qrw`` are None for a scatter-only group.
    :func:`bm25_term_group_topk` slices it back at static offsets."""
    parts = [starts, lens, ws.view(np.int32)]
    if qrows is not None:
        parts = [qrows, qrw.view(np.int32)] + parts
    return np.concatenate(parts)


@partial(jax.jit, static_argnames=("R", "T", "P", "D", "k", "topk_block"))
def bm25_term_group_topk(dense_impact, doc_ids, tfnorm, live, roots, words,
                         *, R: int, T: int, P: int, D: int, k: int,
                         topk_block: int):
    """A pure disjunctive term group, scored and finished in ONE program
    fed by ONE packed argument: ``finish_topk ∘ score``.

    ``words`` i32[2R + 3T] is :func:`pack_term_group_words`' buffer;
    ``R`` > 0 (with ``dense_impact``) scores through
    :func:`bm25_score_hybrid_gather`, ``R`` = 0 (``dense_impact`` None)
    through :func:`bm25_score_segment`; with all-positive weights
    ``scores > 0`` is the match mask. The same traced functions in the
    same order as the staged sequence, so every score and tie is the
    same. Returns the packed i32[2k+1]."""
    f32 = partial(lax.bitcast_convert_type, new_dtype=jnp.float32)
    tail = words[2 * R:]
    starts, lens, ws = tail[:T], tail[T: 2 * T], f32(tail[2 * T:])
    if R:
        scores = bm25_score_hybrid_gather(
            dense_impact, words[:R], f32(words[R: 2 * R]), doc_ids, tfnorm,
            starts, lens, ws, P=P, D=D)
    else:
        scores = bm25_score_segment(doc_ids, tfnorm, starts, lens, ws,
                                    P=P, D=D)
    return finish_topk(scores, scores > 0, live, roots, k=k,
                       topk_block=topk_block)[0]


def merge_shard_topk(all_packed, *, k: int):
    """The global top-k of S shards' packed results, by (score desc,
    shard, local doc): ``all_packed`` i32[S, 2k+1] is every shard's
    :func:`pack_topk_result` (an ``all_gather`` of them inside the sharded
    program). Returns ONE i32[3k + S]: ``scores | shard | local doc |
    the shards' totals``. Only selects: a score leaves as the bits it
    came in with; non-hits (-inf) sort last."""
    S = all_packed.shape[0]
    vals = lax.bitcast_convert_type(all_packed[:, :k], jnp.float32)
    shard = lax.broadcasted_iota(jnp.int32, (S, k), 0)
    neg, shard, local = lax.sort(
        (-vals.reshape(-1), shard.reshape(-1),
         all_packed[:, k: 2 * k].reshape(-1)), num_keys=3)
    return jnp.concatenate([
        lax.bitcast_convert_type(-neg[:k], jnp.int32), shard[:k], local[:k],
        all_packed[:, 2 * k]])


def unpack_shard_topk(packed_np, k: int, S: int):
    """np i32[3k+S] → (vals f32[k], shard i32[k], local i32[k],
    totals i32[S])."""
    return (packed_np[:k].view(np.float32), packed_np[k: 2 * k],
            packed_np[2 * k: 3 * k], packed_np[3 * k: 3 * k + S])


# ---------------------------------------------------------------------------
# per-field segment reductions (aggregation building blocks)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("num_buckets",))
def bucket_sum(values, bucket_ids, weights, *, num_buckets: int):
    """segment-sum of values*weights into num_buckets (ordinal reductions)."""
    contrib = values * weights
    out = jnp.zeros(num_buckets, dtype=jnp.float32)
    return out.at[bucket_ids].add(contrib, mode="drop")


@partial(jax.jit, static_argnames=("num_buckets", "scatter_free"))
def _bucket_count_jit(bucket_ids, mask, *, num_buckets: int,
                      scatter_free: bool):
    # `mask` is a 0/1 SELECTION mask, never fractional weights: the
    # scatter-free branch is a selected-id histogram (sort + boundary
    # diffs — the len(ids)-element scatter serializes on TPU) and would
    # silently diverge from the scatter-add branch for any other value.
    # Weighted reductions belong in bucket_sum.
    if scatter_free:
        ids = jnp.where(mask > 0, bucket_ids, num_buckets)
        sids = jnp.sort(ids)
        bounds = jnp.searchsorted(
            sids, jnp.arange(num_buckets + 1, dtype=sids.dtype))
        return (bounds[1:] - bounds[:-1]).astype(jnp.float32)
    out = jnp.zeros(num_buckets, dtype=jnp.float32)
    return out.at[bucket_ids].add(mask, mode="drop")


def bucket_count(bucket_ids, mask, *, num_buckets: int):
    """Selected-id histogram. ``mask`` MUST be a 0/1 selection mask —
    the parameter is named to make a fractional-weights call read wrong
    at the call site (ADVICE r5: the TPU scatter-free branch computes a
    histogram, not a weighted sum, so non-mask values diverge from the
    CPU branch with no error). Eager wrapper: reads the platform
    scatter-free switch outside jit."""
    return _bucket_count_jit(bucket_ids, mask, num_buckets=num_buckets,
                             scatter_free=tail_mode_batch())
