"""Mesh query compiler: parsed query DSL tree → one shard_map program.

Reference: org/elasticsearch/action/search/type/
TransportSearchQueryThenFetchAction.java:1-148 — ES scatters the query to
every shard and merges per-shard top-k on the coordinating node. Here the
whole scatter/score/merge IS one XLA program over the ('shard',) mesh: this
module splits a parsed query tree into

  * a STATIC structure (the emit tree) — identical on every shard, baked
    into the traced shard_map body and cached per structure, and
  * per-shard DATA tables (postings chunk tables, column slabs, bound
    scalars, id bitmaps) — uploaded as [S, ...] arrays sharded over 'shard'.

Per-shard variability (shard-local vocabularies, idf, term-dict expansions,
column offsets) is *data*, never control flow, so a single trace serves all
shards. Queries outside the supported subset raise MeshCompileError and the
caller falls back to the host per-shard loop (mirroring how ES falls back
from query-then-fetch optimizations).

Supported: match_all/none, term, terms, match (or/and/minimum_should_match),
match_phrase (device positional program), range (numeric i64-exact + f32,
date, keyword via term expansion), exists, ids, prefix, wildcard, regexp,
fuzzy, bool, constant_score, filtered, dis_max, boosting, knn (brute
force), function_score (weight / field_value_factor / decay / random,
score_mode+boost_mode algebra). Sorting: numeric or keyword primary key
(global-ordinal preselect), multi-key via host full-tuple ordering.
Aggregations: terms-without-subs reduce fully on device. A size-0 tree
that search/aggregations/program.py serves on every segment (histogram
or fixed-interval date_histogram with metric subs, or metrics alone,
under a conjunction of ranges over coded columns) is declined here on
purpose: the host loop serves it as one ``agg_tree`` program a segment
with no [D] mask pulled to the host. Every other agg tree consumes this
program's match mask through the host collectors.
Still host-loop-only: spans, joins, geo, scripts, IVF knn, more_like_this,
query_string, fuzzy-match expansion.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from elasticsearch_tpu.utils.shapes import pow2_bucket


class MeshCompileError(Exception):
    """Query can't ride the mesh program. `by_design=True` marks paths
    that are INTENTIONALLY host-orchestrated (e.g. IVF probing) — the
    dispatch counters report them as `mesh_host_by_design`, not
    `mesh_fallback_total`, so the fallback==0 budget on product workloads
    keeps meaning 'should have ridden the mesh but could not'."""

    def __init__(self, msg: str, by_design: bool = False):
        super().__init__(msg)
        self.by_design = by_design


def _jnp():
    import jax.numpy as jnp

    return jnp


# ---------------------------------------------------------------------------
# data primitives: per-shard host arrays, stacked [S, ...] over the mesh
# ---------------------------------------------------------------------------

class DataPrim:
    """One device-input group. build() returns (arrays, static) where
    `arrays` is a list of np arrays with leading dim S and `static` is a
    hashable tuple of trace-affecting parameters (chunk window P, Vmax, …).
    Big immutable arrays go through `cache(key, fn)` keyed by segment ids."""

    n_arrays = 1

    def build(self, seg_row, ctxs, D: int, S: int, cache) -> Tuple[list, tuple]:
        raise NotImplementedError


class LivePrim(DataPrim):
    n_arrays = 1

    def build(self, seg_row, ctxs, D, S, cache):
        def fill():
            h = np.zeros((S, D), bool)
            for si, seg in enumerate(seg_row):
                if seg is not None:
                    lv = np.asarray(seg.live_host)
                    h[si, : lv.shape[0]] = lv
            return [h]

        # deletes invalidate via the deleted_count in the key — otherwise
        # the upload (a per-query device round-trip) reuses the cached copy
        key = ("live", tuple(id(s) for s in seg_row),
               tuple(s.deleted_count if s is not None else 0 for s in seg_row),
               D)
        return cache(key, fill), ()


class NumDocsPrim(DataPrim):
    n_arrays = 1

    def build(self, seg_row, ctxs, D, S, cache):
        def fill():
            return [np.asarray(
                [(s.num_docs if s is not None else 0) for s in seg_row],
                np.int32)]

        key = ("nd", tuple(id(s) for s in seg_row))
        return cache(key, fill), ()


def stacked_nnz(seg_row, field: str) -> int:
    """Length of the stacked [S, nnz] postings of one field: the pow2
    bucket of the longest shard's padded postings."""
    nnz = 1
    for seg in seg_row:
        inv = seg.inverted.get(field) if seg is not None else None
        if inv is not None:
            nnz = max(nnz, inv.nnz_pad)
    return pow2_bucket(nnz)


class PostingsPrim(DataPrim):
    """Stacked postings of one field: doc_ids [S, nnz] (pad → D sentinel),
    tfnorm [S, nnz]."""

    n_arrays = 2

    def __init__(self, field: str):
        self.field = field

    def build(self, seg_row, ctxs, D, S, cache):
        nnz = stacked_nnz(seg_row, self.field)

        def fill():
            h_doc = np.full((S, nnz), D, np.int32)
            h_tfn = np.zeros((S, nnz), np.float32)
            for si, seg in enumerate(seg_row):
                inv = seg.inverted.get(self.field) if seg is not None else None
                if inv is not None:
                    # host mirrors (never np.asarray(device): big d2h pulls
                    # degrade network-attached sessions)
                    d = (inv.doc_ids_host if inv.doc_ids_host is not None
                         else np.asarray(inv.doc_ids)[: inv.nnz])
                    h_doc[si, : d.shape[0]] = np.where(d >= seg.max_docs, D, d)
                    t = (inv.tfnorm_host if inv.tfnorm_host is not None
                         else np.asarray(inv.tfnorm)[: inv.nnz])
                    h_tfn[si, : t.shape[0]] = t
            return [h_doc, h_tfn]

        key = ("postings", self.field,
               tuple(id(s) for s in seg_row), nnz, D)
        return cache(key, fill), ()


class TGroupPrim(DataPrim):
    """Chunk tables for one term group: starts/lens/ws [S, T]. terms_fn(ctx)
    yields the (terms, weights) lists for that shard — per-shard idf and
    term-dict expansions resolve here, on host, as data."""

    n_arrays = 3

    def __init__(self, field: str, terms_fn: Callable):
        self.field = field
        self.terms_fn = terms_fn

    def build(self, seg_row, ctxs, D, S, cache):
        from elasticsearch_tpu.search.context import stack_chunk_tables

        per_shard = []
        for seg, ctx in zip(seg_row, ctxs):
            inv = seg.inverted.get(self.field) if seg is not None else None
            runs = []
            if inv is not None and ctx is not None:
                terms, weights = self.terms_fn(ctx)
                for t, w in zip(terms, weights):
                    s, ln = inv.term_slice(t)
                    runs.append((s, ln, w))
            per_shard.append(runs)
        h_starts, h_lens, h_ws, P = stack_chunk_tables(
            per_shard, stacked_nnz(seg_row, self.field))
        return [h_starts, h_lens, h_ws], (P,)


class HybridTGroupPrim(DataPrim):
    """Term group scored via the hybrid dense-impact path: the segment's
    frequent terms live as rows of an impact[F, D] block, the rare tail
    stays as (start, len) scatter chunks — the same split the host loop's
    ctx.hybrid_slices makes (ops/scoring.py:94).

    Arrays: impact [S, F, D] (stacked per-shard blocks, zero rows where a
    shard has no dense block — its terms all fall to the tail),
    qrows [S, R] / qrw [S, R] (the query's dense-row indices and idf*boost
    weights, -1/0 padded) — the DSL path is per-request (Q=1), so scoring
    reads only those rows instead of multiplying the whole block
    (ops/scoring._fold_dense_rows) — and starts/lens/ws [S, T]
    tail chunk tables. Per-shard F/dense_rows variability is data; the
    emit tree stays identical on every shard."""

    n_arrays = 6

    def __init__(self, field: str, terms_fn: Callable):
        self.field = field
        self.terms_fn = terms_fn

    def build(self, seg_row, ctxs, D, S, cache):
        from elasticsearch_tpu.search.context import stack_chunk_tables

        blocks = []
        F = 8
        for seg in seg_row:
            inv = seg.inverted.get(self.field) if seg is not None else None
            blk = inv.dense_block() if inv is not None else None
            blocks.append((inv, blk))
            if blk is not None:
                F = max(F, int(blk[1].shape[0]))

        def fill_impact():
            h = np.zeros((S, F, D), np.float32)
            for si, (inv_i, blk) in enumerate(blocks):
                if blk is not None:
                    imp = (inv_i._dense_host if inv_i._dense_host is not None
                           else np.asarray(blk[1]))
                    h[si, : imp.shape[0], : imp.shape[1]] = imp
            return [h]

        key = ("hyb_impact", self.field, tuple(id(s) for s in seg_row), F, D)
        arrays = list(cache(key, fill_impact))

        per_shard = []
        row_ws: List[Dict[int, float]] = []
        for si, ((inv, blk), ctx) in enumerate(zip(blocks, ctxs)):
            runs = []
            row_w: Dict[int, float] = {}
            if inv is not None and ctx is not None:
                terms, weights = self.terms_fn(ctx)
                dense_rows = blk[0] if blk is not None else None
                for t, w in zip(terms, weights):
                    tid = inv.term_id(t)
                    if tid < 0:
                        continue
                    row = int(dense_rows[tid]) if dense_rows is not None else -1
                    if row >= 0:
                        row_w[row] = row_w.get(row, 0.0) + w
                    else:
                        s0 = int(inv.offsets[tid])
                        runs.append((s0, int(inv.offsets[tid + 1]) - s0, w))
            per_shard.append(runs)
            row_ws.append(row_w)
        from elasticsearch_tpu.ops.scoring import pack_dense_rows

        # shared packing (ops/scoring.pack_dense_rows): per-shard R may
        # differ, so pack each then pad to the common pow2 R
        packed = [pack_dense_rows(rw) for rw in row_ws]
        R = max(p[0].shape[0] for p in packed)
        h_qrows = np.full((S, R), -1, np.int32)
        h_qrw = np.zeros((S, R), np.float32)
        for si, (qr, qv) in enumerate(packed):
            h_qrows[si, : qr.shape[0]] = qr
            h_qrw[si, : qv.shape[0]] = qv
        h_starts, h_lens, h_ws, P = stack_chunk_tables(
            per_shard, stacked_nnz(seg_row, self.field))
        return arrays + [h_qrows, h_qrw, h_starts, h_lens, h_ws], (P, R)


class RangePrim(DataPrim):
    """Numeric/date range: column slab + bounds. Emits the exact-i64 pair
    form when the column carries (hi, lo) int32 pairs and the bounds are
    integral (mirror of RangeQuery.execute), else the f32 form with
    per-shard offset-adjusted bounds."""

    def __init__(self, field: str, lo, hi, use_int: bool):
        self.field = field
        self.lo = lo
        self.hi = hi
        self.use_int = use_int

    def build(self, seg_row, ctxs, D, S, cache):
        cols = [(s.numerics.get(self.field) if s is not None else None)
                for s in seg_row]
        has_pair = any(c is not None and c.has_pair for c in cols)
        pair = has_pair and self.use_int
        if pair:
            def fill():
                h_hi = np.zeros((S, D), np.int32)
                h_lo = np.zeros((S, D), np.int32)
                h_ex = np.zeros((S, D), bool)
                from elasticsearch_tpu.index.segment import split_i64

                for si, c in enumerate(cols):
                    if c is not None and c.has_pair:
                        hi, lo = split_i64(c.exact)  # host, no d2h
                        h_hi[si, : hi.shape[0]] = hi
                        h_lo[si, : lo.shape[0]] = lo
                        ex = (c.exists_host if c.exists_host is not None
                              else np.asarray(c.exists))
                        h_ex[si, : ex.shape[0]] = ex
                return [h_hi, h_lo, h_ex]

            key = ("colpair", self.field, tuple(id(s) for s in seg_row), D)
            arrays = list(cache(key, fill))
            from elasticsearch_tpu.index.segment import split_i64

            lo_v = int(self.lo) if self.lo is not None else -(2 ** 63)
            hi_v = int(self.hi) if self.hi is not None else 2 ** 63 - 1
            (lhi,), (llo,) = split_i64(np.array([lo_v]))
            (hhi,), (hlo,) = split_i64(np.array([hi_v]))
            bounds = np.broadcast_to(
                np.asarray([lhi, llo, hhi, hlo], np.int32), (S, 4)).copy()
            arrays.append(bounds)
            return arrays, ("pair",)

        def fill():
            h_val = np.zeros((S, D), np.float32)
            h_ex = np.zeros((S, D), bool)
            for si, c in enumerate(cols):
                if c is not None:
                    v = ((c.exact - c.offset).astype(np.float32)
                         if c.exact is not None else np.asarray(c.values))
                    h_val[si, : v.shape[0]] = v
                    ex = (c.exists_host if c.exists_host is not None
                          else np.asarray(c.exists))
                    h_ex[si, : ex.shape[0]] = ex
            return [h_val, h_ex]

        key = ("colf32", self.field, tuple(id(s) for s in seg_row), D)
        arrays = list(cache(key, fill))
        bounds = np.zeros((S, 2), np.float32)
        for si, c in enumerate(cols):
            off = c.offset if c is not None else 0.0
            bounds[si, 0] = (float(self.lo) - off) if self.lo is not None else -np.inf
            bounds[si, 1] = (float(self.hi) - off) if self.hi is not None else np.inf
        arrays.append(bounds)
        return arrays, ("f32",)


class SortColPrim(DataPrim):
    """Sort-key column: values [S, D] f32 + exists [S, D] bool.

    Column values are stored offset-relative PER SEGMENT (offset = segment
    min, for f32 precision); ranking across shards needs one common scale,
    so each slot is rebased to the minimum offset of the row — magnitudes
    stay as small as the spread between segments allows."""

    n_arrays = 2

    def __init__(self, field: str):
        self.field = field

    def build(self, seg_row, ctxs, D, S, cache):
        cols = [(s.numerics.get(self.field) if s is not None else None)
                for s in seg_row]
        base = min((c.offset for c in cols if c is not None), default=0.0)

        def fill():
            h_val = np.zeros((S, D), np.float32)
            h_ex = np.zeros((S, D), bool)
            for si, c in enumerate(cols):
                if c is not None:
                    v = ((c.exact - c.offset).astype(np.float32)
                         if c.exact is not None
                         else np.asarray(c.values)) + np.float32(c.offset - base)
                    h_val[si, : v.shape[0]] = v
                    ex = (c.exists_host if c.exists_host is not None
                          else np.asarray(c.exists))
                    h_ex[si, : ex.shape[0]] = ex
            return [h_val, h_ex]

        key = ("sortcol", self.field, tuple(id(s) for s in seg_row), D)
        return cache(key, fill), ()


class SortOrdPrim(DataPrim):
    """Keyword sort key: per-shard ordinals are meaningless across shards
    (each segment's vocab is local), so the prim builds ONE global rank
    space on host — the sorted union of every shard's terms — and uploads
    each doc's global rank as f32. Exact string ordering still happens on
    host over the fetched values (mesh_service); this is the device
    preselect, exactly the role kw.ords plays in the host loop."""

    n_arrays = 2

    def __init__(self, field: str):
        self.field = field

    def build(self, seg_row, ctxs, D, S, cache):
        def fill():
            kws = [(s.keywords.get(self.field) if s is not None else None)
                   for s in seg_row]
            all_terms = sorted(set().union(
                *[set(s.inverted[self.field].terms)
                  if s is not None and self.field in s.inverted else set()
                  for s in seg_row]))
            rank_of = {t: i for i, t in enumerate(all_terms)}
            h_val = np.zeros((S, D), np.float32)
            h_ex = np.zeros((S, D), bool)
            for si, (seg, kw) in enumerate(zip(seg_row, kws)):
                if seg is None or kw is None:
                    continue
                terms = seg.inverted[self.field].terms
                local2global = np.asarray(
                    [rank_of[t] for t in terms] or [0], np.float32)
                ords = (kw.ords_host if kw.ords_host is not None
                        else np.asarray(kw.ords))
                h_val[si, : ords.shape[0]] = np.where(
                    ords >= 0, local2global[np.maximum(ords, 0)], 0.0)
                ex = (kw.exists_host if kw.exists_host is not None
                      else np.asarray(kw.exists))
                h_ex[si, : ex.shape[0]] = ex
            return [h_val, h_ex]

        key = ("sortord", self.field, tuple(id(s) for s in seg_row), D)
        return cache(key, fill), ()


class ExistsPrim(DataPrim):
    n_arrays = 1

    def __init__(self, field: str):
        self.field = field

    def build(self, seg_row, ctxs, D, S, cache):
        f = self.field

        def fill():
            h = np.zeros((S, D), bool)
            for si, seg in enumerate(seg_row):
                if seg is None:
                    continue
                # mirror ExistsQuery.execute resolution order
                if f in seg.numerics:
                    c = seg.numerics[f]
                    ex = (c.exists_host if c.exists_host is not None
                          else np.asarray(c.exists))
                elif f in seg.keywords:
                    kw = seg.keywords[f]
                    ex = (kw.exists_host if kw.exists_host is not None
                          else np.asarray(kw.exists))
                elif f in seg.vectors:
                    vc = seg.vectors[f]
                    ex = (vc.exists_host if vc.exists_host is not None
                          else np.asarray(vc.exists))
                elif f in seg.field_lengths:
                    ex = np.asarray(seg.field_lengths[f]) > 0
                elif f"{f}.lat" in seg.numerics:  # geo_point split columns
                    c = seg.numerics[f"{f}.lat"]
                    ex = (c.exists_host if c.exists_host is not None
                          else np.asarray(c.exists))
                elif f"{f}.__cells" in seg.keywords:  # geo_shape cell tokens
                    kw = seg.keywords[f"{f}.__cells"]
                    ex = (kw.exists_host if kw.exists_host is not None
                          else np.asarray(kw.exists))
                else:
                    continue
                h[si, : ex.shape[0]] = ex
            return [h]

        key = ("exists", f, tuple(id(s) for s in seg_row), D)
        return cache(key, fill), ()


class IdsPrim(DataPrim):
    n_arrays = 1

    def __init__(self, values: List[str]):
        self.values = [str(v) for v in values]

    def build(self, seg_row, ctxs, D, S, cache):
        h = np.zeros((S, D), bool)
        for si, seg in enumerate(seg_row):
            if seg is None:
                continue
            for doc_id in self.values:
                loc = seg.id_map.get(doc_id)
                if loc is not None:
                    h[si, loc] = True
        return [h], ()


class ColPrim(DataPrim):
    """Absolute-value numeric column: values+offset folded to f32 [S, D]
    (the same f32 arithmetic the host loop's function_score path does) +
    exists [S, D]."""

    n_arrays = 2

    def __init__(self, field: str):
        self.field = field

    def build(self, seg_row, ctxs, D, S, cache):
        def fill():
            h_val = np.zeros((S, D), np.float32)
            h_ex = np.zeros((S, D), bool)
            for si, seg in enumerate(seg_row):
                c = seg.numerics.get(self.field) if seg is not None else None
                if c is not None:
                    v = (c.exact.astype(np.float32) if c.exact is not None
                         else np.asarray(c.values) + np.float32(c.offset))
                    h_val[si, : v.shape[0]] = v
                    ex = (c.exists_host if c.exists_host is not None
                          else np.asarray(c.exists))
                    h_ex[si, : ex.shape[0]] = ex
            return [h_val, h_ex]

        key = ("colabs", self.field, tuple(id(s) for s in seg_row), D)
        return cache(key, fill), ()


class VecsPrim(DataPrim):
    """dense_vector slab for knn-as-query: vecs [S, D, dims] + exists
    [S, D] (cached per segment round) + the query vector broadcast
    [S, dims] (per-request data) + the stacked slab's stored row term
    [S, D] (ops/knn.knn_row_terms: built once on the device from the
    cached slab and cached beside it; a ``dot_product`` field has none
    and the group is three arrays)."""

    n_arrays = 4

    def __init__(self, field: str, qvec, metric: str):
        self.field = field
        self.qvec = np.asarray(qvec, np.float32)
        self.metric = metric

    def build(self, seg_row, ctxs, D, S, cache):
        dims = self.qvec.shape[0]

        def fill():
            h_vecs = np.zeros((S, D, dims), np.float32)
            h_ex = np.zeros((S, D), bool)
            for si, seg in enumerate(seg_row):
                vc = seg.vectors.get(self.field) if seg is not None else None
                if vc is not None:
                    v = (vc.vecs_host if vc.vecs_host is not None
                         else np.asarray(vc.vecs))
                    h_vecs[si, : v.shape[0]] = v
                    ex = (vc.exists_host if vc.exists_host is not None
                          else np.asarray(vc.exists))
                    h_ex[si, : ex.shape[0]] = ex
            return [h_vecs, h_ex]

        from elasticsearch_tpu.ops.knn import has_row_terms, knn_row_terms

        segs = tuple(id(s) for s in seg_row)
        arrays = list(cache(("vecs", self.field, segs, D, dims), fill))
        arrays.append(np.broadcast_to(self.qvec, (S, dims)).copy())
        if has_row_terms(self.metric):
            slab = arrays[0]
            arrays += cache(
                ("vec_terms", self.field, segs, D, dims, self.metric),
                lambda: [knn_row_terms(slab, metric=self.metric)])
        return arrays, (dims,)


class PhrasePrim(DataPrim):
    """Per-shard inputs of the anchor-entry positional program
    (ops/positional.py phrase_freq_program): anchors from the first query
    term's positional entries, padded doc runs + positional CSR of every
    other term, plus field lengths and (avg_len, idf_sum) scalars for
    BM25 phrase scoring. Shards missing a term (or positions entirely)
    contribute an all-invalid anchor block — no match, like the host
    loop's per-segment empty result."""

    n_arrays = 11

    def __init__(self, field: str, toks: List[Tuple[str, int]]):
        self.field = field
        self.toks = toks  # [(term, position)] — query-side, analyzer output

    def build(self, seg_row, ctxs, D, S, cache):
        M = len(self.toks) - 1
        per_shard = []
        A = R = 8
        NP = NE = 8
        for seg in seg_row:
            inv = seg.inverted.get(self.field) if seg is not None else None
            ok = (inv is not None and inv.positions is not None
                  and inv.doc_ids_host is not None
                  and all(inv.term_slice(t)[1] > 0 for t, _ in self.toks))
            per_shard.append((inv, ok))
            if ok:
                t0 = self.toks[0][0]
                s0, ln0 = inv.term_slice(t0)
                A = max(A, int(inv.pos_offsets[s0 + ln0]
                               - inv.pos_offsets[s0]))
                R = max(R, max(inv.term_slice(t)[1]
                               for t, _ in self.toks[1:]))
                NP = max(NP, int(inv.positions.shape[0]))
                NE = max(NE, int(inv.pos_offsets.shape[0]))
        A, R = pow2_bucket(A), pow2_bucket(R)
        NP, NE = pow2_bucket(NP), pow2_bucket(NE)

        def fill():
            h_adoc = np.full((S, A), D, np.int32)
            h_apos = np.zeros((S, A), np.int32)
            h_aval = np.zeros((S, A), bool)
            h_runs = np.full((S, M, R), D, np.int32)
            h_rstart = np.zeros((S, M), np.int32)
            h_rlen = np.zeros((S, M), np.int32)
            h_delta = np.zeros((S, M), np.int32)
            h_pos = np.zeros((S, NP), np.int32)
            h_offs = np.zeros((S, NE), np.int32)
            h_len = np.zeros((S, D), np.float32)
            d0 = self.toks[0][1]
            for si, ((inv, ok), ctx) in enumerate(zip(per_shard, ctxs)):
                if not ok or ctx is None:
                    continue
                counts = np.diff(inv.pos_offsets).astype(np.int64)
                doc_per_pos = np.repeat(
                    inv.doc_ids_host[: counts.shape[0]], counts)
                t0 = self.toks[0][0]
                s0, ln0 = inv.term_slice(t0)
                p_lo = int(inv.pos_offsets[s0])
                p_hi = int(inv.pos_offsets[s0 + ln0])
                n_anchor = p_hi - p_lo
                h_apos[si, :n_anchor] = inv.positions[p_lo:p_hi]
                h_adoc[si, :n_anchor] = doc_per_pos[p_lo:p_hi]
                h_aval[si, :n_anchor] = True
                for j, (t, d) in enumerate(self.toks[1:]):
                    s, ln = inv.term_slice(t)
                    h_runs[si, j, :ln] = inv.doc_ids_host[s: s + ln]
                    h_rstart[si, j] = s
                    h_rlen[si, j] = ln
                    h_delta[si, j] = d - d0
                npos = int(inv.positions.shape[0])
                h_pos[si, :npos] = inv.positions
                ne = int(inv.pos_offsets.shape[0])
                h_offs[si, :ne] = inv.pos_offsets
                h_offs[si, ne:] = inv.pos_offsets[-1]
                fl = ctx.segment.field_lengths.get(self.field)
                if fl is not None:
                    flv = np.asarray(fl)
                    h_len[si, : flv.shape[0]] = flv
            return [h_adoc, h_apos, h_aval, h_runs, h_rstart, h_rlen,
                    h_delta, h_pos, h_offs, h_len]

        key = ("phrase", self.field, tuple(t for t, _ in self.toks),
               tuple(d for _, d in self.toks),
               tuple(id(s) for s in seg_row), A, R, NP, NE, D)
        arrays = list(cache(key, fill))
        # idf depends on global_stats (dfs) — per-request, never cached
        h_stats = np.zeros((S, 2), np.float32)
        for si, ((inv, ok), ctx) in enumerate(zip(per_shard, ctxs)):
            if not ok or ctx is None:
                continue
            h_stats[si, 0] = inv.avg_len
            h_stats[si, 1] = sum(
                ctx.idf(self.field, t)
                for t in dict.fromkeys(t for t, _ in self.toks))
        arrays.append(h_stats)
        return arrays, (M,)


class AggTermsPrim(DataPrim):
    """Keyword terms-agg inputs: postings doc_ids/term_ids + per-shard real
    vocab size (mirrors TermsAggregator's postings-based multi-value-correct
    count)."""

    n_arrays = 3

    def __init__(self, field: str):
        self.field = field

    def build(self, seg_row, ctxs, D, S, cache):
        nnz, vmax = 1, 1
        for seg in seg_row:
            inv = seg.inverted.get(self.field) if seg is not None else None
            if inv is not None:
                nnz = max(nnz, inv.nnz_pad)
                vmax = max(vmax, inv.vocab_size)
        nnz = pow2_bucket(nnz)
        vmax = pow2_bucket(vmax)

        def fill():
            h_doc = np.zeros((S, nnz), np.int32)
            h_tid = np.full((S, nnz), vmax, np.int32)
            for si, seg in enumerate(seg_row):
                inv = seg.inverted.get(self.field) if seg is not None else None
                if inv is not None:
                    d = (inv.doc_ids_host if inv.doc_ids_host is not None
                         else np.asarray(inv.doc_ids)[: inv.nnz])
                    h_doc[si, : d.shape[0]] = np.clip(d, 0, D - 1)
                    # term ids reconstruct from the CSR df (postings are
                    # term-major) — no device pull
                    t = np.repeat(np.arange(inv.vocab_size, dtype=np.int32),
                                  inv.df)
                    h_tid[si, : t.shape[0]] = t
            return [h_doc, h_tid]

        key = ("aggterms", self.field, tuple(id(s) for s in seg_row), nnz, D, vmax)
        arrays = list(cache(key, fill))
        vreal = np.asarray(
            [(s.inverted[self.field].vocab_size
              if s is not None and self.field in s.inverted else 0)
             for s in seg_row], np.int32)
        arrays.append(vreal)
        return arrays, (vmax,)


# ---------------------------------------------------------------------------
# emit tree: static structure, traced once per structure+shape class
# ---------------------------------------------------------------------------

class Emit:
    boost: float = 1.0

    def key(self) -> tuple:
        raise NotImplementedError

    def ex(self, env, meta):
        """-> (scores f32[D] | None, mask bool[D]); mirrors Query.execute."""
        raise NotImplementedError

    def sm(self, env, meta):
        """mirrors Query.score_or_mask (filter-as-boost semantics)."""
        s, m = self.ex(env, meta)
        if s is None:
            s = m.astype(_jnp().float32) * self.boost
        return s, m


class EMatchAll(Emit):
    def __init__(self, boost: float, nd: int, D: int):
        self.boost = boost
        self.nd = nd
        self.D = D

    def key(self):
        return ("all", self.boost)

    def ex(self, env, meta):
        jnp = _jnp()
        mask = jnp.arange(self.D) < env[self.nd][0]
        return jnp.full(self.D, self.boost, jnp.float32) * mask, mask


class ENone(Emit):
    def __init__(self, D: int):
        self.D = D

    def key(self):
        return ("none",)

    def ex(self, env, meta):
        jnp = _jnp()
        return None, jnp.zeros(self.D, bool)


def _scatter_free(meta) -> bool:
    """The executor plumbs its scatter-vs-lookup choice (including the
    force_scatter insurance rebuild) through ``meta["_cfg"]``; emits used
    outside the executor fall back to the platform/env default."""
    cfg = meta.get("_cfg")
    if cfg is not None and "scatter_free" in cfg:
        return bool(cfg["scatter_free"])
    from elasticsearch_tpu.ops.scoring import tail_mode_batch

    return tail_mode_batch()


class ETermGroup(Emit):
    """mode 'scores': BM25 scores, mask = scores > 0 (all-positive weights).
    mode 'count_ge': conjunction — distinct matched terms >= n.
    mode 'mask': presence only (terms filter / expansions)."""

    def __init__(self, prim: int, post: int, mode: str, n: int, boost: float,
                 D: int):
        self.prim = prim
        self.post = post
        self.mode = mode
        self.n = n
        self.boost = boost
        self.D = D

    def key(self):
        return ("tg", self.mode, self.n, self.boost)

    def ex(self, env, meta):
        from elasticsearch_tpu.ops import scoring as S

        # trace-time switch, PLUMBED by the executor through meta["_cfg"]
        # (so its force_scatter insurance rebuild really does trace the
        # scatter forms; the program cache keys on the mode): the lookup
        # forms build the same [D] vectors without scatter, which XLA
        # serializes per slot on TPU
        lk = _scatter_free(meta)
        doc_ids, tfnorm = env[self.post]
        starts, lens, ws = env[self.prim]
        (P,) = meta[self.prim]
        if self.mode == "mask":
            fn = S.term_mask_lookup if lk else S.term_mask
            return None, fn(doc_ids, starts, lens, P=P, D=self.D)
        sfn = S.bm25_score_segment_lookup if lk else S.bm25_score_segment
        scores = sfn(doc_ids, tfnorm, starts, lens, ws, P=P, D=self.D)
        if self.mode == "count_ge":
            cfn = (S.match_count_segment_lookup if lk
                   else S.match_count_segment)
            counts = cfn(doc_ids, starts, lens, P=P, D=self.D)
            return scores, counts >= self.n
        return scores, scores > 0


class ETermGroupHybrid(Emit):
    """ETermGroup over the hybrid dense-impact path: a read of the
    query's dense rows + scatter for the tail (mirror of
    _score_term_group's hybrid branch — the per-request DSL path is Q=1,
    where reading the query's own rows beats multiplying the whole
    block; ops/scoring._fold_dense_rows has what a row costs). Same
    three modes as ETermGroup."""

    def __init__(self, prim: int, post: int, mode: str, n: int, boost: float,
                 D: int):
        self.prim = prim
        self.post = post
        self.mode = mode
        self.n = n
        self.boost = boost
        self.D = D

    def key(self):
        return ("tgh", self.mode, self.n, self.boost)

    def ex(self, env, meta):
        from elasticsearch_tpu.ops import scoring as S

        lk = _scatter_free(meta)  # plumbed via meta["_cfg"] (see ETermGroup)
        doc_ids, tfnorm = env[self.post]
        impact, qrows, qrw, starts, lens, ws = env[self.prim]
        (P, _R) = meta[self.prim]
        if self.mode == "mask":
            fn = (S.term_mask_hybrid_lookup if lk
                  else S.term_mask_hybrid_gather)
            return None, fn(impact, qrows, doc_ids, starts, lens,
                            P=P, D=self.D)
        sfn = (S.bm25_score_hybrid_lookup if lk
               else S.bm25_score_hybrid_gather)
        scores = sfn(impact, qrows, qrw, doc_ids, tfnorm, starts, lens,
                     ws, P=P, D=self.D)
        if self.mode == "count_ge":
            cfn = (S.match_count_hybrid_lookup if lk
                   else S.match_count_hybrid_gather)
            counts = cfn(impact, qrows, doc_ids, starts, lens,
                         P=P, D=self.D)
            return scores, counts >= self.n
        return scores, scores > 0


class ERange(Emit):
    def __init__(self, prim: int, ilo: bool, ihi: bool):
        self.prim = prim
        self.ilo = ilo
        self.ihi = ihi

    def key(self):
        return ("range", self.ilo, self.ihi, self.boost)

    def ex(self, env, meta):
        from elasticsearch_tpu.ops.scoring import range_mask_f32, range_mask_i64pair

        jnp = _jnp()
        (form,) = meta[self.prim]
        if form == "pair":
            hi_col, lo_col, exists, b = env[self.prim]
            mask = range_mask_i64pair(
                hi_col, lo_col, exists, b[0], b[1], b[2], b[3],
                jnp.bool_(self.ilo), jnp.bool_(self.ihi))
        else:
            values, exists, b = env[self.prim]
            mask = range_mask_f32(values, exists, b[0], b[1],
                                  jnp.bool_(self.ilo), jnp.bool_(self.ihi))
        return None, mask


class EMaskData(Emit):
    """Mask handed over as data (exists / ids)."""

    def __init__(self, prim: int, tag: str):
        self.prim = prim
        self.tag = tag

    def key(self):
        return (self.tag, self.boost)

    def ex(self, env, meta):
        return None, env[self.prim][0]


class EOr(Emit):
    """OR of child masks (numeric terms query)."""

    def __init__(self, children: List[Emit], D: int):
        self.children = children
        self.D = D

    def key(self):
        return ("or", self.boost) + tuple(c.key() for c in self.children)

    def ex(self, env, meta):
        jnp = _jnp()
        mask = jnp.zeros(self.D, bool)
        for c in self.children:
            _, m = c.ex(env, meta)
            mask = mask | m
        return None, mask


class EConstScore(Emit):
    def __init__(self, child: Emit, boost: float):
        self.child = child
        self.boost = boost

    def key(self):
        return ("const", self.boost, self.child.key())

    def ex(self, env, meta):
        jnp = _jnp()
        _, mask = self.child.ex(env, meta)
        return mask.astype(jnp.float32) * self.boost, mask


class EBool(Emit):
    def __init__(self, must, should, must_not, filter_, need: int,
                 boost: float, nd: int, D: int):
        self.must = must
        self.should = should
        self.must_not = must_not
        self.filter = filter_
        self.need = need
        self.boost = boost
        self.nd = nd
        self.D = D

    def key(self):
        return ("bool", self.need, self.boost,
                tuple(c.key() for c in self.must),
                tuple(c.key() for c in self.should),
                tuple(c.key() for c in self.must_not),
                tuple(c.key() for c in self.filter))

    def ex(self, env, meta):
        jnp = _jnp()
        all_live = jnp.arange(self.D) < env[self.nd][0]
        mask = all_live
        scores = jnp.zeros(self.D, jnp.float32)
        for c in self.must:
            s, m = c.sm(env, meta)
            scores = scores + s
            mask = mask & m
        for c in self.filter:
            _, m = c.ex(env, meta)
            mask = mask & m
        for c in self.must_not:
            _, m = c.ex(env, meta)
            mask = mask & ~m
        if self.should:
            should_count = jnp.zeros(self.D, jnp.int32)
            for c in self.should:
                s, m = c.sm(env, meta)
                scores = scores + jnp.where(m, s, 0.0)
                should_count = should_count + m.astype(jnp.int32)
            if self.need > 0:
                mask = mask & (should_count >= self.need)
        if not (self.must or self.should or self.filter or self.must_not):
            return None, jnp.zeros(self.D, bool)
        if self.boost != 1.0:
            scores = scores * self.boost
        return scores * mask, mask


class EPhrase(Emit):
    """match_phrase via the device positional program (ops/positional.py)
    — anchor-entry interval verification + BM25 phrase pseudo-term score,
    identical math to MatchPhraseQuery.execute."""

    def __init__(self, prim: int, slop: int, boost: float, D: int):
        self.prim = prim
        self.slop = slop
        self.boost = boost
        self.D = D

    def key(self):
        return ("phrase", self.slop, self.boost)

    def ex(self, env, meta):
        from elasticsearch_tpu.ops.positional import (phrase_freq_program,
                                                      phrase_score)

        jnp = _jnp()
        (adoc, apos, aval, runs, rstart, rlen, delta, pos, offs,
         lengths, stats) = env[self.prim]
        freq = phrase_freq_program(adoc, apos, aval, runs, rstart, rlen,
                                   delta, pos, offs, slop=self.slop,
                                   D=self.D,
                                   scatter_free=_scatter_free(meta))
        mask = freq > 0
        scores = phrase_score(freq, lengths, stats[0], stats[1],
                              D=self.D) * self.boost
        return scores, mask


class EKnn(Emit):
    """knn-as-query: fused scores+mask+topk per shard (brute force; IVF
    queries fall back to the host loop), candidates scattered back into the
    (scores, mask) contract exactly like KnnQuery.execute."""

    def __init__(self, prim: int, filt: Optional[Emit], live: int, kc: int,
                 metric: str, boost: float, D: int):
        self.prim = prim
        self.filter = filt
        self.live = live
        self.kc = kc
        self.metric = metric
        self.boost = boost
        self.D = D

    def key(self):
        return ("knn", self.kc, self.metric, self.boost,
                self.filter.key() if self.filter is not None else None)

    def ex(self, env, meta):
        from elasticsearch_tpu.ops.pallas_kernels import knn_topk_auto

        jnp = _jnp()
        vecs, exists, q, *terms = env[self.prim]
        lv = exists & env[self.live][0]
        if self.filter is not None:
            _, fm = self.filter.ex(env, meta)
            lv = lv & fm
        vals, idx = knn_topk_auto(q[None, :], vecs,
                                  terms[0] if terms else None, lv,
                                  k=self.kc, metric=self.metric,
                                  precise=True)
        valid = vals[0] > -jnp.inf
        scores = jnp.zeros(self.D, jnp.float32).at[idx[0]].max(
            jnp.where(valid, vals[0] * self.boost, 0.0), mode="drop")
        mask = jnp.zeros(self.D, bool).at[idx[0]].max(valid, mode="drop")
        return scores, mask


class EDisMax(Emit):
    def __init__(self, children: List[Emit], tie: float, boost: float,
                 D: int):
        self.children = children
        self.tie = tie
        self.boost = boost
        self.D = D

    def key(self):
        return ("dismax", self.tie, self.boost,
                tuple(c.key() for c in self.children))

    def ex(self, env, meta):
        jnp = _jnp()
        parts = [c.sm(env, meta) for c in self.children]
        mask = parts[0][1]
        for _, m in parts[1:]:
            mask = mask | m
        stacked = jnp.stack([jnp.where(m, s, 0.0) for s, m in parts])
        best = jnp.max(stacked, axis=0)
        if self.tie > 0:
            total = jnp.sum(stacked, axis=0)
            best = best + self.tie * (total - best)
        return best * self.boost * mask, mask


class EBoosting(Emit):
    def __init__(self, positive: Emit, negative: Emit, neg_boost: float,
                 boost: float):
        self.positive = positive
        self.negative = negative
        self.neg_boost = neg_boost
        self.boost = boost

    def key(self):
        return ("boosting", self.neg_boost, self.boost,
                self.positive.key(), self.negative.key())

    def ex(self, env, meta):
        jnp = _jnp()
        s, mask = self.positive.sm(env, meta)
        _, neg = self.negative.ex(env, meta)
        s = jnp.where(neg, s * self.neg_boost, s)
        return s * self.boost * mask, mask


class FEmit:
    """function_score function over env data — mirrors ScoreFunction."""

    weight = 1.0
    filter: Optional[Emit] = None

    def key(self) -> tuple:
        raise NotImplementedError

    def value(self, env, meta, D):
        raise NotImplementedError

    def weighted(self, env, meta, D):
        jnp = _jnp()
        v = self.value(env, meta, D) * self.weight
        if self.filter is not None:
            _, fm = self.filter.ex(env, meta)
            return v, fm
        return v, jnp.ones(D, dtype=bool)

    def _fkey(self):
        return (self.weight,
                self.filter.key() if self.filter is not None else None)


class FWeight(FEmit):
    def __init__(self, weight, filt):
        self.weight = weight
        self.filter = filt

    def key(self):
        return ("fw",) + self._fkey()

    def value(self, env, meta, D):
        jnp = _jnp()
        return jnp.ones(D, dtype=jnp.float32)


class FFieldValue(FEmit):
    def __init__(self, prim, factor, modifier, missing, weight, filt):
        self.prim = prim
        self.factor = factor
        self.modifier = modifier
        self.missing = missing
        self.weight = weight
        self.filter = filt

    def key(self):
        return ("ffv", self.factor, self.modifier,
                self.missing) + self._fkey()

    def value(self, env, meta, D):
        jnp = _jnp()
        values, exists = env[self.prim]
        v = jnp.where(exists, values,
                      jnp.float32(self.missing if self.missing is not None
                                  else 0.0))
        v = v * self.factor
        m = self.modifier
        if m in ("none", None):
            return v
        if m == "log":
            return jnp.log10(jnp.maximum(v, 1e-9))
        if m == "log1p":
            return jnp.log10(v + 1.0)
        if m == "log2p":
            return jnp.log10(v + 2.0)
        if m == "ln":
            return jnp.log(jnp.maximum(v, 1e-9))
        if m == "ln1p":
            return jnp.log1p(v)
        if m == "ln2p":
            return jnp.log(v + 2.0)
        if m == "square":
            return v * v
        if m == "sqrt":
            return jnp.sqrt(jnp.maximum(v, 0.0))
        if m == "reciprocal":
            return 1.0 / jnp.maximum(v, 1e-9)
        raise MeshCompileError(f"field_value_factor modifier [{m}]")


class FDecay(FEmit):
    def __init__(self, prim, kind, origin, scale, offset, decay, weight,
                 filt):
        self.prim = prim
        self.kind = kind
        self.origin = origin
        self.scale = scale
        self.offset = offset
        self.decay = decay
        self.weight = weight
        self.filter = filt

    def key(self):
        return ("fdecay", self.kind, self.origin, self.scale, self.offset,
                self.decay) + self._fkey()

    def value(self, env, meta, D):
        jnp = _jnp()
        values, exists = env[self.prim]
        dist = jnp.maximum(
            jnp.abs(values - jnp.float32(self.origin))
            - jnp.float32(self.offset), 0.0)
        decay = jnp.float32(self.decay)
        scale_f = jnp.float32(self.scale)
        if self.kind == "gauss":
            sigma2 = -(scale_f ** 2) / (2.0 * jnp.log(decay))
            out = jnp.exp(-(dist ** 2) / (2.0 * sigma2))
        elif self.kind == "exp":
            lam = jnp.log(decay) / scale_f
            out = jnp.exp(lam * dist)
        else:  # linear
            s = scale_f / (1.0 - decay)
            out = jnp.maximum((s - dist) / s, 0.0)
        return jnp.where(exists, out, jnp.float32(1.0))


class FRandom(FEmit):
    def __init__(self, seed, weight, filt):
        self.seed = int(seed)
        self.weight = weight
        self.filter = filt

    def key(self):
        return ("frand", self.seed) + self._fkey()

    def value(self, env, meta, D):
        from elasticsearch_tpu.utils.hashing import hash32_device

        jnp = _jnp()
        x = hash32_device(jnp.arange(D, dtype=jnp.uint32)
                          + jnp.uint32(self.seed))
        return (x.astype(jnp.float32) / jnp.float32(2 ** 32)).astype(
            jnp.float32)


class EFuncScore(Emit):
    """function_score — same combination algebra as FunctionScoreQuery
    (search/function_score.py), over env-resolved functions."""

    def __init__(self, child: Emit, functions: List[FEmit], score_mode: str,
                 boost_mode: str, max_boost, min_score, boost: float,
                 D: int):
        self.child = child
        self.functions = functions
        self.score_mode = score_mode
        self.boost_mode = boost_mode
        self.max_boost = max_boost
        self.min_score = min_score
        self.boost = boost
        self.D = D

    def key(self):
        return ("fscore", self.score_mode, self.boost_mode, self.max_boost,
                self.min_score, self.boost, self.child.key(),
                tuple(f.key() for f in self.functions))

    def ex(self, env, meta):
        jnp = _jnp()
        D = self.D
        scores, mask = self.child.sm(env, meta)
        if not self.functions:
            return scores * self.boost, mask
        pairs = [f.weighted(env, meta, D) for f in self.functions]
        sm = self.score_mode
        any_match = pairs[0][1]
        for _, m in pairs[1:]:
            any_match = any_match | m
        if sm == "multiply":
            fv = jnp.ones(D, dtype=jnp.float32)
            for v, m in pairs:
                fv = fv * jnp.where(m, v, 1.0)
        elif sm in ("sum", "avg"):
            fv = jnp.zeros(D, dtype=jnp.float32)
            nm = jnp.zeros(D, dtype=jnp.float32)
            for v, m in pairs:
                fv = fv + jnp.where(m, v, 0.0)
                nm = nm + m.astype(jnp.float32)
            if sm == "avg":
                fv = fv / jnp.maximum(nm, 1.0)
        elif sm == "max":
            fv = jnp.full(D, -jnp.inf, dtype=jnp.float32)
            for v, m in pairs:
                fv = jnp.maximum(fv, jnp.where(m, v, -jnp.inf))
        elif sm == "min":
            fv = jnp.full(D, jnp.inf, dtype=jnp.float32)
            for v, m in pairs:
                fv = jnp.minimum(fv, jnp.where(m, v, jnp.inf))
        elif sm == "first":
            fv = jnp.ones(D, dtype=jnp.float32)
            taken = jnp.zeros(D, dtype=bool)
            for v, m in pairs:
                use = m & ~taken
                fv = jnp.where(use, v, fv)
                taken = taken | m
        else:
            raise MeshCompileError(f"score_mode [{sm}]")
        fv = jnp.where(any_match, fv, jnp.float32(1.0))
        if self.max_boost is not None:
            fv = jnp.minimum(fv, jnp.float32(self.max_boost))
        bm = self.boost_mode
        if bm == "multiply":
            out = scores * fv
        elif bm == "replace":
            out = fv
        elif bm == "sum":
            out = scores + fv
        elif bm == "avg":
            out = (scores + fv) / 2.0
        elif bm == "max":
            out = jnp.maximum(scores, fv)
        elif bm == "min":
            out = jnp.minimum(scores, fv)
        else:
            raise MeshCompileError(f"boost_mode [{bm}]")
        out = out * self.boost
        if self.min_score is not None:
            mask = mask & (out >= self.min_score)
        return out * mask, mask


# ---------------------------------------------------------------------------
# compiler
# ---------------------------------------------------------------------------

class CompiledMeshQuery:
    """Result of compile_mesh_query: emit tree + data primitives. One
    instance per request; program caching happens in the executor keyed by
    (struct_key, static/shape tuple)."""

    def __init__(self, root: Emit, prims: List[DataPrim], live: int, nd: int,
                 D: int, sort_prim: Optional[int], sort_cfg: Optional[tuple],
                 agg_prims: List[Tuple[str, int]], want_mask: bool = False):
        self.root = root
        self.prims = prims
        self.live = live
        self.nd = nd
        self.D = D
        self.sort_prim = sort_prim
        self.sort_cfg = sort_cfg  # (desc, missing_first) or None
        self.agg_prims = agg_prims  # [(agg_name, prim_idx)]
        # also return the per-shard match mask [S, D] — the host-side agg
        # collectors consume it, so any aggregation (not just device terms
        # counts) runs off the mesh query phase without a full fallback
        self.want_mask = want_mask

    def struct_key(self):
        return (self.root.key(), self.D, self.sort_prim is not None,
                self.sort_cfg, tuple(name for name, _ in self.agg_prims),
                self.want_mask)


class MeshQueryCompiler:
    def __init__(self, mappings, analysis, global_stats=None, D: int = 0,
                 has_dense: Optional[Callable[[str], bool]] = None,
                 col_everywhere: Optional[Callable[[str], bool]] = None):
        self.mappings = mappings
        self.analysis = analysis
        self.gs = global_stats
        self.D = D
        # has_dense(field) → True when any segment of the current round has a
        # dense impact block for the field; term groups then score via the
        # hybrid MXU-matmul + scatter-tail path (mirror of the host loop's
        # ctx.hybrid_slices dispatch, ops/scoring.py:94)
        self.has_dense = has_dense or (lambda field: False)
        # col_everywhere(field) → True when every segment of the round has
        # the numeric column (function_score without [missing] raises on a
        # column-less segment in the host loop — a per-shard condition the
        # traced program can't reproduce, so such rounds fall back)
        self.col_everywhere = col_everywhere or (lambda field: False)
        self.prims: List[DataPrim] = []
        self._postings: Dict[str, int] = {}

    def _add(self, prim: DataPrim) -> int:
        self.prims.append(prim)
        return len(self.prims) - 1

    def _postings_for(self, field: str) -> int:
        if field not in self._postings:
            self._postings[field] = self._add(PostingsPrim(field))
        return self._postings[field]

    def compile(self, query, sort_spec: Optional[list],
                agg_specs: Optional[list],
                want_mask: bool = False) -> CompiledMeshQuery:
        live = self._add(LivePrim())
        nd = self._add(NumDocsPrim())
        self._nd = nd
        self._live = live
        root = self._c(query)
        sort_prim = None
        sort_cfg = None
        if sort_spec:
            # device preselect ranks on the PRIMARY key only (oversampled);
            # the exact multi-key ordering happens on host over the full
            # value tuples (mesh_service), mirroring the host loop's
            # _sorted_candidates two-stage sort
            s = sort_spec[0]
            if s["field"] in ("_score", "_geo_distance"):
                raise MeshCompileError(f"{s['field']} primary sort")
            # _score as ANY key needs the score vector at fetch time, which
            # sorted mesh candidates don't carry (their val is the primary
            # rank) — host loop handles it (_geo_distance secondaries are
            # fine: _sort_value computes them from columns)
            if any(x["field"] == "_score" for x in sort_spec[1:]):
                raise MeshCompileError("_score secondary sort")
            fm = self.mappings.get(s["field"])
            if fm is not None and fm.is_numeric:
                sort_prim = self._add(SortColPrim(s["field"]))
            elif fm is not None and fm.is_keyword:
                sort_prim = self._add(SortOrdPrim(s["field"]))
            else:
                raise MeshCompileError("unsortable primary sort field")
            sort_cfg = (s["order"] == "desc",
                        str(s.get("missing", "_last")) == "_first")
        agg_prims: List[Tuple[str, int]] = []
        for name, field in (agg_specs or []):
            agg_prims.append((name, self._add(AggTermsPrim(field))))
        return CompiledMeshQuery(root, self.prims, live, nd, self.D,
                                 sort_prim, sort_cfg, agg_prims,
                                 want_mask=want_mask)

    # -- tree walk (mirrors search/queries.py execute semantics) -------------

    def _c(self, q) -> Emit:
        from elasticsearch_tpu.search import queries as Q

        D = self.D
        if q is None or isinstance(q, Q.MatchAllQuery):
            boost = getattr(q, "boost", 1.0)
            return EMatchAll(boost, self._nd, D)
        if isinstance(q, Q.MatchNoneQuery):
            return ENone(D)
        if isinstance(q, Q.TermQuery):
            fm = self.mappings.get(q.field)
            if fm is not None and fm.is_numeric:
                return self._range(Q.RangeQuery(q.field, gte=q.value,
                                                lte=q.value, boost=q.boost))
            return self._tgroup_scores(
                q.field, q.boost,
                lambda ctx, q=q: ([q._term_str(ctx)], None))
        if isinstance(q, Q.TermsQuery):
            fm = self.mappings.get(q.field)
            if fm is not None and fm.is_numeric:
                kids = [self._range(Q.RangeQuery(q.field, gte=v, lte=v))
                        for v in q.values]
                node = EOr(kids, D)
                node.boost = q.boost
                return node
            terms = [str(v) for v in q.values]
            return self._tgroup_mask(q.field, q.boost,
                                     lambda ctx, t=terms: list(dict.fromkeys(t)))
        if isinstance(q, Q.MatchQuery):
            if q.fuzziness is not None:
                raise MeshCompileError("fuzzy match")
            return self._match(q)
        if isinstance(q, Q.RangeQuery):
            return self._range(q)
        if isinstance(q, Q.ExistsQuery):
            node = EMaskData(self._add(ExistsPrim(q.field)), "exists")
            node.boost = q.boost
            return node
        if isinstance(q, Q.IdsQuery):
            node = EMaskData(self._add(IdsPrim(q.values)), "ids")
            node.boost = q.boost
            return node
        if isinstance(q, Q.PrefixQuery):
            return self._tgroup_mask(
                q.field, q.boost,
                lambda ctx, q=q: Q._expand_prefix(
                    ctx.inv(q.field), str(q.value), q.max_expansions)
                if ctx.inv(q.field) is not None else [])
        if isinstance(q, Q.WildcardQuery):
            return self._tgroup_mask(
                q.field, q.boost, lambda ctx, q=q: _wildcard_terms(ctx, q))
        if isinstance(q, Q.RegexpQuery):
            return self._tgroup_mask(
                q.field, q.boost, lambda ctx, q=q: _regexp_terms(ctx, q))
        if isinstance(q, Q.FuzzyQuery):
            return self._tgroup_scores(
                q.field, q.boost, lambda ctx, q=q: (_fuzzy_terms(ctx, q), None))
        if isinstance(q, Q.BoolQuery):
            if (q.boost == 1.0 and not q.should and not q.must_not
                    and not q.filter and len(q.must) == 1
                    and q.msm is None):
                # trivial single-must wrapper (a common client pattern):
                # collapse so the child keeps its fast-path eligibility
                # (the single-group candidate top-k matches on the ROOT)
                return self._c(q.must[0])
            must = [self._c(c) for c in q.must]
            should = [self._c(c) for c in q.should]
            must_not = [self._c(c) for c in q.must_not]
            filt = [self._c(c) for c in q.filter]
            default_msm = 0 if (q.must or q.filter) else 1
            need = (Q._min_should_match(q.msm, len(q.should))
                    if q.msm is not None else default_msm) if q.should else 0
            return EBool(must, should, must_not, filt, need, q.boost,
                         self._nd, D)
        if isinstance(q, Q.ConstantScoreQuery):
            return EConstScore(self._c(q.inner), q.boost)
        if isinstance(q, Q.MatchPhraseQuery):
            return self._phrase(q)
        if isinstance(q, Q.KnnQuery):
            return self._knn(q)
        if isinstance(q, Q.DisMaxQuery):
            if not q.queries:
                return ENone(D)
            return EDisMax([self._c(c) for c in q.queries],
                           q.tie_breaker, q.boost, D)
        if isinstance(q, Q.BoostingQuery):
            return EBoosting(self._c(q.positive), self._c(q.negative),
                             q.negative_boost, q.boost)
        from elasticsearch_tpu.search.function_score import FunctionScoreQuery

        if isinstance(q, FunctionScoreQuery):
            return self._function_score(q)
        from elasticsearch_tpu.search.hybrid import HybridQuery

        if isinstance(q, HybridQuery):
            # hybrid runs its own fused single-program path per searcher
            # (search/hybrid.hybrid_fused_topk) — host orchestration is
            # the intended route, not a capability gap, so it must not
            # count against the fallback==0 budget
            raise MeshCompileError("hybrid rides its own fused program",
                                   by_design=True)
        raise MeshCompileError(f"unsupported query type {type(q).__name__}")

    def _search_analyzer(self, field: str):
        fm = self.mappings.get(field)
        if fm is None or not fm.is_text:
            return None
        return self.analysis.get(fm.search_analyzer or fm.analyzer)

    def _phrase(self, q) -> Emit:
        fm = self.mappings.get(q.field)
        if fm is None or not fm.is_text:
            # host loop: no positions → empty; keep the conservative
            # fallback rather than guessing keyword-field semantics
            raise MeshCompileError("match_phrase on non-text field")
        an = self._search_analyzer(q.field)
        toks = an.analyze(str(q.text)) if an else [(str(q.text), 0)]
        if not toks:
            return ENone(self.D)
        if len(toks) == 1:
            t0 = toks[0][0]
            return self._tgroup_scores(q.field, q.boost,
                                       lambda ctx, t=t0: ([t], None))
        prim = self._add(PhrasePrim(q.field, [(t, p) for t, p in toks]))
        return EPhrase(prim, int(q.slop), q.boost, self.D)

    def _knn(self, q) -> Emit:
        fm = self.mappings.get(q.field)
        use_ann = bool(q.ann) if q.ann is not None else (
            fm is not None and bool(getattr(fm, "index_options", None))
            and fm.index_options.get("type") in ("ivf", "ivf_flat",
                                                 "ivf_pq"))
        if use_ann:
            # host loop probes IVF (and the PQ coarse->fine pipeline):
            # coarse-quantizer routing is a designed host-orchestrated
            # pipeline, not a missing mesh feature
            raise MeshCompileError("knn via IVF", by_design=True)
        if getattr(q, "maxsim", False):
            # host loop runs the fused per-token sweep + scatter-max
            # merge (queries.KnnQuery._execute_maxsim) — a designed
            # routing, like IVF probing
            raise MeshCompileError("knn multi-vector MaxSim",
                                   by_design=True)
        dims = getattr(fm, "dims", None) if fm is not None else None
        if fm is None or not dims:
            return ENone(self.D)  # unmapped vector field: empty everywhere
        if q.tokens.shape[1] != int(dims):
            from elasticsearch_tpu.utils.errors import QueryParsingException

            raise QueryParsingException(
                f"knn query vector has {q.tokens.shape[1]} dims but field "
                f"[{q.field}] is mapped with {dims}")
        filt = self._c(q.filter) if q.filter is not None else None
        # tokens[0], not the raw body value: a single-token query_vectors
        # body arrives nested ([1, dims]) and VecsPrim wants the 1-D vector
        metric = getattr(fm, "similarity", None) or "cosine"
        prim = self._add(VecsPrim(q.field, q.tokens[0], metric))
        kc = int(min(max(q.num_candidates, q.k), self.D))
        return EKnn(prim, filt, self._live, kc, metric, q.boost, self.D)

    def _function_score(self, q) -> Emit:
        from elasticsearch_tpu.search import function_score as FS
        from elasticsearch_tpu.utils.dates import (interval_to_millis,
                                                   parse_date)

        child = self._c(q.inner)
        fns: List[FEmit] = []
        for f in q.functions:
            filt = self._c(f.filter) if f.filter is not None else None
            if type(f) is FS.WeightFunction:
                fns.append(FWeight(f.weight, filt))
            elif type(f) is FS.FieldValueFactorFunction:
                fm = self.mappings.get(f.field)
                if fm is None or not fm.is_numeric:
                    raise MeshCompileError("field_value_factor field")
                if f.missing is None and not self.col_everywhere(f.field):
                    # host loop raises on a column-less segment; a traced
                    # program can't — fall back for exact error parity
                    raise MeshCompileError(
                        "field_value_factor without [missing] on a round "
                        "with column-less segments")
                prim = self._add(ColPrim(f.field))
                fns.append(FFieldValue(prim, float(f.factor), f.modifier,
                                       f.missing, f.weight, filt))
            elif type(f) is FS.DecayFunction:
                fm = self.mappings.get(f.field)
                if fm is None or not fm.is_numeric:
                    raise MeshCompileError("decay field")
                if fm.type == "date":
                    if f.origin in (None, "now"):
                        raise MeshCompileError("decay origin now/None")
                    origin = float(parse_date(f.origin, fm.fmt))
                    scale = (interval_to_millis(f.scale)
                             if isinstance(f.scale, str) else float(f.scale))
                    offset = (interval_to_millis(f.offset)
                              if isinstance(f.offset, str)
                              else float(f.offset or 0))
                else:
                    origin = float(f.origin)
                    scale = float(f.scale)
                    offset = float(f.offset or 0)
                prim = self._add(ColPrim(f.field))
                fns.append(FDecay(prim, f.kind, origin, scale, offset,
                                  float(f.decay), f.weight, filt))
            elif type(f) is FS.RandomScoreFunction:
                fns.append(FRandom(f.seed, f.weight, filt))
            else:
                raise MeshCompileError(
                    f"function_score function {type(f).__name__}")
        return EFuncScore(child, fns, q.score_mode, q.boost_mode,
                          q.max_boost, q.min_score, q.boost, self.D)

    def _tgroup_scores(self, field: str, boost: float, base_terms_fn) -> Emit:
        """Scoring term group (mask = scores > 0): weights = idf*boost,
        duplicate terms summed (mirror _score_term_group/_dedupe_terms)."""
        from elasticsearch_tpu.search.queries import _dedupe_terms

        if boost <= 0:
            # weights are idf*boost: with boost <= 0 the host path switches
            # to an explicit term mask (scores > 0 would invert/empty the
            # match set) — a shape this emit node doesn't carry. Fall back.
            raise MeshCompileError("non-positive boost on scoring term group")

        def terms_fn(ctx):
            terms, _ = base_terms_fn(ctx)
            if not terms:
                return [], []
            return _dedupe_terms(terms, boost,
                                 lambda t: ctx.idf(field, t))

        idx, post, hybrid = self._tgroup_prim(field, terms_fn)
        cls = ETermGroupHybrid if hybrid else ETermGroup
        return cls(idx, post, "scores", 0, boost, self.D)

    def _tgroup_prim(self, field: str, terms_fn) -> Tuple[int, int, bool]:
        """Add the term-group data prim for a field: the hybrid dense-impact
        form when any segment of the round carries a dense block (frequent
        terms ride one MXU matmul), the pure scatter form otherwise."""
        hybrid = bool(self.has_dense(field))
        prim = (HybridTGroupPrim if hybrid else TGroupPrim)(field, terms_fn)
        post = self._postings_for(field)
        return self._add(prim), post, hybrid

    def _tgroup_mask(self, field: str, boost: float, expand_fn) -> Emit:
        def terms_fn(ctx):
            terms = list(dict.fromkeys(expand_fn(ctx)))
            return terms, [1.0] * len(terms)

        idx, post, hybrid = self._tgroup_prim(field, terms_fn)
        cls = ETermGroupHybrid if hybrid else ETermGroup
        node = cls(idx, post, "mask", 0, boost, self.D)
        node.boost = boost
        return node

    def _match(self, q) -> Emit:
        from elasticsearch_tpu.search.queries import (_dedupe_terms,
                                                      _min_should_match)

        field, boost = q.field, q.boost
        if boost <= 0:
            raise MeshCompileError("non-positive boost on match query")

        def analyze(ctx):
            an = ctx.search_analyzer(field)
            if an is None:
                return [str(q.text)]
            return [t for t, _ in an.analyze(str(q.text))]

        def terms_fn(ctx):
            return _dedupe_terms(analyze(ctx), boost,
                                 lambda t: ctx.idf(field, t))

        idx, post, hybrid = self._tgroup_prim(field, terms_fn)
        cls = ETermGroupHybrid if hybrid else ETermGroup
        # the analyzer output is query-side — identical on every shard, so
        # n_terms/msm thresholds are static (resolve once with the analyzer)
        an = self._search_analyzer(field)
        toks = ([t for t, _ in an.analyze(str(q.text))] if an is not None
                else [str(q.text)])
        n_terms = len(set(toks))
        if q.operator == "and":
            return cls(idx, post, "count_ge", max(n_terms, 1), boost,
                       self.D)
        if q.msm is not None:
            need = max(_min_should_match(q.msm, n_terms), 1)
            return cls(idx, post, "count_ge", need, boost, self.D)
        return cls(idx, post, "scores", 0, boost, self.D)

    def _range(self, q) -> Emit:
        from elasticsearch_tpu.search import queries as Q

        fm = self.mappings.get(q.field)
        if fm is not None and (fm.is_text or fm.is_keyword):
            # keyword range: per-shard sorted-term-dict expansion (mirror of
            # RangeQuery keyword branch)
            def expand(ctx, q=q):
                inv = ctx.inv(q.field)
                if inv is None:
                    return []
                from bisect import bisect_left
                lo, ilo, hi, ihi = q._bounds(ctx)
                terms, _ = Q._sorted_terms(inv)
                i0 = bisect_left(terms, str(lo)) if lo is not None else 0
                if lo is not None and not ilo and i0 < len(terms) and terms[i0] == str(lo):
                    i0 += 1
                i1 = bisect_left(terms, str(hi)) if hi is not None else len(terms)
                if hi is not None and ihi and i1 < len(terms) and terms[i1] == str(hi):
                    i1 += 1
                return terms[i0:i1]

            return self._tgroup_mask(q.field, q.boost, expand)
        if fm is None:
            raise MeshCompileError(f"range on unmapped field [{q.field}]")
        # numeric/date: bounds are query-side constants; date parsing uses
        # the mapping format (identical across shards)
        lo, include_lo = (q.gte, True) if q.gte is not None else (q.gt, False)
        hi, include_hi = (q.lte, True) if q.lte is not None else (q.lt, False)
        if fm.type == "date":
            from elasticsearch_tpu.utils.dates import parse_date

            fmt = q.fmt or fm.fmt
            lo = parse_date(lo, fmt) if lo is not None else None
            hi = parse_date(hi, fmt) if hi is not None else None

        def as_int(v):
            if v is None:
                return None
            try:
                f = float(v)
            except (TypeError, ValueError):
                return None
            i = int(f)
            return i if f == i else None

        use_int = ((lo is None or as_int(lo) is not None)
                   and (hi is None or as_int(hi) is not None))
        prim = RangePrim(q.field, lo, hi, use_int)
        idx = self._add(prim)
        node = ERange(idx, include_lo if lo is not None else True,
                      include_hi if hi is not None else True)
        node.boost = q.boost
        return node


def _wildcard_terms(ctx, q):
    import fnmatch
    import re

    inv = ctx.inv(q.field)
    if inv is None:
        return []
    from elasticsearch_tpu.search.queries import _expand_prefix

    pat = str(q.value)
    prefix = re.match(r"^[^*?\[\]]*", pat).group(0)
    cands = _expand_prefix(inv, prefix, 1 << 30) if prefix else inv.terms
    rx = re.compile(fnmatch.translate(pat))
    return [t for t in cands if rx.match(t)][: q.max_expansions]


def _regexp_terms(ctx, q):
    import re

    inv = ctx.inv(q.field)
    if inv is None:
        return []
    from elasticsearch_tpu.utils.errors import QueryParsingException

    try:
        rx = re.compile(str(q.value))
    except re.error as e:
        raise QueryParsingException(f"invalid regexp [{q.value}]: {e}")
    return [t for t in inv.terms if rx.fullmatch(t)][: q.max_expansions]


def _fuzzy_terms(ctx, q):
    from elasticsearch_tpu.search.queries import (_edit_distance_le,
                                                  _fuzziness_to_edits)

    inv = ctx.inv(q.field)
    if inv is None:
        return []
    t = str(q.value)
    k = _fuzziness_to_edits(q.fuzziness, t)
    return [c for c in inv.terms if _edit_distance_le(t, c, k)][: q.max_expansions]
