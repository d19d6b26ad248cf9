"""Batched ``_msearch`` execution: one fused kernel per request batch.

Reference: org/elasticsearch/action/search/TransportMultiSearchAction.java —
ES executes msearch items as independent parallel searches on the search
thread pool. Here the eligible subset of a batch (simple bodies whose
queries are same-field BM25 term groups on one index) amortizes into one
device program per segment: pure-dense batches take the streaming top-k
kernel (queries.fused_bm25_topk_batch); batches with scatter tails take
the hybrid matmul + batched-scatter + on-device top-k tier
(queries.hybrid_bm25_topk_batch). This is the product path behind the
bench's batched-QPS headline AND the serving coalescer's flush
(serving/coalescer.py).

Partial batching: eligibility is per ITEM, not all-or-nothing — one
aggs-bearing or off-shape item rides the sequential path while the other
255 still amortize. Malformed-query items surface as ES-shaped msearch
item failures instead of silently de-amortizing the whole batch.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from elasticsearch_tpu.search.context import SegmentContext
from elasticsearch_tpu.search.queries import (KnnQuery,
                                              _fused_eligible_terms,
                                              fused_bm25_topk_batch,
                                              hybrid_bm25_topk_batch,
                                              parse_query)
from elasticsearch_tpu.search.service import ShardDoc
from elasticsearch_tpu.tracing.tracer import span
from elasticsearch_tpu.utils.errors import ElasticsearchTpuException

_ALLOWED_KEYS = {"query", "size", "from", "_source"}

#: 2.0 msearch reports error entries as strings like
#: "IndexMissingException[no such index]" — legacy class-name mapping
_LEGACY_ERROR_NAMES = {"index_not_found_exception": "IndexMissingException"}


def msearch_error_entry(e: ElasticsearchTpuException) -> dict:
    """ES-shaped (2.0-style) msearch item failure for a typed error."""
    name = _LEGACY_ERROR_NAMES.get(e.error_type, e.error_type)
    return {"error": f"{name}[{e}]", "status": e.status}


def split_batchable(bodies: List[dict]) -> Tuple[
        List[int], Dict[int, object], Dict[int, ElasticsearchTpuException]]:
    """Per-item batch eligibility over an msearch body list.

    Returns ``(eligible, parsed, errors)``: positions whose bodies may
    batch (simple key set, parseable query, sane result window) with
    their parsed query trees, and positions whose queries raised a TYPED
    parse error — those become per-item msearch failures instead of
    forcing the whole batch sequential. Anything else (aggs, sort,
    unexpected parser bugs) is left to the sequential path, whose
    behavior is the reference."""
    eligible: List[int] = []
    parsed: Dict[int, object] = {}
    errors: Dict[int, ElasticsearchTpuException] = {}
    for i, b in enumerate(bodies):
        if not isinstance(b, dict) or set(b) - _ALLOWED_KEYS:
            continue
        try:
            q = parse_query(b.get("query"))
        except ElasticsearchTpuException as e:
            # typed malformed-query error: the sequential path would
            # report exactly this per-item failure — surface it without
            # de-amortizing the remaining items
            errors[i] = e
            continue
        except Exception:
            continue  # unexpected: the sequential path decides
        try:
            frm, size = int(b.get("from", 0)), int(b.get("size", 10))
        except (TypeError, ValueError):
            continue
        if not 1 <= frm + size <= 10_000:
            continue
        eligible.append(i)
        parsed[i] = q
    return eligible, parsed, errors


def _probe_segment(svc):
    for g in svc.groups:
        for sh in g.copies:
            if sh.searcher.segments:
                return sh.searcher.segments[0]
    return None


def _batch_bucket(svc, ctx, query) -> Optional[str]:
    """The micro-batch bucket key for ``query`` (None = sequential).

    BM25 same-field term groups bucket on their dense-impact field (one
    impact block per kernel call). kNN queries — single-vector AND
    multi-vector MaxSim — bucket on (field, num_candidates): a bucket's
    bodies stack into one token tensor for one fused device sweep.
    Hybrid bodies bucket on (fusion method, lexical field, vector field):
    per-request weights/rank_constant/num_candidates/boost ride as traced
    batch rows, so they never fragment the bucket. Filters and
    effective-ANN single-vector queries stay sequential (the batch tier
    is exact brute-force; batching an IVF-probing query would silently
    change its results vs the sequential reference)."""
    from elasticsearch_tpu.search.hybrid import HybridQuery

    if isinstance(query, HybridQuery):
        if query.rerank is not None:
            return None  # stage 2 re-orders per request: sequential
        knn = query.knn
        if knn.filter is not None or knn.maxsim or knn._use_ann(ctx):
            return None
        vc = ctx.segment.vectors.get(knn.field)
        if vc is None or knn.tokens.shape[1] != vc.dims:
            return None
        e = _fused_eligible_terms(ctx, query.lexical)
        if e is None or not all(w > 0 for w in e[1][1]):
            return None
        return f"__hybrid__:{query.method}:{e[0]}:{knn.field}"
    if isinstance(query, KnnQuery):
        vc = ctx.segment.vectors.get(query.field)
        if vc is None or query.filter is not None:
            return None
        if query.tokens.shape[1] != vc.dims:
            return None  # the sequential path raises the typed error
        if not query.maxsim:
            if query.ann is not None:
                ann = bool(query.ann)
            else:
                fm = svc.mappings.get(query.field)
                opts = (getattr(fm, "index_options", None)
                        if fm is not None else None)
                ann = bool(opts) and opts.get("type") in (
                    "ivf", "ivf_flat", "ivf_pq")
            if ann:
                return None
        return f"__knn__:{query.field}:nc{query.num_candidates}"
    e = _fused_eligible_terms(ctx, query)
    return None if e is None else e[0]


def batch_field(svc, query) -> Optional[str]:
    """The micro-batch bucket ``query`` would coalesce into (None = not
    batchable). Probes the index's first frozen segment — per-segment
    tiers may still refuse at execution time; the caller falls back
    sequentially then."""
    probe = _probe_segment(svc)
    if probe is None or probe.has_nested:
        return None
    try:
        ctx = SegmentContext(probe, svc.mappings, svc.analysis,
                             index_name=svc.name)
        return _batch_bucket(svc, ctx, query)
    except Exception:
        return None


def knn_topk_fused_batch(ctx, queries, k: int):
    """Fused batched kNN/MaxSim over one segment: stack every request's
    token matrix into one [Q, T, dims] tensor (repeat-padding shorter
    token lists — a duplicated token never changes a max), run ONE
    fused per-token top-kc sweep, then a device dedup-by-max merge per
    request. Returns (vals [Q, k], ids [Q, k], totals [Q]) matching the
    fused_bm25_topk_batch contract, or None when the batch is not
    uniform (mixed fields/num_candidates, a filter, a dims mismatch).

    Exactness: precise=True f32 scoring + the per-token-union property
    (a doc in the per-doc-max top-k must appear in some token's top-kc)
    make results identical to Q sequential brute-force searches."""
    import jax.numpy as jnp

    from elasticsearch_tpu.monitor import kernels
    from elasticsearch_tpu.ops.knn import merge_candidate_topk
    from elasticsearch_tpu.ops.pallas_kernels import knn_topk_auto
    from elasticsearch_tpu.utils.shapes import pow2_bucket

    if not queries or not all(isinstance(q, KnnQuery) for q in queries):
        return None
    q0 = queries[0]
    if any(q.field != q0.field or q.filter is not None
           or q.num_candidates != q0.num_candidates for q in queries):
        return None
    vc = ctx.segment.vectors.get(q0.field)
    if vc is None:
        return None
    if any(q.tokens.shape[1] != vc.dims for q in queries):
        return None
    Q = len(queries)
    with span("search.plan"):
        T = pow2_bucket(max(q.tokens.shape[0] for q in queries), minimum=1)
        toks = np.empty((Q, T, vc.dims), np.float32)
        for i, q in enumerate(queries):
            t = q.tokens
            reps = -(-T // t.shape[0])
            toks[i] = np.tile(t, (reps, 1))[:T]
        boosts = np.asarray([q.boost for q in queries], np.float32)
    kc = int(min(max(q0.num_candidates, k), ctx.D))
    with span("device.dispatch", program="batch_knn_fused"):
        lv = vc.exists & ctx.segment.live
        flat = jnp.asarray(toks.reshape(Q * T, vc.dims))
        vals, idx = knn_topk_auto(flat, vc.vecs, vc.row_terms(), lv, k=kc,
                                  metric=vc.similarity, precise=True)
        best_v, best_i, n_unique = merge_candidate_topk(
            vals.reshape(Q, T * kc), idx.reshape(Q, T * kc), k=min(k, kc))
    kernels.record("knn_fused_batch", n=Q)
    with span("device.wait"):
        return (np.asarray(best_v) * boosts[:, None], np.asarray(best_i),
                np.asarray(n_unique).astype(np.int64))


def execute_batch(svc, bodies: List[dict], queries: Optional[list] = None,
                  pad_pow2: bool = False) -> Optional[List[dict]]:
    """Fused batch execution of uniform single-search bodies over one
    index: one vmapped device program per segment, per-request responses
    in order, or None when the fused tiers refuse (the sequential path
    is always correct).

    ``pad_pow2`` pads the batch (and the top-k width) to power-of-two
    buckets with copies of the first query so the coalescer's
    variable-size batches reuse compiled programs instead of retracing
    per distinct batch size; padded rows are dropped before the
    per-request merge, so responses are byte-identical either way."""
    t0 = time.perf_counter()
    if queries is None:
        try:
            queries = [parse_query(b.get("query")) for b in bodies]
        except ElasticsearchTpuException:
            return None  # caller's sequential path reports the error
    sizes = [(int(b.get("from", 0)), int(b.get("size", 10)))
             for b in bodies]
    k = max(frm + size for frm, size in sizes)
    if not 1 <= k <= 10_000:
        return None
    Q = len(bodies)
    exec_queries = list(queries)
    if pad_pow2:
        from elasticsearch_tpu.utils.shapes import pow2_bucket

        exec_queries += [queries[0]] * (pow2_bucket(Q, minimum=2) - Q)
        # a wider k only ADDS candidates; the per-request truncation at
        # its own from+size keeps results exact
        k = min(pow2_bucket(k, minimum=8), 10_000)
    searchers = [g.reader().searcher for g in svc.groups]
    cands: List[list] = [[] for _ in range(Q)]
    totals = np.zeros(len(exec_queries), np.int64)
    from elasticsearch_tpu.search.hybrid import (HybridQuery,
                                                 hybrid_fused_topk_batch)

    all_knn = all(isinstance(q, KnnQuery) for q in exec_queries)
    all_hybrid = all(isinstance(q, HybridQuery) for q in exec_queries)
    from elasticsearch_tpu.monitor.programs import (REGISTRY, index_scope,
                                                    static_sig)
    from elasticsearch_tpu.tracing import retrace

    with index_scope(svc.name):
        mesh_served = False
        if not all_knn and not all_hybrid and len(searchers) > 1 \
                and getattr(svc, "_mesh_enabled", lambda: False)():
            # ISSUE 16: the coalesced bucket prefers the mesh data plane —
            # the whole batch's query phase (per-shard score, per-shard
            # top-k, all_gather + global merge) is ONE shard_map program
            # per segment round, so batching × sharding multiply. Any
            # refusal (mixed fields, breaker denial, no mesh) falls
            # through to the per-searcher fused tiers unchanged.
            from elasticsearch_tpu.parallel.mesh_service import \
                try_mesh_msearch

            mout = try_mesh_msearch(svc, searchers, exec_queries, k)
            if mout is not None:
                mcands, mtotals = mout
                for qi in range(Q):
                    cands[qi] = mcands[qi]
                totals += np.asarray(mtotals, np.int64)
                mesh_served = True
                # feed the replayable census half: coalesced bodies never
                # cross IndexService.search, so a relocated/restarted
                # coordinator could not pre-warm the sharded program
                # without this record (serving/warmup.py replays it)
                from elasticsearch_tpu.serving import warmup as warmup_mod

                if not warmup_mod.in_prewarm():
                    for b in bodies:
                        svc._record_census_body(b)
        if not mesh_served:
            for pos, s in enumerate(searchers):
                for seg in s.segments:
                    if seg.has_nested:
                        return None
                    with span("search.plan"):
                        ctx = SegmentContext(seg, svc.mappings,
                                             svc.analysis,
                                             index_name=svc.name)
                    # observatory: classify/record only AFTER the tier
                    # accepts — a refusal (None) ran no device program. A
                    # tier-1 refusal re-snapshots so tier 2 isn't billed
                    # tier 1's probe time.
                    kb = min(k, seg.max_docs)
                    snap = retrace.snapshot()
                    t0b = time.perf_counter()
                    if all_hybrid:
                        # hybrid tier: both engines + per-request fusion +
                        # batched top-k as ONE program (search/hybrid.py)
                        prog_name = "batch_hybrid_fused"
                        with span("device.dispatch", program=prog_name):
                            out = hybrid_fused_topk_batch(ctx, exec_queries,
                                                          kb)
                    elif all_knn:
                        # kNN/MaxSim tier: one fused per-token sweep +
                        # device dedup-by-max merge (same (vals, ids,
                        # totals) contract)
                        prog_name = "batch_knn_fused"
                        out = knn_topk_fused_batch(ctx, exec_queries, kb)
                    else:
                        prog_name = "batch_bm25_fused"
                        out = fused_bm25_topk_batch(ctx, exec_queries, kb)
                        if out is None:
                            # tier 2: scatter tails allowed — one matmul +
                            # batched scatter + on-device per-query top-k
                            # (queries.hybrid_bm25_topk_batch)
                            prog_name = "batch_bm25_hybrid"
                            snap = retrace.snapshot()
                            t0b = time.perf_counter()
                            out = hybrid_bm25_topk_batch(ctx, exec_queries,
                                                         kb)
                    if out is None:
                        return None
                    REGISTRY.record_call(
                        prog_name,
                        static_sig(Q=len(exec_queries), D=seg.max_docs,
                                   k=kb),
                        time.perf_counter() - t0b,
                        retrace.traces_since(snap),
                        field=(exec_queries[0].field if all_knn else None))
                    vals, ids, tot = out
                    totals += tot
                    for qi in range(Q):
                        v = vals[qi]
                        # hybrid fused scores can be legitimately 0.0
                        # (linear fusion of a 0.0 cosine) — -inf alone
                        # marks top-k padding there; the BM25/kNN tiers
                        # keep score>0 as the match signature
                        keep = (np.isfinite(v) if all_hybrid
                                else np.isfinite(v) & (v > 0))
                        for j in np.nonzero(keep)[0]:
                            cands[qi].append(
                                (float(v[j]), pos, seg, int(ids[qi, j])))
    q_ms = (time.perf_counter() - t0) * 1000
    for s in searchers:
        # counters must match what Q sequential requests would record
        # (padding rows are compile-shape filler, not served requests)
        s.stats.on_query(q_ms / max(len(searchers), 1), n=Q)

    responses = []
    for qi, body in enumerate(bodies):
        with span("search.fetch"):
            responses.append(_batch_response(
                svc, searchers, body, sizes[qi], cands[qi],
                int(totals[qi]), q_ms))
    return responses


def _batch_response(svc, searchers, body: dict, frm_size: Tuple[int, int],
                    cands: list, total: int, q_ms: float) -> dict:
    """One request's response out of the batch's candidates: per-shard
    then global merge, paging, fetch."""
    t_resp = time.perf_counter()
    frm, size = frm_size
    k_q = frm + size
    # mirror the sequential path exactly: per-shard candidates order by
    # (-score, seg_id, local) and truncate at k (query_phase), THEN the
    # global merge orders by (-score, shard, local) (search_shards)
    by_pos: Dict[int, list] = {}
    for t in cands:
        by_pos.setdefault(t[1], []).append(t)
    lst: list = []
    for pos in sorted(by_pos):
        shard_lst = by_pos[pos]
        shard_lst.sort(key=lambda t: (-t[0], t[2].seg_id, t[3]))
        lst.extend(shard_lst[:k_q])
    lst.sort(key=lambda t: (-t[0], t[1], t[3]))
    page = [ShardDoc(pos, seg, local, val)
            for val, pos, seg, local in lst[frm: frm + size]]
    by_shard: Dict[int, List[ShardDoc]] = {}
    for d in page:
        by_shard.setdefault(d.shard_ord, []).append(d)
    hits: List[dict] = []
    fetched: List[ShardDoc] = []
    for pos in sorted(by_shard):
        tf = time.perf_counter()
        hits.extend(searchers[pos].fetch_phase(by_shard[pos], body,
                                               svc.name))
        searchers[pos].stats.on_fetch((time.perf_counter() - tf) * 1000)
        fetched.extend(by_shard[pos])
    order = {id(d): i for i, d in enumerate(page)}
    hd = sorted(zip(hits, fetched), key=lambda x: order[id(x[1])])
    return {
        # this request's cost: the shared query phase + its own fetch
        # (NOT the cumulative fetch time of earlier batch members)
        "took": int(q_ms + (time.perf_counter() - t_resp) * 1000),
        "timed_out": False,
        "_shards": {"total": len(searchers),
                    "successful": len(searchers), "failed": 0},
        "hits": {
            "total": total,
            "max_score": lst[0][0] if lst else None,
            "hits": [h for h, _ in hd],
        },
    }


def try_batched_msearch(svc, bodies: List[dict],
                        min_batch: int = 2) -> Optional[List[Optional[dict]]]:
    """Partial batch execution over one index.

    Returns None when nothing amortizes (the caller runs everything
    sequentially — the old all-or-nothing contract), else a per-item
    list aligned with ``bodies``: a response dict for items served by
    the fused batch, an msearch error entry for typed malformed-query
    items, and None for the sequential remainder the caller must run
    itself (aggs/sort items, off-shape queries, per-segment tier
    refusals)."""
    eligible, parsed, errors = split_batchable(bodies)
    out: List[Optional[dict]] = [None] * len(bodies)
    for i, e in errors.items():
        out[i] = msearch_error_entry(e)
    # group by micro-batch bucket (dense-impact field for BM25 term
    # groups; (field, num_candidates) for kNN/MaxSim bodies): one fused
    # kernel call per group, so only the largest group batches;
    # stragglers stay sequential (a second fused pass would rarely pay
    # for its compile)
    probe = _probe_segment(svc)
    groups: Dict[str, List[int]] = {}
    if probe is not None and not probe.has_nested:
        ctx = SegmentContext(probe, svc.mappings, svc.analysis,
                             index_name=svc.name)
        for i in eligible:
            try:
                bucket = _batch_bucket(svc, ctx, parsed[i])
            except Exception:
                continue  # sequential path decides
            if bucket is not None:
                groups.setdefault(bucket, []).append(i)
    batch_idx = max(groups.values(), key=len, default=[])
    if len(batch_idx) < min_batch:
        return out if errors else None
    responses = execute_batch(svc, [bodies[i] for i in batch_idx],
                              queries=[parsed[i] for i in batch_idx])
    if responses is None:
        return out if errors else None
    for i, r in zip(batch_idx, responses):
        out[i] = r
    return out
