"""Product search path over the shard mesh.

Reference: org/elasticsearch/action/search/type/
TransportSearchQueryThenFetchAction.java:1-148. `/index/_search` lands here
first: the parsed query compiles (parallel/compiler.py) into ONE shard_map
program per segment round — per-shard scoring, local top-k, all_gather +
global top-k, psum totals, terms-agg partials — and only the fetch phase
(_source, highlight) stays on host. Anything the compiler can't express
returns None and the caller falls back to the host per-shard loop in
search/service.py (same result, sequential execution).
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import numpy as np

from elasticsearch_tpu.parallel.compiler import MeshCompileError
from elasticsearch_tpu.tracing.tracer import span


# host-loop-only request features: their presence skips the mesh path.
# highlight is NOT here: it is a fetch-phase feature and the mesh path's
# fetch_phase handles it like the host loop does (matched_queries too —
# the fetch phase attaches them on either path).
_UNSUPPORTED_KEYS = ("rescore", "search_after", "min_score", "scroll",
                     "profile", "terminate_after", "timeout",
                     "indices_boost")


_BY_DESIGN = object()  # host path chosen on purpose (e.g. IVF probing)


def try_mesh_search(svc, searchers, body: dict, global_stats=None) -> Optional[dict]:
    """Mesh-execute a search request; None → caller uses the host loop."""
    from elasticsearch_tpu.monitor import kernels

    resp = _try_mesh_search(svc, searchers, body, global_stats)
    if resp is _BY_DESIGN:
        kernels.record("mesh_host_by_design")
        return None
    kernels.record("mesh_search" if resp is not None else "mesh_fallback_total")
    return resp


def try_mesh_msearch(svc, searchers, queries, k: int):
    """Batched (coalesced-bucket) QUERY phase over the shard mesh: every
    query of the batch scores on every shard inside ONE shard_map program
    per segment round — per-shard BM25, per-shard ``lax.top_k``,
    on-device ``all_gather`` + global merge, psum'd totals — instead of
    the per-searcher × per-segment host loop. ISSUE 16's batching ×
    sharding product: the coalescer's fused buckets hand their whole
    batch here first.

    Returns ``(cands, totals)`` in search/batch.py's accumulator format
    — ``cands[qi]`` a list of ``(score, shard_pos, segment, local_id)``
    holding each query's global top-``k`` survivors — or None, in which
    case the caller falls back to the fused host tiers (same results,
    per-shard sequential execution). Fetch, paging, and response
    assembly stay with the caller so both paths share one code path
    byte-for-byte."""
    from elasticsearch_tpu.monitor import kernels

    out = _try_mesh_msearch(svc, searchers, queries, k)
    kernels.record("mesh_msearch" if out is not None
                   else "mesh_msearch_fallback")
    return out


def _try_mesh_msearch(svc, searchers, queries, k: int):
    from elasticsearch_tpu.utils.errors import CircuitBreakingException

    if len(searchers) < 2 or k < 1:
        return None  # one shard: the fused host tier already is one program
    executor = svc.mesh_executor()
    if executor is None:
        return None
    shard_segs = [list(s.segments) for s in searchers]
    probe = None
    for segs in shard_segs:
        for seg in segs:
            if seg.has_nested:
                return None
            if any(inv.wants_postings_shard()
                   for inv in seg.inverted.values()):
                return None
            if probe is None:
                probe = seg
    if probe is None:
        return None  # empty snapshot: host loop owns the empty response
    from elasticsearch_tpu.search.context import SegmentContext
    from elasticsearch_tpu.search.queries import _fused_eligible_terms

    # probe context for analysis/mappings only — weights stay idf-FREE
    # (idf=False): the sharded program folds each segment's own idf in
    # its chunk tables, exactly like the per-segment host tiers do
    ctx = SegmentContext(probe, svc.mappings, svc.analysis,
                         index_name=svc.name)
    field = None
    qterms: List[List[tuple]] = []
    for q in queries:
        e = _fused_eligible_terms(ctx, q, idf=False)
        if e is None:
            return None
        f, (tlist, wlist) = e
        if field is None:
            field = f
        elif f != field:
            return None  # one postings field per program
        qterms.append(list(zip(tlist, wlist)))
    try:
        out = executor.search_terms(field, qterms, k=k, shards=shard_segs)
    except MeshCompileError:
        return None
    except CircuitBreakingException:
        # breaker-denied device residency: the host tiers score the
        # batch segment-at-a-time within whatever budget remains
        return None
    if out is None:
        return None
    vals, shard, local, seg_ord, totals = out
    cands: List[list] = [[] for _ in range(len(queries))]
    for qi in range(len(queries)):
        v = vals[qi]
        ok = np.isfinite(v) & (v > 0)
        for j in np.nonzero(ok)[0]:
            sh = int(shard[qi, j])
            cands[qi].append((float(v[j]), sh,
                              shard_segs[sh][int(seg_ord[qi, j])],
                              int(local[qi, j])))
    return cands, [int(t) for t in np.asarray(totals)]


def _try_mesh_search(svc, searchers, body: dict, global_stats=None) -> Optional[dict]:
    body = body or {}
    for key in _UNSUPPORTED_KEYS:
        if body.get(key):
            return None
    size = int(body.get("size", 10))
    frm = int(body.get("from", 0))
    if frm + size > 10_000:
        return None  # host loop raises the max_result_window error
    from elasticsearch_tpu.search.aggregations import parse_aggs, reduce_aggs
    from elasticsearch_tpu.search.queries import parse_query
    from elasticsearch_tpu.search.service import _parse_sort

    # any nested segment → block-join masks the program doesn't carry
    shard_segs = [list(s.segments) for s in searchers]
    for segs in shard_segs:
        for seg in segs:
            if seg.has_nested:
                return None
            # an oversized field can't stack into the [S, ...] per-shard
            # arrays this program ships; the host loop scores it through
            # the cross-device postings split instead
            if any(inv.wants_postings_shard()
                   for inv in seg.inverted.values()):
                return None
    with span("search.rewrite"):
        aggs = parse_aggs(body.get("aggs") or body.get("aggregations"))
        # terms aggs without subs reduce fully on device; ANY other agg tree
        # consumes the program's match mask through the host-side
        # collectors — the query phase stays one mesh program either way
        device_aggs = bool(aggs) and all(
            _terms_agg_eligible(a, svc.mappings) for a in aggs)
        agg_specs = ([(a.name, a.body.get("field")) for a in aggs]
                     if device_aggs else None)
        want_mask = bool(aggs) and not device_aggs
        sort_spec = _parse_sort(body.get("sort"))
        query = parse_query(body.get("query"))
        if want_mask and size == 0:
            from elasticsearch_tpu.search.aggregations import program

            if program.in_scope(query, aggs):
                # the host loop serves this tree as one agg_tree program a
                # segment, with no [D] mask pulled to the host
                return _BY_DESIGN
    t0 = time.perf_counter()
    executor = svc.mesh_executor()
    if executor is None:
        return None
    k = max(frm + size, 1)
    # prepared-query memo key: the canonical request body (repeated hot
    # queries skip compile/build/transfer; executor.search_dsl re-executes
    # the program every time — results are never cached here)
    with span("search.plan"):
        try:
            import json as _json

            memo_key = _json.dumps(body, sort_keys=True)
        except TypeError:
            memo_key = None
    try:
        cands, totals, agg_rounds, mask_rounds = executor.search_dsl(
            query, svc.mappings, svc.analysis, k,
            sort_spec=sort_spec or None, agg_specs=agg_specs or None,
            global_stats=global_stats, shards=shard_segs,
            want_mask=want_mask, memo_key=memo_key)
    except MeshCompileError as e:
        return _BY_DESIGN if getattr(e, "by_design", False) else None
    q_ms = (time.perf_counter() - t0) * 1000
    for s in searchers:
        s.stats.on_query(q_ms / max(len(searchers), 1),
                         groups=body.get("stats"))

    from elasticsearch_tpu.search.context import SegmentContext
    from elasticsearch_tpu.search.service import ShardDoc, _sort_key, _sort_value

    with span("search.fetch"):
        # candidates → ShardDocs (resolve segment objects from the snapshot)
        docs: List[ShardDoc] = []
        ctx_cache: Dict[tuple, Any] = {}
        for val, sh, seg_ord, local in cands:
            seg = shard_segs[sh][seg_ord]
            if sort_spec:
                key2 = (sh, seg_ord)
                ctx = ctx_cache.get(key2)
                if ctx is None:
                    ctx = SegmentContext(seg, svc.mappings, svc.analysis)
                    ctx_cache[key2] = ctx
                sv = tuple(_sort_value(ctx, s, local, None) for s in sort_spec)
                d = ShardDoc(sh, seg, local, float("nan"), sv)
            else:
                d = ShardDoc(sh, seg, local, val)
            d._seg_ord = seg_ord
            docs.append(d)
        if sort_spec:
            # exact host ordering on the full value tuple (device rank is the
            # f32 preselect, like the host loop's _sorted_candidates), staged
            # the way the host loop stages it: per-segment full-tuple top-k,
            # per-shard top-k, then the global merge — a global primary-rank
            # truncation would drop tied docs the full tuple ranks higher
            k_req = frm + size
            by_seg: Dict[tuple, List[ShardDoc]] = {}
            for d in docs:
                by_seg.setdefault((d.shard_ord, d._seg_ord), []).append(d)
            per_shard: Dict[int, List[ShardDoc]] = {}
            for (sh, _so), ds in sorted(by_seg.items()):
                ds.sort(key=lambda d: (_sort_key(d.sort_values, sort_spec),
                                       d.local_id))
                per_shard.setdefault(sh, []).extend(ds[:k_req])
            docs = []
            for sh in sorted(per_shard):
                ds = per_shard[sh]
                ds.sort(key=lambda d: (_sort_key(d.sort_values, sort_spec),
                                       d._seg_ord, d.local_id))
                docs.extend(ds[:k_req])
            docs.sort(key=lambda d: (_sort_key(d.sort_values, sort_spec),
                                     d.shard_ord, d._seg_ord, d.local_id))
        page = docs[frm: frm + size]
        max_score = None
        if not sort_spec and cands:
            max_score = max(v for v, *_ in cands)

        # fetch phase per shard, then restore global order
        by_shard: Dict[int, List[ShardDoc]] = {}
        for d in page:
            by_shard.setdefault(d.shard_ord, []).append(d)
        hits: List[dict] = []
        fetched_docs: List[ShardDoc] = []
        for sh, ds in by_shard.items():
            tf = time.perf_counter()
            hits.extend(searchers[sh].fetch_phase(ds, body, svc.name))
            searchers[sh].stats.on_fetch((time.perf_counter() - tf) * 1000,
                                         groups=body.get("stats"))
            fetched_docs.extend(ds)
        order = {id(d): i for i, d in enumerate(page)}
        hd = sorted(zip(hits, fetched_docs), key=lambda x: order[id(x[1])])
        hits = [h for h, _ in hd]

        response: Dict[str, Any] = {
            "took": int((time.perf_counter() - t0) * 1000),
            "timed_out": False,
            "_shards": {"total": len(searchers), "successful": len(searchers),
                        "failed": 0},
            "hits": {
                "total": totals,
                "max_score": None if (sort_spec or max_score is None) else max_score,
                "hits": hits,
            },
        }
    if aggs:
        if device_aggs:
            partial_lists, partial_shards = _agg_partials(
                aggs, agg_rounds, shard_segs)
        else:
            # arbitrary agg trees: host collectors over the program's mask
            # (same per-segment device reductions as the host loop — only
            # the query scoring isn't recomputed)
            import jax.numpy as jnp

            from elasticsearch_tpu.search.aggregations import run_aggs

            partial_lists = []
            partial_shards = []
            for sh, seg_ord, seg, mask in mask_rounds:
                ctx = SegmentContext(seg, svc.mappings, svc.analysis,
                                     global_stats,
                                     all_segments=shard_segs[sh],
                                     index_name=svc.name)
                partial_lists.append(run_aggs(aggs, ctx, jnp.asarray(mask)))
                partial_shards.append(sh)
        # ISSUE 16: cross-shard merges of the integer segment_sum lanes
        # ride the mesh_psum collective; float lanes keep the host f64
        # sum (byte-identical responses on either path)
        partial_lists = _psum_merge_partials(
            executor, aggs, partial_lists, partial_shards)
        with span("search.aggs"):
            response["aggregations"] = reduce_aggs(aggs, partial_lists)
        if not device_aggs:
            from elasticsearch_tpu.monitor import kernels

            # a tree out of the agg_tree program's shape, served here
            kernels.record("agg_declined_mesh")
    return response


def _terms_agg_eligible(agg, mappings) -> bool:
    from elasticsearch_tpu.search.aggregations.bucket import TermsAggregator

    if type(agg) is not TermsAggregator or agg.subs:
        return False
    field = agg.body.get("field")
    if field is None:
        return False
    fm = mappings.get(field)
    return fm is not None and fm.is_keyword


def _agg_partials(aggs, agg_rounds, shard_segs):
    """Device count vectors → per-(shard, segment) partial dicts in the same
    shape TermsAggregator.collect produces, so the existing reduce phase
    (and its ordering/size/min_doc_count handling) applies unchanged.
    Returns (partial_dicts, shard_of) — parallel lists; the shard ids feed
    the cross-shard psum merge."""
    by_seg: Dict[tuple, dict] = {}
    for agg in aggs:
        for sh, seg_ord, seg, counts in agg_rounds.get(agg.name, []):
            inv = seg.inverted.get(agg.body.get("field"))
            if inv is None:
                v = 0
                keys: List[str] = []
            else:
                v = inv.vocab_size
                keys = inv.terms
            cnt = counts[:v].astype(np.int64)
            partial = agg.partial_from_counts(cnt, keys)
            by_seg.setdefault((sh, seg_ord), {})[agg.name] = partial
    items = sorted(by_seg.items())
    return [p for _, p in items], [sh for (sh, _so), _ in items]


def _psum_merge_partials(executor, aggs, partial_dicts, partial_shards):
    """Cross-shard agg merges on the mesh (ISSUE 16's aggs leg): the
    integer lanes of the segment_sum partials — terms bucket doc_counts,
    value_count totals, avg/stats doc counts — stack into one per-shard
    vector and merge through the ``mesh_psum`` collective instead of the
    host sum loop. int32 psum is EXACT, so responses stay byte-identical
    to the host reduce; float lanes (sums) keep the host f64 fold in the
    original partial order for the same reason. Within-shard (cross-
    segment) folds stay on host — only the cross-SHARD reduction is a
    collective. Aggs the merge can't express keep their partials
    untouched; ``reduce_aggs`` handles the mix."""
    if executor is None or getattr(executor, "S", 1) < 2:
        return partial_dicts
    merged: Dict[str, Any] = {}
    for agg in aggs:
        rows = [(sh, p[agg.name])
                for sh, p in zip(partial_shards, partial_dicts)
                if p is not None and agg.name in p]
        if len({sh for sh, _ in rows}) < 2:
            continue  # nothing crosses a shard boundary
        try:
            m = _device_merge_one(executor, agg, rows)
        except Exception:  # tpulint: allow[R006] — the collective merge
            m = None       # is an optimization; host reduce owns fallback
        if m is not None:
            merged[agg.name] = m
    if not merged:
        return partial_dicts
    out = [{k: v for k, v in p.items() if k not in merged}
           for p in partial_dicts if p is not None]
    out = [p for p in out if p]
    out.append(merged)
    return out


def _psum_int_lanes(executor, per_shard: Dict[int, np.ndarray]):
    """{shard: int64[L]} → exact device-summed int64[L] via the mesh_psum
    collective, or None when a lane total would overflow int32 (the host
    fold handles it). Shards beyond the mesh size pre-fold onto slots
    round-robin (the executor's slot discipline) — integer adds, exact."""
    S = executor.S
    L = next(iter(per_shard.values())).shape[0]
    if L == 0:
        return None
    arr = np.zeros((S, L), np.int64)
    for sh, v in per_shard.items():
        arr[sh % S] += v
    if arr.min(initial=0) < 0 \
            or arr.sum(axis=0).max(initial=0) > np.iinfo(np.int32).max:
        return None
    return executor.psum_partials(arr.astype(np.int32)).astype(np.int64)


def _device_merge_one(executor, agg, rows):
    """One agg's cross-shard merge → a single pre-merged partial (what
    reduce() would produce intermediate counts for), or None when this
    agg type has no exact device form."""
    from elasticsearch_tpu.search.aggregations.bucket import TermsAggregator
    from elasticsearch_tpu.search.aggregations.metrics import (
        AvgAggregator, ExtendedStatsAggregator, StatsAggregator,
        ValueCountAggregator)

    if type(agg) is TermsAggregator:
        ps = [p for _, p in rows]
        if any("subs" in b for p in ps for b in p["buckets"].values()):
            return None  # sub-agg partials must reach reduce_subs intact
        keys = sorted({k for p in ps for k in p["buckets"]}, key=repr)
        idx = {k: i for i, k in enumerate(keys)}
        per_shard: Dict[int, np.ndarray] = {}
        for sh, p in rows:
            v = per_shard.setdefault(
                sh, np.zeros(len(keys) + 1, np.int64))
            for k2, b in p["buckets"].items():
                v[idx[k2]] += int(b["doc_count"])
            v[len(keys)] += int(p.get("sum_other_doc_count", 0))
        tot = _psum_int_lanes(executor, per_shard)
        if tot is None:
            return None
        return {
            "buckets": {k: {"doc_count": int(tot[i])}
                        for i, k in enumerate(keys)},
            "sum_other_doc_count": int(tot[len(keys)]),
            "order": rows[0][1].get("order", {"_count": "desc"}),
            "doc_count_error_upper_bound": 0,
        }
    if type(agg) is ValueCountAggregator:
        per_shard = {}
        for sh, p in rows:
            v = per_shard.setdefault(sh, np.zeros(1, np.int64))
            v[0] += int(p)
        tot = _psum_int_lanes(executor, per_shard)
        return None if tot is None else int(tot[0])
    if type(agg) is AvgAggregator:
        per_shard = {}
        s_host = 0.0  # f64 fold in partial order == reduce()'s own sum
        for sh, p in rows:
            v = per_shard.setdefault(sh, np.zeros(1, np.int64))
            v[0] += int(p[1])
            s_host += p[0]
        tot = _psum_int_lanes(executor, per_shard)
        return None if tot is None else (s_host, int(tot[0]))
    if type(agg) in (StatsAggregator, ExtendedStatsAggregator):
        per_shard = {}
        s_host = 0.0
        sq_host = 0.0
        mns: List[float] = []
        mxs: List[float] = []
        for sh, p in rows:
            v = per_shard.setdefault(sh, np.zeros(1, np.int64))
            v[0] += int(p["count"])
            s_host += p["sum"]
            if p["min"] is not None:
                mns.append(p["min"])
            if p["max"] is not None:
                mxs.append(p["max"])
            if type(agg) is ExtendedStatsAggregator:
                sq_host += p["sum_sq"]
        tot = _psum_int_lanes(executor, per_shard)
        if tot is None:
            return None
        out = {"count": int(tot[0]), "sum": s_host,
               "min": min(mns) if mns else None,
               "max": max(mxs) if mxs else None}
        if type(agg) is ExtendedStatsAggregator:
            out["sum_sq"] = sq_host
        return out
    return None
