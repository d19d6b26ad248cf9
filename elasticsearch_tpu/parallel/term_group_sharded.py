"""One sharded program a search: a plain term-group search over an index
whose shards each hold their one segment whole on a chip of their own.

The host loop (search/service.py ``search_shards``) runs a shard's query
phase after another's: S plans, S enqueues, S blocking pulls in series.
Here the S plans are built as there (each shard's own statistics, dense
rows and postings runs — ``queries.plan_term_group``), padded to ONE
``(R, T, P)`` class and packed as ``words[S, 2R+3T]``; the program is
``ops.scoring.bm25_term_group_topk`` itself under ``shard_map`` over a
('shard',) mesh of the shards' chips, its inputs global arrays assembled
from the arrays ALREADY resident on each chip (no second copy of a
shard); every shard's packed top-k is ``all_gather``ed, merged by (score
desc, shard, local doc) (``ops.scoring.merge_shard_topk``) and pulled
once. One ``device.dispatch``, one ``device.wait``, one argument a search.

The body is a COLLECTIVE region (tpulint R014): no host sync in its reach.

Anything this does not take — a query tree, a sort, aggregations, a
scroll, a shard with 0 or ≥ 2 segments or nested documents, shards whose
resident arrays differ in shape, two shards on one chip — returns None and
``search_shards`` serves it as before. A one-shard index never enters.
"""
from __future__ import annotations

import threading
from typing import List, Optional

import numpy as np

PROGRAM = "bm25_term_group_topk_sharded"

# what of a body rules the route out before any plan is built: all that
# makes query_phase's request not ``fused_ok``, and what a shard's query
# phase times, bounds or profiles on its own
_DECLINED_KEYS = ("sort", "aggs", "aggregations", "rescore", "scroll",
                  "profile", "terminate_after", "timeout")


def _declined(body: dict) -> bool:
    return (body.get("min_score") is not None
            or body.get("search_after") is not None
            or any(body.get(key) for key in _DECLINED_KEYS))


_LOCK = threading.Lock()
_MESHES: dict = {}
_PROGRAMS: dict = {}


def _mesh(devices: tuple):
    mesh = _MESHES.get(devices)
    if mesh is None:
        from jax.sharding import Mesh

        with _LOCK:
            mesh = _MESHES.setdefault(
                devices, Mesh(np.asarray(devices), ("shard",)))
    return mesh


def _program(mesh, R: int, T: int, P: int, D: int, k: int, topk_block: int):
    key = (mesh, R, T, P, D, k, topk_block)
    prog = _PROGRAMS.get(key)
    if prog is not None:
        return prog
    import jax
    from jax.sharding import PartitionSpec as PS

    from elasticsearch_tpu.ops.scoring import (bm25_term_group_topk,
                                               merge_shard_topk)

    def local(*blocks):
        # this chip's shard: its own rows of the global arrays
        impact = blocks[0] if R else None
        doc_ids, tfnorm, live, words = blocks[-4:]
        packed = bm25_term_group_topk(
            impact, doc_ids, tfnorm, live, None, words[0], R=R, T=T, P=P,
            D=D, k=k, topk_block=topk_block)
        return merge_shard_topk(jax.lax.all_gather(packed, "shard"), k=k)

    sharded = jax.shard_map(
        local, mesh=mesh,
        in_specs=((PS("shard", None),) if R else ())
        + (PS("shard"), PS("shard"), PS("shard"), PS("shard", None)),
        out_specs=PS(), check_vma=False)
    prog = jax.jit(sharded)
    with _LOCK:
        prog = _PROGRAMS.setdefault(key, prog)
    return prog


def _stackable(arrays, devices) -> bool:
    """One shape and dtype, and shard s's array on chip s alone."""
    first = arrays[0]
    return all(a.shape == first.shape and a.dtype == first.dtype
               and a.devices() == {d} for a, d in zip(arrays, devices))


def _global(mesh, spec, arrays):
    """One global array over the mesh from the S arrays resident on its
    chips: no copy, the buffers are the shards' own."""
    import jax
    from jax.sharding import NamedSharding

    first = arrays[0]
    shape = (len(arrays) * first.shape[0],) + tuple(first.shape[1:])
    return jax.make_array_from_single_device_arrays(
        shape, NamedSharding(mesh, spec), list(arrays))


def query_phase(searchers, body: dict, global_stats=None) -> Optional[List]:
    """The query phase of every shard as one program, or None where the
    request or the index is not this route's: a ``QueryPhaseResult`` a
    searcher, in ``searchers``' order, whose ``docs`` are the shard's part
    of the GLOBAL top-k (what the coordinator would have kept of it)."""
    S = len(searchers)
    if S < 2 or _declined(body):
        return None
    segs = []
    for s in searchers:
        if len(s.segments) != 1:
            return None
        seg = s.segments[0]
        if seg.device is None or seg.has_nested:
            return None
        segs.append(seg)
    devices = tuple(seg.device for seg in segs)
    D = segs[0].max_docs
    if len(set(devices)) != S or any(seg.max_docs != D for seg in segs):
        return None
    size, frm = int(body.get("size", 10)), int(body.get("from", 0))
    if frm + size > 10_000:
        return None  # the host loop raises what it raises today
    k = min(max(size + frm, 1), 10_000, D)

    from elasticsearch_tpu.monitor import kernels
    from elasticsearch_tpu.ops.scoring import (pack_term_group_words,
                                               topk_block_config,
                                               unpack_shard_topk)
    from elasticsearch_tpu.search.context import SegmentContext
    from elasticsearch_tpu.search.queries import (MatchQuery, TermQuery,
                                                  parse_query,
                                                  build_term_group_plan)
    from elasticsearch_tpu.search.service import QueryPhaseResult, ShardDoc
    from elasticsearch_tpu.tracing.tracer import span, tag_active
    from elasticsearch_tpu.utils.shapes import pad_to

    with span("search.rewrite"):
        queries = [parse_query(body.get("query")) for _ in searchers]
    if not isinstance(queries[0], (MatchQuery, TermQuery)):
        return None  # (neither has a shard-wide prepare pass)
    with span("search.plan"):
        plans = []
        for s, seg, query in zip(searchers, segs, queries):
            ctx = SegmentContext(seg, s.mappings, s.analysis, global_stats,
                                 all_segments=s.segments,
                                 index_name=s.index_name)
            plan = build_term_group_plan(ctx, query)
            if plan is None:
                return None
            plans.append(plan)
        R = max(0 if p.impact is None else p.qrows.shape[0] for p in plans)
        T = max(p.starts.shape[0] for p in plans)
        P = max(p.P for p in plans)
        impacts = []
        if R:
            for p in plans:
                impact = p.impact
                if impact is None:  # no dense row for THIS shard's terms
                    block = p.inv.dense_block()
                    if block is None:
                        return None
                    impact = block[1]
                impacts.append(impact)
        doc_ids = [p.inv.doc_ids for p in plans]
        tfnorm = [p.inv.tfnorm for p in plans]
        live = [seg.live for seg in segs]
        for arrays in (impacts, doc_ids, tfnorm, live):
            if arrays and not _stackable(arrays, devices):
                return None
        if P > doc_ids[0].shape[0]:
            return None
        # a shard's plan in the common class: -1/0 rows behind its own,
        # (0, 0) chunks behind its own, its chunks as they were cut in a
        # window that may be wider — the sums inside a shard keep their
        # order

        def rows(p):
            if not R:
                return None, None
            if p.impact is None:
                return np.full(R, -1, np.int32), np.zeros(R, np.float32)
            return pad_to(p.qrows, R, -1), pad_to(p.qrw, R, 0.0)

        words = np.stack([pack_term_group_words(
            *rows(p), pad_to(p.starts, T, 0), pad_to(p.lens, T, 0),
            pad_to(p.ws, T, 0.0)) for p in plans])
    kernels.record("bm25_hybrid" if R else "bm25_scatter", S)
    kernels.record("bm25_sharded_program")
    kernels.record("shard_exchange_bytes", S * (2 * k + 1) * 4)
    kernels.record("tail_window_slots", S * T * P)
    kernels.record("tail_window_postings",
                   int(sum(int(p.lens.sum()) for p in plans)))
    with span("device.dispatch", program=PROGRAM, shards=S):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as PS

        mesh = _mesh(devices)
        prog = _program(mesh, R, T, P, D, k, topk_block_config())
        args = [_global(mesh, PS("shard"), a)
                for a in (doc_ids, tfnorm, live)]
        if R:
            args.insert(0, _global(mesh, PS("shard", None), impacts))
        # the search's ONE argument: a row of words a chip
        args.append(jax.device_put(  # tpulint: offbudget
            words, NamedSharding(mesh, PS("shard", None))))
        packed_dev = prog(*args)
    with span("device.wait"):
        packed = np.asarray(packed_dev)
        tag_active(bytes=packed.nbytes)
    vals, shard, local, totals = unpack_shard_topk(packed, k, S)
    results = [QueryPhaseResult(docs=[], total_hits=int(t),
                                max_score=float("nan")) for t in totals]
    for v, si, li in zip(vals, shard, local):
        if np.isfinite(v):
            r = results[int(si)]
            r.docs.append(ShardDoc(int(si), segs[int(si)], int(li),
                                   float(v)))
            if len(r.docs) == 1:  # a shard's docs arrive best first
                r.max_score = float(v)
    return results
