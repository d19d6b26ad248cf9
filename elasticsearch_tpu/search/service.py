"""Search service: query-then-fetch over a shard's segments.

Reference: org/elasticsearch/search/SearchService.java (executeQueryPhase /
executeFetchPhase), search/query/QueryPhase.java, search/fetch/FetchPhase.java,
action/search/type/TransportSearchQueryThenFetchAction.java (the two-phase
scatter/gather contract), search/sort/SortParseElement.java.

Per shard: every segment executes the compiled query program → (scores,
mask); top-k (possibly sort-keyed) candidates come back as (segment, local,
score, sort_values); shard results merge on the coordinating side
(cluster/search coordinator or parallel/executor for the mesh path);
the fetch phase materializes _source/highlight for the final page only.
"""
from __future__ import annotations

import time
import uuid
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from elasticsearch_tpu.ops.scoring import (finish_topk, hit_mask,
                                           topk_block_config, topk_with_mask,
                                           unpack_topk_result)
from elasticsearch_tpu.search.aggregations import parse_aggs, reduce_aggs, run_aggs
from elasticsearch_tpu.search.context import GlobalStats, SegmentContext
from elasticsearch_tpu.search.highlight import extract_query_terms, highlight_field
from elasticsearch_tpu.search.queries import (parse_query, plan_term_group,
                                              term_group_topk)
from elasticsearch_tpu.utils.errors import SearchParseException


def _jnp():
    import jax.numpy as jnp

    return jnp


@dataclass
class ShardDoc:
    """One candidate doc from the query phase (pre-fetch)."""

    shard_ord: int
    seg: Any  # TpuSegment
    local_id: int
    score: float
    sort_values: Tuple = ()


@dataclass
class QueryPhaseResult:
    docs: List[ShardDoc]
    total_hits: int
    max_score: float
    agg_partials: Optional[dict] = None
    # scroll snapshot (score-ordered scrolls): complete per-segment orders as
    # compact numpy arrays — (segment, int32 order of ALL matches, f32 scores)
    full: Optional[List[Tuple[Any, np.ndarray, np.ndarray]]] = None
    terminated_early: bool = False
    timed_out: bool = False
    # ?profile=true: TPU phase breakdown (tracing/profiler.py), JSON-safe
    profile: Optional[dict] = None
    # hybrid retrieval status (search/hybrid.py): stage-2 rerank outcome —
    # {"rerank": "applied"|"declined", ...}; a breaker decline degrades the
    # request to stage-1 results with this typed partial marker (never a 500)
    hybrid: Optional[dict] = None


def _parse_timeout(v) -> Optional[float]:
    """Request timeout → seconds ("10ms", "1s", "2m", or numeric millis)."""
    if v in (None, -1, "-1"):
        return None
    s = str(v).strip().lower()
    for suf, mul in (("ms", 1e-3), ("s", 1.0), ("m", 60.0), ("h", 3600.0)):
        if s.endswith(suf) and s[: -len(suf)].replace(".", "", 1).isdigit():
            return float(s[: -len(suf)]) * mul
    try:
        return float(s) * 1e-3  # bare number = millis (ES convention)
    except ValueError:
        raise SearchParseException(f"failed to parse timeout value [{v}]")


# in-memory scroll registry: scroll_id -> (snapshot state)
_SCROLLS: Dict[str, dict] = {}


class ShardSearcher:
    """Executes search phases against one shard (list of segments)."""

    def __init__(self, segments, mappings, analysis, shard_ord: int = 0,
                 index_name: str = ""):
        from elasticsearch_tpu.monitor.stats import SearchStats

        self.segments = segments
        self.mappings = mappings
        self.analysis = analysis
        self.shard_ord = shard_ord
        self.index_name = index_name
        self.stats = SearchStats()

    # -- query phase -----------------------------------------------------------

    def query_phase(self, body: dict, global_stats: Optional[GlobalStats] = None,
                    collect_full: bool = False) -> QueryPhaseResult:
        from elasticsearch_tpu.search.joins import prepare_tree

        # ?profile=true: per-phase timing with device compile/execute
        # split (tracing/profiler.py). Scroll snapshots profile nothing —
        # their cost is the snapshot, not the phases.
        from contextlib import nullcontext

        from elasticsearch_tpu.tracing.tracer import span, tag_active

        prof = None
        if body.get("profile") and not collect_full:
            from elasticsearch_tpu.tracing.profiler import PhaseTimer

            prof = PhaseTimer()

        def _p(span_name: Optional[str], phase: Optional[str] = None,
               **tags):
            """The tracer span of a boundary and, under ?profile=true,
            the same duration filed under the profile's phase: one set
            of clocks. A phase with no span (the host collectors of aggs,
            rerank) keeps the timer's own."""
            if span_name is None:
                return prof.phase(phase) if prof is not None \
                    else nullcontext()
            sp = span(span_name, **tags)
            if prof is None or phase is None:
                return sp
            return prof.span_phase(sp, phase)

        def _dev(fn, bucket: Optional[str] = None):
            """A device call, timed into the profile's compile/execute
            split (and ``bucket``) under ?profile=true."""
            return fn() if prof is None else prof.device_call(fn, bucket)

        with _p("search.rewrite", "rewrite"):
            query = parse_query(body.get("query"))
            prepare_tree(query, self.segments, self.mappings, self.analysis,
                         global_stats)
        aggs = parse_aggs(body.get("aggs") or body.get("aggregations"))
        size = int(body.get("size", 10))
        frm = int(body.get("from", 0))
        if not collect_full and frm + size > 10_000:
            # explicit, like ES's index.max_result_window — never a silent cap
            raise SearchParseException(
                f"Result window is too large, from + size must be less than "
                f"or equal to: [10000] but was [{frm + size}]. Use scroll or "
                f"search_after for deep pagination.")
        k = min(max(size + frm, 1), 10_000)
        min_score = body.get("min_score")
        sort_spec = _parse_sort(body.get("sort"))
        if collect_full and body.get("search_type") == "scan":
            sort_spec = []  # scan ignores sort entirely (ScanContext)
        search_after = body.get("search_after")
        if search_after is not None and not sort_spec:
            raise SearchParseException(
                "Sort must contain at least one field when using [search_after]")
        if search_after is not None and len(search_after) != len(sort_spec):
            raise SearchParseException(
                f"search_after has {len(search_after)} value(s) but sort has "
                f"{len(sort_spec)}")
        rescore_specs = []
        if body.get("rescore") and sort_spec:
            raise SearchParseException(
                "cannot use [rescore] in combination with [sort]")
        if body.get("rescore") and collect_full:
            raise SearchParseException(
                "cannot use [rescore] in combination with [scroll]")
        if body.get("rescore"):
            from elasticsearch_tpu.search.rescore import parse_rescore

            rescore_specs = parse_rescore(body["rescore"])
            # candidate pool must cover the largest rescore window
            # (reference: query phase collects max(window_size, from+size))
            k = min(max([k] + [s["window_size"] for s in rescore_specs]), 10_000)

        docs: List[ShardDoc] = []
        total = 0
        max_score = float("-inf")
        agg_partials: List[dict] = []
        # score-ordered scrolls snapshot EVERY match as compact arrays (no
        # 10k cap, no re-read of live state between pages); sorted scrolls
        # materialize the complete candidate list instead
        full_snap = [] if (collect_full and not sort_spec) else None
        scan = collect_full and body.get("search_type") == "scan"
        # terminate_after caps the per-shard COLLECTED count; timeout stops
        # between segments (whole-segment programs aren't interruptible —
        # the boundary is the segment, like Lucene's per-leaf cancellation)
        terminate_after = body.get("terminate_after")
        terminate_after = int(terminate_after) if terminate_after else None
        timeout_s = _parse_timeout(body.get("timeout"))
        t_begin = time.perf_counter()
        terminated_early = False
        timed_out = False
        # plain: score-ordered top-k, no snapshot of every match — one
        # finishing program a segment (ops.scoring.finish_topk)
        plain = not sort_spec and full_snap is None
        # single-program paths: eligible request shapes hand the whole
        # segment to one program (hybrid_fused_topk, term_group_topk)
        fused_ok = (plain and not aggs and min_score is None
                    and search_after is None and not rescore_specs
                    and not collect_full)
        # a size-0 aggregation tree the program covers: filter, keys and
        # per-bucket metrics as ONE program a segment (ops/aggs.py)
        agg_tree_ok = (bool(aggs) and size == 0 and plain
                       and min_score is None and search_after is None
                       and not rescore_specs and terminate_after is None
                       and not body.get("post_filter"))
        from elasticsearch_tpu.search.hybrid import HybridQuery
        # attach the profile timer for the duration of segment execution
        # so fielddata rehydrations (resources/residency.py) file under
        # the `rehydrate` phase of THIS request (explicitly scoped — see
        # profiler.attached)
        from elasticsearch_tpu.tracing import profiler as _profmod

        with _profmod.attached(prof):
            for seg in self.segments:
                if timeout_s is not None and (time.perf_counter() - t_begin
                                              > timeout_s):
                    timed_out = True
                    break
                if terminate_after is not None and total >= terminate_after:
                    terminated_early = True
                    break
                with _p("search.plan", "executor_build"):
                    ctx = SegmentContext(seg, self.mappings, self.analysis,
                                         global_stats,
                                         all_segments=self.segments,
                                         index_name=self.index_name)
                if prof is not None:
                    prof.segments += 1
                kk = min(k, seg.max_docs)
                if aggs:
                    served = (_agg_program(ctx, query, aggs, _p, _dev)
                              if agg_tree_ok and not seg.has_nested
                              else None)
                    if served is not None:
                        total += served[0]
                        agg_partials.append(served[1])
                        continue
                    from elasticsearch_tpu.monitor import kernels

                    kernels.record("agg_declined")
                if fused_ok and not seg.has_nested \
                        and isinstance(query, HybridQuery):
                    # hybrid stage 1: BOTH engines + fusion + top-k as ONE
                    # device program (search/hybrid.py). Zero fused scores
                    # are legitimate hits (linear fusion of a 0.0 cosine),
                    # so the filter is isfinite-only — -inf marks top-k
                    # padding beyond the match count.
                    from elasticsearch_tpu.search.hybrid import hybrid_fused_topk

                    fused = _dev(lambda: hybrid_fused_topk(ctx, query, kk),
                                 "fuse")
                    if fused is not None:
                        vals, ids, seg_total = fused
                        total += seg_total
                        for v, i in zip(vals, ids):
                            if np.isfinite(v):
                                max_score = max(max_score, float(v))
                                docs.append(ShardDoc(self.shard_ord, seg,
                                                     int(i), float(v)))
                        continue
                # a pure disjunctive term group under a plain request is
                # planned ONCE and served by a single-program path
                plan = plan_term_group(ctx, query) if fused_ok else None
                if plan is not None:
                    packed_dev = _dev(lambda: term_group_topk(ctx, plan, kk),
                                      "topk")
                else:
                    scores, mask = _dev(lambda: query.score_or_mask(ctx))
                    if plain:
                        # mask ops, count, top-k and pack as ONE program
                        # after the query's own (ops.scoring.finish_topk)
                        with _p("device.dispatch", program="finish_topk"):
                            packed_dev, mask = _dev(lambda: finish_topk(
                                scores, mask, seg.live,
                                seg.roots_dev if seg.has_nested else None,
                                None if min_score is None
                                else float(min_score),
                                k=kk, topk_block=topk_block_config(),
                                with_mask=bool(aggs)), "topk")
                    else:
                        # the sorted and scroll-snapshot branches go on
                        # composing [D] vectors: the mask rule and its
                        # count as one program, no top-k
                        with _p("device.dispatch", program="mask_ops"):
                            mask, tot_dev = hit_mask(
                                scores, mask, seg.live,
                                seg.roots_dev if seg.has_nested else None,
                                None if min_score is None
                                else float(min_score))
                if aggs:
                    with _p(None, "aggs"):
                        agg_partials.append(run_aggs(aggs, ctx, mask))
                if sort_spec:
                    total += int(tot_dev)
                    seg_k = seg.max_docs if collect_full else k
                    with _p(None, "topk"):
                        seg_docs = self._sorted_candidates(ctx, scores, mask,
                                                           sort_spec, seg_k,
                                                           search_after)
                elif full_snap is not None:
                    total += int(tot_dev)
                    sc = np.asarray(scores)
                    mk = np.asarray(mask)
                    if scan:
                        # scan search_type: index order, no ranking (reference:
                        # search/scan/ScanContext.java — docs stream in doc-id
                        # order; the initial page returns no hits)
                        order = np.nonzero(mk[: seg.num_docs])[0].astype(np.int32)
                        full_snap.append((seg, order, sc))
                        seg_docs = []
                    else:
                        n_match = int(mk[: seg.num_docs].sum())
                        eff = np.where(mk, sc, -np.inf)
                        order = np.argsort(-eff, kind="stable")[:n_match].astype(np.int32)
                        full_snap.append((seg, order, sc))
                        seg_docs = [
                            ShardDoc(self.shard_ord, seg, int(i), float(sc[i]))
                            for i in order[: min(k, order.size)]
                        ]
                else:
                    # ONE host transfer: per-array pulls each pay a fixed
                    # device round-trip (network-attached chips: ~5-20 ms)
                    with _p("device.wait", "host_sync"):
                        packed = np.asarray(packed_dev)
                        tag_active(bytes=packed.nbytes)
                    vals, idx, tot = unpack_topk_result(packed, kk)
                    total += tot
                    seg_docs = [
                        ShardDoc(self.shard_ord, seg, int(i), float(v))
                        for v, i in zip(vals, idx)
                        if np.isfinite(v)
                    ]
                for d in seg_docs:
                    if np.isfinite(d.score):
                        max_score = max(max_score, d.score)
                docs.extend(seg_docs)

        # merge segment candidates
        if sort_spec:
            docs.sort(key=lambda d: _sort_key(d.sort_values, sort_spec))
        else:
            docs.sort(key=lambda d: (-d.score, d.seg.seg_id, d.local_id))
        if not (collect_full and sort_spec):
            docs = docs[:k]
        hybrid_status = None
        if (isinstance(query, HybridQuery) and query.rerank is not None
                and not sort_spec and not collect_full):
            # stage 2: MaxSim re-rank of the merged top-k window. Breaker
            # denial comes back as the typed "declined" dict with every
            # stage-1 score untouched (apply_hybrid_rerank catches it).
            from elasticsearch_tpu.search.hybrid import apply_hybrid_rerank

            with _p(None, "rerank"):
                hybrid_status = apply_hybrid_rerank(
                    docs, query, self.mappings, self.analysis)
            max_score = max((d.score for d in docs
                             if np.isfinite(d.score)), default=float("-inf"))
        if rescore_specs:
            from elasticsearch_tpu.search.rescore import apply_rescore

            apply_rescore(docs, rescore_specs, self.mappings, self.analysis,
                          segments=self.segments)
            docs = docs[: min(max(size + frm, 1), 10_000)]
            max_score = max((d.score for d in docs), default=float("-inf"))
        if terminate_after is not None and total >= terminate_after:
            terminated_early = True
            total = min(total, terminate_after)
        merged_aggs = agg_partials if aggs else None
        return QueryPhaseResult(
            docs=docs,
            total_hits=total,
            max_score=max_score if docs and max_score != float("-inf") else float("nan"),
            agg_partials={"_list": merged_aggs, "_aggs": aggs} if aggs else None,
            full=full_snap,
            terminated_early=terminated_early,
            timed_out=timed_out,
            profile=prof.to_json() if prof is not None else None,
            hybrid=hybrid_status,
        )

    def _sorted_candidates(self, ctx, scores, mask, sort_spec, k, search_after):
        """Sort by field(s): oversampled device top-k on the primary key,
        exact host ordering on the full key tuple."""
        jnp = _jnp()
        primary = sort_spec[0]
        key_vec, _ = _sort_key_vector(ctx, primary, scores)
        sel = mask
        if search_after is not None:
            sa = search_after[0]
            if isinstance(sa, (int, float)) and not isinstance(sa, bool):
                # device prefilter on the primary key — NON-strict so docs
                # tied on key[0] survive; the exact full-tuple cursor
                # comparison happens on host below (reference: ES compares
                # the whole sort tuple, FieldDoc searchAfter semantics)
                sa_f = float(sa) - (primary.get("_offset") or 0.0)
                if primary["order"] == "desc":
                    sel = sel & (key_vec <= sa_f)
                else:
                    sel = sel & (key_vec >= sa_f)
        oversample = min(max(k * 4, 128), ctx.segment.max_docs)
        dirn = 1.0 if primary["order"] == "desc" else -1.0
        vals, idx = topk_with_mask(key_vec * dirn, sel, k=oversample)
        vals = np.asarray(vals)
        idx = np.asarray(idx)
        cand = [int(i) for v, i in zip(vals, idx) if np.isfinite(v)]
        np_scores = np.asarray(scores)
        out = []
        for local in cand:
            sv = tuple(_sort_value(ctx, s, local, np_scores) for s in sort_spec)
            if search_after is not None and not _after_cursor(sv, search_after, sort_spec):
                continue
            out.append(ShardDoc(self.shard_ord, ctx.segment, local, float(np_scores[local]), sv))
        out.sort(key=lambda d: _sort_key(d.sort_values, sort_spec))
        return out[:k]

    # -- fetch phase -----------------------------------------------------------

    def fetch_phase(self, docs: List[ShardDoc], body: dict, index_name: str = "") -> List[dict]:
        query = parse_query(body.get("query"))
        src_filter = body.get("_source", True)
        hl = body.get("highlight")
        want_version = bool(body.get("version", False))
        script_fields = body.get("script_fields")
        stored_fields = body.get("stored_fields", body.get("fields"))
        sf_cache: Dict[Tuple[int, str], Any] = {}  # (seg_id, field) → values
        hits = []
        for d in docs:
            tcol = d.seg.keywords.get("_type")
            tvals = tcol.host_values[d.local_id] if tcol is not None else None
            hit: Dict[str, Any] = {
                # the owning index, not the (possibly comma-joined) request
                # expression — multi-index searches report per-hit provenance
                "_index": self.index_name or index_name,
                "_type": tvals[0] if tvals else "_doc",
                "_id": d.seg.ids[d.local_id],
                "_score": None if d.sort_values else d.score,
            }
            if d.sort_values:
                hit["sort"] = [v if not isinstance(v, tuple) else list(v) for v in d.sort_values]
                hit["_score"] = None
            src = d.seg.sources[d.local_id]
            filtered = _filter_source(src, src_filter)
            if filtered is not None:
                hit["_source"] = filtered
            if stored_fields:
                names = ([stored_fields] if isinstance(stored_fields, str)
                         else list(stored_fields))
                flds = {}
                for f in names:
                    if f == "_source":
                        continue
                    sv = d.seg.stored[d.local_id].get(f) if d.seg.stored[d.local_id] else None
                    if sv is None and src:
                        # non-stored leaves extract from _source, dotted
                        # paths included (2.0 FetchPhase fields loading)
                        cur = source_path(src, f)
                        if cur is not None:
                            sv = cur if isinstance(cur, list) else [cur]
                    if sv is not None:
                        flds[f] = sv
                if flds:
                    hit["fields"] = flds
                if "_source" not in names and "_source" not in body:
                    # a fields list suppresses _source unless asked for
                    hit.pop("_source", None)
            if script_fields:
                hit.setdefault("fields", {})
                for fname, spec in script_fields.items():
                    hit["fields"][fname] = [
                        self._script_field(d, spec, fname, sf_cache)]
            if hl:
                ctx = SegmentContext(d.seg, self.mappings, self.analysis)
                hit["highlight"] = self._highlight(ctx, query, src, hl)
            hits.append(hit)
        self._attach_matched_queries(query, docs, hits)
        self._attach_inner_hits(query, docs, hits, index_name)
        return hits

    def _attach_matched_queries(self, query, docs: List[ShardDoc],
                                hits: List[dict]) -> None:
        """matched_queries (reference: search/fetch/matchedqueries/
        MatchedQueriesFetchSubPhase.java:1-95): for each _name'd node in
        the query tree, report which page hits its mask matches — one mask
        evaluation per (segment, name), never per doc."""
        from elasticsearch_tpu.search.queries import collect_named

        named = collect_named(query)
        if not named:
            return
        cache: Dict[tuple, Optional[np.ndarray]] = {}
        for d, hit in zip(docs, hits):
            names = []
            for nm, node in named:
                key = (nm, id(d.seg))
                mk = cache.get(key, False)
                if mk is False:
                    try:
                        ctx = SegmentContext(d.seg, self.mappings,
                                             self.analysis)
                        mk = np.asarray(node.execute(ctx)[1])
                    except Exception:
                        mk = None  # e.g. join nodes needing prepare_tree
                    cache[key] = mk
                if mk is not None and mk[d.local_id]:
                    names.append(nm)
            if names:
                hit["matched_queries"] = names

    def _attach_inner_hits(self, query, docs: List[ShardDoc], hits: List[dict],
                           index_name: str) -> None:
        """inner_hits for nested queries (reference: search/fetch/innerhits/
        InnerHitsFetchSubPhase.java): per root hit, the matching children of
        the nested path, their _source extracted from the root's source."""
        from elasticsearch_tpu.search.joins import collect_nested_inner_hits

        nq_list = collect_nested_inner_hits(query)
        if not nq_list:
            return
        sel_cache: Dict[Tuple[int, int], np.ndarray] = {}
        for nq_i, nq in enumerate(nq_list):
            name = nq.inner_hits.get("name", nq.path)
            ih_size = int(nq.inner_hits.get("size", 3))
            ih_from = int(nq.inner_hits.get("from", 0))
            for d, hit in zip(docs, hits):
                seg = d.seg
                if not seg.has_nested or nq.path not in seg.nested_paths:
                    continue
                key = (nq_i, seg.seg_id)
                cached = sel_cache.get(key)
                if cached is None:
                    ctx = SegmentContext(seg, self.mappings, self.analysis)
                    sel, child_scores = nq.child_selection(ctx)
                    cached = (np.asarray(sel), np.asarray(child_scores))
                    sel_cache[key] = cached
                sel_np, scores_np = cached
                kids = np.nonzero(sel_np[: seg.num_docs]
                                  & (seg.root_id_host[: seg.num_docs] == d.local_id))[0]
                if kids.size == 0:
                    continue
                order = kids[np.argsort(-scores_np[kids], kind="stable")]
                window = order[ih_from : ih_from + ih_size]
                root_src = seg.sources[d.local_id] or {}
                child_hits = []
                for k in window:
                    ordn = int(seg.nested_ord_host[k])
                    sub = _nested_sub_source(root_src, nq.path, ordn)
                    child_hits.append({
                        "_index": self.index_name or index_name,
                        "_id": hit["_id"],
                        "_nested": {"field": nq.path, "offset": ordn},
                        "_score": float(scores_np[k]),
                        "_source": sub,
                    })
                hit.setdefault("inner_hits", {})[name] = {
                    "hits": {
                        "total": int(kids.size),
                        "max_score": float(scores_np[order[0]]),
                        "hits": child_hits,
                    }
                }

    def _script_field(self, d: ShardDoc, spec, fname: str = "",
                      cache: Optional[dict] = None):
        """Script-field value for one hit. Scripts evaluate to a whole
        per-segment vector, so the (segment, field) result — pulled to host
        once — is cached across the hits of one fetch and indexed per hit
        (the per-hit recompute was one script run + one device sync per
        hit per field)."""
        from elasticsearch_tpu.search.function_score import doc_resolver
        from elasticsearch_tpu.search.scripting import (compile_script,
                                                        script_source)

        key = (d.seg.seg_id, fname)
        vals = cache.get(key) if cache is not None else None
        if vals is None:
            s = spec.get("script", spec) if isinstance(spec, dict) else spec
            src = script_source(s)
            params = {} if isinstance(s, str) else s.get("params", {})
            ctx = SegmentContext(d.seg, self.mappings, self.analysis)
            vals = compile_script(src).run(doc_resolver(ctx), params=params)
            if hasattr(vals, "shape") or hasattr(vals, "item"):
                # host copy once per segment — 0-d device scalars included,
                # else float(vals) below would sync the device per hit
                vals = np.asarray(vals)
            if cache is not None:
                cache[key] = vals
        if hasattr(vals, "shape") and getattr(vals, "shape", ()) != ():
            return float(vals[d.local_id])
        return float(vals) if hasattr(vals, "item") or isinstance(vals, (int, float)) else vals

    def _highlight(self, ctx, query, src, hl_spec) -> Dict[str, List[str]]:
        out = {}
        pre = (hl_spec.get("pre_tags") or ["<em>"])[0]
        post = (hl_spec.get("post_tags") or ["</em>"])[0]
        for fname, fspec in hl_spec.get("fields", {}).items():
            fm = self.mappings.get(fname)
            if fm is None or src is None:
                continue
            raw = src.get(fname)
            if not isinstance(raw, str):
                continue
            terms = extract_query_terms(query, fname, ctx)
            analyzer = ctx.search_analyzer(fname)
            frags = highlight_field(
                raw, terms, analyzer,
                pre_tag=pre, post_tag=post,
                fragment_size=int(fspec.get("fragment_size", 100)),
                number_of_fragments=int(fspec.get("number_of_fragments", 5)),
            )
            if frags:
                out[fname] = frags
        return out

    def count(self, body: dict) -> int:
        jnp = _jnp()
        query = parse_query(body.get("query"))
        from elasticsearch_tpu.search.joins import prepare_tree

        prepare_tree(query, self.segments, self.mappings, self.analysis)
        total = 0
        for seg in self.segments:
            ctx = SegmentContext(seg, self.mappings, self.analysis)
            _, mask = query.execute(ctx)
            mask = mask & seg.live
            if seg.has_nested:
                mask = mask & seg.roots_dev
            total += int(jnp.sum(mask.astype(jnp.int32)))
        return total


# ---------------------------------------------------------------------------
# coordinating search across shards (single node)
# ---------------------------------------------------------------------------

def _agg_program(ctx, query, aggs, _p, _dev):
    """(matching documents, agg partials) of one segment from ONE
    ``agg_tree`` program and one pull, or None where the request or the
    segment is out of the program's scope (search/aggregations/
    program.py)."""
    from elasticsearch_tpu.monitor import kernels
    from elasticsearch_tpu.search.aggregations import program
    from elasticsearch_tpu.tracing.tracer import tag_active

    with _p("search.plan", "executor_build"):
        plan = program.plan(ctx, query, aggs)
    if plan is None:
        return None
    kernels.record("agg_one_program")
    with _p("device.dispatch", program="agg_tree"):
        words_dev = _dev(lambda: program.dispatch(ctx, plan), "aggs")
    with _p("device.wait", "host_sync"):
        words = np.asarray(words_dev)
        tag_active(bytes=words.nbytes)
    with _p("search.aggs", "aggs"):
        return program.partials(ctx, plan, words)


def search_shards(
    searchers: List[ShardSearcher],
    body: dict,
    index_name: str = "",
    global_stats: Optional[GlobalStats] = None,
) -> dict:
    """Query-then-fetch across shards, ES response shape."""
    t0 = time.perf_counter()
    size = int(body.get("size", 10))
    frm = int(body.get("from", 0))
    sort_spec = _parse_sort(body.get("sort"))
    if body.get("scroll") and body.get("search_type") == "scan":
        sort_spec = []  # scan ignores sort entirely (ScanContext)

    # scroll snapshots the COMPLETE match set (point-in-time: segment object
    # refs pin the frozen segments; merges/deletes between pages can't
    # corrupt fetches) — score-ordered scrolls as compact numpy arrays,
    # sorted scrolls as full candidate lists
    scroll = bool(body.get("scroll"))
    profile = bool(body.get("profile"))
    shard_profiles: List[dict] = []
    results = []
    # per-shard breaker trips degrade to partial results with an
    # ES-shaped `_shards.failures[]` entry, the same accounting the
    # distributed coordinator gives a dead peer (reference:
    # ShardSearchFailure). ONLY CircuitBreakingException degrades here —
    # parse errors etc. must keep failing the whole request with their
    # own status, and unexpected bugs must surface as 500s, not as
    # silently thinner results.
    from elasticsearch_tpu.utils.errors import CircuitBreakingException

    shard_failures: List[dict] = []
    # shards that each hold their one segment on a chip of their own
    # answer a plain term-group search with ONE program over all of them
    # (parallel/term_group_sharded.py); None: shard after shard, below
    from elasticsearch_tpu.parallel import term_group_sharded

    tq = time.perf_counter()
    sharded = term_group_sharded.query_phase(searchers, body, global_stats)
    for pos, s in enumerate(searchers):
        if sharded is not None:
            # (its docs carry their searcher's list position already)
            results.append(sharded[pos])
            s.stats.on_query((time.perf_counter() - tq) * 1000,
                             groups=body.get("stats"))
            continue
        tq = time.perf_counter()
        try:
            r = s.query_phase(body, global_stats, collect_full=scroll)
        except CircuitBreakingException as e:
            shard_failures.append({
                "shard": pos, "index": s.index_name or index_name,
                "node": None, "status": e.status,
                "reason": {"type": e.error_type, "reason": str(e)}})
            r = QueryPhaseResult(docs=[], total_hits=0,
                                 max_score=float("nan"))
        # fetch resolves searchers positionally in THIS list — stamp each
        # candidate with its searcher's list position rather than trusting
        # the searcher's own shard_ord (shared, and multi-index searches
        # would otherwise have to renumber persistent searcher state)
        for d in r.docs:
            d.shard_ord = pos
        q_ms = (time.perf_counter() - tq) * 1000
        s.stats.on_query(q_ms, groups=body.get("stats"))
        results.append(r)
        if profile:
            from elasticsearch_tpu.tracing.profiler import \
                shard_profile_entry

            shard_profiles.append(shard_profile_entry(
                f"[{s.index_name or index_name or 'shard'}][{pos}]",
                int(q_ms * 1e6), r.profile))
    if shard_failures and len(shard_failures) == len(searchers):
        # graceful degradation has a floor: NOTHING answered (reference:
        # SearchPhaseExecutionException "all shards failed") — re-raise
        # the breaker error so the client sees the 429
        raise CircuitBreakingException(
            "all shards failed: "
            + "; ".join(f["reason"]["reason"] for f in shard_failures))
    # indices_boost: per-index score multipliers applied BEFORE the global
    # merge (reference: SearchRequest.indicesBoost / query-phase boost)
    ib = body.get("indices_boost")
    if ib:
        import fnmatch as _fn

        items = (ib.items() if isinstance(ib, dict)
                 else [(k, v) for d in ib for k, v in d.items()])
        boosts = [(pat, float(v)) for pat, v in items]
        for s, r in zip(searchers, results):
            b = next((v for pat, v in boosts
                      if _fn.fnmatch(s.index_name, pat)), None)
            if b is None or b == 1.0:
                continue
            for d in r.docs:
                if np.isfinite(d.score):
                    d.score *= b
            if not np.isnan(r.max_score):
                r.max_score *= b
            if r.full:
                # snapshot scores may be read-only views of device arrays —
                # rebuild rather than multiply in place
                r.full = [(seg, order, sc * b) for seg, order, sc in r.full]
    all_docs: List[ShardDoc] = []
    total = 0
    max_score = float("-inf")
    for r in results:
        all_docs.extend(r.docs)
        total += r.total_hits
        if r.docs and not np.isnan(r.max_score):
            max_score = max(max_score, r.max_score)
    if sort_spec:
        all_docs.sort(key=lambda d: _sort_key(d.sort_values, sort_spec))
    else:
        all_docs.sort(key=lambda d: (-d.score, d.shard_ord, d.local_id))

    # score-ordered scroll: one complete global snapshot in compact arrays.
    # Page 1 is served FROM the snapshot so its tie ordering and every later
    # page's agree exactly (keys: -score, shard, local, then segment).
    snapshot = None
    scan = scroll and body.get("search_type") == "scan"
    if scroll and not sort_spec:
        segs: List[Tuple[int, Any]] = []
        seg_of_parts, shard_parts, local_parts, score_parts = [], [], [], []
        for pos, r in enumerate(results):
            for seg, order, sc in (r.full or []):
                si = len(segs)
                segs.append((pos, seg))
                seg_of_parts.append(np.full(order.size, si, dtype=np.int32))
                shard_parts.append(np.full(order.size, pos, dtype=np.int32))
                local_parts.append(order)
                score_parts.append(sc[order].astype(np.float32))
        if segs:
            seg_of = np.concatenate(seg_of_parts)
            shard_of = np.concatenate(shard_parts)
            local = np.concatenate(local_parts)
            score = np.concatenate(score_parts)
            if scan:
                # scan: stream in (shard, segment, doc-id) order, unranked
                glob = np.lexsort((local, seg_of, shard_of))
            else:
                glob = np.lexsort((seg_of, local, shard_of, -score))
            snapshot = {"segs": segs, "seg_of": seg_of[glob],
                        "local": local[glob], "score": score[glob]}
        else:
            snapshot = {"segs": [], "seg_of": np.empty(0, np.int32),
                        "local": np.empty(0, np.int32),
                        "score": np.empty(0, np.float32)}
        segs_l = snapshot["segs"]
        if scan:
            page = []  # scan's first response carries no hits — only the
            # scroll id and total (reference: ScanContext)
        else:
            page = [
                ShardDoc(segs_l[si][0], segs_l[si][1], int(li), float(sc))
                for si, li, sc in zip(snapshot["seg_of"][frm: frm + size],
                                      snapshot["local"][frm: frm + size],
                                      snapshot["score"][frm: frm + size])
            ]
    else:
        page = all_docs[frm : frm + size]

    from elasticsearch_tpu.tracing.tracer import span

    with span("search.fetch"):
        by_shard: Dict[int, List[ShardDoc]] = {}
        for d in page:
            by_shard.setdefault(d.shard_ord, []).append(d)
        hits: List[dict] = []
        for shard_ord, docs in by_shard.items():
            tf = time.perf_counter()
            hits.extend(searchers[shard_ord].fetch_phase(docs, body,
                                                         index_name))
            f_ms = (time.perf_counter() - tf) * 1000
            searchers[shard_ord].stats.on_fetch(f_ms,
                                                groups=body.get("stats"))
            if profile and shard_ord < len(shard_profiles):
                shard_profiles[shard_ord]["fetch"] = {
                    "time_in_nanos": int(f_ms * 1e6)}
        # restore global order after per-shard fetch
        order = {(d.shard_ord, id(d.seg), d.local_id): i
                 for i, d in enumerate(page)}
        hits_docs = list(zip(hits, [d for docs in by_shard.values()
                                    for d in docs]))
        hits_docs.sort(key=lambda hd: order[(hd[1].shard_ord, id(hd[1].seg),
                                             hd[1].local_id)])
        hits = [h for h, _ in hits_docs]

        response: Dict[str, Any] = {
            "took": int((time.perf_counter() - t0) * 1000),
            "timed_out": any(r.timed_out for r in results),
            "_shards": {"total": len(searchers),
                        "successful": len(searchers) - len(shard_failures),
                        "failed": len(shard_failures)},
            "hits": {
                "total": total,
                "max_score": (None if (max_score == float("-inf")
                                       or sort_spec) else max_score),
                "hits": hits,
            },
        }
    if shard_failures:
        response["_shards"]["failures"] = shard_failures
    # hybrid stage-2 status: a breaker decline on ANY shard marks the whole
    # response as degraded-to-stage-1 (typed partial — the contract is
    # "never a 500"), with per-shard counts so partial degradation is visible
    hyb_statuses = [r.hybrid for r in results if r.hybrid is not None]
    if hyb_statuses:
        declined = [h for h in hyb_statuses if h.get("rerank") == "declined"]
        if declined:
            response["hybrid"] = dict(
                declined[0],
                shards_declined=len(declined),
                shards_applied=len(hyb_statuses) - len(declined))
        else:
            response["hybrid"] = {
                "rerank": "applied",
                "window": sum(int(h.get("window", 0)) for h in hyb_statuses)}
    if any(r.terminated_early for r in results):
        response["terminated_early"] = True
    aggs_present = [r.agg_partials for r in results if r.agg_partials]
    if aggs_present:
        aggs = aggs_present[0]["_aggs"]
        partial_lists = [p for r in aggs_present for p in r["_list"]]
        from elasticsearch_tpu.tracing.tracer import span

        with span("search.aggs"):
            response["aggregations"] = reduce_aggs(aggs, partial_lists)
    if profile:
        response["profile"] = {"shards": shard_profiles}
    if scroll:
        # one scroll CONTEXT per shard (reference SearchStats semantics:
        # counts contexts, not pages)
        for s in searchers:
            s.stats.on_scroll()
        scroll_id = uuid.uuid4().hex
        state: Dict[str, Any] = {
            # scan serves every doc via scrolling — page 1 consumed nothing
            "pos": 0 if scan else frm + size,
            "body": body,
            "searchers": searchers,
            "index_name": index_name,
            "total": total,
        }
        if snapshot is not None:
            state.update(mode="arrays", **snapshot)
        else:
            # sorted scroll: complete candidate list (already merged)
            state.update(mode="docs", docs=all_docs)
        _SCROLLS[scroll_id] = state
        response["_scroll_id"] = scroll_id
    return response


def register_scroll_hits(body: dict, hits: List[dict], total: int,
                         consumed: Optional[int] = None) -> str:
    """Register a MATERIALIZED scroll: the full hit list is already
    fetched (the cross-host scroll path — the per-owner fetch contexts
    are one-shot, so the coordinator snapshots the window up front).
    Pages serve straight from the list. `consumed` is how many hits the
    INITIAL response already delivered (0 for search_type=scan, whose
    first response carries no hits by contract)."""
    import uuid as _uuid

    scroll_id = _uuid.uuid4().hex
    _SCROLLS[scroll_id] = {
        "mode": "hits", "hits": hits, "total": total,
        "pos": (int(body.get("size", 10)) if consumed is None
                else consumed),
        "body": body,
    }
    return scroll_id


def scroll_next(scroll_id: str, size: Optional[int] = None) -> dict:
    # cooperative cancellation: a scroll drained under a registered task
    # (REST /_search/scroll) stops paging when that task is cancelled
    from elasticsearch_tpu.tracing import check_cancelled

    check_cancelled()
    state = _SCROLLS.get(scroll_id)
    if state is None:
        from elasticsearch_tpu.utils.errors import \
            SearchContextMissingException

        raise SearchContextMissingException(
            f"No search context found for id [{scroll_id}]")
    body = state["body"]
    sz = size or int(body.get("size", 10))
    lo = state["pos"]
    state["pos"] += sz
    if state.get("mode") == "hits":
        return {
            "took": 0, "timed_out": False, "_scroll_id": scroll_id,
            "hits": {"total": state["total"], "max_score": None,
                     "hits": state["hits"][lo: lo + sz]},
        }
    if state.get("mode") == "arrays":
        segs = state["segs"]
        page = [
            ShardDoc(segs[si][0], segs[si][1], int(li), float(sc))
            for si, li, sc in zip(state["seg_of"][lo : lo + sz],
                                  state["local"][lo : lo + sz],
                                  state["score"][lo : lo + sz])
        ]
    else:
        page = state["docs"][lo : lo + sz]
    by_shard: Dict[int, List[ShardDoc]] = {}
    for d in page:
        by_shard.setdefault(d.shard_ord, []).append(d)
    hits = []
    for shard_ord, docs in by_shard.items():
        hits.extend(state["searchers"][shard_ord].fetch_phase(docs, body, state["index_name"]))
    # restore global page order after per-shard fetch
    order = {(d.shard_ord, id(d.seg), d.local_id): i for i, d in enumerate(page)}
    hd = list(zip(hits, [d for docs in by_shard.values() for d in docs]))
    hd.sort(key=lambda x: order[(x[1].shard_ord, id(x[1].seg), x[1].local_id)])
    return {
        "took": 0,
        "timed_out": False,
        "_scroll_id": scroll_id,
        "hits": {"total": state["total"], "max_score": None,
                 "hits": [h for h, _ in hd]},
    }


def scroll_state(scroll_id: str) -> Optional[dict]:
    """The live scroll context for ``scroll_id`` (None when unknown) —
    the REST layer attaches its persistent scroll TASK here so the same
    task spans every page of one drain (rest/server.py::_scroll)."""
    return _SCROLLS.get(scroll_id)


def clear_scroll(scroll_id: str) -> bool:
    return _SCROLLS.pop(scroll_id, None) is not None


# ---------------------------------------------------------------------------
# source filtering (fetch/source/FetchSourceSubPhase semantics)
# ---------------------------------------------------------------------------

def _nested_sub_source(root_src: dict, path: str, ordn: int):
    """Extract the ordn-th object under a (possibly dotted) nested path from
    the root document's _source."""
    cur: Any = root_src
    for part in path.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    if isinstance(cur, list):
        return cur[ordn] if 0 <= ordn < len(cur) else None
    return cur if ordn == 0 else None


def source_path(src, path: str):
    """Walk a dotted path into a source dict; None when any hop misses
    (shared by fetch-phase `fields`, GET/mget fields extraction)."""
    cur = src
    for part in str(path).split("."):
        cur = cur.get(part) if isinstance(cur, dict) else None
    return cur


def _filter_source(src: Optional[dict], spec) -> Optional[dict]:
    import fnmatch

    if src is None or spec is False:
        return None
    if spec is True or spec is None:
        return src
    if isinstance(spec, str):
        spec = [spec]
    if isinstance(spec, list):
        includes, excludes = spec, []
    else:
        includes = spec.get("includes", spec.get("include", []))
        excludes = spec.get("excludes", spec.get("exclude", []))
        if isinstance(includes, str):
            includes = [includes]
        if isinstance(excludes, str):
            excludes = [excludes]

    def _could_descend(path: str, pat: str) -> bool:
        """True when `pat` could match somewhere strictly below `path`."""
        psegs, segs = path.split("."), pat.split(".")
        if len(psegs) >= len(segs):
            return False
        return all(fnmatch.fnmatch(ps, sg)
                   for ps, sg in zip(psegs, segs))

    def _walk(obj, prefix: str, in_included: bool = False):
        """Path-aware include/exclude (XContentMapValues.filter): a pattern
        like 'obj.inner' keeps that nested leaf; an included ancestor keeps
        its whole subtree (children face only the excludes)."""
        if not isinstance(obj, dict):
            return obj
        out = {}
        for k, v in obj.items():
            path = f"{prefix}{k}"
            if excludes and any(fnmatch.fnmatch(path, pat)
                                for pat in excludes):
                continue
            inc = (in_included or not includes
                   or any(fnmatch.fnmatch(path, pat) for pat in includes))
            if inc:
                out[k] = (_walk(v, f"{path}.", True)
                          if isinstance(v, dict) and excludes else v)
            elif isinstance(v, dict) and any(_could_descend(path, pat)
                                             for pat in includes):
                sub = _walk(v, f"{path}.")
                if sub:
                    out[k] = sub
        return out

    return _walk(src, "")


# ---------------------------------------------------------------------------
# sort helpers
# ---------------------------------------------------------------------------

def _parse_sort(spec) -> List[dict]:
    if not spec:
        return []
    if isinstance(spec, (str, dict)):
        spec = [spec]
    out = []
    for item in spec:
        if isinstance(item, str):
            if item in ("_score",):
                out.append({"field": "_score", "order": "desc"})
            else:
                out.append({"field": item, "order": "asc"})
        else:
            (fieldname, cfg), = item.items()
            if fieldname == "_geo_distance":
                # reference: search/sort/GeoDistanceSortParser.java:1-211 —
                # {"_geo_distance": {"<field>": <point>, "order", "unit"}}
                from elasticsearch_tpu.search.geo import _UNIT_M
                from elasticsearch_tpu.index.mappings import _parse_geo_point

                cfg = dict(cfg)
                order = cfg.pop("order", "asc")
                unit = cfg.pop("unit", "m")
                cfg.pop("distance_type", None)
                cfg.pop("mode", None)
                (geo_field, point), = cfg.items()
                lat0, lon0 = _parse_geo_point(point)
                out.append({"field": "_geo_distance", "order": order,
                            "geo_field": geo_field, "origin": (lat0, lon0),
                            "unit_m": _UNIT_M.get(unit, 1.0)})
            elif isinstance(cfg, str):
                out.append({"field": fieldname, "order": cfg})
            else:
                out.append({
                    "field": fieldname,
                    "order": cfg.get("order", "desc" if fieldname == "_score" else "asc"),
                    "missing": cfg.get("missing", "_last"),
                })
    # drop trailing pure-score sort into score path
    if len(out) == 1 and out[0]["field"] == "_score" and out[0]["order"] == "desc":
        return []
    return out


def _sort_key_vector(ctx, s, scores):
    """Device vector used for primary-key top-k preselection."""
    jnp = _jnp()
    if s["field"] == "_score":
        return scores, 0.0
    if s["field"] == "_geo_distance":
        from elasticsearch_tpu.search.geo import haversine_device

        lat = ctx.col(f"{s['geo_field']}.lat")
        lon = ctx.col(f"{s['geo_field']}.lon")
        if lat is None or lon is None:
            fill = jnp.float32(-jnp.inf if s["order"] == "desc" else jnp.inf)
            return jnp.full(ctx.D, fill), 0.0
        lat0, lon0 = s["origin"]
        d = haversine_device(lat.values + jnp.float32(lat.offset),
                             lon.values + jnp.float32(lon.offset),
                             lat0, lon0) / jnp.float32(s["unit_m"])
        missing = jnp.float32(-jnp.inf if s["order"] == "desc" else jnp.inf)
        return jnp.where(lat.exists, d, missing), 0.0
    col = ctx.col(s["field"])
    if col is not None:
        missing_val = jnp.float32(-jnp.inf if s["order"] == "desc" else jnp.inf)
        if str(s.get("missing", "_last")) == "_first":
            missing_val = -missing_val
        s["_offset"] = col.offset
        return jnp.where(col.exists, col.values, missing_val), col.offset
    kw = ctx.segment.keywords.get(s["field"])
    if kw is not None:
        return kw.ords.astype(jnp.float32), 0.0
    return jnp.zeros(ctx.D, dtype=jnp.float32), 0.0


def _host_exists(col) -> np.ndarray:
    """Host mirror of a column's exists bitmap, backfilled once per
    (immutable) column slab. Per-hit sort/fetch paths index this instead
    of pulling the device array once per hit (tpulint R002)."""
    if col.exists_host is None:
        col.exists_host = np.asarray(col.exists)
    return col.exists_host


def _sort_value(ctx, s, local: int, np_scores):
    if s["field"] == "_score":
        return float(np_scores[local])
    if s["field"] == "_geo_distance":
        from elasticsearch_tpu.search.geo import haversine_np

        lat = ctx.col(f"{s['geo_field']}.lat")
        lon = ctx.col(f"{s['geo_field']}.lon")
        if lat is None or lon is None or not bool(_host_exists(lat)[local]):
            return None
        lat0, lon0 = s["origin"]
        d = haversine_np(float(lat.exact[local]), float(lon.exact[local]),
                         lat0, lon0) / s["unit_m"]
        return float(d)
    col = ctx.col(s["field"])
    if col is not None:
        if not bool(_host_exists(col)[local]):
            return None
        ex = col.exact[local]
        return int(ex) if col.exact.dtype.kind == "i" else float(ex)
    kw = ctx.segment.keywords.get(s["field"])
    if kw is not None and kw.host_values[local]:
        return kw.host_values[local][0]
    return None


_MISSING_LAST = object()


def _after_cursor(sort_values: Tuple, cursor, sort_spec: List[dict]) -> bool:
    """True iff a doc's full sort tuple strictly follows the search_after
    cursor in sort order (ES compares every key, not just the primary)."""
    for v, c, s in zip(sort_values, cursor, sort_spec):
        desc = s["order"] == "desc"
        missing_first = str(s.get("missing", "_last")) == "_first"
        if v is None and c is None:
            continue
        if v is None:
            # doc missing on this key: _last ranks after every concrete
            # value, _first before
            return not missing_first
        if c is None:
            return missing_first
        if isinstance(v, str) != isinstance(c, str):
            v, c = str(v), str(c)
        if isinstance(v, bool):
            v = int(v)
        if isinstance(c, bool):
            c = int(c)
        if v == c:
            continue
        return (v > c) != desc
    return False  # tuple equal to cursor → exclusive, not after


def _sort_key(sort_values: Tuple, sort_spec: List[dict]):
    key = []
    for v, s in zip(sort_values, sort_spec):
        desc = s["order"] == "desc"
        missing_first = str(s.get("missing", "_last")) == "_first"
        if v is None:
            rank = 0 if missing_first else 2
            key.append((rank, 0))
        elif isinstance(v, str):
            key.append((1, _StrKey(v, desc)))
        else:
            key.append((1, -v if desc else v))
    return tuple(key)


class _StrKey:
    __slots__ = ("v", "desc")

    def __init__(self, v, desc):
        self.v = v
        self.desc = desc

    def __lt__(self, other):
        return (self.v > other.v) if self.desc else (self.v < other.v)

    def __eq__(self, other):
        return self.v == other.v
