# patch jax.jit with the retrace auditor BEFORE the imports below bind
# `@jax.jit` decorators — the search profiler's device compile/execute
# split depends on it (tracing/retrace.py); this package pulls in jax
# anyway, so the root elasticsearch_tpu import stays light
from elasticsearch_tpu.tracing import retrace as _retrace

_retrace.ensure_installed()

from elasticsearch_tpu.ops.scoring import (
    bm25_score_segment,
    bm25_score_batch,
    bm25_score_hybrid_batch,
    term_mask,
    topk_with_mask,
    range_mask_f32,
    range_mask_i64pair,
)
from elasticsearch_tpu.ops.knn import knn_scores, knn_topk

__all__ = [
    "bm25_score_segment",
    "bm25_score_batch",
    "bm25_score_hybrid_batch",
    "term_mask",
    "topk_with_mask",
    "range_mask_f32",
    "range_mask_i64pair",
    "knn_scores",
    "knn_topk",
]
