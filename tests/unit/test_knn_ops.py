"""Direct parity coverage for ops/knn.py's chunked scan (ISSUE-9
satellite: knn_topk_chunked had no direct unit test) — against
knn_topk_stored across chunk boundaries, all three metrics, and a masked
tail, plus the chunk-divisibility contract. Both read the rows' stored
term (knn_row_terms), built once in _setup as a column builds it."""
import numpy as np
import pytest

from elasticsearch_tpu.ops.knn import (knn_row_terms, knn_topk_chunked,
                                       knn_topk_stored)

METRICS = ("cosine", "dot_product", "l2_norm")


def _setup(D=256, dims=16, Q=5, live=None, seed=0, metric="cosine"):
    import jax

    rng = np.random.default_rng(seed)
    vecs = rng.standard_normal((D, dims)).astype(np.float32)
    queries = rng.standard_normal((Q, dims)).astype(np.float32)
    mask = np.ones(D, bool) if live is None else live
    vecs = jax.device_put(vecs)
    return (jax.device_put(queries), vecs,
            knn_row_terms(vecs, metric=metric), jax.device_put(mask))


@pytest.mark.parametrize("metric", METRICS)
def test_chunked_matches_unchunked_all_metrics(metric):
    q, v, t, m = _setup(metric=metric)
    vals_a, idx_a = knn_topk_stored(q, v, t, m, k=7, metric=metric,
                                   use_bf16=False)
    vals_b, idx_b = knn_topk_chunked(q, v, t, m, k=7, metric=metric,
                                     chunk=64, use_bf16=False)
    # random floats: ties measure-zero, so ids match exactly
    np.testing.assert_array_equal(np.asarray(idx_a), np.asarray(idx_b))
    np.testing.assert_allclose(np.asarray(vals_a), np.asarray(vals_b),
                               rtol=1e-6)


def test_chunked_across_chunk_boundaries():
    """k straddling chunk sizes: winners spread across chunks and a k
    larger than one chunk's local top-k contribution still merges
    exactly (the per-chunk contribution is min(k, chunk))."""
    q, v, t, m = _setup(D=512, Q=3)
    for chunk, k in ((32, 48), (64, 64), (128, 10)):
        vals_a, idx_a = knn_topk_stored(q, v, t, m, k=k, use_bf16=False)
        vals_b, idx_b = knn_topk_chunked(q, v, t, m, k=k, chunk=chunk,
                                         use_bf16=False)
        np.testing.assert_array_equal(np.asarray(idx_a),
                                      np.asarray(idx_b))
        np.testing.assert_allclose(np.asarray(vals_a),
                                   np.asarray(vals_b), rtol=1e-6)


def test_chunked_masked_tail():
    """A padded tail (mask False past n live docs) never surfaces: ids
    stay below n and parity holds against the unchunked form."""
    D, n = 256, 180
    live = np.zeros(D, bool)
    live[:n] = True
    q, v, t, m = _setup(D=D, live=live)
    vals_a, idx_a = knn_topk_stored(q, v, t, m, k=9, use_bf16=False)
    vals_b, idx_b = knn_topk_chunked(q, v, t, m, k=9, chunk=64,
                                     use_bf16=False)
    assert np.asarray(idx_b).max() < n
    np.testing.assert_array_equal(np.asarray(idx_a), np.asarray(idx_b))
    np.testing.assert_allclose(np.asarray(vals_a), np.asarray(vals_b),
                               rtol=1e-6)
    # a fully-masked final chunk contributes nothing but -inf slots
    live2 = np.zeros(D, bool)
    live2[:5] = True
    q2, v2, t2, m2 = _setup(D=D, live=live2, seed=1)
    vals_c, idx_c = knn_topk_chunked(q2, v2, t2, m2, k=9, chunk=64,
                                     use_bf16=False)
    vc = np.asarray(vals_c)
    assert np.isneginf(vc[:, 5:]).all()
    assert np.asarray(idx_c)[:, :5].max() < 5


def test_chunked_rejects_undivisible_corpus():
    q, v, t, m = _setup(D=250)
    with pytest.raises(ValueError):
        knn_topk_chunked(q, v, t, m, k=5, chunk=64)


def test_chunked_bf16_parity_with_bf16_unchunked():
    """bf16 parity too: the chunked matmul computes the same row values
    as the full one (same dtype path), so merged top-k agrees."""
    q, v, t, m = _setup(D=256, seed=2)
    vals_a, idx_a = knn_topk_stored(q, v, t, m, k=5, use_bf16=True)
    vals_b, idx_b = knn_topk_chunked(q, v, t, m, k=5, chunk=64,
                                     use_bf16=True)
    np.testing.assert_array_equal(np.asarray(idx_a), np.asarray(idx_b))
    np.testing.assert_allclose(np.asarray(vals_a), np.asarray(vals_b),
                               rtol=1e-6)
