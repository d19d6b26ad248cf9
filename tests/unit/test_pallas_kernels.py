"""Pallas fused kNN kernel vs the XLA path and an exact numpy oracle.

Runs in interpret mode on CPU (tests); the same kernel compiles for TPU and
is dispatched by knn_topk_auto when running on a real chip.
"""
import numpy as np
import pytest

from elasticsearch_tpu.ops.knn import knn_row_terms, knn_topk_stored
from elasticsearch_tpu.ops.pallas_kernels import knn_topk_auto, knn_topk_pallas


def _exact_topk(q, v, mask, k, metric):
    qn = q / np.maximum(np.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
    vn = v / np.maximum(np.linalg.norm(v, axis=-1, keepdims=True), 1e-12)
    if metric == "cosine":
        s = (1 + qn @ vn.T) / 2
    elif metric in ("dot_product", "dot"):
        s = (1 + q @ v.T) / 2
    else:
        d2 = ((q[:, None, :] - v[None, :, :]) ** 2).sum(-1)
        s = 1.0 / (1.0 + d2)
    s = np.where(mask[None, :], s, -np.inf)
    idx = np.argsort(-s, axis=1)[:, :k]
    return np.take_along_axis(s, idx, axis=1), idx


@pytest.mark.parametrize("metric", ["cosine", "dot_product", "l2_norm"])
def test_pallas_knn_matches_oracle(metric):
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    Q, D, dims, k = 4, 8192, 64, 10
    q = rng.normal(size=(Q, dims)).astype(np.float32)
    v = rng.normal(size=(D, dims)).astype(np.float32)
    mask = rng.random(D) > 0.1
    pv, pi = knn_topk_pallas(jnp.asarray(q), jnp.asarray(v), jnp.asarray(mask),
                             k=k, metric=metric, interpret=True)
    ev, ei = _exact_topk(q, v, mask, k, metric)
    pv, pi = np.asarray(pv), np.asarray(pi)
    # scores agree to bf16 matmul tolerance (relative: dot magnitudes scale
    # with dims); recall@k vs the exact oracle must be near-perfect
    np.testing.assert_allclose(pv, ev, rtol=5e-3, atol=5e-3)
    recall = np.mean([len(set(pi[i]) & set(ei[i])) / k for i in range(Q)])
    assert recall >= 0.95
    # masked docs never surface
    assert not np.isin(pi, np.nonzero(~mask)[0]).any()
    # results descending per row
    assert (np.diff(pv, axis=1) <= 1e-6).all()


def test_pallas_matches_xla_path():
    import jax.numpy as jnp

    rng = np.random.default_rng(4)
    Q, D, dims, k = 2, 4096, 32, 5
    q = jnp.asarray(rng.normal(size=(Q, dims)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(D, dims)).astype(np.float32))
    m = jnp.asarray(np.ones(D, dtype=bool))
    pv, _ = knn_topk_pallas(q, v, m, k=k, metric="cosine", interpret=True)
    xv, _ = knn_topk_stored(q, v, knn_row_terms(v, metric="cosine"), m, k=k,
                            metric="cosine")
    np.testing.assert_allclose(np.asarray(pv), np.asarray(xv), atol=5e-3)


def test_auto_dispatch_falls_back_on_cpu():
    import jax.numpy as jnp

    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(2, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(100, 16)).astype(np.float32))  # not tile-aligned
    m = jnp.asarray(np.ones(100, dtype=bool))
    vals, idx = knn_topk_auto(q, v, knn_row_terms(v, metric="cosine"), m,
                              k=3)
    assert vals.shape == (2, 3) and idx.shape == (2, 3)


def test_knn_auto_pads_small_q():
    # Q=1 must not crash on the padded path (CPU takes XLA anyway; this
    # asserts the pad/slice contract via the pallas kernel in interpret)
    import jax.numpy as jnp
    from elasticsearch_tpu.ops.pallas_kernels import knn_topk_pallas

    rng = np.random.default_rng(7)
    dims, D, k = 128, 4096, 5
    q = rng.normal(size=(1, dims)).astype(np.float32)
    qpad = np.concatenate([q, np.zeros((7, dims), np.float32)], axis=0)
    v = rng.normal(size=(D, dims)).astype(np.float32)
    mask = np.ones(D, bool)
    pv, pi = knn_topk_pallas(jnp.asarray(qpad), jnp.asarray(v),
                             jnp.asarray(mask), k=k, metric="cosine",
                             tile=2048, interpret=True)
    ev, ei = _exact_topk(q, v, mask, k, "cosine")
    assert len(set(np.asarray(pi)[0]) & set(ei[0])) >= 4


def test_scoped_vmem_exhaustion_latches_as_a_compile_error():
    """Mosaic reports a tile over the scoped VMEM limit as
    RESOURCE_EXHAUSTED at compile time: deterministic for the shapes, so
    it latches at once instead of being retried as a transient. An HBM
    RESOURCE_EXHAUSTED at run time stays transient."""
    from elasticsearch_tpu.ops.pallas_kernels import _is_compile_error

    vmem = RuntimeError(
        "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem while "
        "allocating on stack for %adc. Scoped allocation with size 31.74M "
        "and limit 16.00M exceeded scoped vmem limit by 15.74M.")
    hbm = RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
        "4294967296 bytes.")
    assert _is_compile_error(vmem)
    assert not _is_compile_error(hbm)
