"""Product quantization: PQ-coded vector slabs + asymmetric distance.

IVF throughput falls off a cliff as num_candidates grows (a CPU run of
round 5 recorded 389.5 -> 73.3 -> 12.6 qps from 1k to 16k; that record
is no longer in the tree) because the fine-rank stage gathers and
re-scores full-precision f32 vectors for EVERY probed candidate — a
memory-bandwidth wall, exactly what TileMaxSim (arXiv:2606.26439)
attacks with tiled scoring over fused product quantization. The fix is
the classical PQ/ADC split:

  * BUILD (host/offline, at segment freeze beside the IVF quantizer):
    split dims into M subspaces of dsub dims, k-means K centroids per
    subspace (reusing ops/ivf.kmeans — device matmuls, host in/out),
    then encode every slab row into M uint8 codes. The code array is
    dims*4/M times smaller than the f32 slab (128d, M=32 -> 16x).
  * QUERY (asymmetric distance computation, ADC): one M x K lookup
    table of partial similarities between the UNQUANTIZED query and
    every codeword, then each candidate's coarse score is a table-sum
    over its M codes — a uint8 gather + add, no f32 vector gather, no
    matmul over the candidate set. Cost per candidate is O(M) bytes
    instead of O(dims) floats, so the coarse rank no longer scales
    with num_candidates in any way that hurts.
  * The fine stage re-scores only the top ~4k ADC survivors in exact
    f32 (ops/knn.exact metrics), restoring exact ES score semantics.

Metric mapping (coarse scores are MONOTONE PROXIES — ranking-only;
the fine stage emits the real ES-shaped scores):

  cosine       slab rows are l2-normalized before training/encoding;
               LUT = normalized-query-subvector . codeword, so the
               table-sum approximates cos(q, v).
  dot_product  LUT = query-subvector . codeword (vectors unit-norm by
               ES contract).
  l2_norm      LUT = 2 q_m.c - ||c||^2 (the norm expansion of
               -||q_m - c||^2 with the constant ||q_m||^2 dropped) —
               monotone in negative squared distance.

Residency: code arrays register as EVICTABLE fielddata-tier
ResidentArray handles (resources/residency.py) — pressure evicts them
LRU-first and the next query rehydrates bit-exactly from the host
mirror; a breaker denial at placement is best-effort (the caller keeps
the exact f32 fine-rank path — same contract as dense impact blocks).
Codebooks are tiny (M*K*dsub f32 = the slab's footprint / D) and place
through the accounted RESIDENCY.device_put choke point beside the IVF
centroids; both persist via the content-addressed blob cache
(index/ivf_cache.py) so restarts and snapshot restores skip the
k-means.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Tuple

import numpy as np

#: encode-time chunk: bounds the [chunk, M, K] argmax intermediate so a
#: million-row slab never materializes an N x M x K affinity tensor
_ENCODE_CHUNK = 16384


def _jax():
    import jax

    return jax


def pq_layout(dims: int) -> Tuple[int, int]:
    """(M subspaces, dsub dims each) for a vector field.

    Targets dsub >= 4 with M capped at 32 (LUT stays M*K f32 <= 32 KB —
    VMEM-resident for the Pallas ADC kernel); tiny dims degrade to
    dsub 2, then to a single-subspace VQ.
    """
    for M in (32, 16, 8, 4, 2):
        if dims % M == 0 and dims // M >= 4:
            return M, dims // M
    for M in (16, 8, 4, 2):
        if dims % M == 0 and dims // M >= 2:
            return M, dims // M
    return 1, dims


def pq_codebook_size(n_train: int) -> int:
    """K for a training set of n_train live vectors: 256 when the slab
    affords it, else the largest power of two that keeps >= 8 training
    vectors per codeword."""
    if n_train >= 2048:
        return 256
    k = 1 << max(int(np.floor(np.log2(max(n_train // 8, 1)))), 0)
    return max(min(k, 256), 1)


@dataclass
class PqHostParts:
    """Host-side build output — placement (and its breaker accounting)
    stays with the caller so a denial can retry later."""

    codebooks: np.ndarray  # f32[M, K, dsub]
    codes: np.ndarray  # uint8[max_docs, M]
    M: int
    K: int
    dsub: int
    dims: int
    metric: str


@dataclass
class PqIndex:
    """Device-resident PQ tier for one (immutable) vector slab."""

    codebooks: Any  # f32[M, K, dsub] (device, accounted)
    codes: Any  # ResidentArray handle (evictable) or device array
    M: int
    K: int
    dsub: int
    dims: int
    metric: str
    codebooks_host: Optional[np.ndarray] = None
    codes_host: Optional[np.ndarray] = None

    def codes_dev(self):
        """The device code array, rehydrating an evicted handle."""
        from elasticsearch_tpu.resources.residency import ResidentArray

        if isinstance(self.codes, ResidentArray):
            return self.codes.get()
        return self.codes


def _normalize_rows(x: np.ndarray) -> np.ndarray:
    n = np.linalg.norm(x, axis=-1, keepdims=True)
    return x / np.maximum(n, 1e-12)


_ENCODE_PROGRAMS: dict = {}


def _encode_program(M: int, dsub: int):
    """Compiled chunk encoder for one (M, dsub) shape class: nearest
    codeword per subspace via the norm expansion (argmin ||x - c||^2 ==
    argmax x.c - ||c||^2 / 2) — one einsum on the MXU per chunk."""
    key = (M, dsub)
    prog = _ENCODE_PROGRAMS.get(key)
    if prog is not None:
        return prog
    jax = _jax()
    import jax.numpy as jnp

    @jax.jit
    def run(chunk, codebooks):
        x = chunk.reshape(chunk.shape[0], M, dsub)
        aff = jnp.einsum("nmd,mkd->nmk", x, codebooks,
                         preferred_element_type=jnp.float32)
        # codebooks are repeat-padded when training was tiny (train_pq
        # tiles codewords to K): duplicate codewords are argmax-neutral,
        # and the norm sum runs over the full dsub axis  # tpulint: masked
        aff = aff - 0.5 * jnp.sum(codebooks * codebooks, axis=-1)[None, :, :]
        return jnp.argmax(aff, axis=2).astype(jnp.uint8)

    # factory-key discipline (ROADMAP #6): the encoder rides the AOT
    # blob cache so a restarted node re-encodes without recompiling
    from elasticsearch_tpu.parallel import aot

    run = aot.wrap(run, "pq_encode", key)
    _ENCODE_PROGRAMS[key] = run
    return run


def train_pq(train: np.ndarray, M: int, K: int, iters: int = 6,
             metric: str = "cosine") -> np.ndarray:
    """Per-subspace k-means codebooks f32[M, K, dsub] over live training
    rows (already normalized for cosine). Subspace clustering is ALWAYS
    squared-l2 (standard PQ — the reconstruction objective), regardless
    of the field similarity; the similarity shapes the LUT instead."""
    from elasticsearch_tpu.ops.ivf import kmeans

    n, dims = train.shape
    dsub = dims // M
    books = np.empty((M, K, dsub), np.float32)
    for m in range(M):
        sub = np.ascontiguousarray(train[:, m * dsub:(m + 1) * dsub])
        cents, _ = kmeans(sub, K, iters=iters, metric="l2")
        if cents.shape[0] < K:  # tiny training set: repeat-pad codewords
            reps = int(np.ceil(K / cents.shape[0]))
            cents = np.tile(cents, (reps, 1))[:K]
        books[m] = cents
    return books


def pq_encode(vecs: np.ndarray, codebooks: np.ndarray) -> np.ndarray:
    """uint8[N, M] codes for every slab row (chunked device encode)."""
    jax = _jax()

    M, _K, dsub = codebooks.shape
    # (M, dsub) is the pq_layout shape class for the field's dims — a
    # config-bounded universe, one program per layout  # tpulint: bucketed
    prog = _encode_program(M, dsub)
    # offbudget: build-time temporaries, freed when the encode returns
    d_books = jax.device_put(codebooks)  # tpulint: offbudget
    N = vecs.shape[0]
    out = np.empty((N, M), np.uint8)
    step = _ENCODE_CHUNK
    for s in range(0, N, step):
        chunk = vecs[s:s + step]
        if chunk.shape[0] < step and N > step:
            pad = np.zeros((step - chunk.shape[0], vecs.shape[1]),
                           np.float32)
            enc = prog(jax.device_put(  # tpulint: offbudget
                np.concatenate([chunk, pad])), d_books)
            out[s:s + chunk.shape[0]] = np.asarray(enc)[: chunk.shape[0]]
        else:
            enc = prog(jax.device_put(chunk), d_books)  # tpulint: offbudget
            out[s:s + chunk.shape[0]] = np.asarray(enc)
    return out


def build_pq(vecs_np: np.ndarray, exists_np: np.ndarray, metric: str,
             M: Optional[int] = None, K: Optional[int] = None,
             iters: int = 6, min_train: int = 128) -> Optional[PqHostParts]:
    """Train + encode the PQ tier for one frozen slab (host in, host
    out — placement is the caller's). None = declined (too few live
    vectors for a codebook worth having; exact scoring wins there)."""
    # host-side BUILD path (freeze-time, never traced)
    ids = np.nonzero(exists_np)[0]  # tpulint: host
    n = ids.size
    if n < min_train:
        return None
    dims = vecs_np.shape[1]
    if M is None:
        M, dsub = pq_layout(dims)
    else:
        if dims % M:
            raise ValueError(f"pq subspaces [{M}] must divide dims [{dims}]")
        dsub = dims // M
    if K is None:
        K = pq_codebook_size(n)
    slab = vecs_np.astype(np.float32, copy=False)
    if metric == "cosine":
        # encode the DIRECTIONS: the ADC table-sum then approximates
        # cos(q, v) directly (query side normalizes in the LUT build)
        slab = _normalize_rows(slab)
        train = slab[ids]
    else:
        train = slab[ids]
    books = train_pq(train, M, K, iters=iters, metric=metric)
    codes = pq_encode(slab, books)
    return PqHostParts(codebooks=books, codes=codes, M=M, K=K, dsub=dsub,
                       dims=dims, metric=metric)


def place_pq(parts: PqHostParts, label: str = "pq") -> Optional[PqIndex]:
    """Place a built PQ tier on device. Codebooks go through the
    accounted RESIDENCY.device_put choke point (tiny, always-resident,
    owned by the column like IVF centroids); the code array registers
    as an EVICTABLE fielddata-tier handle. best_effort: a breaker
    denial returns None — PQ is a pure acceleration, the caller keeps
    the exact fine-rank path and retries on a later query."""
    from elasticsearch_tpu import resources

    handle = resources.RESIDENCY.put_array(
        parts.codes, label=f"{label}.codes", tier="fielddata",
        best_effort=True)
    if handle is None:
        return None
    try:
        books = resources.RESIDENCY.device_put(parts.codebooks,
                                               label=f"{label}.codebooks")
    except Exception:
        # a codebook breaker denial must not strand the codes handle's
        # fielddata charge — evict it before propagating
        handle.evict()
        raise
    return PqIndex(codebooks=books, codes=handle, M=parts.M, K=parts.K,
                   dsub=parts.dsub, dims=parts.dims, metric=parts.metric,
                   codebooks_host=parts.codebooks, codes_host=parts.codes)


# ---------------------------------------------------------------------------
# traced ADC pieces (inlined into the IVF coarse->fine program)
# ---------------------------------------------------------------------------

def adc_lut(jnp, query, codebooks, metric: str):
    """[M, K] partial-similarity lookup table for one query (traced).

    Higher is better for every metric; values are ranking proxies, not
    calibrated ES scores (the fine stage re-scores survivors exactly).
    """
    if metric == "cosine":
        q = query / jnp.maximum(jnp.linalg.norm(query), 1e-12)
    else:
        q = query
    M, _K, dsub = codebooks.shape
    qs = q.reshape(M, dsub)
    lut = jnp.einsum("md,mkd->mk", qs, codebooks,
                     preferred_element_type=jnp.float32)
    if metric in ("l2_norm", "l2"):
        # monotone in -||q_m - c||^2 (constant ||q_m||^2 dropped)
        lut = 2.0 * lut - jnp.sum(codebooks * codebooks, axis=-1)
    return lut


def adc_sum(jnp, codes, lut):
    """Table-sum coarse scores f32[W] for codes [W, M] (traced XLA
    form — a [W, M] gather + row sum; the Pallas variant lives in
    ops/pallas_kernels.adc_scores_pallas)."""
    M = lut.shape[0]
    idx = codes.astype(jnp.int32)
    return jnp.sum(lut[jnp.arange(M)[None, :], idx], axis=1)
