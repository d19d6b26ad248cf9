"""IVF-flat approximate kNN — coarse k-means quantizer + inverted lists.

No ES 2.0 counterpart (the reference predates vector search); the north-star
plan (SURVEY §2.4 knn row, BASELINE configs[3]) calls for an ANN path beside
the brute-force MXU matmul. The classical IVF recipe (train a coarse
quantizer, bucket vectors by nearest centroid, probe the closest nprobe
lists at query time) maps exceptionally well to TPU:

  * k-means training IS batched matmuls: assignment = argmax(vecs @ cᵀ),
    update = segment-sum — both MXU/VPU-shaped, no pointer chasing.
  * inverted lists become a PADDED [C, Lmax] id matrix (static shapes —
    no ragged CSR walks); probing = one gather + one small matmul.
  * probe selection, candidate scoring, and top-k fuse into one XLA
    program; `num_candidates` tunes nprobe.

Recall/latency contract mirrors FAISS IVF-flat: with C ≈ 4√N lists and
nprobe sized so probed lists cover ≥ num_candidates vectors, recall@10 on
clustered data ≥ 0.95 at a fraction of brute-force FLOPs.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache, partial
from typing import Any, Optional

import numpy as np

from elasticsearch_tpu.tracing.tracer import span
from elasticsearch_tpu.utils.shapes import pow2_bucket


def _jax():
    import jax

    return jax


# ---------------------------------------------------------------------------
# k-means (device)
# ---------------------------------------------------------------------------

#: training rows a list for the coarse quantizer: above faiss's warning
#: floor (39 a centroid) and under its default cap (256 a centroid,
#: ClusteringParameters.max_points_per_centroid)
TRAIN_PER_LIST = 64
#: bytes of the [chunk, C] f32 affinity one step of a chunked pass holds
_AFFINITY_BYTES = 1 << 28
_CHUNK_CAP = 1 << 14


def _quantizer_affinity(jnp, vecs, cents, metric: str, precision=None):
    """[N, C] affinity used for BOTH k-means assignment and query-time
    probing — argmax row-wise picks the nearest centroid under the field's
    similarity. l2_norm uses the norm expansion (argmin ||v-c||^2 ==
    argmax v.c - ||c||^2/2); cosine/dot normalize centroids (dot against a
    unit-norm direction — standard spherical k-means for MIPS/cosine)."""
    if metric in ("l2_norm", "l2"):
        vc = jnp.matmul(vecs, cents.T, precision=precision,
                        preferred_element_type=jnp.float32)
        return vc - 0.5 * jnp.sum(cents * cents, axis=-1)[None, :]
    cn = cents / jnp.maximum(
        jnp.linalg.norm(cents, axis=-1, keepdims=True), 1e-12)
    return jnp.matmul(vecs, cn.T, precision=precision,
                      preferred_element_type=jnp.float32)


def chunk_rows(C: int, rows: int) -> int:
    """Rows a step of a chunked pass over ``rows`` rows against C
    centroids: the largest power of two whose [chunk, C] affinity stays
    within _AFFINITY_BYTES, and never more rows than there are."""
    c = max(8, min(_CHUNK_CAP, _AFFINITY_BYTES // (4 * max(C, 1))))
    c = 1 << (c.bit_length() - 1)
    return max(1, min(c, rows))


@lru_cache(maxsize=None)
def _programs():
    """The build's jitted steps, made once a process (jax.jit then
    compiles each once per shape): a Lloyd step over training rows, a
    nearest-centroid pass over slab rows. Both walk their rows in chunks
    (chunk_rows), so no [N, C] affinity is ever whole; a chunk that would
    run past the end starts earlier instead, and its rows already seen
    weigh 0 (sums) or are written again with the same value (labels)."""
    jax = _jax()
    import jax.numpy as jnp
    from jax import lax

    highest = lax.Precision.HIGHEST

    @partial(jax.jit, static_argnames=("metric", "chunk"))
    def kmeans_step(train, cents, *, metric, chunk):
        T, dims = train.shape
        C = cents.shape[0]

        def body(i, acc):
            sums, counts = acc
            s = jnp.minimum(i * chunk, T - chunk)
            x = lax.dynamic_slice_in_dim(train, s, chunk)
            fresh = (s + jnp.arange(chunk)) >= i * chunk
            a = jnp.argmax(_quantizer_affinity(jnp, x, cents, metric), axis=1)
            onehot = ((a[:, None] == jnp.arange(C)[None, :]) & fresh[:, None]
                      ).astype(jnp.float32)
            # the update as a matmul on the MXU, not a scatter-add
            sums = sums + jnp.matmul(onehot.T, x,
                                     preferred_element_type=jnp.float32)
            return sums, counts + jnp.sum(onehot, axis=0)

        sums, counts = lax.fori_loop(
            0, -(-T // chunk), body,
            (jnp.zeros((C, dims), jnp.float32), jnp.zeros((C,), jnp.float32)))
        new = sums / jnp.maximum(counts[:, None], 1.0)
        # keep old centroid where a cluster went empty
        return jnp.where(counts[:, None] > 0, new, cents)

    @partial(jax.jit, static_argnames=("metric", "chunk", "rows"))
    def assign_rows(slab, cents, *, metric, chunk, rows):
        # full f32 products: each row goes to its nearest centroid, not
        # to one a bf16 rounding puts level with it
        def body(i, out):
            s = jnp.minimum(i * chunk, rows - chunk)
            x = lax.dynamic_slice_in_dim(slab, s, chunk)
            a = jnp.argmax(_quantizer_affinity(jnp, x, cents, metric,
                                               precision=highest), axis=1)
            return lax.dynamic_update_slice_in_dim(out, a.astype(jnp.int32),
                                                   s, 0)

        return lax.fori_loop(0, -(-rows // chunk), body,
                             jnp.zeros((rows,), jnp.int32))

    return kmeans_step, assign_rows


def is_device_array(x) -> bool:
    jax = _jax()

    return isinstance(x, jax.Array)


def sample_rows(ids: np.ndarray, n_train: Optional[int],
                seed: int) -> np.ndarray:
    """The training rows: every id when ``n_train`` covers them, else a
    seeded draw of ``n_train`` of them without repeats, in id order."""
    if n_train is None or n_train >= ids.size:
        return ids
    pick = np.random.default_rng(seed).choice(ids.size, int(n_train),
                                              replace=False)
    return ids[np.sort(pick)]


def gather_rows(slab, ids: np.ndarray, normalize: bool = False,
                host=None):
    """Device f32[len(ids), dims] of the slab's rows ``ids``, read from the
    host mirror ``host`` where there is one and sent up. (On a lane-major
    slab, see lane_major, a row gather on the device copies the whole slab
    first.)"""
    jax = _jax()

    rows = np.asarray((slab if host is None else host)[ids], np.float32)
    if normalize:
        rows = rows / np.maximum(np.linalg.norm(rows, axis=-1,
                                                keepdims=True), 1e-12)
    return jax.device_put(rows)  # tpulint: offbudget


def train_centroids(train, C: int, iters: int = 8, metric: str = "cosine"):
    """Lloyd's k-means over device rows ``train`` [T, dims] -> device
    f32[min(C, T), dims]. Deterministic: the first centroids are an
    evenly strided pick of the rows (no RNG in the build path, mirroring
    how segment freezes must be reproducible for recovery); a cluster
    that goes empty keeps its centroid."""
    T = train.shape[0]
    C = min(int(C), T)
    stride = max(T // C, 1)
    cents = train[::stride][:C]
    step, _ = _programs()
    chunk = chunk_rows(C, T)
    for _ in range(iters):
        cents = step(train, cents, metric=metric, chunk=chunk)
    return cents


def assign_rows(slab, cents, rows: int, metric: str = "cosine") -> np.ndarray:
    """int32[rows]: the nearest centroid of each of the slab's first
    ``rows`` rows, in chunks of the resident slab (a host slab goes up
    once, for the pass)."""
    jax = _jax()

    _, assign = _programs()
    if not is_device_array(slab):
        slab = jax.device_put(  # tpulint: offbudget
            np.asarray(slab[:rows], np.float32))
    chunk = chunk_rows(int(cents.shape[0]), rows)
    return np.asarray(assign(slab, cents, metric=metric, chunk=chunk,
                             rows=int(rows)))


def kmeans(vecs, C: int, iters: int = 8, seed: int = 1234,
           metric: str = "cosine", n_train: Optional[int] = None):
    """Train C centroids over vecs [N, dims] (host or device in, host
    out): Lloyd's steps over ``n_train`` rows drawn by ``seed`` (all of
    them when None), then ONE final assignment of every row against the
    FINAL centroids (not the stale pre-update assignment), keeping lists
    consistent with the quantizer actually probed at query time.

    The assignment metric follows the field's similarity (advisor r2):
    l2_norm fields cluster/probe by squared-l2, cosine/dot by normalized
    dot — so the inverted lists agree with query-time probing.
    """
    N = vecs.shape[0]
    rows = sample_rows(np.arange(N), n_train, seed)
    cents = train_centroids(gather_rows(vecs, rows), C, iters, metric)
    return np.asarray(cents), assign_rows(vecs, cents, N, metric)


def fill_lists(ids: np.ndarray, assign: np.ndarray, C: int,
               sentinel: int):
    """(i32[C, Lmax] lists padded with ``sentinel``, i32[C] lengths): each
    id in the list of its centroid, in id order — one stable sort, no
    loop over ids."""
    counts = np.bincount(assign, minlength=C).astype(np.int32)
    Lmax = pow2_bucket(int(counts.max()) if counts.size else 1)
    order = np.argsort(assign, kind="stable")
    a = assign[order]
    pos = np.arange(ids.size) - (np.cumsum(counts) - counts)[a]
    lists = np.full((C, Lmax), sentinel, np.int32)
    lists[a, pos] = ids[order]
    return lists, counts


# ---------------------------------------------------------------------------
# index
# ---------------------------------------------------------------------------

@dataclass
class IvfIndex:
    centroids: Any  # f32[C, dims] (device)
    lists: Any  # i32[C, Lmax] doc ids, padded with `sentinel` (device)
    list_lens: Any  # i32[C] (device)
    C: int
    Lmax: int
    sentinel: int  # = max_docs of the owning segment
    avg_len: float
    metric: str = "cosine"  # quantizer metric (follows the field similarity)

    @property
    def ntotal(self) -> int:
        """Indexed vector count (avg_len is n / C at build time)."""
        return max(int(round(self.avg_len * self.C)), 1)

    def nprobe_for(self, num_candidates: int) -> int:
        """nprobe sized so probed lists cover ≈ num_candidates vectors.

        num_candidates clamps to [1, ntotal] BEFORE the coverage math
        (the final max/min already bounded the result to [1, C]; the
        early clamp keeps the sizing honest at the edges — asking for
        more candidates than indexed vectors means "probe everything",
        C exactly, not whatever ceil(nc / avg_len) lands on when lists
        run short)."""
        nc = min(max(int(num_candidates), 1), self.ntotal)
        n = int(np.ceil(nc / max(self.avg_len, 1.0)))
        return max(1, min(n, self.C))


def build_ivf(vecs, exists_np: np.ndarray, max_docs: int,
              C: Optional[int] = None, iters: int = 8,
              metric: str = "cosine", seed: int = 1234,
              vecs_host=None) -> Optional[IvfIndex]:
    """Build an IVF index over the live vectors of one segment slab.

    ``vecs`` is the slab [max_docs, dims], resident on the device (read in
    place, never copied) or a host array; ``vecs_host`` its host mirror,
    where the training rows are read. The quantizer trains on
    TRAIN_PER_LIST rows a list drawn by ``seed`` (every live row where
    there are fewer), then every live row goes to the list of its nearest
    centroid, in chunks: no [N, C] array and no second slab on the
    device. ``C`` is the mapping's ``nlist``."""
    # host-side BUILD path (freeze-time, never traced): the ragged live-id
    # set is exactly what the padded [C, Lmax] device lists exist to absorb
    ids = np.nonzero(exists_np)[0].astype(np.int32)  # tpulint: host
    n = ids.size
    if n < 64:
        return None  # brute force is strictly better at this scale
    if C is None:  # the mapping names no nlist: 4 sqrt(n) lists
        C = int(max(8, min(4 * np.sqrt(n), n // 8)))
    C = min(int(C), n)
    with span("ann.kmeans", lists=C, rows=n):
        train = gather_rows(vecs, sample_rows(ids, TRAIN_PER_LIST * C, seed),
                            host=vecs_host)
        cents = train_centroids(train, C, iters=iters, metric=metric)
        del train
    with span("ann.assign", lists=C, rows=n):
        # the rows up to the last live one, in whole chunks where the
        # slab has them (one program a slab shape, not a live count)
        chunk = chunk_rows(C, int(ids[-1]) + 1)
        rows = min(vecs.shape[0], -(-(int(ids[-1]) + 1) // chunk) * chunk)
        assign = assign_rows(vecs, cents, rows, metric)[ids]
        lists, counts = fill_lists(ids, assign, C, max_docs)
    # IVF device caches live as long as the owning VectorColumn — place
    # through the residency choke point so their HBM is accounted
    from elasticsearch_tpu import resources

    put = resources.RESIDENCY.device_put
    return IvfIndex(
        centroids=put(np.asarray(cents), label="ivf.centroids"),
        lists=put(lists, label="ivf.lists"),
        list_lens=put(counts, label="ivf.list_lens"),
        C=C, Lmax=lists.shape[1], sentinel=max_docs,
        avg_len=float(n) / C, metric=metric,
    )


def lane_major(vecs) -> bool:
    """True where the device holds the slab [N, dims] with its N rows along
    the lanes: XLA:TPU's own layout for f32 rows narrower than a lane tile
    (96 dims: no padding to 128). A row gather from such a slab first
    copies all of it into rows (8 GiB at 2^24 x 96), so the programs read
    a row as the lane-aligned [dims, 128] block that holds it instead."""
    layout = getattr(getattr(vecs, "format", None), "layout", None)
    return (tuple(getattr(layout, "major_to_minor", ())) == (1, 0)
            and vecs.shape[0] % _LANES == 0)


_LANES = 128


def _take_rows(vecs, ids, lane_rows: bool):
    """f32[len(ids), dims] rows of the slab (traced): a gather, or on a
    lane-major slab (lane_major) one lane-aligned block a row and the
    row's lane picked out of it."""
    jax = _jax()
    import jax.numpy as jnp
    from jax import lax

    if not lane_rows:
        return vecs[ids]
    cols = vecs.T  # [dims, N]: the same bytes, rows along the lanes
    blocks = jax.vmap(lambda b: lax.dynamic_slice(
        cols, (0, b), (cols.shape[0], _LANES)))((ids // _LANES) * _LANES)
    return jnp.take_along_axis(blocks, (ids % _LANES)[:, None, None],
                               axis=2)[:, :, 0]


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------

_PROGRAMS: dict = {}


def ivf_candidate_scores(index: IvfIndex, vecs, query_np: np.ndarray,
                         num_candidates: int, metric: str, D: int,
                         pq=None, fine_k: Optional[int] = None,
                         filter_words=None):
    """Scatter ANN candidate scores into a whole-segment [D] score vector.

    Probes the nprobe closest lists (nprobe sized so probed lists cover
    ≈ num_candidates vectors) and emits dense f32[D] scores (−inf
    elsewhere) + bool[D] mask — the same (scores, mask) contract every
    other query program has, so IVF composes with filters/bool/rescore
    unchanged.

    Without ``pq`` every probed candidate's f32 vector is gathered and
    scored exactly — the r05 path whose cost scales linearly with
    num_candidates (the measured 389 -> 12.6 qps cliff). With ``pq`` (a
    PqIndex over the same slab) the pipeline is asymmetric coarse->fine:
    an ADC table-sum ranks ALL candidates from uint8 codes (O(M) bytes
    each), then only the top ``fine_k`` survivors pay the exact f32
    gather+re-rank — cost stops scaling with num_candidates.

    ``filter_words`` (packed uint32[D/32], ops/bitvec.pack_mask) is an
    optional PRE-filter: candidates failing it are dropped before the
    coarse rank, so the fine stage spends its budget entirely on docs
    the filter admits (ES applies the kNN filter during the search).
    """
    jax = _jax()

    from elasticsearch_tpu.monitor import kernels
    from elasticsearch_tpu.ops.scoring import tail_mode_batch

    nprobe = index.nprobe_for(num_candidates)
    # the candidate slots the program reads, padding and all
    kernels.record("ann_candidate_slots", nprobe * index.Lmax)
    sf = tail_mode_batch()
    lane_rows = lane_major(vecs)
    # offbudget: transient per-query upload
    q = jax.device_put(np.asarray(query_np, np.float32))  # tpulint: offbudget
    from elasticsearch_tpu.monitor.programs import REGISTRY, static_sig

    if pq is None and filter_words is None:
        key = (index.C, index.Lmax, D, nprobe, metric, index.metric, sf,
               lane_rows)
        prog = _PROGRAMS.get(key)
        if prog is None:
            from elasticsearch_tpu.parallel import aot

            prog = make_ivf_search(index.C, index.Lmax, D, nprobe, metric,
                                   quantizer_metric=index.metric,
                                   scatter_free=sf, lane_rows=lane_rows)
            # factory-key discipline (ROADMAP #6): the kernel entry rides
            # the AOT blob cache like every executor program
            prog = aot.wrap(prog, "ivf_search", key)
            _PROGRAMS[key] = prog
        # observatory: kernel-entry dispatch time on the shape-class key
        with REGISTRY.timed("ivf_search",
                            static_sig(C=index.C, Lmax=index.Lmax, D=D,
                                       nprobe=nprobe)), \
                span("device.dispatch", program="ivf_search"):
            return prog(q, index.centroids, index.lists, vecs)

    from elasticsearch_tpu.ops import pallas_kernels as pk

    W = nprobe * index.Lmax
    fk = max(1, min(int(fine_k or 64), W, D))
    use_filter = filter_words is not None
    # this dispatcher runs EAGERLY (the Pallas ADC's first real-TPU call
    # may fail at Mosaic lowering time) — same latch discipline as BM25
    force_xla = False
    for _attempt in range(2):
        tile = (0 if force_xla or pq is None
                else pk.adc_pallas_tile(W, pq.M, pq.K))
        key = ("pq", index.C, index.Lmax, D, nprobe, metric, index.metric,
               sf, fk, use_filter, tile, lane_rows,
               (pq.M, pq.K, pq.dsub, pq.metric) if pq is not None else None)
        prog = _PROGRAMS.get(key)
        if prog is None:
            prog = make_ivf_pq_search(
                index.C, index.Lmax, D, nprobe, metric,
                quantizer_metric=index.metric, scatter_free=sf, fine_k=fk,
                pq_meta=((pq.M, pq.K, pq.dsub, pq.metric)
                         if pq is not None else None),
                use_filter=use_filter, adc_tile=tile, lane_rows=lane_rows)
            if not tile:
                # the Pallas-tiled variant keeps its eager first-call
                # latch (Mosaic lowering may fail on device); only the
                # XLA shape classes ride the AOT blob cache
                from elasticsearch_tpu.parallel import aot

                prog = aot.wrap(
                    prog, "ivf_pq_search" if pq is not None else "ivf_search",
                    key)
            _PROGRAMS[key] = prog
        args = [q, index.centroids, index.lists, vecs]
        if pq is not None:
            args += [pq.codes_dev(), pq.codebooks]
        if use_filter:
            args.append(filter_words)
        name = "ivf_pq_search" if pq is not None else "ivf_search"
        try:
            # timed() records nothing when the dispatch raises — the
            # Pallas→XLA retry must not pollute the execute histogram
            with REGISTRY.timed(
                    name,
                    static_sig(C=index.C, Lmax=index.Lmax, D=D,
                               nprobe=nprobe, fk=fk,
                               filtered=use_filter, tile=tile)), \
                    span("device.dispatch", program=name):
                out = prog(*args)
        except Exception as e:
            if tile:
                pk.note_adc_failure(e)
                force_xla = True
                continue
            raise
        if pq is not None:
            if tile:
                pk.note_adc_success()
            kernels.record("adc_pallas" if tile else "adc_xla")
        return out
    raise AssertionError("unreachable: ADC retry loop exits via return")


def make_ivf_search(C: int, Lmax: int, D: int, nprobe: int, metric: str,
                    quantizer_metric: str = "cosine",
                    scatter_free: bool = False, lane_rows: bool = False):
    """Compiled IVF probe+score program for one shape class."""
    jax = _jax()
    import jax.numpy as jnp
    from jax import lax

    from elasticsearch_tpu.ops.knn import knn_row_terms, knn_scores

    @jax.jit
    def ivf_search(query, centroids, lists, vecs):
        # 1. probe: closest nprobe centroids under the SAME metric the
        # lists were clustered with (cosine/dot → normalized dot; l2 →
        # norm-expanded squared distance), so probing agrees with build
        csim = _quantizer_affinity(jnp, query[None, :], centroids,
                                   quantizer_metric)[0]  # [C]
        _, probe = lax.top_k(csim, nprobe)  # [nprobe]
        # 2. candidates: padded ids of the probed lists
        cand = lists[probe].reshape(-1)  # [nprobe * Lmax], pad = D sentinel
        valid = cand < D
        safe = jnp.where(valid, cand, 0)
        cvecs = _take_rows(vecs, safe, lane_rows)  # [nprobe*Lmax, dims]
        # 3. exact metric on candidates only — f32: the whole point of IVF
        # is to spend full precision on a small candidate set (the brute
        # path's bf16 trade-off buys nothing on a matmul this size)
        # (the row term of the gathered candidates, never of the slab)
        cscores = knn_scores(query[None, :], cvecs,
                             knn_row_terms(cvecs, metric=metric),
                             metric=metric, use_bf16=False)[0]
        # 4. expand to the whole-segment score vector
        if scatter_free:
            # each vector belongs to exactly ONE list, so candidate ids
            # are unique: sort (cand, score) by id and gather each doc's
            # single entry via boundary search — no serialized TPU
            # scatter (padding sorts past every real doc)
            sc, ss = lax.sort((cand, jnp.where(valid, cscores, -jnp.inf)),
                              num_keys=1)
            bounds = jnp.searchsorted(sc, jnp.arange(D + 1,
                                                     dtype=sc.dtype))
            lo, n = bounds[:-1], bounds[1:] - bounds[:-1]
            W = sc.shape[0]
            scores = jnp.where(n > 0,
                               ss[jnp.clip(lo, 0, W - 1)], -jnp.inf)
            mask = n > 0
        else:
            scores = jnp.full(D, -jnp.inf, jnp.float32)
            scores = scores.at[cand].max(
                jnp.where(valid, cscores, -jnp.inf), mode="drop")
            mask = jnp.zeros(D, bool).at[cand].max(valid, mode="drop")
        return scores, mask

    return ivf_search


def make_ivf_pq_search(C: int, Lmax: int, D: int, nprobe: int, metric: str,
                       quantizer_metric: str = "cosine",
                       scatter_free: bool = False, fine_k: int = 64,
                       pq_meta=None, use_filter: bool = False,
                       adc_tile: int = 0, lane_rows: bool = False):
    """Compiled asymmetric coarse->fine IVF program for one shape class.

    Stages (all one fused XLA program; statically shaped throughout):

      1. probe — closest nprobe centroids under the quantizer metric.
      2. pre-filter — candidates failing the packed bit-vector filter
         (``use_filter``) drop out of the validity lane BEFORE any
         scoring, so the fine budget is spent on admissible docs only.
      3. coarse — ADC table-sum over uint8 codes (``pq_meta`` =
         (M, K, dsub, pq_metric)); the Pallas tiled kernel when
         ``adc_tile`` > 0, the XLA gather form otherwise. With no PQ
         tier the "coarse" stage IS the exact f32 scoring of every
         candidate (the pre-PQ path, kept for pre-filter-only callers).
      4. fine — exact f32 re-rank of the top ``fine_k`` ADC survivors
         only; their exact scores scatter into the [D] row. Scores the
         executor sees are always exact-metric f32 — PQ never leaks an
         approximate score past this program.
    """
    jax = _jax()
    import jax.numpy as jnp
    from jax import lax

    from elasticsearch_tpu.ops.bitvec import test_bits
    from elasticsearch_tpu.ops.knn import knn_row_terms, knn_scores

    @jax.jit
    def ivf_pq_search(query, centroids, lists, vecs, *rest):
        rest = list(rest)
        if pq_meta is not None:
            codes, codebooks = rest[0], rest[1]
            rest = rest[2:]
        words = rest[0] if use_filter else None
        csim = _quantizer_affinity(jnp, query[None, :], centroids,
                                   quantizer_metric)[0]  # [C]
        _, probe = lax.top_k(csim, nprobe)
        cand = lists[probe].reshape(-1)  # [W], pad = D sentinel
        valid = cand < D
        safe = jnp.where(valid, cand, 0)
        if use_filter:
            valid = valid & test_bits(words, safe)
        if pq_meta is not None:
            from elasticsearch_tpu.ops.pq import adc_lut, adc_sum

            M, K, dsub, pq_metric = pq_meta
            lut = adc_lut(jnp, query, codebooks, pq_metric)
            ccodes = codes[safe]  # [W, M] uint8 — M bytes per candidate
            if adc_tile:
                from elasticsearch_tpu.ops.pallas_kernels import \
                    adc_scores_pallas

                coarse = adc_scores_pallas(ccodes.astype(jnp.int32), lut,
                                           tile=adc_tile)
            else:
                coarse = adc_sum(jnp, ccodes, lut)
            coarse = jnp.where(valid, coarse, -jnp.inf)
            fv, fpos = lax.top_k(coarse, fine_k)
            fids = jnp.take(cand, fpos)
            fvalid = fv > -jnp.inf
            fsafe = jnp.where(fvalid, fids, 0)
            # [fine_k, dims] — the ONLY f32 gather
            fvecs = _take_rows(vecs, fsafe, lane_rows)
            fscores = knn_scores(query[None, :], fvecs,
                                 knn_row_terms(fvecs, metric=metric),
                                 metric=metric, use_bf16=False)[0]
            fscores = jnp.where(fvalid, fscores, -jnp.inf)
        else:
            # pre-filter-only caller: exact scores for every candidate
            cvecs = _take_rows(vecs, safe, lane_rows)
            cs = knn_scores(query[None, :], cvecs,
                            knn_row_terms(cvecs, metric=metric),
                            metric=metric, use_bf16=False)[0]
            fids, fvalid = cand, valid
            fscores = jnp.where(valid, cs, -jnp.inf)
        tgt = jnp.where(fvalid, fids, D)  # invalid -> out of range, dropped
        # fine_k survivors are fine_k scatter updates; the scatter-free
        # form's boundary search gathers over all D slots, once a step of
        # its log2(fine_k) loop and once more for the scores: only the
        # W-wide pre-filter-only expansion takes it
        if scatter_free and pq_meta is None:
            # survivor ids are unique (one inverted list per vector);
            # same sort + boundary-search expansion as make_ivf_search
            sc, ss = lax.sort((tgt, fscores), num_keys=1)
            bounds = jnp.searchsorted(sc, jnp.arange(D + 1, dtype=sc.dtype))
            lo, n = bounds[:-1], bounds[1:] - bounds[:-1]
            Wf = sc.shape[0]
            scores = jnp.where(n > 0, ss[jnp.clip(lo, 0, Wf - 1)], -jnp.inf)
            mask = n > 0
        else:
            scores = jnp.full(D, -jnp.inf, jnp.float32).at[tgt].max(
                fscores, mode="drop")
            mask = jnp.zeros(D, bool).at[tgt].max(fvalid, mode="drop")
        return scores, mask

    return ivf_pq_search
