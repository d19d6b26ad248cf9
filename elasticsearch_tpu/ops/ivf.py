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
from functools import partial
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

def _quantizer_affinity(jnp, vecs, cents, metric: str):
    """[N, C] affinity used for BOTH k-means assignment and query-time
    probing — argmax row-wise picks the nearest centroid under the field's
    similarity. l2_norm uses the norm expansion (argmin ||v-c||^2 ==
    argmax v.c - ||c||^2/2); cosine/dot normalize centroids (dot against a
    unit-norm direction — standard spherical k-means for MIPS/cosine)."""
    if metric in ("l2_norm", "l2"):
        vc = jnp.matmul(vecs, cents.T, preferred_element_type=jnp.float32)
        return vc - 0.5 * jnp.sum(cents * cents, axis=-1)[None, :]
    cn = cents / jnp.maximum(
        jnp.linalg.norm(cents, axis=-1, keepdims=True), 1e-12)
    return jnp.matmul(vecs, cn.T, preferred_element_type=jnp.float32)


def kmeans(vecs_np: np.ndarray, C: int, iters: int = 8, seed: int = 1234,
           metric: str = "cosine"):
    """Train C centroids over vecs [N, dims] (host in, host out).

    Deterministic: init = evenly strided sample of the corpus (stable across
    runs — no RNG in the build path, mirroring how segment freezes must be
    reproducible for recovery). Empty clusters re-seed from the farthest
    vectors of the biggest cluster's assignment pass.

    The assignment metric follows the field's similarity (advisor r2):
    l2_norm fields cluster/probe by squared-l2, cosine/dot by normalized
    dot — so the inverted lists agree with query-time probing. Returns
    (centroids, assign) where `assign` is ONE FINAL assignment pass against
    the FINAL centroids (not the stale pre-update assignment), keeping the
    lists consistent with the quantizer actually probed at query time.
    """
    jax = _jax()
    import jax.numpy as jnp

    N, dims = vecs_np.shape
    C = min(C, N)
    stride = max(N // C, 1)
    cents = vecs_np[:: stride][:C].astype(np.float32).copy()

    @partial(jax.jit, static_argnames=("nc", "metric"))
    def step(vecs, cents, *, nc, metric):
        # one [N, C] matmul on the MXU
        sim = _quantizer_affinity(jnp, vecs, cents, metric)
        assign = jnp.argmax(sim, axis=1)
        one = jnp.zeros((nc,), jnp.float32).at[assign].add(1.0)
        sums = jnp.zeros((nc, vecs.shape[1]), jnp.float32).at[assign].add(vecs)
        new = sums / jnp.maximum(one[:, None], 1.0)
        # keep old centroid where a cluster went empty
        new = jnp.where(one[:, None] > 0, new, cents)
        return new, assign

    @partial(jax.jit, static_argnames=("metric",))
    def assign_only(vecs, cents, *, metric):
        return jnp.argmax(_quantizer_affinity(jnp, vecs, cents, metric), axis=1)

    # offbudget: k-means build temporaries — freed when the build returns
    d_vecs = jax.device_put(vecs_np.astype(np.float32))  # tpulint: offbudget
    d_cents = jax.device_put(cents)  # tpulint: offbudget
    for _ in range(iters):
        d_cents, _ = step(d_vecs, d_cents, nc=C, metric=metric)
    assign = assign_only(d_vecs, d_cents, metric=metric)
    return np.asarray(d_cents), np.asarray(assign)


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


def build_ivf(vecs_np: np.ndarray, exists_np: np.ndarray, max_docs: int,
              C: Optional[int] = None, iters: int = 8,
              metric: str = "cosine") -> Optional[IvfIndex]:
    """Build an IVF index over the live vectors of one segment slab."""
    jax = _jax()

    # host-side BUILD path (freeze-time, never traced): the ragged live-id
    # set is exactly what the padded [C, Lmax] device lists exist to absorb
    ids = np.nonzero(exists_np)[0].astype(np.int32)  # tpulint: host
    n = ids.size
    if n < 64:
        return None  # brute force is strictly better at this scale
    live = vecs_np[ids]
    if C is None:
        C = int(max(8, min(4 * np.sqrt(n), n // 8)))
    cents, assign = kmeans(live, C, iters=iters, metric=metric)
    C = cents.shape[0]
    counts = np.bincount(assign, minlength=C)
    Lmax = pow2_bucket(int(counts.max()) if counts.size else 1)
    lists = np.full((C, Lmax), max_docs, np.int32)
    fill = np.zeros(C, np.int64)
    for i, a in zip(ids, assign):
        lists[a, fill[a]] = i
        fill[a] += 1
    # IVF device caches live as long as the owning VectorColumn — place
    # through the residency choke point so their HBM is accounted
    from elasticsearch_tpu import resources

    put = resources.RESIDENCY.device_put
    return IvfIndex(
        centroids=put(cents, label="ivf.centroids"),
        lists=put(lists, label="ivf.lists"),
        list_lens=put(counts.astype(np.int32), label="ivf.list_lens"),
        C=C, Lmax=Lmax, sentinel=max_docs,
        avg_len=float(n) / C, metric=metric,
    )


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

    from elasticsearch_tpu.ops.scoring import tail_mode_batch

    nprobe = index.nprobe_for(num_candidates)
    sf = tail_mode_batch()
    # offbudget: transient per-query upload
    q = jax.device_put(np.asarray(query_np, np.float32))  # tpulint: offbudget
    from elasticsearch_tpu.monitor.programs import REGISTRY, static_sig

    if pq is None and filter_words is None:
        key = (index.C, index.Lmax, D, nprobe, metric, index.metric, sf)
        prog = _PROGRAMS.get(key)
        if prog is None:
            from elasticsearch_tpu.parallel import aot

            prog = make_ivf_search(index.C, index.Lmax, D, nprobe, metric,
                                   quantizer_metric=index.metric,
                                   scatter_free=sf)
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

    from elasticsearch_tpu.monitor import kernels
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
               sf, fk, use_filter, tile,
               (pq.M, pq.K, pq.dsub, pq.metric) if pq is not None else None)
        prog = _PROGRAMS.get(key)
        if prog is None:
            prog = make_ivf_pq_search(
                index.C, index.Lmax, D, nprobe, metric,
                quantizer_metric=index.metric, scatter_free=sf, fine_k=fk,
                pq_meta=((pq.M, pq.K, pq.dsub, pq.metric)
                         if pq is not None else None),
                use_filter=use_filter, adc_tile=tile)
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
                    scatter_free: bool = False):
    """Compiled IVF probe+score program for one shape class."""
    jax = _jax()
    import jax.numpy as jnp
    from jax import lax

    from elasticsearch_tpu.ops.knn import knn_row_terms, knn_scores

    @jax.jit
    def run(query, centroids, lists, vecs):
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
        cvecs = vecs[safe]  # [nprobe*Lmax, dims]
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

    return run


def make_ivf_pq_search(C: int, Lmax: int, D: int, nprobe: int, metric: str,
                       quantizer_metric: str = "cosine",
                       scatter_free: bool = False, fine_k: int = 64,
                       pq_meta=None, use_filter: bool = False,
                       adc_tile: int = 0):
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
    def run(query, centroids, lists, vecs, *rest):
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
            fvecs = vecs[fsafe]  # [fine_k, dims] — the ONLY f32 gather
            fscores = knn_scores(query[None, :], fvecs,
                                 knn_row_terms(fvecs, metric=metric),
                                 metric=metric, use_bf16=False)[0]
            fscores = jnp.where(fvalid, fscores, -jnp.inf)
        else:
            # pre-filter-only caller: exact scores for every candidate
            cvecs = vecs[safe]
            cs = knn_scores(query[None, :], cvecs,
                            knn_row_terms(cvecs, metric=metric),
                            metric=metric, use_bf16=False)[0]
            fids, fvalid = cand, valid
            fscores = jnp.where(valid, cs, -jnp.inf)
        tgt = jnp.where(fvalid, fids, D)  # invalid -> out of range, dropped
        if scatter_free:
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

    return run
