"""Distributed query execution over a shard Mesh.

Reference: org/elasticsearch/action/search/type/
TransportSearchQueryThenFetchAction.java — ES scatters the query phase to
every shard over netty, each node runs Lucene locally, and the coordinating
node merges per-shard top-k priority queues on the CPU.

Here the scatter/gather is a *single compiled XLA program*: shard-local
arrays (postings, doc values, vector slabs) are laid out with a
``NamedSharding`` over the ('shard',) mesh axis, a ``shard_map`` body scores
its local segment and takes a local top-k, and the merge is an
``all_gather`` + global ``lax.top_k`` executed identically on every device
(so the result is replicated — every "node" holds the final hit list, no
separate coordinator round-trip). Aggregation partials and total-hit counts
merge with ``psum``. All collectives ride ICI; nothing goes through a host.

Programs are cached per shape-class (S shards × Q queries × T term-chunks ×
P postings window × D docs × k), mirroring how one Lucene Weight tree
serves many queries of the same structure.

COLLECTIVE PURITY (tpulint R014): every ``body`` below — and every
helper it calls, at any depth — runs SPMD on all mesh slots; one host
sync (``device_get``/``.item()``/``np.asarray`` of a traced value)
inside that region stalls every chip at the next psum/all_gather. The
whole-program analyzer marks everything reachable from a
``wrap(body, ...)`` call as collective and gates the repo on zero
violations — keep host work (device_put, result pulls, the pack_spec
construction) OUTSIDE the bodies, as the code below does.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from elasticsearch_tpu.tracing.tracer import span, tag_active
from elasticsearch_tpu.utils.shapes import pow2_bucket

# device-array LRU capacity per executor (entries are whole segment rounds;
# eviction frees HBM for indexes that refresh frequently)
_DATA_CACHE_CAP = 32


def _dev_nbytes(val) -> int:
    """Total device bytes referenced by a cache entry (arrays nested in
    lists/tuples) — the executor caches' residency accounting."""
    total, stack = 0, [val]
    while stack:
        v = stack.pop()
        if isinstance(v, (list, tuple)):
            stack.extend(v)
        else:
            total += int(getattr(v, "nbytes", 0) or 0)
    return total


def _jax():
    import jax

    return jax


def _collectives(mesh):
    """(psum, all_gather, wrap, sl) for this mesh.

    A single-slot mesh compiles the body as a PLAIN jit program over
    PRE-SQUEEZED arrays (no leading shard dim): slicing the [1, ...]
    shard dim inside the program wraps the downstream dot_general in a
    loop fusion, which XLA:CPU executes as naive scalar loops instead of
    the GEMM kernel — the identical matvec+top-k body measures ~30x
    slower that way — and a 1-chip mesh (the single-TPU serving case)
    needs no collectives at all. `sl` is the per-shard local-view
    accessor bodies use in place of `a[0]`; output shapes are identical
    between the two paths.
    """
    jax = _jax()
    from jax import lax

    from elasticsearch_tpu.parallel.mesh import mesh_size

    if mesh_size(mesh) == 1:
        psum = lambda x, _axis: x
        all_gather = lambda x, _axis: x[None]
        wrap = lambda body, in_specs, out_specs: jax.jit(body)
        sl = lambda a: a  # host already dropped the shard dim
        return psum, all_gather, wrap, sl

    def wrap(body, in_specs, out_specs):
        return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                     out_specs=out_specs, check_vma=False))

    return lax.psum, lax.all_gather, wrap, (lambda a: a[0])


# ---------------------------------------------------------------------------
# compiled programs
# ---------------------------------------------------------------------------

def _bm25_program(mesh, cache, *, Q: int, T: int, P: int, D: int, k: int):
    """Batched distributed BM25: Q queries × S shards → global top-k.

    Inputs (S = mesh 'shard' size; all sharded on axis 0 over 'shard'):
      doc_ids  i32[S, nnz]   postings doc ids (per-shard segment)
      tfnorm   f32[S, nnz]   precomputed tf-normalization
      starts   i32[S, Q, T]  per-shard per-query chunk starts (vocab is
      lens     i32[S, Q, T]  shard-local, so chunk tables differ per shard)
      weights  f32[S, Q, T]  idf × boost, folded on host
      live     bool[S, D]    live-doc mask
    Returns (replicated): vals f32[Q,k], shard i32[Q,k], local i32[Q,k],
      totals i32[Q] (exact hit counts via psum).
    """
    from elasticsearch_tpu.ops.scoring import (bm25_score_segment,
                                               topk_auto, topk_block_config)

    blk = topk_block_config()  # static: part of the program cache key
    key = ("bm25", Q, T, P, D, k, blk)
    if key in cache:
        return cache[key]
    jax = _jax()
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as PS

    psum, all_gather, wrap, sl = _collectives(mesh)

    def body(doc_ids, tfnorm, starts, lens, weights, live):
        # sl: local shard view ([1, ...]-sliced under shard_map; identity
        # on a pre-squeezed single-slot mesh)
        score1 = lambda s, l, w: bm25_score_segment(
            sl(doc_ids), sl(tfnorm), s, l, w, P=P, D=D)
        scores = jax.vmap(score1)(sl(starts), sl(lens), sl(weights))  # [Q, D]
        masked = jnp.where(sl(live)[None, :], scores, -jnp.inf)
        hit = masked > 0.0
        totals = psum(jnp.sum(hit.astype(jnp.int32), axis=1), "shard")
        vals, idx = topk_auto(masked, k, blk)  # [Q, k] local
        av = all_gather(vals, "shard")  # [S, Q, k]
        ai = all_gather(idx, "shard")
        S = av.shape[0]
        flat = jnp.transpose(av, (1, 0, 2)).reshape(Q, S * k)
        gvals, gpos = lax.top_k(flat, k)  # [Q, k]
        gshard = (gpos // k).astype(jnp.int32)
        flat_idx = jnp.transpose(ai, (1, 0, 2)).reshape(Q, S * k)
        glocal = jnp.take_along_axis(flat_idx, gpos, axis=1).astype(jnp.int32)
        return gvals, gshard, glocal, totals

    sh = PS("shard")
    fn = wrap(body, (sh, sh, sh, sh, sh, sh), (PS(), PS(), PS(), PS()))
    # AOT executable cache (parallel/aot.py): first call per concrete
    # arg-shape class resolves memo → serialized-blob deserialize →
    # fresh compile(+store) — the restart path skips XLA entirely
    from elasticsearch_tpu.parallel import aot

    fn = aot.wrap(fn, "mesh_bm25", key)
    cache[key] = fn
    return fn


def _knn_program(mesh, cache, *, Q: int, dims: int, D: int, k: int, metric: str):
    """Distributed brute-force kNN: queries replicated, vector slabs sharded.

    vecs f32[S, D, dims] sharded over 'shard'; terms f32[S, D] the
    slabs' stored row term (ops/knn.knn_row_terms; None for dot_product);
    queries f32[Q, dims] replicated; live bool[S, D]. bf16 matmul on the
    MXU per shard, local top-k, all_gather merge — the ES-2.0-era
    equivalent would be a per-shard Lucene scan + coordinator merge.
    """
    from elasticsearch_tpu.ops.scoring import topk_block_config

    # the body's knn_topk_auto dispatcher reads the topk config during
    # tracing — key the program on it so an env flip retraces
    key = ("knn", Q, dims, D, k, metric, topk_block_config())
    if key in cache:
        return cache[key]
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as PS

    from elasticsearch_tpu.ops.knn import exact_rescore_topk, has_row_terms
    from elasticsearch_tpu.ops.pallas_kernels import knn_topk_auto

    psum, all_gather, wrap, sl = _collectives(mesh)

    def body(queries, vecs, terms, live):
        # per-shard fused scores+mask+topk: the Pallas streaming kernel on
        # TPU (no [Q, D] HBM intermediate), the XLA path elsewhere. bf16
        # sweep OVERSAMPLED 4x (bf16's ~3-digit mantissa can rank a true
        # top-k neighbor just outside position k on near-tie corpora), then
        # an f32 re-rank of the candidates cut back to k — FAISS-style
        # two-stage refinement, so merged results keep exact recall.
        kp = min(max(4 * k, k), D)
        vals, idx = knn_topk_auto(queries, sl(vecs),
                                  None if terms is None else sl(terms),
                                  sl(live), k=kp, metric=metric)
        vals, idx = exact_rescore_topk(queries, sl(vecs), vals, idx,
                                       metric=metric)
        vals, idx = vals[:, :k], idx[:, :k]
        av = all_gather(vals, "shard")
        ai = all_gather(idx, "shard")
        S = av.shape[0]
        flat = jnp.transpose(av, (1, 0, 2)).reshape(Q, S * k)
        gvals, gpos = lax.top_k(flat, k)
        gshard = (gpos // k).astype(jnp.int32)
        flat_idx = jnp.transpose(ai, (1, 0, 2)).reshape(Q, S * k)
        glocal = jnp.take_along_axis(flat_idx, gpos, axis=1).astype(jnp.int32)
        return gvals, gshard, glocal

    from elasticsearch_tpu.parallel import aot

    # a dot_product slab has no row term: None in, no spec for it
    terms_spec = PS("shard") if has_row_terms(metric) else None
    fn = wrap(body, (PS(), PS("shard"), terms_spec, PS("shard")),
              (PS(), PS(), PS()))
    fn = aot.wrap(fn, "mesh_knn", key)
    cache[key] = fn
    return fn


def _maxsim_program(mesh, cache, *, Q: int, T: int, dims: int, D: int,
                    k: int, metric: str):
    """Distributed multi-vector MaxSim: token matrices replicated, vector
    slabs sharded.

    tokens f32[Q, T, dims] (T query tokens per request, repeat-padded);
    per-doc score = max over tokens (one vector per doc). Per shard: one
    fused [Q*T] top-k sweep (bf16 oversampled + f32 re-rank — the same
    two-stage refinement as the kNN program), a dedup-by-max merge per
    request, then the all_gather global top-k merge."""
    from elasticsearch_tpu.ops.scoring import topk_block_config

    key = ("maxsim", Q, T, dims, D, k, metric, topk_block_config())
    if key in cache:
        return cache[key]
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as PS

    from elasticsearch_tpu.ops.knn import (exact_rescore_topk,
                                           has_row_terms,
                                           merge_candidate_topk)
    from elasticsearch_tpu.ops.pallas_kernels import knn_topk_auto

    psum, all_gather, wrap, sl = _collectives(mesh)

    def body(tokens, vecs, terms, live):
        flat = tokens.reshape(Q * T, dims)
        kp = min(max(4 * k, k), D)
        vals, idx = knn_topk_auto(flat, sl(vecs),
                                  None if terms is None else sl(terms),
                                  sl(live), k=kp, metric=metric)
        vals, idx = exact_rescore_topk(flat, sl(vecs), vals, idx,
                                       metric=metric)
        # per-request dedup-by-max over the token axis, then local top-k
        vals, idx, _ = merge_candidate_topk(
            vals.reshape(Q, T * kp), idx.reshape(Q, T * kp), k=k)
        av = all_gather(vals, "shard")
        ai = all_gather(idx, "shard")
        S = av.shape[0]
        flat_v = jnp.transpose(av, (1, 0, 2)).reshape(Q, S * k)
        gvals, gpos = lax.top_k(flat_v, k)
        gshard = (gpos // k).astype(jnp.int32)
        flat_i = jnp.transpose(ai, (1, 0, 2)).reshape(Q, S * k)
        glocal = jnp.take_along_axis(flat_i, gpos, axis=1).astype(jnp.int32)
        return gvals, gshard, glocal

    from elasticsearch_tpu.parallel import aot

    # a dot_product slab has no row term: None in, no spec for it
    terms_spec = PS("shard") if has_row_terms(metric) else None
    fn = wrap(body, (PS(), PS("shard"), terms_spec, PS("shard")),
              (PS(), PS(), PS()))
    fn = aot.wrap(fn, "mesh_maxsim", key)
    cache[key] = fn
    return fn


def _tail_candidates_mode(compiled) -> bool:
    """True when this structure should run the scatter-free candidate-set
    top-k: a single hybrid scores-mode term group with no sort/aggs/mask
    (the plain match/term single-query shape — the latency headline).
    ``ESTPU_TAIL_MODE``: auto (default — candidates on TPU, where XLA
    serializes scatter-adds; the [D] scatter elsewhere) | candidates |
    scatter. Read at program-build time; search_dsl keys its cache on it.
    """
    import os

    from elasticsearch_tpu.parallel.compiler import ETermGroupHybrid

    if not (isinstance(compiled.root, ETermGroupHybrid)
            and compiled.root.mode == "scores"
            and compiled.sort_prim is None and not compiled.agg_prims
            and not compiled.want_mask):
        return False
    mode = os.environ.get("ESTPU_TAIL_MODE", "auto").lower()
    if mode == "candidates":
        return True
    if mode == "scatter":
        return False
    return _jax().default_backend() == "tpu"


def _dsl_program(mesh, compiled, counts, statics, k: int, pack_spec=(),
                 force_scatter: bool = False, aot_key=None):
    """Build the shard_map program for one compiled DSL structure: emit-tree
    score/mask → local top-k → all_gather + global top-k, exact totals via
    psum, per-shard terms-agg count vectors.

    ``pack_spec`` — tuple of (flat_index, per_shard_shape, dtype_str) for
    logical inputs that arrive CONCATENATED in one trailing i32 word
    buffer instead of as separate arrays: every device_put is a full
    host→device round trip, and a query's small tables (row lists, chunk
    tables, range bounds) would otherwise ship as 5+ separate transfers. The body slices each segment back out
    and bitcasts to its dtype (all 4-byte, so a pure reinterpret)."""
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as PS

    from elasticsearch_tpu.ops.scoring import topk_auto, topk_block_config

    from elasticsearch_tpu.ops.scoring import tail_mode_batch

    blk = topk_block_config()  # read OUTSIDE the traced body; the caller
    # keys its program cache on it too (search_dsl prog_key)
    meta = {i: s for i, s in enumerate(statics)}
    n_aggs = len(compiled.agg_prims)
    psum, all_gather, wrap, sl = _collectives(mesh)
    packed_idx = {i for i, _, _ in pack_spec}
    tail_candidates = _tail_candidates_mode(compiled) and not force_scatter
    # ONE switch for every scatter-vs-sort choice in this program, plumbed
    # to the emits through meta["_cfg"] (compiler._scatter_free) so the
    # force_scatter insurance rebuild traces scatter forms INSIDE the
    # emit tree too, not just at this program's top level
    scatter_free = tail_mode_batch() and not force_scatter
    meta["_cfg"] = {"scatter_free": scatter_free}

    def body(*phys):
        raw = list(phys)
        unpacked = {}
        if pack_spec:
            words = sl(raw.pop())  # [W] local word view
            off = 0
            for idx, shp, dt in pack_spec:
                n = int(np.prod(shp)) if shp else 1
                seg = words[off: off + n]
                if dt != "int32":
                    seg = lax.bitcast_convert_type(seg, jnp.dtype(dt))
                unpacked[idx] = seg.reshape(shp)
                off += n
        it = iter(raw)
        env = {}
        pos = 0
        for i, c in enumerate(counts):
            env[i] = tuple(unpacked[j] if j in packed_idx
                           else sl(next(it))
                           for j in range(pos, pos + c))
            pos += c
        if tail_candidates:
            # scatter-free fast path: a single hybrid scores-mode group
            # with no sort/aggs/mask computes its local top-k Lucene-style
            # (only tail-TOUCHED docs scored; ops/scoring.
            # bm25_hybrid_candidates_topk has the traffic/serialization
            # math) — XLA's scatter lowering serializes on TPU, so the
            # [D]-vector construction is the single-query wall
            from elasticsearch_tpu.ops.scoring import (
                bm25_hybrid_candidates_topk)

            root = compiled.root
            doc_ids, tfnorm = env[root.post]
            impact, qrows, qrw, starts, lens, ws = env[root.prim]
            (P, _R) = meta[root.prim]
            live = env[compiled.live][0]
            vals, idx, tot = bm25_hybrid_candidates_topk(
                impact, qrows, qrw, doc_ids, tfnorm, starts, lens, ws,
                live, P=P, D=root.D, k=k, topk_block=blk)
            # boost is already folded into qrw/ws by the prim's terms_fn
            totals = psum(tot, "shard")
        else:
            scores, mask = compiled.root.sm(env, meta)
            live = env[compiled.live][0]
            mask = mask & live
            totals = psum(jnp.sum(mask.astype(jnp.int32)), "shard")
            if compiled.sort_prim is not None:
                desc, miss_first = compiled.sort_cfg
                values, exists = env[compiled.sort_prim]
                missing = jnp.float32(-jnp.inf if desc else jnp.inf)
                if miss_first:
                    missing = -missing
                keyv = jnp.where(exists, values, missing)
                rank = keyv * (1.0 if desc else -1.0)
            else:
                rank = scores
            masked = jnp.where(mask, rank, -jnp.inf)
            vals, idx = topk_auto(masked, k, blk)
        av = all_gather(vals, "shard")  # [S, k]
        ai = all_gather(idx, "shard")
        S = av.shape[0]
        # field-sorted queries keep EVERY per-shard candidate: the device
        # rank is a primary-key preselect only, and a global top-k by that
        # rank would drop tied docs the full tuple ranks higher (the host
        # staging in mesh_service does the exact ordering)
        kg = S * k if compiled.sort_prim is not None else k
        gvals, gpos = lax.top_k(av.reshape(S * k), kg)
        gslot = (gpos // k).astype(jnp.int32)
        glocal = ai.reshape(S * k)[gpos].astype(jnp.int32)
        # ONE packed result array: each device→host array pull pays a fixed
        # round-trip latency (network-attached chips: ~5-20 ms), so four
        # tiny outputs would quadruple per-query latency
        packed = jnp.concatenate([
            lax.bitcast_convert_type(gvals, jnp.int32), gslot, glocal,
            jnp.asarray(totals, jnp.int32)[None]])
        outs = [packed]
        for _name, prim in compiled.agg_prims:
            doc_ids, term_ids, vreal = env[prim]
            (vmax,) = meta[prim]
            w = mask[doc_ids] & (term_ids < vreal)
            if scatter_free:
                # TPU: histogram via sort + boundary search — the
                # len(term_ids)-element scatter-add into the bin vector
                # serializes on TPU like the scoring tail did. Masked
                # entries sort to the vmax+1 sentinel past every bin.
                ids = jnp.where(w, term_ids, vmax + 1)
                sids = jnp.sort(ids)
                bounds = jnp.searchsorted(
                    sids, jnp.arange(vmax + 2, dtype=sids.dtype))
                cnts = (bounds[1:] - bounds[:-1]).astype(jnp.float32)
            else:
                cnts = jnp.zeros(vmax + 1, jnp.float32).at[term_ids].add(
                    w.astype(jnp.float32), mode="drop")
            outs.append(cnts[None, :])  # keep per-shard partials
        if compiled.want_mask:
            outs.append(mask[None, :])  # [S, D] sharded, for host-side aggs
        return tuple(outs)

    # physical inputs: the non-packed arrays in order, then the word buffer
    n_in = sum(counts) - len(pack_spec) + (1 if pack_spec else 0)
    in_specs = tuple(PS("shard") for _ in range(n_in))
    out_specs = (PS(),) + tuple(
        PS("shard") for _ in range(n_aggs + (1 if compiled.want_mask else 0)))
    fn = wrap(body, in_specs, out_specs)
    if aot_key is not None:
        # AOT executable cache: aot_key is the caller's full program-cache
        # key (struct key + statics + shapes + kernel config) — two DSL
        # trees with identical arg shapes stay distinct blobs
        from elasticsearch_tpu.parallel import aot

        fn = aot.wrap(
            fn, "mesh_dsl_scatter" if force_scatter else "mesh_dsl",
            (aot_key, force_scatter))
    return fn


def _psum_program(mesh, cache, shape):
    """Merge per-shard numeric agg partials: psum over 'shard'."""
    key = ("psum", tuple(shape))
    if key in cache:
        return cache[key]
    from jax.sharding import PartitionSpec as PS

    psum, _all_gather, wrap, sl = _collectives(mesh)

    def body(x):
        return psum(sl(x), "shard")

    from elasticsearch_tpu.parallel import aot

    fn = wrap(body, (PS("shard"),), PS())
    fn = aot.wrap(fn, "mesh_psum", key)
    cache[key] = fn
    return fn


# ---------------------------------------------------------------------------
# host-side executor
# ---------------------------------------------------------------------------

class MeshSearchExecutor:
    """Runs batched queries over N shards laid out on a shard Mesh.

    Host work is only per-query *preparation* (analysis, shard-local term
    lookup, chunk-table construction) — scoring + merge is one XLA program.
    Segments within a shard are searched in rounds (round r stacks the r-th
    segment of every shard, padding shards that have fewer segments with an
    empty slot), then rounds merge on host; a force-merged index is a single
    round and fully fused.
    """

    def __init__(self, mesh, shards):
        from elasticsearch_tpu.parallel.mesh import mesh_size

        self.mesh = mesh
        self.S = mesh_size(mesh)
        # each entry: IndexShard | list[TpuSegment] | TpuSegment. More
        # shards than mesh slots wrap round-robin (shard i → slot i % S,
        # its segments joining that slot's rounds) — ES packs multiple
        # shards per node the same way.
        self.shards = list(shards)
        if len(shards) < self.S:
            raise ValueError(
                f"mesh has {self.S} shard slots but got only {len(shards)} "
                f"shards; build the mesh with shard_mesh(n_shards)")
        # compiled programs die with the executor (and thus the mesh)
        self._programs: Dict[Tuple, Any] = {}
        # prepared-query memo (LRU): (canonical body, round, segment
        # identity + tombstone counts, k) → (compiled, prog, device
        # inputs, kk, segment refs — pinned so an id() in the key can
        # never be recycled while its entry is alive, the _cached_data
        # discipline —, residency token)
        self._prep: "OrderedDict[Tuple, Any]" = OrderedDict()
        # _qc_lock discipline (index_service.py): searches race under the
        # threading REST server, and a concurrent cap-overflow popitem
        # racing a move_to_end corrupts the OrderedDict into a 500
        self._prep_lock = threading.Lock()
        # sharded device arrays per segment round — postings and vector slabs
        # are immutable once frozen, so reuse them across queries; only the
        # (small) live mask is re-uploaded every call. LRU-bounded.
        self._data: "OrderedDict[Tuple, Any]" = OrderedDict()
        self._data_lock = threading.Lock()

    def _put_sharded(self, a):
        """Device-put a host array laid out [S, ...] for the mesh. On a
        single-slot mesh the shard dim is dropped HERE, on host: slicing
        it inside the program wraps downstream dots in loop fusions (see
        _collectives). np indexing is a view — no host copy. An array a
        cached group derived on the device from its own placed arrays (a
        slab's row term) is in that layout already and passes through."""
        if hasattr(a, "sharding"):
            return a
        jax = _jax()
        # offbudget: mesh placement choke point — transient per-query
        # inputs; the persistent rounds are charged via RESIDENCY.track
        # in _cached_data / the prepared-query memo
        if self.S == 1:
            return jax.device_put(np.asarray(a)[0],  # tpulint: offbudget
                                  self.mesh.devices.flat[0])
        from jax.sharding import NamedSharding, PartitionSpec as PS

        return jax.device_put(a, NamedSharding(self.mesh, PS("shard")))  # tpulint: offbudget

    def _cached_data(self, key, build, refs):
        """Cache device arrays keyed by segment ids. `refs` (the segments
        themselves) are stored alongside so a cached id() can never be
        recycled by a new object while its entry is alive. Dict ops are
        locked (concurrent searches race); build() runs unlocked — a
        duplicate build is wasted work, a serialized compile is a stall.
        Entries carry a residency token so the cache's HBM shows in
        /_nodes (request tier, force-charged: the LRU cap is the ceiling)."""
        from elasticsearch_tpu.monitor import kernels

        with self._data_lock:
            if key in self._data:
                self._data.move_to_end(key)
                kernels.record("executor_data_hit")
                return self._data[key][0]
        kernels.record("executor_data_miss")
        val = build()
        from elasticsearch_tpu import resources

        tok = resources.RESIDENCY.track(_dev_nbytes(val),
                                        label="executor.data")
        with self._data_lock:
            self._data[key] = (val, list(refs), tok)
            evicted = (self._data.popitem(last=False)
                       if len(self._data) > _DATA_CACHE_CAP else None)
        if evicted is not None:
            evicted[1][2].close()
        return val

    # -- BM25 ---------------------------------------------------------------

    def search_terms(self, field: str, query_terms: List[List[Tuple[str, float]]],
                     k: int = 10, shards=None):
        """query_terms: per query, list of (term, boost). Returns
        (vals [Q,k], shard [Q,k], local [Q,k], seg_ord [Q,k], totals [Q])
        merged across every segment round; (shard, seg_ord, local) addresses
        a doc as (originating shard, segment ordinal within it, local id).

        ``shards`` overrides the live shard list with a caller-held
        snapshot (per-shard segment lists), the way search_dsl takes one:
        the mesh query-then-fetch path must score exactly the reader
        snapshot it will fetch from."""
        merged = None
        rows = (self._segment_rounds() if shards is None
                else self._rounds_for(list(shards)))
        for row in rows:
            out = self._search_round(field, query_terms, row, k)
            merged = out if merged is None else _merge_rounds(merged, out, k)
        return merged

    def _segment_rounds(self):
        """Rows of (orig_shard_index, seg_ordinal, segment)|None per round.

        Slot s holds the concatenated segments of shards s, s+S, s+2S, …
        (round-robin wrap); `shard_index` on results maps a slot back to the
        originating shard via the stored pairs.
        """
        return self._rounds_for(self.shards)

    def _search_round(self, field, query_terms, row, k):
        with span("search.plan"):
            prog, sig, dev, Qr, lut_shard, lut_ord = self._plan_round(
                field, query_terms, row, k)
        from elasticsearch_tpu.monitor.programs import REGISTRY

        # program observatory: wall time (dispatch + the host pull below)
        # lands on the (program, padded shape class, backend) key, split
        # compile-vs-execute by this thread's trace delta
        with REGISTRY.timed("mesh_bm25", sig, field=field):
            with span("device.dispatch", program="mesh_bm25"):
                vals, slot, local, totals = prog(*dev)
            with span("device.wait"):
                slot = np.asarray(slot)[:Qr]
                vals, local, totals = (np.asarray(vals)[:Qr],
                                       np.asarray(local)[:Qr],
                                       np.asarray(totals)[:Qr])
        # slot index → originating shard + its segment ordinal (wrap-aware);
        # [:Qr] drops the pow2 query-padding rows
        return vals, lut_shard[slot], local, lut_ord[slot], totals

    def _plan_round(self, field, query_terms, row, k):
        """Host half of one ``mesh_bm25`` round: shape buckets, chunk
        tables, device inputs. Returns (program, shape signature, device
        arguments, real query count, slot→shard and slot→segment maps)."""
        seg_row = [e[2] if e is not None else None for e in row]
        lut_shard = np.asarray([e[0] if e is not None else -1 for e in row],
                               np.int32)
        lut_ord = np.asarray([e[1] if e is not None else 0 for e in row],
                             np.int32)

        # shape buckets common across shards
        D = pow2_bucket(max((s.max_docs if s is not None else 1) for s in seg_row))
        from elasticsearch_tpu.parallel.compiler import stacked_nnz
        from elasticsearch_tpu.search.context import (chunk_count_bucket,
                                                      split_runs, tail_width)

        nnz = stacked_nnz(seg_row, field)

        # per-shard chunk tables (vocab is shard-local), every run cut at
        # the one width of the round
        runs = [[_term_runs(seg, field, terms) for terms in query_terms]
                for seg in seg_row]
        P = tail_width(nnz, [r for per_q in runs for rs in per_q for r in rs])
        # (starts[Q,?], lens, weights) variable T
        tables = [[split_runs(rs, P) for rs in per_q] for per_q in runs]
        Tmax = max((len(starts) for per_q in tables
                    for starts, _l, _w in per_q), default=1)
        # the floor keeps the short queries of a cold mesh in one class,
        # where each T is a sharded program to compile for every Q bucket
        T = chunk_count_bucket(Tmax, P, minimum=8)
        # pow2-bucket the query axis: Q rides the program cache key, so a
        # raw len() would mint one compiled program per distinct query
        # count (recompile storm). Padded query rows carry all-zero chunk
        # tables (no terms, zero weights) and are sliced off below.
        Qr = len(query_terms)
        Q = pow2_bucket(Qr, minimum=1)

        def pad_t(a, fill=0, dtype=np.int32):
            out = np.full(T, fill, dtype)
            out[: len(a)] = a
            return out

        put = self._put_sharded

        def build_postings():
            h_doc = np.full((self.S, nnz), D, np.int32)
            h_tfn = np.zeros((self.S, nnz), np.float32)
            for si, seg in enumerate(seg_row):
                if seg is None:
                    continue
                inv = seg.inverted.get(field)
                if inv is not None:
                    d = (inv.doc_ids_host if inv.doc_ids_host is not None
                         else np.asarray(inv.doc_ids)[: inv.nnz])
                    h_doc[si, : d.shape[0]] = np.where(d >= seg.max_docs, D, d)
                    t = (inv.tfnorm_host if inv.tfnorm_host is not None
                         else np.asarray(inv.tfnorm)[: inv.nnz])
                    h_tfn[si, : t.shape[0]] = t
            return put(h_doc), put(h_tfn)

        data_key = ("bm25", field, tuple(id(s) for s in seg_row), nnz, D)
        d_doc, d_tfn = self._cached_data(data_key, build_postings, seg_row)

        h_live = np.zeros((self.S, D), bool)
        h_starts = np.zeros((self.S, Q, T), np.int32)
        h_lens = np.zeros((self.S, Q, T), np.int32)
        h_ws = np.zeros((self.S, Q, T), np.float32)
        for si, seg in enumerate(seg_row):
            if seg is not None:
                lv = np.asarray(seg.live_host)
                h_live[si, : lv.shape[0]] = lv
            for qi, (st, ln, ws) in enumerate(tables[si]):
                h_starts[si, qi] = pad_t(st)
                h_lens[si, qi] = pad_t(ln)
                h_ws[si, qi] = pad_t(ws, dtype=np.float32)

        prog = _bm25_program(self.mesh, self._programs,
                             Q=Q, T=T, P=P, D=D, k=min(k, D))
        from elasticsearch_tpu.monitor.programs import static_sig

        # nnz in the sig: the postings buffers are [S, nnz], so two nnz
        # classes are two distinct device programs — census keys must
        # separate them or warmup verification over-reports warm
        sig = static_sig(S=self.S, Q=Q, T=T, P=P, D=D, k=min(k, D),
                         nnz=nnz)
        dev = (d_doc, d_tfn, put(h_starts), put(h_lens), put(h_ws),
               put(h_live))
        return prog, sig, dev, Qr, lut_shard, lut_ord

    # -- kNN ----------------------------------------------------------------

    def search_knn(self, field: str, queries: np.ndarray, k: int = 10,
                   metric: str = "cosine"):
        """queries f32[Q, dims] → (vals, shard, local, round, totals=None)."""
        Qr, dims = queries.shape
        # pow2-bucket the query axis (Q rides the program cache key — the
        # raw request count would mint one program per distinct value).
        # Repeat-padding (batch.py discipline): duplicate rows score
        # normally and are sliced off below.
        Q = pow2_bucket(Qr, minimum=1)
        if Q != Qr:
            queries = np.concatenate(
                [queries, np.repeat(queries[:1], Q - Qr, axis=0)])
        out = self._search_vector_rounds(
            field, queries, k, dims, metric,
            # dims is the field mapping's embedding width — a config-bounded
            # shape class, not request data  # tpulint: bucketed
            lambda D: _knn_program(self.mesh, self._programs, Q=Q,
                                   dims=dims, D=D, k=min(k, D),
                                   metric=metric),
            prog_name="mesh_knn")
        return tuple(a[:Qr] if isinstance(a, np.ndarray) else a
                     for a in out)

    def search_maxsim(self, field: str, tokens: np.ndarray, k: int = 10,
                      metric: str = "cosine"):
        """Batched multi-vector MaxSim: tokens f32[Q, T, dims] (T query
        tokens per request) → (vals, shard, local, round, totals=None).
        Same data-cache discipline as search_knn (the slab group is
        shared between the two — one upload serves both programs)."""
        Qr, T, dims = tokens.shape
        # pow2-bucket the query axis like search_knn; padded rows are
        # repeat-copies, sliced off below
        Q = pow2_bucket(Qr, minimum=1)
        if Q != Qr:
            tokens = np.concatenate(
                [tokens, np.repeat(tokens[:1], Q - Qr, axis=0)])
        out = self._search_vector_rounds(
            field, tokens, k, dims, metric,
            # T is the encoder's token grid (repeat-padded to its bucket
            # upstream — search/batch.py) and dims the mapping's embedding
            # width: config-bounded shape classes  # tpulint: bucketed
            lambda D: _maxsim_program(self.mesh, self._programs, Q=Q, T=T,
                                      dims=dims, D=D, k=min(k, D),
                                      metric=metric),
            prog_name="mesh_maxsim")
        return tuple(a[:Qr] if isinstance(a, np.ndarray) else a
                     for a in out)

    def _search_vector_rounds(self, field: str, qarr: np.ndarray, k: int,
                              dims: int, metric: str, make_prog,
                              prog_name: str = "mesh_knn"):
        """Per-round scaffold shared by the kNN and MaxSim programs:
        slab group build/cache (one upload serves both — the data key is
        program-agnostic), live∧exists mask fill, program dispatch, and
        the cross-round top-k merge. ``make_prog(D)`` supplies the
        compiled program for the round's shape class."""
        from elasticsearch_tpu.ops.knn import has_row_terms, knn_row_terms

        jax = _jax()

        merged = None
        for row in self._segment_rounds():
            seg_row = [e[2] if e is not None else None for e in row]
            lut_shard = np.asarray(
                [e[0] if e is not None else -1 for e in row], np.int32)
            lut_ord = np.asarray(
                [e[1] if e is not None else 0 for e in row], np.int32)
            D = pow2_bucket(max((s.max_docs if s is not None else 1)
                                for s in seg_row))

            def build_vecs():
                h_vecs = np.zeros((self.S, D, dims), np.float32)
                for si, seg in enumerate(seg_row):
                    vc = seg.vectors.get(field) if seg is not None else None
                    if vc is not None:
                        v = (vc.vecs_host if vc.vecs_host is not None
                             else np.asarray(vc.vecs))
                        h_vecs[si, : v.shape[0]] = v
                return self._put_sharded(h_vecs)

            segs = tuple(id(s) for s in seg_row)
            d_vecs = self._cached_data(("knn", field, segs, D, dims),
                                       build_vecs, seg_row)
            # the stacked slab's stored row term [S, D], built once on the
            # device from the cached slab (None for dot_product)
            d_terms = (self._cached_data(
                ("knn_terms", field, segs, D, dims, metric),
                lambda: knn_row_terms(d_vecs, metric=metric), seg_row)
                if has_row_terms(metric) else None)

            h_live = np.zeros((self.S, D), bool)
            for si, seg in enumerate(seg_row):
                if seg is None:
                    continue
                vc = seg.vectors.get(field)
                if vc is not None:
                    lv = np.asarray(seg.live_host)
                    ex = (vc.exists_host if vc.exists_host is not None
                          else np.asarray(vc.exists))
                    h_live[si, : lv.shape[0]] = lv & ex
            prog = make_prog(D)
            from elasticsearch_tpu.monitor.programs import (REGISTRY,
                                                            static_sig)

            with REGISTRY.timed(prog_name,
                                static_sig(S=self.S, Q=qarr.shape[0],
                                           T=(qarr.shape[1]
                                              if qarr.ndim == 3 else 1),
                                           D=D, dims=dims, k=min(k, D)),
                                field=field):
                with span("device.dispatch", program=prog_name):
                    vals, slot, local = prog(
                        # offbudget: transient per-call query/token upload
                        jax.device_put(np.asarray(qarr, np.float32)),  # tpulint: offbudget
                        d_vecs, d_terms, self._put_sharded(h_live))
                with span("device.wait"):
                    slot = np.asarray(slot)
                    vals, local = np.asarray(vals), np.asarray(local)
            out = (vals, lut_shard[slot], local, lut_ord[slot], None)
            merged = out if merged is None else _merge_rounds(merged, out, k)
        return merged

    # -- full DSL (compiled query trees) -------------------------------------

    # prepared-query memo capacity (entries hold device-array handles)
    _PREP_CACHE_CAP = 64

    def search_dsl(self, body_query, mappings, analysis, k: int,
                   sort_spec=None, agg_specs=None, global_stats=None,
                   shards=None, want_mask: bool = False,
                   memo_key: Optional[str] = None):
        """Execute a compiled query DSL tree over the mesh.

        Returns (cands, totals, agg_rounds, mask_rounds) where cands is a
        list of (val, shard, seg_ord, local) for the global top candidates
        (k oversampled ×4 when sorting, mirroring the host path), totals is
        the exact hit count (psum), agg_rounds maps agg name → list of
        (shard, seg_ord, segment, counts np[V]) per segment for the host
        reduce phase, and mask_rounds (when want_mask) is a list of
        (shard, seg_ord, segment, mask np[seg.max_docs]) — the program's
        match mask, consumed by host-side agg collectors so arbitrary
        aggregations run off the mesh query phase. Raises MeshCompileError
        for unsupported queries.
        """
        from elasticsearch_tpu.parallel.compiler import MeshQueryCompiler
        from elasticsearch_tpu.search.context import SegmentContext

        jax = _jax()

        shard_list = self.shards if shards is None else list(shards)
        rows = self._rounds_for(shard_list)
        merged: List[tuple] = []
        totals = 0
        agg_rounds: Dict[str, list] = {}
        mask_rounds: List[tuple] = []
        k_dev = k if not sort_spec else min(max(k * 4, 128), 1 << 20)
        for rno, row in enumerate(rows):
            seg_row = [e[2] if e is not None else None for e in row]
            lut_shard = [e[0] if e is not None else -1 for e in row]
            lut_ord = [e[1] if e is not None else 0 for e in row]
            # prepared-query memo: a REPEATED identical request (memo_key
            # = the canonical body; None under dfs) skips parse-free
            # re-compilation, prim building, and device transfer, going
            # straight to program execution with the cached device inputs.
            # The program always RE-EXECUTES — results are never cached
            # here (that is the shard query cache's job, with its own
            # opt-in semantics). Segment identity + per-segment tombstone
            # counts key the entry, so any write/refresh invalidates.
            prep_key = None
            with span("search.plan"):
                if memo_key is not None and global_stats is None:
                    prep_key = (memo_key, rno,
                                tuple((id(s), s.deleted_count)
                                      if s is not None else None
                                      for s in seg_row),
                                k, k_dev, want_mask)
                with self._prep_lock:
                    prep = (self._prep.get(prep_key)
                            if prep_key is not None else None)
            from elasticsearch_tpu.monitor.programs import (
                REGISTRY as _PROGRAMS, shape_sig as _shape_sig)

            if prep is not None:
                compiled, prog, dev, kk, _refs, _tok = prep
                try:
                    # observatory: the memo path re-executes a cached
                    # program — its wall time (dispatch + packed-result
                    # pull) accrues as execute on the padded-shape key
                    with _PROGRAMS.timed("mesh_dsl", _shape_sig(dev)):
                        out = _run_and_pull(prog, dev, "mesh_dsl")
                except Exception:
                    # drop the entry and fall through to the fresh path,
                    # which carries the scatter-fallback insurance
                    with self._prep_lock:
                        self._prep.pop(prep_key, None)
                    prep = None
                else:
                    with self._prep_lock:
                        if prep_key in self._prep:  # not popped by a
                            # concurrent cap-overflow eviction
                            self._prep.move_to_end(prep_key)  # LRU recency
                    from elasticsearch_tpu.monitor import kernels

                    kernels.record("executor_prep_hit")
                    self._record_tgroup_kernels(compiled)
                    with span("search.fetch"):
                        self._decode_round(out, compiled, kk, sort_spec,
                                           lut_shard, lut_ord, seg_row,
                                           merged, agg_rounds, mask_rounds,
                                           want_mask)
                    totals += int(out[0][-1])
                    continue
            D = pow2_bucket(max((s.max_docs if s is not None else 1)
                                for s in seg_row))
            with span("search.plan"):
                ctxs = [SegmentContext(s, mappings, analysis, global_stats)
                        if s is not None else None for s in seg_row]

            def has_dense(field, _row=seg_row):
                # triggers the lazy dense-impact build exactly like the host
                # loop's ctx.hybrid_slices → inv.dense_block() does
                for s in _row:
                    inv = s.inverted.get(field) if s is not None else None
                    if inv is not None and inv.dense_block() is not None:
                        return True
                return False

            def col_everywhere(field, _row=seg_row):
                return all(s is None or field in s.numerics for s in _row)

            with span("search.rewrite"):
                comp = MeshQueryCompiler(mappings, analysis, global_stats,
                                         D=D, has_dense=has_dense,
                                         col_everywhere=col_everywhere)
                compiled = comp.compile(body_query, sort_spec, agg_specs,
                                        want_mask=want_mask)
            self._record_tgroup_kernels(compiled)

            # build per-prim data + statics; cacheable groups are device-put
            # once and reused across queries (postings, columns)
            def cache_fn(key, fn):
                return self._cached_data(
                    key, lambda: [self._put_sharded(a) for a in fn()],
                    seg_row)

            # device inputs: per-prim data + statics, the program for
            # their shape class, the per-query host tables placed
            with span("search.plan"):
                arrays: List[Any] = []
                counts: List[int] = []
                statics: List[tuple] = []
                for prim in compiled.prims:
                    arrs, static = prim.build(seg_row, ctxs, D, self.S, cache_fn)
                    arrays.extend(arrs)
                    counts.append(len(arrs))
                    statics.append(static)
                kk = min(k_dev, D)
                from elasticsearch_tpu.ops.scoring import topk_block_config

                from elasticsearch_tpu.ops.scoring import tail_mode_batch

                prog_key = ("dsl", compiled.struct_key(), tuple(statics),
                            tuple(tuple(a.shape) + (str(a.dtype),) for a in arrays),
                            kk, topk_block_config(),
                            _tail_candidates_mode(compiled), tail_mode_batch())
                # per-query host tables (row lists, chunk tables, bounds) ship
                # as ONE packed word buffer: each separate device_put is a
                # full host→device round trip
                pack_idx = [i for i, a in enumerate(arrays)
                            if not hasattr(a, "sharding")
                            and isinstance(a, np.ndarray) and a.ndim >= 2
                            and a.shape[0] == self.S and a.dtype.itemsize == 4]
                pack_spec = ()
                if len(pack_idx) >= 2:
                    pack_spec = tuple((i, arrays[i].shape[1:],
                                       str(arrays[i].dtype)) for i in pack_idx)
                prog = self._programs.get((prog_key, pack_spec))
                if prog is None:
                    prog = _dsl_program(self.mesh, compiled, counts, statics,
                                        kk, pack_spec,
                                        aot_key=(prog_key, pack_spec))
                    self._programs[(prog_key, pack_spec)] = prog
                in_pack = set(pack_idx) if pack_spec else set()
                # fresh_bytes: only THIS entry's exclusive placements count
                # toward its residency token — arrays that arrive already
                # device-resident (hasattr .sharding) are the shared
                # _cached_data groups, charged once by their own token;
                # re-counting them per memo entry multiplied phantom bytes
                # until the parent breaker tripped real reservations
                fresh_bytes = 0
                dev = []
                for i, a in enumerate(arrays):
                    if i in in_pack:
                        continue
                    if hasattr(a, "sharding"):
                        dev.append(a)
                    else:
                        d = self._put_sharded(a)
                        fresh_bytes += int(getattr(d, "nbytes", 0) or 0)
                        dev.append(d)
                if pack_spec:
                    words = np.concatenate(
                        [np.ascontiguousarray(arrays[i]).reshape(self.S, -1)
                         .view(np.int32) for i in pack_idx], axis=1)
                    packed_dev = self._put_sharded(words)
                    fresh_bytes += int(getattr(packed_dev, "nbytes", 0) or 0)
                    dev.append(packed_dev)
            # ONE host transfer for the packed result — per-array pulls
            # each pay a fixed device round-trip (the dominant per-query
            # cost on network-attached chips)
            try:
                with _PROGRAMS.timed("mesh_dsl", _shape_sig(dev)):
                    out = _run_and_pull(prog, dev, "mesh_dsl")
            except Exception:
                from elasticsearch_tpu.ops.scoring import tail_mode_batch

                if not (tail_mode_batch()
                        or _tail_candidates_mode(compiled)):
                    raise
                # insurance for the scatter-free forms (first validated on
                # real TPU at capture time): a backend-specific failure
                # falls back to the scatter program rather than failing
                # the search; the counter makes the degradation visible
                from elasticsearch_tpu.monitor import kernels

                kernels.record("tail_scatter_free_failed")
                prog = _dsl_program(self.mesh, compiled, counts,
                                    statics, kk, pack_spec,
                                    force_scatter=True,
                                    aot_key=(prog_key, pack_spec))
                # replace the cached entry: same-shape queries go straight
                # to the scatter program instead of re-failing
                self._programs[(prog_key, pack_spec)] = prog
                with _PROGRAMS.timed("mesh_dsl_scatter", _shape_sig(dev)):
                    out = _run_and_pull(prog, dev, "mesh_dsl_scatter")
            if prep_key is not None:
                from elasticsearch_tpu import resources
                from elasticsearch_tpu.monitor import kernels

                kernels.record("executor_prep_miss")
                # the live set is computed BEFORE the residency charge:
                # _segments_of is fallible, and an exception between
                # track() and the store below would strand the reservation
                # (R020)
                live_ids = {id(seg) for sh in self.shards
                            for seg in _segments_of(sh)}
                tok = resources.RESIDENCY.track(fresh_bytes,
                                                label="executor.prep")
                # prune entries keyed by segments that left the live set
                # (a refresh/merge replaced them): their keys can never
                # match again, but they would pin dead segments + device
                # buffers until the LRU cycles
                with self._prep_lock:
                    dead = [kk2 for kk2, ent in self._prep.items()
                            if any(id(s) not in live_ids for s in ent[4])]
                    for kk2 in dead:
                        self._prep.pop(kk2, None)
                    self._prep[prep_key] = (compiled, prog, dev, kk,
                                            [s for s in seg_row
                                             if s is not None], tok)
                    if len(self._prep) > self._PREP_CACHE_CAP:
                        self._prep.popitem(last=False)
            totals += int(out[0][-1])
            with span("search.fetch"):
                self._decode_round(out, compiled, kk, sort_spec, lut_shard,
                                   lut_ord, seg_row, merged, agg_rounds,
                                   mask_rounds, want_mask)
        if sort_spec:
            # field-sorted: every per-shard candidate goes back — the exact
            # full-tuple ordering AND truncation happen on host
            # (mesh_service staging); a rank-based cut here would be
            # tie-blind on the primary key
            return merged, totals, agg_rounds, mask_rounds
        # mirror the host loop exactly: per-shard candidates merge in
        # (-score, seg, local) order and truncate at k (query_phase), THEN
        # the global merge orders by (-score, shard, local) with the
        # per-shard (seg, local) order as the stable fallback (search_shards)
        by_shard: Dict[int, list] = {}
        for t in merged:
            by_shard.setdefault(t[1], []).append(t)
        out: List[tuple] = []
        for sh in sorted(by_shard):
            lst = by_shard[sh]
            lst.sort(key=lambda t: (-t[0], t[2], t[3]))
            out.extend(lst[:k])
        out.sort(key=lambda t: (-t[0], t[1], t[3]))  # stable: seg order kept
        return out[:k_dev], totals, agg_rounds, mask_rounds

    def _decode_round(self, out, compiled, kk, sort_spec, lut_shard,
                      lut_ord, seg_row, merged, agg_rounds, mask_rounds,
                      want_mask) -> None:
        """Unpack one round's program outputs into the host accumulators
        (shared by the fresh-build and prepared-memo paths)."""
        packed = out[0]
        kg = self.S * kk if sort_spec else kk  # mirrors the program
        gvals = packed[:kg].view(np.float32)
        gslot, glocal = packed[kg: 2 * kg], packed[2 * kg: 3 * kg]
        for v, sl, lc in zip(gvals, gslot, glocal):
            if np.isfinite(v):
                merged.append((float(v), lut_shard[int(sl)],
                               lut_ord[int(sl)], int(lc)))
        n_aggs = len(compiled.agg_prims)
        for (name, _prim), acounts in zip(compiled.agg_prims,
                                          out[1:1 + n_aggs]):
            ac = np.asarray(acounts)  # [S, Vmax+1]
            for si, seg in enumerate(seg_row):
                if seg is None:
                    continue
                agg_rounds.setdefault(name, []).append(
                    (lut_shard[si], lut_ord[si], seg, ac[si]))
        if want_mask:
            mk = np.asarray(out[1 + n_aggs])  # [S, D]
            for si, seg in enumerate(seg_row):
                if seg is None:
                    continue
                mask_rounds.append((lut_shard[si], lut_ord[si], seg,
                                    mk[si, : seg.max_docs]))

    @staticmethod
    def _record_tgroup_kernels(compiled) -> None:
        """Dispatch counters for the mesh round (host-side decision point,
        monitor/kernels.py contract): which scoring prim serves each term
        group of this compiled structure."""
        from elasticsearch_tpu.monitor import kernels
        from elasticsearch_tpu.parallel.compiler import (HybridTGroupPrim,
                                                         TGroupPrim)

        n_hybrid = sum(1 for p in compiled.prims
                       if isinstance(p, HybridTGroupPrim))
        n_scatter = sum(1 for p in compiled.prims
                        if type(p) is TGroupPrim)
        if n_hybrid:
            kernels.record("bm25_hybrid", n_hybrid)
        if n_scatter:
            kernels.record("bm25_scatter", n_scatter)

    def _rounds_for(self, shard_list):
        cols = [[] for _ in range(self.S)]
        for i, s in enumerate(shard_list):
            cols[i % self.S].extend(
                (i, ordinal, seg)
                for ordinal, seg in enumerate(_segments_of(s)))
        max_rounds = max((len(c) for c in cols), default=0) or 1
        return [[c[r] if r < len(c) else None for c in cols]
                for r in range(max_rounds)]

    # -- aggs ---------------------------------------------------------------

    def psum_partials(self, partials: np.ndarray):
        """partials [S, ...] per-shard numeric agg tensors → summed [...]."""
        from elasticsearch_tpu.monitor.programs import REGISTRY, shape_sig

        # partials' trailing shape is the compiled agg structure's output
        # class (per-field vocab caps), not request data  # tpulint: bucketed
        prog = _psum_program(self.mesh, self._programs, partials.shape[1:])
        with REGISTRY.timed("mesh_psum", shape_sig((partials,))):
            with span("device.dispatch", program="mesh_psum"):
                res = prog(self._put_sharded(partials))
            with span("device.wait"):
                return np.asarray(res)


def _run_and_pull(prog, dev, name: str):
    """``jax.device_get(prog(*dev))`` as its two halves: the call that
    only enqueues the program, and the pull that blocks on its result."""
    with span("device.dispatch", program=name):
        res = prog(*dev)
    with span("device.wait"):
        out = _jax().device_get(res)
        tag_active(bytes=sum(int(getattr(a, "nbytes", 0)) for a in out))
    return out


def _segments_of(s) -> list:
    """Resolve a shard slot to its segment list (live view where possible)."""
    if s is None:
        return []
    if isinstance(s, list):
        return s
    segs = getattr(s, "segments", None)
    if callable(segs):
        return list(segs())
    if isinstance(segs, list):
        return segs
    return [s]  # bare TpuSegment


def _term_runs(seg, field, terms):
    """Shard-local (start, len, weight) postings runs of a (term, boost)
    list; idf folded in."""
    runs = []
    inv = seg.inverted.get(field) if seg is not None else None
    if inv is not None:
        for term, boost in terms:
            s, ln = inv.term_slice(term)
            if ln > 0:
                runs.append((s, ln, inv.idf(term) * boost))
    return runs


def _merge_rounds(a, b, k):
    """Host merge of two (vals, shard, local, round, totals) result sets."""
    av, ash, al, ar, at = a
    bv, bsh, bl, br, bt = b
    v = np.concatenate([av, bv], axis=1)
    sh = np.concatenate([ash, bsh], axis=1)
    lo = np.concatenate([al, bl], axis=1)
    rn = np.concatenate([ar, br], axis=1)
    order = np.argsort(-v, axis=1, kind="stable")[:, :k]
    take = lambda x: np.take_along_axis(x, order, axis=1)
    totals = None if at is None else at + bt
    return take(v), take(sh), take(lo), take(rn), totals
