"""Pallas TPU kernels for the kNN hot path.

The XLA path (ops/knn.py) materializes the full [Q, D] similarity matrix in
HBM before top-k — at SIFT scale (D=1M, Q=64) that is a 256 MB round trip
per batch. This kernel streams corpus tiles HBM→VMEM, runs the MXU matmul
per tile, applies the metric transform + live-doc mask on the VPU, and
maintains the running top-k in the output block across sequential grid
steps — the [Q, D] intermediate never exists.

Top-k merge strategy: k is small (ES size/num_candidates, ≤64 here) so each
tile does k iterations of (row-max, argmax, knock-out) over the fused
[Q, TILE+K] candidate block — pure VPU reductions, no sort network needed.

Falls back to interpret mode on CPU (tests) and to the XLA path for shapes
the kernel doesn't cover; both produce identical results (modulo fp
reduction order), asserted in tests/unit/test_pallas_kernels.py.
"""
from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = float("-inf")  # python scalar: jnp constants would be captured consts in pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# Scoped-VMEM ceiling every kernel here hands to Mosaic, and the budget the
# tile choosers below fit their estimates under. The compiler's default
# scoped limit is 16 MiB of a v5e core's 128 MiB; one explicit number keeps
# what the choosers assume and what the compiler enforces the same thing.
# The estimates count what Mosaic actually allocates: every BlockSpec'd
# block twice (the pipeline double-buffers it), the minor dim padded to 128
# lanes, and the kernel body's live temporaries. The quarter left over is
# for what they cannot see (relayouts, spills).
_VMEM_LIMIT_BYTES = 32 << 20
_VMEM_BUDGET_BYTES = 24 << 20


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT_BYTES)


def _lanes(n: int) -> int:
    """Minor-dim extent as VMEM lays it out: padded to 128 lanes."""
    return ((n + 127) // 128) * 128


@partial(jax.jit, static_argnames=("k", "metric", "tile", "interpret",
                                   "precise"))
def knn_topk_pallas(queries, vecs, mask, *, k: int, metric: str = "cosine",
                    tile: int = 2048, interpret: bool = False,
                    precise: bool = False):
    """Fused scores + mask + running top-k over corpus tiles.

    queries: f32[Q, dims] (Q, dims small enough for VMEM residency)
    vecs:    f32[D, dims], D % tile == 0 (caller pads; padded rows masked)
    mask:    bool[D] live-doc mask
    precise: score in f32 (multi-pass on the MXU, ~3x the matmul cost) —
             for exact-kNN recall on corpora whose neighbor gaps are below
             bf16 resolution; default bf16 for throughput.
    Returns ([Q, k] scores, [Q, k] int32 doc ids), same contract as
    ops.knn.knn_topk.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if metric not in ("cosine", "dot_product", "dot", "l2_norm", "l2"):
        raise ValueError(f"unknown knn metric [{metric}]")  # match ops.knn
    Q, dims = queries.shape
    D = vecs.shape[0]
    assert D % tile == 0, "corpus must be padded to a tile multiple"
    n_tiles = D // tile

    if metric == "cosine":
        qn = queries / jnp.maximum(
            jnp.linalg.norm(queries, axis=-1, keepdims=True), 1e-12)
    else:
        qn = queries
    qh = qn.astype(jnp.float32 if precise else jnp.bfloat16)

    def kernel(q_ref, v_ref, m_ref, out_v_ref, out_i_ref):
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _init():
            out_v_ref[:] = jnp.full((Q, k), NEG_INF, dtype=jnp.float32)
            out_i_ref[:] = jnp.zeros((Q, k), dtype=jnp.int32)

        v = v_ref[:]  # [tile, dims] f32
        if metric == "cosine":
            norm = jnp.sqrt(jnp.sum(v * v, axis=-1, keepdims=True))
            v = v / jnp.maximum(norm, 1e-12)
        s = jax.lax.dot_general(
            q_ref[:], v if precise else v.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST if precise else None,
        )  # [Q, tile]
        if metric in ("cosine", "dot_product", "dot"):
            s = (1.0 + s) * 0.5
        else:  # l2_norm via norm expansion
            q2 = jnp.sum(q_ref[:].astype(jnp.float32) ** 2, axis=-1,
                         keepdims=True)
            v2 = jnp.sum(v.astype(jnp.float32) ** 2, axis=-1)[None, :]
            s = 1.0 / (1.0 + jnp.maximum(q2 - 2.0 * s + v2, 0.0))
        s = jnp.where(m_ref[:], s, NEG_INF)  # mask block is [1, tile]

        base = step * tile
        tile_ids = base + jax.lax.broadcasted_iota(jnp.int32, (Q, tile), 1)

        # fused candidates: previous best (k) + this tile
        cand_v = jnp.concatenate([out_v_ref[:], s], axis=1)  # [Q, k+tile]
        cand_i = jnp.concatenate([out_i_ref[:], tile_ids], axis=1)

        # k iterations of extract-max (VPU row reductions). No gathers —
        # Mosaic lowers mask-reduce, not take_along_axis: the picked id is
        # recovered by masking the id matrix with the argmax column.
        def extract(j, carry):
            cv, ci, bv, bi = carry
            m = jnp.max(cv, axis=1)  # [Q]
            am = jnp.argmax(cv, axis=1)  # [Q]
            width = cv.shape[1]
            knock = jax.lax.broadcasted_iota(jnp.int32, (Q, width), 1) == am[:, None]
            picked_i = jnp.max(jnp.where(knock, ci, jnp.int32(-1)), axis=1)
            # column-j store via iota mask (dynamic .at[] would be a scatter)
            col_j = jax.lax.broadcasted_iota(jnp.int32, (Q, k), 1) == j
            bv = jnp.where(col_j, m[:, None], bv)
            bi = jnp.where(col_j, picked_i[:, None], bi)
            cv = jnp.where(knock, NEG_INF, cv)  # knock out the chosen column
            return cv, ci, bv, bi

        bv0 = jnp.full((Q, k), NEG_INF, dtype=jnp.float32)
        bi0 = jnp.zeros((Q, k), dtype=jnp.int32)
        _, _, bv, bi = jax.lax.fori_loop(
            0, k, extract, (cand_v, cand_i, bv0, bi0))
        out_v_ref[:] = bv
        out_i_ref[:] = bi

    out_v, out_i = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((Q, dims), lambda i: (0, 0)),          # queries: resident
            pl.BlockSpec((tile, dims), lambda i: (i, 0)),       # corpus tile
            # mask rides as [1, D]: Mosaic refuses a 1-D block below the
            # XLA tiling ("XLA layout ({0:T(1024)S(1)}) does not match
            # Mosaic layout ({0:T(512)S(1)})" at tile=512)
            pl.BlockSpec((1, tile), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((Q, k), lambda i: (0, 0)),             # running top-k
            pl.BlockSpec((Q, k), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Q, k), jnp.float32),
            jax.ShapeDtypeStruct((Q, k), jnp.int32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(qh, vecs, mask[None, :])
    return out_v, out_i


# error shapes that mean "this kernel will NEVER compile/lower here" —
# deterministic, so one failure latches. That includes a tile over the
# scoped VMEM limit, which Mosaic reports at compile time as
# "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem ... Scoped
# allocation with size 31.74M and limit 16.00M" (first v5e run): the same
# shapes fail the same way every time. Everything else is treated as
# transient (HBM RESOURCE_EXHAUSTED, cancelled transfers, backend
# restarts).
_COMPILE_ERR_MARKERS = ("mosaic", "lowering", "unsupported", "unimplemented",
                        "compilation", "cannot lower", "memory space vmem",
                        "scoped allocation")


def _is_compile_error(e: BaseException) -> bool:
    if isinstance(e, NotImplementedError):
        return True
    text = f"{type(e).__name__}: {e}".lower()
    return any(m in text for m in _COMPILE_ERR_MARKERS)


# ---------------------------------------------------------------------------
# ADC (PQ table-sum) kernel — the coarse stage of the IVF coarse->fine rank
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("tile", "interpret"))
def adc_scores_pallas(codes, lut, *, tile: int = 2048,
                      interpret: bool = False):
    """Tiled asymmetric-distance table-sum: codes i32[W, M], lut
    f32[M, K] -> f32[W] coarse scores.

    Mosaic doesn't lower general gathers, so the per-subspace table
    lookup is phrased as a one-hot [tile, K] compare + matvec against
    the LUT row — an M-step static unroll of VPU compare + MXU matvec,
    with the LUT (<= 32 KB) resident in VMEM across the whole sweep.
    This is the TileMaxSim shape: candidate tiles stream HBM->VMEM as
    uint8-sized codes (M bytes/candidate), never as f32 vectors.
    """
    from jax.experimental import pallas as pl

    W, M = codes.shape
    K = lut.shape[1]
    assert W % tile == 0, "candidate set must be padded to a tile multiple"
    n_tiles = W // tile

    def kernel(c_ref, lut_ref, out_ref):
        c = c_ref[:]  # [tile, M] int32
        acc = jnp.zeros((tile,), jnp.float32)
        for m in range(M):  # static unroll, M <= 32
            onehot = (jax.lax.broadcasted_iota(jnp.int32, (tile, K), 1)
                      == c[:, m][:, None]).astype(jnp.float32)
            acc = acc + jax.lax.dot_general(
                onehot, lut_ref[m, :], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        out_ref[0, :] = acc

    out = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((tile, M), lambda i: (i, 0)),   # code tile
            pl.BlockSpec((M, K), lambda i: (0, 0)),      # LUT: resident
        ],
        # 1-D i32/f32 blocks can hit XLA/Mosaic layout mismatches at
        # small tiles (same note as knn_topk_pallas' mask) — ride as [1, W]
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, W), jnp.float32),
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(codes, lut)
    return out[0]


# The ADC and MaxSim kernels unroll over the M subspaces, and Mosaic keeps
# one lane-padded [tile, 128] f32 temporary alive per unrolled step instead
# of reusing it: compiling adc_scores_pallas for a v5e at tile=4096, M=16
# allocates 31.74 MiB of scoped VMEM (484 B per row and subspace; MaxSim at
# the same shape 17.91 MiB, 273 B). Both choosers charge the rounded bound.
_UNROLL_ROW_BYTES = 512


# sticky failure latch for the ADC kernel (list so the traced-free eager
# dispatcher can flip it in place). Latches on the first deterministic
# compile/lowering failure; a transient runtime error (momentary device
# OOM, transfer hiccup) falls back per-call and the kernel retries, up to
# a bounded run of consecutive failures so a persistently-broken device
# can't pay a fresh kernel attempt on every batch until restart.
_ADC_PALLAS_BROKEN = [False]
_ADC_TRANSIENT_FAILS = [0]
_ADC_TRANSIENT_LIMIT = 8


def adc_pallas_tile(W: int, M: int, K: int) -> int:
    """Largest candidate tile the ADC kernel may use (0 = use the XLA
    gather form). Static shape gates only — the dispatch site runs
    EAGERLY (ops/ivf.ivf_candidate_scores), so a first-call Mosaic
    failure is catchable there and flips the latch."""
    if _ADC_PALLAS_BROKEN[0] or not _on_tpu():
        return 0
    if K % 128 != 0 or M > 32:
        return 0  # lane-aligned LUT rows; M bounds the unroll
    for tile in (4096, 2048, 1024, 512):
        if W % tile:
            continue
        est = (tile * M * _UNROLL_ROW_BYTES      # per-subspace temporaries
               + 2 * tile * _lanes(M) * 4        # code block
               + 2 * max(M, 8) * K * 4           # LUT
               + 2 * 8 * tile * 4)               # [1, tile] output
        if est <= _VMEM_BUDGET_BYTES:
            return tile
    return 0


def note_adc_failure(e: BaseException) -> bool:
    """Record one ADC kernel failure (called from the eager dispatch in
    ops/ivf.py). Returns True when the latch is now set — the caller
    rebuilds its program without the Pallas ADC from then on; False
    means transient, fall back for this call only."""
    import warnings

    from elasticsearch_tpu.monitor import kernels

    kernels.record("adc_pallas_failed")
    if _is_compile_error(e):
        _ADC_PALLAS_BROKEN[0] = True
        warnings.warn(f"ADC kernel failed ({type(e).__name__}: "
                      f"{str(e)[:200]}); serving PQ coarse rank via the "
                      f"XLA gather path from now on")
        return True
    _ADC_TRANSIENT_FAILS[0] += 1
    if _ADC_TRANSIENT_FAILS[0] >= _ADC_TRANSIENT_LIMIT:
        _ADC_PALLAS_BROKEN[0] = True
        warnings.warn(f"ADC kernel failed {_ADC_TRANSIENT_FAILS[0]} "
                      f"consecutive times ({type(e).__name__}: "
                      f"{str(e)[:200]}); latching to the XLA path")
        return True
    warnings.warn(f"ADC kernel transient failure ({type(e).__name__}: "
                  f"{str(e)[:200]}); XLA fallback for this call")
    return False


def note_adc_success() -> None:
    """A served Pallas ADC call clears the transient-failure run."""
    _ADC_TRANSIENT_FAILS[0] = 0


def _knn_tile_for(Q: int, dims: int, k: int, D: int) -> int:
    """Largest corpus tile keeping the kernel's VMEM working set in budget:
    double-buffered query block, corpus tile and [Q, k] outputs, the
    tile's normalized and cast copies, and ~5 live [Q, tile+k] 4-byte
    candidate arrays in the selection loop. A Q-blind tile (r4 regression:
    Q=256 x tile=8192 = 17 MB stack) OOMs scoped vmem at batch sizes the
    executor actually sends."""
    qpad = ((Q + 7) // 8) * 8
    for tile in (8192, 4096, 2048, 1024, 512):
        if D % tile:
            continue
        est = (2 * qpad * dims * 4 + 2 * tile * dims * 4
               + 2 * tile * dims * 4 + 5 * qpad * _lanes(tile + k) * 4
               + 4 * qpad * 128 * 4)
        if est <= _VMEM_BUDGET_BYTES:
            return tile
    return 0


def knn_topk_auto(queries, vecs, row_terms, mask, *, k: int,
                  metric: str = "cosine", precise: bool = False):
    """Dispatch: Pallas fused kernel on TPU when shapes fit, XLA otherwise.

    ``row_terms`` is the slab's stored per-row term (ops.knn.knn_row_terms;
    ``VectorColumn.row_terms()``), which the XLA program reads; the Pallas
    kernel builds its norms inside the tile it streams anyway.

    precise=True scores in f32 end to end (Pallas multi-pass / XLA
    use_bf16=False) — exact-kNN recall parity for latency-path queries;
    batched throughput callers keep bf16 and follow with
    ops.knn.exact_rescore_topk on the candidates.

    Dispatch is decided purely from STATIC shape gates — no try/except:
    this is routinely called inside an outer jit/shard_map trace, where
    Mosaic lowering errors surface at outer-compile time (after any except
    block here has exited), so a runtime fallback would be an illusion.
    The gates mirror what the kernel is validated for on hardware: Q a
    sublane multiple, lane-aligned dims, small k, tile-divisible corpus.

    Q below the sublane multiple (a single REST knn query is Q=1) pads up
    to 8 with zero queries and slices the result — round 1 sent every
    single-query request down the XLA path that materializes the [Q, D]
    matrix this kernel exists to avoid."""
    from elasticsearch_tpu.ops.knn import knn_topk_stored

    Q, dims = queries.shape
    D = vecs.shape[0]
    tile = _knn_tile_for(Q, dims, k, D)
    if (_on_tpu() and k <= 64 and dims % 128 == 0
            and tile and D >= 2 * tile):
        if Q % 8 != 0:
            qpad = ((Q + 7) // 8) * 8
            queries = jnp.concatenate(
                [queries, jnp.zeros((qpad - Q, dims), queries.dtype)], axis=0)
            vals, idx = knn_topk_pallas(queries, vecs, mask, k=k,
                                        metric=metric, tile=tile,
                                        precise=precise)
            return vals[:Q], idx[:Q]
        return knn_topk_pallas(queries, vecs, mask, k=k, metric=metric,
                               tile=tile, precise=precise)
    from elasticsearch_tpu.ops.scoring import topk_block_config

    return knn_topk_stored(queries, vecs, row_terms, mask, k=k, metric=metric,
                           use_bf16=not precise,
                           topk_block=topk_block_config())


# ---------------------------------------------------------------------------
# MaxSim kernel — tiled multi-vector re-rank with fused PQ ADC decode
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("t_real", "tile", "interpret"))
def maxsim_adc_pallas(codes, luts, *, t_real: int, tile: int = 2048,
                      interpret: bool = False):
    """Tiled MaxSim over PQ codes: codes i32[W, M], luts f32[M, K, Tp]
    -> f32[W] per-candidate MaxSim scores (max over query tokens of the
    token's ADC table-sum).

    The ADC kernel above (`adc_scores_pallas`) is the single-token
    warm-up act: one one-hot compare + matvec per subspace. Here the
    matvec widens to a matmul against ALL token LUT columns at once —
    onehot [tile, K] @ luts[m] [K, Tp] accumulates the per-token partial
    sums [tile, Tp] across the M-step static unroll, and the token max
    collapses on the VPU at the end. Candidate tiles stream HBM->VMEM as
    M-byte code rows, never as f32 vectors — the TileMaxSim shape
    (dimension-tiled over the candidate axis, PQ decode fused into the
    interaction matmul, no [T, W] similarity intermediate in HBM).

    ``t_real`` <= Tp masks LUT pad columns out of the max (callers pad
    the token axis to a sublane multiple; a zero pad column would win
    the max whenever every real table-sum is negative, e.g. l2 LUTs).
    """
    from jax.experimental import pallas as pl

    W, M = codes.shape
    K, Tp = luts.shape[1], luts.shape[2]
    assert W % tile == 0, "candidate set must be padded to a tile multiple"
    n_tiles = W // tile

    def kernel(c_ref, lut_ref, out_ref):
        c = c_ref[:]  # [tile, M] int32
        acc = jnp.zeros((tile, Tp), jnp.float32)
        for m in range(M):  # static unroll, M <= 32
            onehot = (jax.lax.broadcasted_iota(jnp.int32, (tile, K), 1)
                      == c[:, m][:, None]).astype(jnp.float32)
            # HIGHEST: at the default precision the MXU rounds the LUT
            # operand to bf16, and the kernel then disagrees with its XLA
            # twin beyond f32 tolerance (first v5e run, PR 21)
            acc = acc + jax.lax.dot_general(
                onehot, lut_ref[m], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)
        tok = jax.lax.broadcasted_iota(jnp.int32, (tile, Tp), 1)
        acc = jnp.where(tok < t_real, acc, NEG_INF)
        out_ref[0, :] = jnp.max(acc, axis=1)

    out = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((tile, M), lambda i: (i, 0)),    # code tile
            pl.BlockSpec((M, K, Tp), lambda i: (0, 0, 0)),  # LUTs: resident
        ],
        # 1-D outputs ride as [1, W] (same layout note as the ADC kernel)
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, W), jnp.float32),
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(codes, luts)
    return out[0]


# sticky failure latch — same discipline as the ADC kernel's above:
# deterministic compile/lowering failures latch on the first hit;
# transients fall back per-call up to a bounded run.
_MAXSIM_PALLAS_BROKEN = [False]
_MAXSIM_TRANSIENT_FAILS = [0]
_MAXSIM_TRANSIENT_LIMIT = 8


def maxsim_adc_tile(W: int, M: int, K: int, Tp: int) -> int:
    """Largest candidate tile the MaxSim-ADC kernel may use (0 = use the
    XLA gather form). Static shape gates only — the dispatch below runs
    EAGERLY, so a first-call Mosaic failure is catchable there."""
    if _MAXSIM_PALLAS_BROKEN[0] or not _on_tpu():
        return 0
    if K % 128 != 0 or M > 32 or Tp > 64:
        return 0  # lane-aligned LUT rows; M bounds the unroll
    for tile in (4096, 2048, 1024, 512):
        if W % tile:
            continue
        est = (tile * M * _UNROLL_ROW_BYTES      # per-subspace temporaries
               + 2 * tile * _lanes(M) * 4        # code block
               + 2 * M * K * _lanes(Tp) * 4      # resident token LUTs
               + 2 * tile * _lanes(Tp) * 4       # accumulator + masked copy
               + 2 * 8 * tile * 4)               # [1, tile] output
        if est <= _VMEM_BUDGET_BYTES:
            return tile
    return 0


def _note_maxsim_failure(e: BaseException) -> None:
    import warnings

    from elasticsearch_tpu.monitor import kernels

    kernels.record("maxsim_pallas_failed")
    if _is_compile_error(e):
        _MAXSIM_PALLAS_BROKEN[0] = True
        warnings.warn(f"MaxSim-ADC kernel failed ({type(e).__name__}: "
                      f"{str(e)[:200]}); serving the re-rank stage via "
                      f"the XLA gather path from now on")
        return
    _MAXSIM_TRANSIENT_FAILS[0] += 1
    if _MAXSIM_TRANSIENT_FAILS[0] >= _MAXSIM_TRANSIENT_LIMIT:
        _MAXSIM_PALLAS_BROKEN[0] = True
        warnings.warn(f"MaxSim-ADC kernel failed {_MAXSIM_TRANSIENT_FAILS[0]}"
                      f" consecutive times ({type(e).__name__}: "
                      f"{str(e)[:200]}); latching to the XLA path")
        return
    warnings.warn(f"MaxSim-ADC kernel transient failure ({type(e).__name__}"
                  f": {str(e)[:200]}); XLA fallback for this call")


@jax.jit
def _maxsim_adc_xla(codes, luts):
    """XLA reference form: per-token table-sum gather + token max.
    codes i32[W, M], luts f32[T, M, K] -> f32[W]."""
    M = luts.shape[1]
    idx = codes.astype(jnp.int32)  # [W, M]
    # [T, W, M] gather off the LUT tables, summed over subspaces
    per_tok = jnp.sum(luts[:, jnp.arange(M)[None, :], idx], axis=2)
    return jnp.max(per_tok, axis=0)


def maxsim_adc_auto(codes, luts):
    """Dispatch: fused Pallas MaxSim-ADC kernel on TPU when static shape
    gates hold, XLA gather form otherwise. Runs EAGERLY (a Mosaic
    failure is catchable here).

    codes: i32[W, M] PQ code rows of the candidates (gathered upstream)
    luts:  f32[T, M, K] per-token ADC tables (ops.pq.adc_lut per token)
    Returns f32[W] MaxSim scores (max over tokens of the table-sum).

    ESTPU_MAXSIM_KERNEL: auto (default) | pallas | xla — the A/B knob
    for the re-rank stage.
    """
    from elasticsearch_tpu.utils.shapes import round_up

    W, M = codes.shape
    T, _, K = luts.shape
    pref = os.environ.get("ESTPU_MAXSIM_KERNEL", "auto").lower()
    # sublane-align the token axis; Tp (not the raw token count) rides
    # the kernel's static key so a token-count sweep stays in-bucket
    Tp = round_up(T, 8)
    tile = maxsim_adc_tile(W if W % 512 == 0 else ((W + 511) // 512) * 512,
                           M, K, Tp)
    if pref == "pallas" and not tile:
        import warnings

        warnings.warn("ESTPU_MAXSIM_KERNEL=pallas but the kernel's shape "
                      f"gates reject this call (on_tpu={_on_tpu()}, W={W}, "
                      f"M={M}, K={K}, Tp={Tp}) — falling back to XLA")
    if pref != "xla" and tile:
        from elasticsearch_tpu.monitor import kernels

        try:
            Wp = ((W + tile - 1) // tile) * tile
            cp = codes
            if Wp != W:
                cp = jnp.concatenate(
                    [codes, jnp.zeros((Wp - W, M), codes.dtype)], axis=0)
            # [T, M, K] -> [M, K, Tp]: the kernel wants token columns.
            # Pad tokens with large-negative tables (finite: -inf would
            # NaN through the onehot matmul's 0*inf lanes) so pad
            # columns self-mask under the token max, and pass the
            # BUCKETED count as t_real — the static key then only sees
            # sublane multiples, never the raw per-query token count.
            lp = jnp.transpose(luts, (1, 2, 0))
            if Tp != T:
                lp = jnp.concatenate(
                    [lp, jnp.full((M, K, Tp - T), -1e30, lp.dtype)],
                    axis=2)
            out = maxsim_adc_pallas(cp, lp, t_real=Tp, tile=tile)
            _MAXSIM_TRANSIENT_FAILS[0] = 0
            kernels.record("maxsim_adc_pallas")
            return out[:W]
        except Exception as e:  # noqa: BLE001 — latch discipline
            _note_maxsim_failure(e)
    from elasticsearch_tpu.monitor import kernels

    kernels.record("maxsim_adc_xla")
    return _maxsim_adc_xla(codes, luts)
