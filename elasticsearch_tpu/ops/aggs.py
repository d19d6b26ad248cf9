"""One device program for a ``size: 0`` aggregation tree over a segment.

Reference: org/elasticsearch/search/aggregations/bucket/histogram/
HistogramAggregator.java and metrics/stats/StatsAggregator.java collect
one document at a time into per-bucket counters. Here the whole segment
is one pass of one program over the exact int32 codes of the columns the
request reads (``index/segment.column_code``: CODE_MISSING where a
document has no value) and the segment's int8 live mask:

- the filter: a conjunction of inclusive code ranges, ∧ live;
- the bucket key, in int32 arithmetic from the key column's code:
  ``floor((code - c0) / q)`` (the caller turns an interval and the first
  bucket's edge into code units ``q`` and ``c0``), so a date never passes
  through a float (one f32 ulp of epoch millis is 131 s);
- per bucket: documents, and for each metric column the values present,
  their sum, least and greatest code.

No per-document scatter: every bucket is a compare over a tile held in
VMEM (the TPU path is a Pallas kernel that reads each column once, block
by block, and keeps ``[B, 8, 128]`` accumulators resident across the
grid). A pass of the kernel counts four buckets at once: a document adds
``1 << 8 (key % 4)`` to int32 word ``key // 4``, so one compare, select
and add over a vreg count four buckets (a metric's values present
likewise). Only a sum, least or greatest value takes a compare a bucket.
A sum adds the exact int32 codes of the bucket's documents into an int32
partial where the column's codes allow it (``int_sum_fits``), else their
f32 values into a Kahan-compensated f32 pair every chunk. Every
``_DRAIN_CHUNKS`` chunks, and at the end of each grid step, a drain
empties the packed words into the ``[B, 8, 128]`` int32 counts and each
partial, as its two exact 16-bit halves, into its f32 Kahan pair (an
average over 10^8 codes keeps ~1e-7). The drain's invariant: no 8-bit
field holds more than ``4 x _DRAIN_CHUNKS`` = 128 < 256 documents. And no
partial passes int32, since a lane adds at most ``LANE_DOCS`` codes of at
most ``_I32_MAX // LANE_DOCS`` in magnitude between two drains.
Elsewhere (the CPU, tiny segments) the same semantics are one XLA
program over a ``[B, D]`` compare. Both return one packed int32 vector —
the segment's match count, then ``[B]`` per number — for the caller's
one pull.

The kernel's work is (slots scanned) x (bucket passes). Its grid stops
at a run-time bound the caller passes with the parameters, so the
compiled shape (``D``, the bucket class ``B``) never changes: the last
block that holds one of the segment's used slots (``[0, maxDoc)``,
deleted documents included; every slot past it is padding whose live
byte is 0). Later steps fetch no new block (their index map repeats the
last one) and do nothing, so no sum is reordered. The bucket loop runs
all ``B`` passes of the class: a guard on each bucket, or a loop to the
request's bucket count, costs more than the passes it skips (the
compiler interleaves the buckets of one straight-line body, and a branch
between them breaks that), and straight-line bodies for fewer passes
multiply the kernel's compile time.
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

CODE_MISSING = -(2 ** 31)  # index/segment.CODE_MISSING
_I32_MAX = 2 ** 31 - 1
# the bucket counts the program is compiled for: a request pads its
# bucket count up to the next class, so a dashboard's pool compiles in
# warm-up and nothing in the window
BUCKET_CLASSES = (8, 16, 24, 32, 40, 48, 56, 64, 96, 128)
# rows of 128 documents a grid step reads, and a chunk of them the inner
# loop folds into the accumulators at once
_BLOCK_ROWS = 2048
_CHUNK_ROWS = 32
# chunks between two drains of the packed counts and the int32 sums: a
# chunk adds at most _CHUNK_ROWS / 8 = 4 documents to a lane's field, so a
# field holds at most 128 < 256 when it is drained
_DRAIN_CHUNKS = 32
# documents one lane of an int32 partial adds up between two drains
LANE_DOCS = _DRAIN_CHUNKS * _CHUNK_ROWS // 8
_VMEM_LIMIT_BYTES = 48 << 20


def block_slots(D: int) -> int:
    """Slots one grid step of the kernel reads over a ``D``-slot
    segment."""
    return min(_BLOCK_ROWS * 128, D)


def last_block(D: int, used: int) -> int:
    """The kernel's last grid block that holds one of a segment's
    ``used`` slots ``[0, used)`` (block 0 when there are none)."""
    return max(used - 1, 0) // block_slots(D)


def bucket_class(n: int):
    """The compiled bucket count for ``n`` buckets, or None past the
    largest class (the caller declines)."""
    for b in BUCKET_CLASSES:
        if n <= b:
            return b
    return None


def int_sum_fits(code_min: int, code_max: int) -> bool:
    """True where a column's sums can run as exact int32 partials: no
    lane's partial can pass int32 between two drains, whatever codes of
    ``[code_min, code_max]`` its documents hold."""
    return max(abs(code_min), abs(code_max)) * LANE_DOCS <= _I32_MAX


class Metric(NamedTuple):
    col: int  # index into the program's code columns
    count: bool  # values present (needed unless every doc has one)
    sum: bool
    min: bool
    max: bool
    # the kernel sums as exact int32 partials (int_sum_fits), else f32
    # with Kahan compensation every chunk
    int_sum: bool


class TreeSpec(NamedTuple):
    """The static half of a program (its shape class). ``params`` carries
    the rest: each filter's (lo, hi) codes in order, then (c0, q), then
    the kernel's last grid block that holds a used slot
    (``last_block``)."""

    n_cols: int
    filters: Tuple[int, ...]  # code column of each range
    key_col: int  # -1: one bucket (a bucket-less metric tree)
    B: int
    metrics: Tuple[Metric, ...]


def unpack(spec: TreeSpec, words: np.ndarray):
    """(total, doc_count[B], [ {"count","sum","min","max"}: [B] ... ])
    from the pulled vector; sums come back as float64 of the f32 words."""
    words = np.asarray(words)
    B = spec.B
    total = int(words[0])
    at = 1

    def take():
        nonlocal at
        at += B
        return words[at - B:at]

    counts = take().astype(np.int64)
    metrics = []
    for m in spec.metrics:
        got = {}
        if m.count:
            got["count"] = take().astype(np.int64)
        if m.sum:
            got["sum"] = take().view(np.float32).astype(np.float64)
        if m.min:
            got["min"] = take().astype(np.int64)
        if m.max:
            got["max"] = take().astype(np.int64)
        metrics.append(got)
    return total, counts, metrics


def _floor_key(x, q, inv_q):
    """floor(x / q) for int32 x, q >= 1: an f32 estimate, then one exact
    integer correction each way (the estimate is off by at most one for
    the keys that count, which lie in [0, 128))."""
    k = jnp.floor(x.astype(jnp.float32) * inv_q).astype(jnp.int32)
    r = x - k * q
    return jnp.where(r < 0, k - 1, jnp.where(r >= q, k + 1, k))


# --------------------------------------------------------------------------
# the XLA program (CPU, tiny segments): the semantics, plainly
# --------------------------------------------------------------------------

def _xla_tree(params, live, cols, *, spec: TreeSpec):
    sel = live != 0
    for i, c in enumerate(spec.filters):
        x = cols[c]
        sel = sel & (x >= params[2 * i]) & (x <= params[2 * i + 1])
    total = jnp.sum(sel.astype(jnp.int32))
    if spec.key_col < 0:
        key = jnp.where(sel, 0, -1)
    else:
        p = 2 * len(spec.filters)
        kc = cols[spec.key_col]
        c0, q = params[p], params[p + 1]
        key = _floor_key(kc - c0, q, 1.0 / q.astype(jnp.float32))
        key = jnp.where(sel & (kc != CODE_MISSING), key, -1)
    onehot = key[None, :] == jnp.arange(spec.B, dtype=jnp.int32)[:, None]
    out = [total[None], jnp.sum(onehot.astype(jnp.int32), axis=1)]
    for m in spec.metrics:
        v = cols[m.col]
        has = onehot & (v != CODE_MISSING)[None, :]
        if m.count:
            out.append(jnp.sum(has.astype(jnp.int32), axis=1))
        if m.sum:
            s = jnp.sum(jnp.where(has, v.astype(jnp.float32)[None, :], 0.0),
                        axis=1)
            out.append(jax.lax.bitcast_convert_type(s, jnp.int32))
        if m.min:
            out.append(jnp.min(jnp.where(has, v[None, :], _I32_MAX), axis=1))
        if m.max:
            out.append(jnp.max(jnp.where(has, v[None, :], CODE_MISSING),
                               axis=1))
    return jnp.concatenate(out)


# --------------------------------------------------------------------------
# the Pallas kernel (TPU)
# --------------------------------------------------------------------------

def _acc_layout(spec: TreeSpec):
    """Accumulator outputs of the kernel, in order: (name, metric index,
    dtype, init)."""
    rows = [("total", -1, jnp.int32, 0), ("count", -1, jnp.int32, 0)]
    for j, m in enumerate(spec.metrics):
        if m.count:
            rows.append(("mcount", j, jnp.int32, 0))
        if m.sum:
            rows.append(("sum", j, jnp.float32, 0.0))
            rows.append(("comp", j, jnp.float32, 0.0))
        if m.min:
            rows.append(("min", j, jnp.int32, _I32_MAX))
        if m.max:
            rows.append(("max", j, jnp.int32, CODE_MISSING))
    return rows


def _drain_layout(spec: TreeSpec):
    """The kernel's int32 scratch, emptied into the accumulators at every
    drain, in order: (name, metric index, leading dim). ``packed`` and
    ``mpacked`` hold four buckets' counts a word (bucket b in bits
    8(b % 4) .. 8(b % 4) + 7 of word b // 4), ``part`` a metric's sums as
    exact int32 partials."""
    G = spec.B // 4
    rows = [("packed", -1, G)]
    for j, m in enumerate(spec.metrics):
        if m.count:
            rows.append(("mpacked", j, G))
        if m.int_sum:
            rows.append(("part", j, spec.B))
    return rows


def _kahan(s_ref, c_ref, b, *xs):
    """Add each of ``xs`` to row ``b`` of a compensated f32 sum."""
    s, c = s_ref[b], c_ref[b]
    for x in xs:
        y = x - c
        t = s + y
        c = (t - s) - y
        s = t
    s_ref[b] = s
    c_ref[b] = c


def _kernel(*, spec: TreeSpec, TR: int, CH: int):
    from jax.experimental import pallas as pl

    layout = _acc_layout(spec)
    drains = _drain_layout(spec)
    nf = len(spec.filters)
    G = spec.B // 4
    nch = TR // CH
    # chunks between two drains: a divisor of the step's chunks, so a step
    # ends on a drain
    DR = math.gcd(nch, _DRAIN_CHUNKS)
    # a bucket's own compare serves its sum, least and greatest value
    per_bucket = any(m.sum or m.min or m.max for m in spec.metrics)

    def fold(x, op):
        # [CH, 128] -> [8, 128]: vreg-wise, no cross-lane work
        return op(x.reshape(CH // 8, 8, 128), axis=0)

    def kernel(params_ref, *refs):
        cols = refs[:spec.n_cols]
        live = refs[spec.n_cols]
        accs = refs[spec.n_cols + 1:spec.n_cols + 1 + len(layout)]
        scratch = refs[spec.n_cols + 1 + len(layout):]
        by = {}
        for (name, j, _dt, _init), ref in zip(layout, accs):
            by[(name, j)] = ref
        for (name, j, _n), ref in zip(drains, scratch):
            by[(name, j)] = ref

        @pl.when(pl.program_id(0) == 0)
        def _init():
            for (name, j, dt, init), ref in zip(layout, accs):
                ref[...] = jnp.full(ref.shape, init, dt)
            for ref in scratch:
                ref[...] = jnp.zeros(ref.shape, jnp.int32)

        lo = [params_ref[2 * i] for i in range(nf)]
        hi = [params_ref[2 * i + 1] for i in range(nf)]
        if spec.key_col >= 0:
            c0, q = params_ref[2 * nf], params_ref[2 * nf + 1]
            inv_q = 1.0 / q.astype(jnp.float32)
        last = params_ref[2 * nf + 2]

        def chunk(r, carry):
            rows = pl.ds(pl.multiple_of(r * CH, CH), CH)
            sel = live[rows, :].astype(jnp.int32) != 0
            for i, c in enumerate(spec.filters):
                x = cols[c][rows, :]
                sel = sel & (x >= lo[i]) & (x <= hi[i])
            t = by[("total", -1)]
            t[...] += fold(sel.astype(jnp.int32), jnp.sum)
            if spec.key_col < 0:
                key = jnp.where(sel, 0, -1)
            else:
                kc = cols[spec.key_col][rows, :]
                key = _floor_key(kc - c0, q, inv_q)
                key = jnp.where(sel & (kc != CODE_MISSING), key, -1)
            # a key's word and its field's unit; a key outside [0, B)
            # (-1: no bucket) matches no word
            g = key >> 2
            unit = jnp.left_shift(1, (key & 3) * 8)
            vals, units = [], []
            for m in spec.metrics:
                v = cols[m.col][rows, :]
                has = v != CODE_MISSING
                units.append(jnp.where(has, unit, 0) if m.count else None)
                if m.int_sum:
                    vs = jnp.where(has, v, 0)
                elif m.sum:
                    vs = jnp.where(has, v, 0).astype(jnp.float32)
                else:
                    vs = None
                vals.append((vs, jnp.where(has, v, _I32_MAX),
                             v))  # CODE_MISSING is already the least
            packed = by[("packed", -1)]
            for w in range(G):
                in_w = g == w
                packed[w] += fold(jnp.where(in_w, unit, 0), jnp.sum)
                for j, m in enumerate(spec.metrics):
                    if m.count:
                        ref = by[("mpacked", j)]
                        ref[w] += fold(jnp.where(in_w, units[j], 0), jnp.sum)
            if not per_bucket:
                return carry
            for b in range(spec.B):
                hit = key == b
                for j, m in enumerate(spec.metrics):
                    vs, vmin, vmax = vals[j]
                    if m.int_sum:
                        ref = by[("part", j)]
                        ref[b] += fold(jnp.where(hit, vs, 0), jnp.sum)
                    elif m.sum:
                        _kahan(by[("sum", j)], by[("comp", j)], b,
                               fold(jnp.where(hit, vs, 0.0), jnp.sum))
                    if m.min:
                        ref = by[("min", j)]
                        ref[b] = jnp.minimum(
                            ref[b], fold(jnp.where(hit, vmin, _I32_MAX),
                                         jnp.min))
                    if m.max:
                        ref = by[("max", j)]
                        ref[b] = jnp.maximum(
                            ref[b], fold(jnp.where(hit, vmax, CODE_MISSING),
                                         jnp.max))
            return carry

        zero = jnp.zeros((8, 128), jnp.int32)

        def drain_word(w):
            # word w holds buckets 4w .. 4w + 3, one 8-bit field each
            def unpack(src, dst):
                word = src[w]
                for f in range(4):
                    # the mask reads bits 24-31 right where the word is
                    # negative as an int32
                    dst[4 * w + f] += (word >> (8 * f)) & 0xFF
                src[w] = zero

            unpack(by[("packed", -1)], by[("count", -1)])
            for j, m in enumerate(spec.metrics):
                if m.count:
                    unpack(by[("mpacked", j)], by[("mcount", j)])
                if m.int_sum:
                    part = by[("part", j)]
                    s_ref, c_ref = by[("sum", j)], by[("comp", j)]
                    for f in range(4):
                        p = part[4 * w + f]
                        # both 16-bit halves are exact in f32
                        _kahan(s_ref, c_ref, 4 * w + f,
                               (p >> 16).astype(jnp.float32) * 65536.0,
                               (p & 0xFFFF).astype(jnp.float32))
                        part[4 * w + f] = zero

        def period(d, carry):
            jax.lax.fori_loop(d * DR, (d + 1) * DR, chunk, 0)
            for w in range(G):
                drain_word(w)
            return carry

        @pl.when(pl.program_id(0) <= last)
        def _scan():
            jax.lax.fori_loop(0, nch // DR, period, 0)

    return kernel, layout, drains


def _pallas_tree(params, live, cols, *, spec: TreeSpec, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    D = live.shape[0]
    R = D // 128
    TR = block_slots(D) // 128
    CH = min(_CHUNK_ROWS, TR)
    kernel, layout, drains = _kernel(spec=spec, TR=TR, CH=CH)
    at = 2 * len(spec.filters) + 2  # params[at]: the last block to scan
    # past it the index repeats, so Pallas fetches nothing new
    block = pl.BlockSpec((TR, 128), lambda i, p: (jnp.minimum(i, p[at]), 0))
    shapes, out_specs = [], []
    for name, _j, dt, _init in layout:
        if name == "total":
            shapes.append(jax.ShapeDtypeStruct((8, 128), dt))
            out_specs.append(pl.BlockSpec((8, 128), lambda i, p: (0, 0)))
        else:
            shapes.append(jax.ShapeDtypeStruct((spec.B, 8, 128), dt))
            out_specs.append(pl.BlockSpec((spec.B, 8, 128),
                                          lambda i, p: (0, 0, 0)))
    accs = pl.pallas_call(
        kernel,
        out_shape=shapes,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(R // TR,),
            in_specs=[block] * (spec.n_cols + 1), out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((n, 8, 128), jnp.int32)
                            for _name, _j, n in drains]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="agg_tree",
    )(params, *(c.reshape(R, 128) for c in cols), live.reshape(R, 128))
    by = {(name, j): a for (name, j, _dt, _i), a in zip(layout, accs)}
    out = [jnp.sum(by[("total", -1)])[None],
           jnp.sum(by[("count", -1)], axis=(1, 2))]
    for j, m in enumerate(spec.metrics):
        if m.count:
            out.append(jnp.sum(by[("mcount", j)], axis=(1, 2)))
        if m.sum:
            s = jnp.sum(by[("sum", j)] - by[("comp", j)], axis=(1, 2))
            out.append(jax.lax.bitcast_convert_type(s, jnp.int32))
        if m.min:
            out.append(jnp.min(by[("min", j)], axis=(1, 2)))
        if m.max:
            out.append(jnp.max(by[("max", j)], axis=(1, 2)))
    return jnp.concatenate(out)


def use_kernel(D: int) -> bool:
    """The Pallas kernel on a TPU for a segment of at least one block of
    32 rows (the int8 live mask's tile); the XLA program elsewhere."""
    return jax.default_backend() == "tpu" and D >= 32 * 128


@partial(jax.jit, static_argnames=("spec", "kernel", "interpret"))
def agg_tree(params, live, *cols, spec: TreeSpec, kernel: bool = False,
             interpret: bool = False):
    """The whole tree over one segment: ``params`` int32[2F + 3] (filter
    code bounds, then c0 and q, then the kernel's last block), ``live``
    int8[D], ``cols`` int32[D] codes. Returns the packed int32 vector
    ``unpack`` reads."""
    if kernel:
        return _pallas_tree(params, live, cols, spec=spec,
                            interpret=interpret)
    return _xla_tree(params, live, cols, spec=spec)
