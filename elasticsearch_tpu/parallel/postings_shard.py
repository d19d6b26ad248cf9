"""Cross-device postings sharding for an OVERSIZED single segment.

SURVEY §2.12: "postings sharded across devices with psum merge". The usual
scaling unit is the segment (segments-as-shards over the mesh,
parallel/executor.py); the tiered merge policy keeps segments below
single-device HBM, so this path exists for the case that policy can't
help: ONE inverted field whose padded postings alone exceed the
per-device budget (a single shard of a huge index, or merge ceilings
raised by the operator).

Design — term-range decomposition with additive merge:
- The frozen term-major CSR is split into S contiguous TERM ranges,
  balanced by postings mass (prefix sums of the CSR offsets). Each device
  holds only its range's postings slice (doc_ids + tfnorm, padded pow2);
  doc-space stays replicated (scores are f32[D]).
- Every scoring primitive used by the host term-group path
  (bm25_score_segment / match_count_segment / term_mask — ops/scoring.py)
  is a sum of per-CHUNK scatter contributions, and a term's chunks live
  entirely on the device owning its range, so per-device partials merge
  exactly with one psum: scores add, distinct-match counts add, masks
  or-combine (max). No primitive is re-implemented here — each device
  runs the stock single-device kernel on its slice.
- Query time: terms are routed to their owning device host-side
  (vocab → term id → range), producing [S, Tb] chunk tables; one
  shard_map over a ('pshard',) mesh computes partials and psums them.
  The program body is a COLLECTIVE region (tpulint R014): host syncs
  anywhere in its call reach stall every device — keep them out.

Interplay with the mesh product path: a segment big enough to split
cannot be stacked into the [S, ...] per-shard arrays the mesh executor
ships, so mesh_service falls back to the host loop for indices holding
such segments (counted via mesh_fallback_total) and the host loop runs
this program instead — postings-parallelism replaces segment-parallelism
for exactly the segments where the latter is impossible. The mesh
executor declines a second stacked copy from a smaller size on
(``declines_stacked_copy``, 2^26 postings): a field between the two
sizes is resident whole on its shard's chip and scored there by the host
loop (``bm25_term_group_topk``; all shards at once by
parallel/term_group_sharded.py), never split.

HBM contract: freeze does NOT allocate the full single-device postings
for an oversized field — InvertedField's lazy accessors keep the padded
host mirrors and only device_put on explicit access by a fallback path
(phrase/positional programs, terms aggs over the field). Pure-dense
disjunctive queries may still serve via the budget-capped dense impact
block (the batched tier, fused_bm25_topk_batch), which never materializes
the postings arrays.

Reference behavior analogue: an ES shard too big for one node is split by
_reindexing_ into more shards; a TPU segment too big for one chip is
split in place across chips. Counter: ``bm25_postings_sharded``.
"""
from __future__ import annotations

import os
import threading
from typing import List, Optional, Tuple

import numpy as np

from elasticsearch_tpu.utils.shapes import pow2_bucket

# Two questions, two sizes.
#
# Can ONE chip hold the field whole? Asked by freeze (place the postings
# or leave them on the host) and by ``InvertedField.postings_split``
# (score on the chip or split in place) alike: ``chip_holds_whole``.
# Reckoned against the chip's memory as the breaker layer knows it
# (``resources.breakers.hbm_capacity``): a field's padded postings
# (POSTING_BYTES each: doc id, tf, tf-norm, term id) may take a quarter
# of it — 4 of 16 GiB, 2^28 postings. A number in ``POSTINGS_SHARD_NNZ``
# (``ESTPU_POSTINGS_SHARD_NNZ``, or a test) takes the reckoning's place:
# a field of that many postings is split.
POSTINGS_SHARD_NNZ = (int(os.environ["ESTPU_POSTINGS_SHARD_NNZ"])
                      if os.environ.get("ESTPU_POSTINGS_SHARD_NNZ")
                      else None)
POSTING_BYTES = 16
# Can the mesh executor and the batched tiers stack a SECOND [S, ...] copy
# of it? 64M entries ≈ 1 GiB of padded postings: beyond that they decline
# (mesh_service, queries.batch tiers, hybrid) and the host loop serves —
# from the chip that holds the field whole, or through the split.
STACKED_COPY_NNZ = 1 << 26


def chip_holds_whole(nnz: int) -> bool:
    """Whether one chip holds a field of ``nnz`` postings whole."""
    if POSTINGS_SHARD_NNZ is not None:
        return nnz < POSTINGS_SHARD_NNZ
    from elasticsearch_tpu.resources.breakers import hbm_capacity

    return (POSTING_BYTES * pow2_bucket(max(nnz, 1), minimum=8)
            <= hbm_capacity() // 4)


def declines_stacked_copy(nnz: int) -> bool:
    """Whether a second, stacked copy of a field of ``nnz`` postings is
    declined: too big to hold twice, or split in place already."""
    return nnz >= STACKED_COPY_NNZ or not chip_holds_whole(nnz)


def _jax():
    import jax

    return jax


class PostingsShardSplit:
    """Device-resident term-range split of one InvertedField."""

    def __init__(self, mesh, bounds: np.ndarray, bases: np.ndarray,
                 doc_ids_sh, tfnorm_sh, L: int, max_docs: int, vocab,
                 offsets: np.ndarray):
        self.mesh = mesh
        self.S = int(bounds.shape[0]) - 1
        self.bounds = bounds  # i64[S+1] term-id range edges
        self.bases = bases  # i64[S] postings offset of each range start
        self.doc_ids_sh = doc_ids_sh  # i32[S, L] sharded over 'pshard'
        self.tfnorm_sh = tfnorm_sh  # f32[S, L] sharded over 'pshard'
        self.L = L
        self.max_docs = max_docs
        self._vocab = vocab
        self._offsets = offsets
        self._lock = threading.Lock()
        self._programs: dict = {}

    # -- query-time chunk routing (host) ---------------------------------

    def chunk_tables(self, terms, weights) -> Tuple[np.ndarray, np.ndarray,
                                                    np.ndarray, int, int]:
        """Route query terms to owning devices; returns per-device chunk
        tables (starts/lens i32[S, Tb], ws f32[S, Tb], P, n_present) with
        starts REBASED into each device's local postings slice."""
        from elasticsearch_tpu.search.context import stack_chunk_tables

        per_dev: List[List[Tuple[int, int, float]]] = [[] for _ in range(self.S)]
        n_present = 0
        for t, w in zip(terms, weights):
            tid = self._vocab.get(t, -1)
            if tid < 0:
                continue
            n_present += 1
            s = int(np.searchsorted(self.bounds, tid, side="right")) - 1
            start = int(self._offsets[tid] - self.bases[s])
            ln = int(self._offsets[tid + 1] - self._offsets[tid])
            if ln > 0:
                per_dev[s].append((start, ln, float(w)))
        # the same window as SegmentContext's, a device
        starts, lens, ws, P = stack_chunk_tables(per_dev, self.L)
        return starts, lens, ws, P, n_present

    # -- compiled programs ------------------------------------------------

    def _program(self, kind: str, P: int, Tb: int, D: int):
        key = (kind, P, Tb, D)
        prog = self._programs.get(key)
        if prog is not None:
            return prog
        jax = _jax()
        from jax.sharding import PartitionSpec as PS

        from elasticsearch_tpu.ops.scoring import (bm25_score_segment,
                                                   match_count_segment,
                                                   term_mask)

        mesh = self.mesh

        def local(doc_ids, tfnorm, starts, lens, ws):
            d, t = doc_ids[0], tfnorm[0]
            s_, l_, w_ = starts[0], lens[0], ws[0]
            scores = jax.lax.psum(
                bm25_score_segment(d, t, s_, l_, w_, P=P, D=D), "pshard")
            if kind == "counts":
                return scores, jax.lax.psum(
                    match_count_segment(d, s_, l_, P=P, D=D), "pshard")
            if kind == "mask":
                return scores, jax.lax.psum(
                    term_mask(d, s_, l_, P=P, D=D).astype(np.int32), "pshard")
            return (scores,)

        sharded = jax.shard_map(
            local, mesh=mesh,
            in_specs=(PS("pshard"), PS("pshard"), PS("pshard"),
                      PS("pshard"), PS("pshard")),
            out_specs=(PS(),) if kind == "score" else (PS(), PS()),
            check_vma=False,
        )
        prog = jax.jit(sharded)
        with self._lock:
            self._programs[key] = prog
        return prog

    def term_group(self, terms, weights, with_counts: bool, all_positive: bool,
                   D: int):
        """(scores f32[D], matched, n_present) — the sharded counterpart of
        queries._score_term_group's scatter path."""
        jax = _jax()
        starts, lens, ws, P, n_present = self.chunk_tables(terms, weights)
        if n_present == 0:
            jnp = jax.numpy
            matched = (jnp.zeros(D, np.int32) if with_counts
                       else jnp.zeros(D, bool))
            return jnp.zeros(D, np.float32), matched, 0
        kind = "counts" if with_counts else ("score" if all_positive else "mask")
        prog = self._program(kind, P, starts.shape[1], D)
        # offbudget: transient per-query chunk tables
        out = prog(self.doc_ids_sh, self.tfnorm_sh,
                   jax.device_put(starts), jax.device_put(lens),  # tpulint: offbudget
                   jax.device_put(ws))  # tpulint: offbudget
        scores = out[0]
        if with_counts:
            matched = out[1]
        elif all_positive:
            matched = scores > 0
        else:
            matched = out[1] > 0
        return scores, matched, n_present


def build_split(inv, max_docs: int, n_devices: Optional[int] = None
                ) -> Optional["PostingsShardSplit"]:
    """Split ``inv``'s postings across up to ``n_devices`` by balanced
    contiguous term ranges. None when the field is host-mirror-less or a
    single device is available (nothing to split over)."""
    jax = _jax()
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

    if inv.doc_ids_host is None:
        return None
    devs = jax.devices()
    S = min(n_devices or len(devs), len(devs))
    if S < 2:
        return None
    offsets = np.asarray(inv.offsets, np.int64)
    nnz = int(offsets[-1])
    V = len(offsets) - 1
    S = min(S, V)  # never more ranges than terms
    # balanced edges: term id whose prefix mass crosses k * nnz/S
    targets = (np.arange(1, S) * nnz) // S
    cut = np.searchsorted(offsets, targets, side="left")
    bounds = np.concatenate([[0], cut, [V]]).astype(np.int64)
    bounds = np.maximum.accumulate(bounds)  # degenerate ranges stay valid
    bases = offsets[bounds[:-1]]
    sizes = offsets[bounds[1:]] - bases
    L = pow2_bucket(int(sizes.max()), minimum=8)
    doc_ids = np.full((S, L), max_docs, np.int32)  # sentinel pad
    tfnorm = np.zeros((S, L), np.float32)
    tfn_host = (inv.tfnorm_host if inv.tfnorm_host is not None
                else np.ones(nnz, np.float32))
    for s in range(S):
        lo, hi = int(bases[s]), int(offsets[bounds[s + 1]])
        doc_ids[s, : hi - lo] = inv.doc_ids_host[lo:hi]
        tfnorm[s, : hi - lo] = tfn_host[lo:hi]
    mesh = Mesh(np.asarray(devs[:S]), ("pshard",))
    sh = NamedSharding(mesh, PS("pshard"))
    from elasticsearch_tpu import resources

    put = resources.RESIDENCY.device_put  # build-once split: accounted
    return PostingsShardSplit(
        mesh, bounds, bases,
        put(doc_ids, sh, label="pshard.doc_ids"),
        put(tfnorm, sh, label="pshard.tfnorm"),
        L, max_docs, inv.vocab, offsets,
    )
