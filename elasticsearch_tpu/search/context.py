"""Per-segment execution context for query programs.

Mirrors the role of org/elasticsearch/search/internal/SearchContext.java +
Lucene's LeafReaderContext: one segment's arrays plus index-level services
(mappings, analysis) and optional global term statistics (dfs_query_then_fetch,
reference: org/elasticsearch/search/dfs/DfsSearchResult.java).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import numpy as np

from elasticsearch_tpu.analysis.registry import AnalysisRegistry
from elasticsearch_tpu.index.mappings import Mappings
from elasticsearch_tpu.index.segment import InvertedField, NumericColumn, TpuSegment
from elasticsearch_tpu.utils.shapes import half_step_bucket, pow2_bucket

# widest chunk of the tail's [T, P] postings window: a run longer than
# this is cut into pieces of this one width, so a long run neither widens
# the other terms' chunks past it nor gives the query a program class of
# its own. The device pays per window slot, valid or not (PERF.md §6,
# PR 30 has the readings for 2048 / 4096 / 8192)
TAIL_W = 1 << 12


def tail_width(nnz_pad: int, runs) -> int:
    """The window width P of a score program over these (start, len,
    weight) runs: the pow2 bucket of the longest, so that a thousand
    one-posting runs make a [1024, 8] window and not a [1024, 4096] one,
    capped at ``TAIL_W``, and at the whole (pow2-padded) postings array
    where a segment is smaller than one chunk — a ``dynamic_slice``
    cannot be wider than its operand."""
    longest = max((ln for _s, ln, _w in runs), default=0)
    return min(TAIL_W, pow2_bucket(longest), nnz_pad)


def chunk_count_bucket(n: int, W: int, minimum: int = 1) -> int:
    """The padded chunk count T of a window of ``n`` chunks W wide: off
    the ``half_step_bucket`` ladder where the chunks are ``TAIL_W`` wide
    (a padding chunk costs the device W slots), a power of two where the
    runs were short enough for a narrower window — a padding chunk is
    cheap there and a chunk count of its own is a program to compile."""
    return (half_step_bucket if W >= TAIL_W else pow2_bucket)(n, minimum)


def split_runs(runs, W: int):
    """Cut raw (start, len, weight) postings runs into chunks of width W.

    Returns (starts, lens, ws) lists, one entry a chunk, a run's chunks
    adjacent and in order; every chunk but a run's last is W long. An
    empty run keeps its one (start, 0) chunk.
    """
    starts, lens, ws = [], [], []
    for s, ln, w in runs:
        n = max(-(-ln // W), 1)
        starts.extend(range(s, s + n * W, W))
        lens.extend([W] * (n - 1))
        lens.append(ln - (n - 1) * W)
        ws.extend([w] * n)
    return starts, lens, ws


def chunk_table(runs, W: int):
    """(starts i32[T], lens i32[T], ws f32[T]) of :func:`split_runs`, the
    chunk count padded with (0, 0) chunks to its ``chunk_count_bucket``
    T."""
    starts, lens, ws = split_runs(runs, W)
    pad = chunk_count_bucket(len(starts), W) - len(starts)
    return (np.asarray(starts + [0] * pad, np.int32),
            np.asarray(lens + [0] * pad, np.int32),
            np.asarray(ws + [0.0] * pad, np.float32))


def stack_chunk_tables(per_shard, nnz_pad: int):
    """(starts i32[S, T], lens i32[S, T], ws f32[S, T], P) of S lists of
    raw runs, one a shard of a mesh: every shard's runs cut at the
    ``tail_width`` P of them all, T the ``chunk_count_bucket`` of the
    longest table, shorter tables ending in (0, 0) chunks."""
    P = tail_width(nnz_pad, [run for runs in per_shard for run in runs])
    cut = [split_runs(runs, P) for runs in per_shard]
    S = len(cut)
    T = chunk_count_bucket(max(len(starts) for starts, _l, _w in cut), P)
    out = (np.zeros((S, T), np.int32), np.zeros((S, T), np.int32),
           np.zeros((S, T), np.float32))
    for si, cols in enumerate(cut):
        for stacked, col in zip(out, cols):
            stacked[si, : len(col)] = col
    return out + (P,)


@dataclass
class GlobalStats:
    """Cross-shard term statistics for consistent idf (dfs phase)."""

    num_docs: Dict[str, int]  # field -> total docs with field
    df: Dict[Tuple[str, str], int]  # (field, term) -> doc freq


class SegmentContext:
    def __init__(
        self,
        segment: TpuSegment,
        mappings: Mappings,
        analysis: AnalysisRegistry,
        global_stats: Optional[GlobalStats] = None,
        all_segments: Optional[list] = None,
        index_name: str = "",
    ):
        self.segment = segment
        self.mappings = mappings
        self.analysis = analysis
        self.global_stats = global_stats
        self.index_name = index_name  # owning index (indices query)
        # every segment of the owning shard — join queries inside aggs use
        # this for their shard-wide prepare pass
        self.all_segments = all_segments if all_segments is not None else [segment]

    @property
    def D(self) -> int:
        return self.segment.max_docs

    def inv(self, field: str) -> Optional[InvertedField]:
        return self.segment.inverted.get(field)

    def col(self, field: str) -> Optional[NumericColumn]:
        return self.segment.numerics.get(field)

    def idf(self, field: str, term: str) -> float:
        inv = self.inv(field)
        if self.global_stats is not None:
            n = self.global_stats.num_docs.get(field, inv.num_docs if inv else 0)
            df = self.global_stats.df.get((field, term), 0)
            return float(np.log(1.0 + (n - df + 0.5) / (df + 0.5)))
        if inv is None:
            return 0.0
        return inv.idf(term)

    def search_analyzer(self, field: str):
        fm = self.mappings.get(field)
        if fm is None or not fm.is_text:
            return None
        return self.analysis.get(fm.search_analyzer or fm.analyzer)

    def chunked_slices(self, inv: InvertedField, terms, weights):
        """Split (term -> postings run) into chunks of one width.

        Returns (starts i32[Tb], lens i32[Tb], w f32[Tb], P, n_real_terms)
        where P is the runs' ``tail_width`` and Tb the chunk count's
        ``chunk_count_bucket``. Terms absent from the segment contribute
        (0, 0) chunks. n_real_terms counts distinct terms present.
        """
        runs = []
        n_present = 0
        for term, w in zip(terms, weights):
            s, ln = inv.term_slice(term)
            if ln > 0:
                n_present += 1
            runs.append((s, ln, w))
        P = tail_width(inv.nnz_pad, runs)
        starts, lens, ws = chunk_table(runs, P)
        return starts, lens, ws, P, n_present

    def hybrid_slices(self, inv: InvertedField, terms, weights,
                      need_qw: bool = True):
        """Split query terms between the dense impact block and the CSR tail.

        Returns None when the field has no dense block OR no query term maps
        to a dense row (the caller uses the pure scatter path — paying an
        [F, D] matmul of zeros for an all-rare-term query would be far slower
        than scattering its short runs). Else returns (impact, qw f32[F],
        qind f32[F], starts, lens, ws, P, n_present, qrows i32[R],
        qrw f32[R]): frequent terms fold idf*boost into ``qw`` rows (for the
        batched matmul paths) AND into the compact (qrows, qrw) row list
        (-1/0 padded to a pow2 R) that single-query paths gather — reading
        R << F rows instead of the whole block. ``qind`` is the 1.0
        indicator of dense query terms, used for batched counts/masks.
        Single-query callers pass ``need_qw=False`` and get ``None`` for
        qw/qind — skipping the two O(F) fills on the per-request path.
        """
        from elasticsearch_tpu.ops.scoring import pack_dense_rows

        block = inv.dense_block()
        if block is None:
            return None
        dense_rows, impact = block
        F = impact.shape[0]
        qw = np.zeros(F, np.float32) if need_qw else None
        qind = np.zeros(F, np.float32) if need_qw else None
        row_w: Dict[int, float] = {}
        runs = []
        n_present = 0
        for term, w in zip(terms, weights):
            tid = inv.term_id(term)
            if tid < 0:
                continue
            n_present += 1
            row = int(dense_rows[tid])
            if row >= 0:
                if need_qw:
                    qw[row] += w
                    qind[row] = 1.0
                row_w[row] = row_w.get(row, 0.0) + w
            else:
                runs.append((int(inv.offsets[tid]),
                             int(inv.offsets[tid + 1] - inv.offsets[tid]), w))
        if not row_w:
            return None
        P = tail_width(inv.nnz_pad, runs)
        starts, lens, ws = chunk_table(runs, P)
        qrows, qrw = pack_dense_rows(row_w)
        return impact, qw, qind, starts, lens, ws, P, n_present, qrows, qrw
