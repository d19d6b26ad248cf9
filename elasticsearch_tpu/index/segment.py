"""TPU segment: immutable, device-resident columnar index structures.

This replaces Lucene's on-disk segment codecs (reference: Lucene 5.2 postings
formats used by org/elasticsearch/index/engine/InternalEngine.java and
index/store/). Where Lucene stores block-compressed postings streamed
doc-at-a-time through iterators, a TpuSegment keeps every searchable
structure as a *static-shaped dense array in device memory*:

- Inverted index per indexed field: flattened CSR — ``doc_ids[nnz]``,
  ``tf[nnz]``, ``tfnorm[nnz]`` (BM25 tf-normalization precomputed at freeze,
  the BM25S "eager scoring" trick), plus host-side ``offsets[V+1]`` and the
  term dictionary. Query programs slice per-term runs with
  ``lax.dynamic_slice`` at power-of-two bucket widths, so one compiled
  program serves every query of the same shape class.
- ``term_ids[nnz]`` (which term each posting belongs to) enables whole-field
  ``segment_sum`` reductions — the basis of the terms aggregation.
- Doc values per numeric/keyword/date/bool field: dense columns padded to
  ``max_docs`` (power of two). 64-bit values (longs, date millis) keep an
  exact int32 (hi, lo) pair for exact range comparison plus an f32
  channel for arithmetic, and an exact numpy mirror on host for fetch.
  Columns freeze as HOST arrays and load lazily into the EVICTABLE
  fielddata residency tier on first search touch (resources/residency.py
  — the fielddata breaker gates the load, pressure evicts LRU device
  copies, the next touch rehydrates from the retained host array).
- Dense vectors: one ``[max_docs, dims]`` slab (f32; bf16 copy made by the
  kNN op) — MXU-friendly.
- ``live``: deletion mask (Lucene liveDocs equivalent).
- ``_source``/stored fields/_id map stay on host (never needed on device).

All device arrays are padded so that *every* segment exposes shapes drawn
from a small set of buckets; XLA compiles one program per bucket, not per
segment.
"""
from __future__ import annotations

import os
import threading
from collections.abc import Sequence
from dataclasses import dataclass, field as dfield
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from elasticsearch_tpu.index.doc_parser import ParsedDocument
from elasticsearch_tpu.index.mappings import Mappings
from elasticsearch_tpu.utils.shapes import pow2_bucket, pad_to

# BM25 constants (Lucene BM25Similarity defaults, k1=1.2 b=0.75)
K1 = 1.2
B = 0.75


def _jnp():
    import jax.numpy as jnp

    return jnp


def _device_put(x, device=None):
    # every always-resident segment placement goes through the residency
    # choke point (accounting; admission control is the engine's
    # per-segment breaker charge at freeze — see _charge_segment).
    # ``device``: the owning shard's chip (TpuSegment.device); None is the
    # default device, uncommitted — a one-shard index's arrays
    from elasticsearch_tpu import resources

    return resources.RESIDENCY.device_put(x, device, tier="segments")


def _committed_device(arrays):
    """The one device these arrays are committed to, or None (host
    arrays, uncommitted placements, or more than one device)."""
    found = set()
    for a in arrays:
        if getattr(a, "committed", False):
            found |= set(a.devices())
    return found.pop() if len(found) == 1 else None


def split_i64(v: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Split int64 into (hi, lo) int32 pair preserving order lexicographically.

    hi = v >> 32 (arithmetic, fits int32 for the full i64 range); lo = the
    unsigned low 32 bits biased by -2^31 so it fits int32 while keeping the
    ordering monotonic. (hi1,lo1) < (hi2,lo2) lexicographically iff v1 < v2 —
    used for exact 64-bit range masks on a device without native i64.
    """
    v = v.astype(np.int64)
    hi = (v >> 32).astype(np.int32)
    lo = ((v & 0xFFFFFFFF) - (1 << 31)).astype(np.int32)
    return hi, lo


# HbmBudget lives in resources/breakers.py now (the ad-hoc budget grew
# into the ES-shaped hierarchy); re-exported here for embedders/tests
# that construct standalone budgets.
from elasticsearch_tpu.resources import BREAKERS
from elasticsearch_tpu.resources.breakers import HbmBudget  # noqa: F401

# the fielddata-tier breaker now governs every lazily-loaded evictable
# device copy (columns, vector slabs, dense impact blocks) — kept under
# the old name for embedders. NOTE: import-time binding to the default
# service; in-package code resolves via resources.RESIDENCY.breakers at
# use time so swapped test singletons stay consistent
DENSE_IMPACT_BUDGET = BREAKERS.breaker("fielddata")

# node-wide breaker for always-resident segment HBM (postings, live
# masks): every freeze charges the segment's memory_bytes() against it;
# exhaustion fails the REQUEST with a typed CircuitBreakingException
# instead of device-OOMing the node (reference:
# common/breaker/CircuitBreaker.java via resources/breakers.py).
# Merges release-then-charge and never trip (they net-shrink memory).
SEGMENT_HBM_BUDGET = BREAKERS.breaker("segments")


def build_dense_impact(
    doc_ids_host: np.ndarray,
    tfnorm_host: np.ndarray,
    offsets: np.ndarray,
    df: np.ndarray,
    max_docs: int,
    *,
    df_threshold: Optional[int] = None,
    budget_bytes: int = 1 << 30,
) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Dense impact block for frequent terms (hybrid dense/sparse scoring).

    Terms whose postings run is long (df >= threshold) dominate scatter cost
    on TPU; we densify exactly those into rows of an ``impact[F_pad, D]``
    matrix so a query batch scores them with ONE MXU matmul
    (``qw[Q, F] @ impact[F, D]``), while the short tail stays CSR (cheap
    scatter). This is the BM25S eager-impact idea restructured for the MXU.

    Returns (dense_rows int32[V] with -1 for sparse terms, impact f32[F_pad, D])
    or None when no term qualifies.
    """
    V = df.shape[0]
    if V == 0:
        return None
    if df_threshold is None:
        # empirical sweet spot on TPU v5e: densify runs longer than D/256
        # (tail scatter windows stay <=256 wide; F stays within budget)
        df_threshold = max(128, max_docs // 256)
    cand = np.nonzero(df >= df_threshold)[0]
    if cand.size == 0:
        return None
    # cap by HBM budget on the PADDED row count (F_pad x D x 4 is what gets
    # allocated): round the cap down to a power of two, keep the highest-df
    # terms (longest runs = biggest win)
    max_rows = int(budget_bytes // (4 * max_docs))
    if max_rows < 8:  # F_pad minimum is 8
        return None
    max_rows = 1 << (max_rows.bit_length() - 1)
    if cand.size > max_rows:
        cand = cand[np.argsort(-df[cand], kind="stable")[:max_rows]]
        cand.sort()
    F_pad = pow2_bucket(cand.size, minimum=8)
    dense_rows = np.full(V, -1, dtype=np.int32)
    dense_rows[cand] = np.arange(cand.size, dtype=np.int32)
    impact = np.zeros((F_pad, max_docs), dtype=np.float32)
    for row, tid in enumerate(cand):
        s, e = int(offsets[tid]), int(offsets[tid + 1])
        impact[row, doc_ids_host[s:e]] = tfnorm_host[s:e]
    return dense_rows, impact


@dataclass
class InvertedField:
    """Frozen inverted index for one field (text or keyword)."""

    name: str
    vocab: Dict[str, int]  # term -> term id (host)
    terms: List[str]  # term id -> term
    df: np.ndarray  # int32[V] doc freq
    cf: np.ndarray  # int64[V] collection (total term) freq
    offsets: np.ndarray  # int64[V+1] CSR offsets into postings (host)
    # device arrays (jax) — padded to pow2 nnz
    doc_ids: Any  # int32[nnz_pad], padded entries = max_docs sentinel
    tf: Any  # f32[nnz_pad]
    tfnorm: Any  # f32[nnz_pad] — tf*(k1+1)/(tf+k1*(1-b+b*len/avg))
    term_ids: Any  # int32[nnz_pad], padded = V sentinel
    nnz: int
    num_docs: int
    total_terms: int
    avg_len: float
    # positions: host CSR aligned with postings order (for phrase/span)
    pos_offsets: Optional[np.ndarray] = None  # int64[nnz+1]
    positions: Optional[np.ndarray] = None  # int32[total_positions]
    # host mirror of unpadded doc_ids (phrase verification, merges)
    doc_ids_host: Optional[np.ndarray] = None
    # host mirror of tfnorm (dense-impact build, merges)
    tfnorm_host: Optional[np.ndarray] = None
    # host mirror of raw tf (on-disk codec, index/store.py)
    tf_host: Optional[np.ndarray] = None
    # lazy cache: sorted terms for prefix/wildcard expansion
    _sorted_terms: Any = None
    # device positional CSR (padded) — built lazily for phrase programs
    _pos_dev: Any = None
    # host mirror of the dense impact block (set when _dense is built)
    _dense_host: Any = None
    # lazy hybrid dense-impact block: False = checked & permanently absent
    # (no qualifying terms); (dense_rows np.i32[V], ResidentArray handle)
    # when present; None = not built yet (incl. transient budget denial)
    _dense: Any = None
    _dense_lock: Any = dfield(default_factory=threading.Lock)
    # lazy cross-device postings split for an OVERSIZED field (see
    # parallel/postings_shard.py): None = unchecked, False = declined
    _pshard: Any = None
    max_docs: int = 0
    # the owning segment's chip (TpuSegment sets it; None = the default
    # device): where the lazy accessors and the dense block place
    device = None

    def wants_postings_shard(self) -> bool:
        """True when a SECOND, stacked copy of this field's postings is
        more than the mesh executor and the batched tiers take on
        (mesh_service uses this to route such indices to the host loop) —
        whether or not one chip holds the field whole."""
        from elasticsearch_tpu.parallel.postings_shard import \
            declines_stacked_copy

        return declines_stacked_copy(self.nnz)

    def postings_split(self):
        """Build-once term-range split across devices, or None (a field
        one chip holds whole, single device, or no host mirror to split
        from)."""
        if self._pshard is False:
            return None
        if self._pshard is not None:
            return self._pshard
        from elasticsearch_tpu.parallel.postings_shard import \
            chip_holds_whole

        if chip_holds_whole(self.nnz):
            return None
        with self._dense_lock:
            if self._pshard is None:
                from elasticsearch_tpu.parallel.postings_shard import \
                    build_split

                split = build_split(self, self.max_docs)
                self._pshard = split if split is not None else False
        return self._pshard or None

    @staticmethod
    def _dense_get(d):
        """(rows, device impact) from a built block, rehydrating an
        evicted one — BEST-EFFORT like the build: a breaker-denied
        rehydration falls back to the scatter path (None) instead of
        failing the request the block only accelerates."""
        from elasticsearch_tpu.utils.errors import CircuitBreakingException

        rows, handle = d
        try:
            return rows, handle.get()
        except CircuitBreakingException:
            return None

    def dense_block(self):
        """Lazy (dense_rows, device impact) for hybrid scoring, or None.

        Frequent terms (long postings runs) score via one MXU matmul instead
        of scatter-adds; see build_dense_impact. Built on first search that
        touches this field; small segments have no qualifying terms and pay
        nothing. Registered as an EVICTABLE fielddata-tier residency handle
        (resources/residency.py): when HBM is tight the registry evicts LRU
        copies first, and a denied build leaves the field on the scatter
        path to retry once budget frees up (only 'no qualifying terms' is
        cached as a permanent no). An evicted block rehydrates from the
        host mirror on the next touch.
        """
        d = self._dense
        if d is False:
            return None
        if d is not None:
            return self._dense_get(d)
        with self._dense_lock:
            if self._dense is False:
                return None
            if self._dense is not None:
                return self._dense_get(self._dense)
            if self.doc_ids_host is None or not self.max_docs:
                self._dense = False
                return None
            # budget check BEFORE the (expensive) host-side build; a denial
            # is transient — leave _dense = None so a later query retries.
            # Resolve the breaker through the LIVE registry (the one the
            # put_array charge below goes to) — the import-time module
            # binding would read a stale service when tests swap the
            # resources singletons
            from elasticsearch_tpu import resources

            min_bytes = 8 * 4 * self.max_docs
            granted = min(
                1 << 30,
                resources.RESIDENCY.breakers.breaker("fielddata").remaining())
            if granted < min_bytes:
                return None
            tfn = self.tfnorm_host
            if tfn is None:
                tfn = np.ones(self.nnz, dtype=np.float32)
            built = build_dense_impact(
                self.doc_ids_host, tfn, self.offsets, self.df, self.max_docs,
                budget_bytes=granted,
            )
            if built is None:
                self._dense = False  # no qualifying terms: permanent
                return None
            rows, impact = built
            # SURVEY §6 "quantized impacts" lever: bf16 device storage
            # halves the block's HBM and feeds the MXU without a cast
            # (~0.4% relative tfnorm error; bench quantifies the ranking
            # agreement). Host mirror stays f32 for mesh restacking.
            bf16 = os.environ.get("ESTPU_IMPACT_BF16", "").lower() in (
                "1", "true")
            dtype = None
            if bf16:
                import jax.numpy as jnp

                dtype = jnp.bfloat16
            # best_effort: the block is a pure acceleration — a denied
            # reservation (even after LRU eviction) leaves the field on
            # the scatter path instead of failing the request
            handle = resources.RESIDENCY.put_array(
                impact, label=f"dense_impact:{self.name}",
                tier="fielddata", dtype=dtype, best_effort=True,
                device=self.device)
            if handle is None:
                return None  # budget tight: retry later
            # host mirror: mesh prims restack [S, F, D] from it — pulling
            # the device copy back would be a huge d2h transfer (and on
            # network-attached chips big d2h pulls degrade the session)
            self._dense_host = impact
            self._dense = (rows, handle)
            return rows, handle.get()

    @property
    def nnz_pad(self) -> int:
        """Padded postings length WITHOUT forcing device placement (the
        lazy doc_ids accessor would device_put an oversized field's full
        array just to read its shape)."""
        return int(self._doc_ids_raw.shape[0])

    @property
    def vocab_size(self) -> int:
        return len(self.terms)

    def term_id(self, term: str) -> int:
        return self.vocab.get(term, -1)

    def term_slice(self, term: str) -> Tuple[int, int]:
        """(start, length) of the term's postings run; (0, 0) if absent."""
        tid = self.vocab.get(term, -1)
        if tid < 0:
            return 0, 0
        return int(self.offsets[tid]), int(self.offsets[tid + 1] - self.offsets[tid])

    def idf(self, term: str, num_docs: Optional[int] = None, df: Optional[int] = None) -> float:
        """Lucene 5 BM25 idf: ln(1 + (N - df + 0.5)/(df + 0.5)).

        num_docs/df overrides support dfs_query_then_fetch global stats.
        """
        n = self.num_docs if num_docs is None else num_docs
        d = (self.df[self.vocab[term]] if term in self.vocab else 0) if df is None else df
        return float(np.log(1.0 + (n - d + 0.5) / (d + 0.5)))


def _lazy_device_field(name: str):
    """Attach a lazy device-placement accessor for one postings array.

    Freeze passes device arrays for ordinary fields (placement cost paid
    once, off the query path) but HOST arrays for an OVERSIZED field — its
    scoring runs through the cross-device postings split
    (parallel/postings_shard.py), which slices the host mirror per device;
    the full single-device copy these accessors hand out must not be
    allocated unless some path actually asks for it (phrase/positional
    programs, terms aggs over the field). First access device_puts and
    caches, so a fallback path pays the transfer once, not per query.

    Attached after class creation: defining the property inside the
    dataclass body would make the descriptor look like a field default.
    """
    raw = f"_{name}_raw"

    def _get(self):
        v = self.__dict__[raw]
        if isinstance(v, np.ndarray):
            v = _device_put(v, self.device)
            self.__dict__[raw] = v
        return v

    def _set(self, v):
        self.__dict__[raw] = v

    return property(_get, _set)


for _pname in ("doc_ids", "tf", "tfnorm", "term_ids"):
    setattr(InvertedField, _pname, _lazy_device_field(_pname))
del _pname


class Derived:
    """A column's host array that is computed from another on its first
    touch instead of at freeze (``numeric_column``: the f32 channel and
    the (hi, lo) pair of a column whose search never reads them hold no
    host memory)."""

    __slots__ = ("fn",)

    def __init__(self, fn):
        self.fn = fn


def _resident_field(name: str):
    """Attach a lazy EVICTABLE device accessor for one doc-value column
    array (the fielddata tier of resources/residency.py).

    Freeze stores the HOST array; the first search that touches the
    column registers it with the residency registry (charging the
    fielddata breaker — this is the "lazy column load" that can trip
    ``indices.breaker.fielddata.limit``) and hands out the device copy.
    Under HBM pressure the registry drops the device copy LRU-first and
    the next touch rehydrates from the retained host array — the
    reference's fielddata load/evict cycle, with the host mirror playing
    the role of the Lucene disk image. Legacy callers that assign an
    already-placed device array keep working, unaccounted (bench paths).
    """
    raw = f"_{name}_res"
    raw_lock = f"_{name}_res_lock"

    def _get(self):
        v = self.__dict__.get(raw)
        if v is None:
            return None
        from elasticsearch_tpu.resources.residency import ResidentArray

        if isinstance(v, ResidentArray):
            return v.get()
        if isinstance(v, (np.ndarray, Derived)):
            # first-touch registration is locked (dict.setdefault is
            # atomic under the GIL): two concurrent searches must not
            # each charge the breaker and upload the same slab
            lock = self.__dict__.setdefault(raw_lock, threading.Lock())
            with lock:
                v = self.__dict__.get(raw)
                if isinstance(v, Derived):
                    v = v.fn()
                if isinstance(v, np.ndarray):
                    from elasticsearch_tpu import resources

                    v = resources.RESIDENCY.put_array(
                        v, label=f"column:{self.name}.{name}",
                        tier="fielddata",
                        device=self.device)
                    self.__dict__[raw] = v
            if isinstance(v, ResidentArray):
                return v.get()
        return v  # pre-placed device array (legacy construction)

    def _set(self, v):
        self.__dict__[raw] = v

    return property(_get, _set)


@dataclass
class NumericColumn:
    name: str
    values: Any  # f32[max_docs] (device) — arithmetic channel, value - offset
    exists: Any  # bool[max_docs] (device)
    hi: Any = None  # int32[max_docs] exact pair (device) for 64-bit types
    lo: Any = None
    exact: Optional[np.ndarray] = None  # host i64/f64 mirror for fetch/sort
    exists_host: Optional[np.ndarray] = None  # host mirror (no d2h pulls)
    kind: str = "double"  # long|integer|double|float|date|boolean|ip|...
    # 64-bit kinds (dates = epoch millis ~1.7e12) overflow f32 precision, so
    # the arithmetic channel stores segment-relative values: f32 = exact -
    # offset, with offset = segment min. Consumers add offset back (aggs) or
    # shift query bounds down (range masks); exact compares use (hi, lo).
    offset: float = 0.0
    # exact int32 code (device, lazy like the rest; CODE_MISSING where the
    # doc has no value) of the kinds whose values are integers in some
    # unit: exact value = (code_base + code * code_step) / code_factor.
    # None where the column has none (see column_code). The aggregation
    # program (ops/aggs.py) keys, filters and reduces on it alone
    code: Any = None
    code_base: int = 0
    code_step: int = 1
    code_factor: int = 1
    code_min: int = 0  # over the docs with a value (0, 0 where none)
    code_max: int = 0
    value_count: int = 0  # docs with a value
    device = None  # the owning segment's chip (TpuSegment sets it)

    @property
    def has_code(self) -> bool:
        """True when the exact int32 code exists (presence check only,
        like has_pair: never forces the lazy device load)."""
        return self.__dict__.get("_code_res") is not None

    @property
    def has_pair(self) -> bool:
        """True when the exact (hi, lo) int32 pair exists. Presence check
        only — must NOT force the lazy device load (the mesh prims ask
        this and then restack from the host `exact` mirror)."""
        return self.__dict__.get("_hi_res") is not None


@dataclass
class KeywordColumn:
    """Ordinal doc values for keyword fields (single-valued fast path).

    Multi-valued keyword aggregation goes through the InvertedField's
    term_ids/segment_sum path instead; ords are -1 where missing/multi.
    """

    name: str
    ords: Any  # int32[max_docs] (device), -1 = missing
    exists: Any  # bool[max_docs]
    host_values: List[Optional[List[str]]] = dfield(default_factory=list)
    ords_host: Optional[np.ndarray] = None
    exists_host: Optional[np.ndarray] = None
    device = None  # the owning segment's chip (TpuSegment sets it)


@dataclass
class VectorColumn:
    name: str
    vecs: Any  # f32[max_docs, dims] (device)
    exists: Any  # bool[max_docs]
    dims: int
    vecs_host: Any = None  # host mirror (mesh stacking, IVF build)
    exists_host: Any = None
    similarity: str = "cosine"
    # the field's ANN ``index_options`` (mappings._ann_options): its
    # ``nlist`` and ``m`` size the IVF and PQ builds; None = defaults
    index_options: Optional[dict] = None
    # lazy IVF-flat coarse quantizer (ops/ivf.py); False = build attempted
    # and declined (too few vectors)
    _ivf: Any = None
    # lazy PQ tier (ops/pq.py): None = unbuilt OR placement breaker-denied
    # (retryable — dense-impact discipline), False = declined (too few
    # vectors), PqIndex = ready. Host parts memoized separately so a
    # breaker denial never re-pays the k-means train + encode.
    _pq: Any = None
    _pq_parts: Any = None
    # memoized content-address (slabs are immutable; SHA-1 of the full
    # slab per freeze/snapshot call is measurable host CPU)
    _ck: Any = None
    _ck_max: int = -1
    # lazy f32[max_docs] per-row term of this similarity's kNN score
    # (row_terms below), resident beside the slab on the slab's chip
    _row_terms: Any = None
    device = None  # the owning segment's chip (TpuSegment sets it)

    def row_terms(self):
        """The stored per-row term every kNN program over this slab reads
        (ops/knn.knn_row_terms: ||v||^2 for l2_norm, 1/||v|| for cosine;
        None for dot_product, which has none). The slab is immutable, so
        the term is built ONCE, by one pass over the resident slab on its
        own chip at the first kNN search, and lives as long as the column:
        a merged or refreshed segment is a new column and builds its own,
        a delete touches only the segment's live mask, an evicted slab
        rehydrates to the same values and keeps it. Always resident (4 B a
        slot, TpuSegment.memory_bytes charges it to the `segments`
        breaker); never written to the store — a reloaded segment builds
        it again. Counter: knn_row_terms_build, once a column."""
        from elasticsearch_tpu.ops.knn import has_row_terms, knn_row_terms

        if not has_row_terms(self.similarity):
            return None
        if self._row_terms is None:
            # first-touch build is locked, as a column's first placement
            # is (_resident_field): two searches must not both scan
            lock = self.__dict__.setdefault("_row_terms_lock",
                                            threading.Lock())
            with lock:
                if self._row_terms is None:
                    from elasticsearch_tpu.monitor import kernels

                    self._row_terms = knn_row_terms(
                        self.vecs, metric=self.similarity)
                    kernels.record("knn_row_terms_build")
        return self._row_terms

    def cache_key(self, max_docs: int) -> str:
        if self._ck is None or self._ck_max != max_docs:
            from elasticsearch_tpu.index import ivf_cache

            vh = (self.vecs_host if self.vecs_host is not None
                  else np.asarray(self.vecs))
            eh = (self.exists_host if self.exists_host is not None
                  else np.asarray(self.exists))
            self._ck = ivf_cache.content_key(vh, eh, self.similarity,
                                             max_docs)
            self._ck_max = max_docs
        return self._ck

    def _ann_param(self, key: str) -> Optional[int]:
        return (self.index_options or {}).get(key)

    def _build_source(self):
        """(slab, live mask) the builds read: the resident slab where it is
        on the device (read in place), else the host mirror."""
        from elasticsearch_tpu.ops.ivf import is_device_array

        vecs = (self.vecs if is_device_array(self.vecs) else
                self.vecs_host if self.vecs_host is not None
                else np.asarray(self.vecs))
        eh = (self.exists_host if self.exists_host is not None
              else np.asarray(self.exists))
        return vecs, eh

    def _ann_key(self, max_docs: int, key: str) -> str:
        """The blob cache's key: the slab's content, and the mapping's
        parameter where it names one (a default build keeps the bare
        content key)."""
        ck = self.cache_key(max_docs)
        value = self._ann_param(key)
        return ck if value is None else f"{ck}-{key}{value}"

    def build_ann(self, max_docs: int, tracer=None) -> None:
        """The index-time ANN build of this column, as its mapping's
        ``index_options`` ask: the IVF quantizer for every ANN type, the
        PQ tier beside it for ``ivf_pq``. One ``ann.build`` span over
        both (a root span on ``tracer`` where one is given, else a child
        of the open span)."""
        opts = self.index_options or {}
        if opts.get("type") not in ("ivf", "ivf_flat", "ivf_pq"):
            return
        from elasticsearch_tpu.tracing.tracer import span

        cm = (tracer.span("ann.build", field=self.name) if tracer is not None
              else span("ann.build", field=self.name))
        with cm:
            # index-time ANN build (like Lucene building HNSW at flush):
            # refreshes/merges/restores pay the k-means here, never the
            # first query (r3 verdict weak #9)
            self.get_ivf(max_docs)
            if opts["type"] == "ivf_pq":
                # PQ codes ride beside the coarse quantizer; best-effort —
                # a tight fielddata breaker leaves the exact fine-rank
                # path and a later query retries placement
                self.get_pq(max_docs)

    def get_ivf(self, max_docs: int):
        """Build-once IVF index over this (immutable) slab, consulting the
        content-addressed blob cache first so restarts / snapshot restores
        reload the persisted quantizer instead of re-running k-means
        (index/ivf_cache.py; counters ivf_cache_hit / ivf_build)."""
        if self._ivf is None:
            from elasticsearch_tpu.index import ivf_cache
            from elasticsearch_tpu.monitor import kernels
            from elasticsearch_tpu.ops.ivf import build_ivf

            key = self._ann_key(max_docs, "nlist")
            idx = ivf_cache.load(key)
            if idx is None:
                vecs, eh = self._build_source()
                idx = build_ivf(vecs, eh, max_docs, C=self._ann_param("nlist"),
                                metric=self.similarity,
                                vecs_host=self.vecs_host)
                if idx is not None:
                    kernels.record("ivf_build")
                    ivf_cache.store(key, idx)
            self._ivf = idx if idx is not None else False
        return self._ivf or None

    def get_pq(self, max_docs: int):
        """Build-once PQ tier over this (immutable) slab.

        Host parts come from the content-addressed blob cache when the
        slab content matches a persisted build (counter pq_cache_hit),
        else from a fresh train+encode (counter pq_build, re-persisted).
        Device placement is BEST-EFFORT: the uint8 code array registers
        as an evictable fielddata-tier handle, and a breaker denial
        returns None while leaving the build memoized — the caller keeps
        the exact fine-rank path and a later query retries placement
        only (the dense-impact contract)."""
        if self._pq is False:
            return None
        if self._pq is not None:
            return self._pq
        from elasticsearch_tpu.index import ivf_cache
        from elasticsearch_tpu.monitor import kernels
        from elasticsearch_tpu.ops.pq import build_pq, place_pq

        parts = self._pq_parts
        if parts is None:
            key = self._ann_key(max_docs, "m")
            parts = ivf_cache.load_pq(key)
            if parts is None:
                vecs, eh = self._build_source()
                parts = build_pq(vecs, eh, self.similarity,
                                 M=self._ann_param("m"),
                                 vecs_host=self.vecs_host)
                if parts is None:
                    self._pq = False  # too few vectors: permanent decline
                    return None
                kernels.record("pq_build")
                ivf_cache.store_pq(key, parts)
            self._pq_parts = parts
        idx = place_pq(parts, label=f"pq[{self.name}]")
        if idx is None:
            return None  # budget tight: retry later (self._pq stays None)
        self._pq = idx
        return idx


# doc-value columns load lazily into the evictable fielddata tier (see
# _resident_field): freeze stores host arrays, the first search places
# them, pressure evicts them, the next touch rehydrates
_COLUMN_RESIDENT_FIELDS = (
    (NumericColumn, ("values", "exists", "hi", "lo", "code")),
    (KeywordColumn, ("ords", "exists")),
    (VectorColumn, ("vecs", "exists")),
)
for _ccls, _cfields in _COLUMN_RESIDENT_FIELDS:
    for _f in _cfields:
        setattr(_ccls, _f, _resident_field(_f))
del _ccls, _cfields, _f


def _column_resident(col, fields) -> Tuple[int, int, int]:
    """(resident_bytes, evictions, rehydrations) over one column's
    registered residency handles."""
    from elasticsearch_tpu.resources.residency import ResidentArray

    b = ev = rh = 0
    for nm in fields:
        h = col.__dict__.get(f"_{nm}_res")
        if isinstance(h, ResidentArray):
            if h.resident:
                b += h.nbytes
            ev += h.evictions
            rh += h.rehydrations
    return b, ev, rh


# kinds whose exact host mirror is int64 (and whose device form adds the
# exact (hi, lo) pair)
EXACT_INT_KINDS = ("long", "date", "ip", "murmur3", "token_count", "integer")
# the code of a doc with no value: below every code a value takes, so a
# range test on the code drops it without reading ``exists``
CODE_MISSING = -(2 ** 31)
# |code| bound: the program's key arithmetic (code - origin) stays int32
CODE_LIMIT = 2 ** 30


def column_code(kind: str, exact: np.ndarray, exists: np.ndarray,
                scaling_factor: float = 1.0):
    """The exact int32 code of a column (CODE_MISSING where a doc has no
    value), or None where its values are not integers in a unit whose span
    fits ``CODE_LIMIT``: (code, base, step, factor, least code, greatest
    code) with exact = (base + code * step) / factor.

    Integer kinds: base = the least value, step 1000 for a date whose
    every value is a whole second (and 1 else). ``scaled_float``: the
    value times an integral scaling factor, as ES stores it (the caller
    has already rounded ``exact`` to the scale). Floating kinds: None."""
    if not exists.any():
        return None
    if kind in EXACT_INT_KINDS:
        base = int(exact.min(where=exists, initial=np.iinfo(np.int64).max))
        top = int(exact.max(where=exists, initial=np.iinfo(np.int64).min))
        step = 1
        if kind == "date" and base % 1000 == 0 and not np.any(
                exact % 1000, where=exists):
            step = 1000
        if (top - base) // step >= CODE_LIMIT:
            return None
        code = np.full(exact.shape, CODE_MISSING, np.int32)
        np.floor_divide(exact - base, step, out=code, where=exists,
                        casting="unsafe")
        return code, base, step, 1, 0, (top - base) // step
    if kind == "scaled_float" and float(scaling_factor).is_integer() \
            and scaling_factor >= 1:
        factor = int(scaling_factor)
        scaled = np.rint(exact * factor)
        lo = scaled.min(where=exists, initial=np.inf)
        hi = scaled.max(where=exists, initial=-np.inf)
        if max(-lo, hi) >= CODE_LIMIT:
            return None
        code = np.full(exact.shape, CODE_MISSING, np.int32)
        np.copyto(code, scaled, where=exists, casting="unsafe")
        return code, 0, 1, factor, int(lo), int(hi)
    return None


def numeric_column(name: str, kind: str, exact: np.ndarray,
                   exists: np.ndarray, scaling_factor: float = 1.0,
                   device: Any = None) -> NumericColumn:
    """The one codec of a numeric or date column: exact values (int64 for
    ``EXACT_INT_KINDS``, float64 else) and an exists mask, both
    [max_docs], to a NumericColumn of host arrays whose device copies load
    lazily onto ``device`` (the owning segment's chip; TpuSegment sets it
    again). ``SegmentBuilder`` calls it on what it collected document by
    document; a loader that has the arrays calls it directly."""
    exists = np.asarray(exists, dtype=bool)
    needs_exact = kind in EXACT_INT_KINDS
    exact = np.asarray(exact, dtype=np.int64 if needs_exact else np.float64)
    if kind == "scaled_float" and float(scaling_factor).is_integer() \
            and scaling_factor >= 1:
        # ES keeps round(value * factor): doc values read back that / factor
        exact = np.rint(exact * scaling_factor) / scaling_factor
    offset = 0.0
    if needs_exact and exists.any():
        offset = float(exact.min(where=exists, initial=np.iinfo(np.int64).max))

    def values():
        return np.where(exists, (exact - offset).astype(np.float32),
                        np.float32(0))

    # host arrays, or Derived: the device copies load lazily into the
    # evictable fielddata tier on first touch (_resident_field)
    col = NumericColumn(name=name, values=Derived(values), exists=exists,
                        exact=exact, exists_host=exists, kind=kind,
                        offset=offset)
    col.device = device
    col.value_count = int(np.count_nonzero(exists))
    if needs_exact:
        col.hi = Derived(lambda: split_i64(exact)[0])
        col.lo = Derived(lambda: split_i64(exact)[1])
    coded = column_code(kind, exact, exists, scaling_factor)
    if coded is not None:
        (col.code, col.code_base, col.code_step, col.code_factor,
         col.code_min, col.code_max) = coded
    return col


class RangeIds(Sequence):
    """The ``_id`` of every document of a segment whose ids are
    ``start``, ``start + 1``, ... as strings: one int, no Python object a
    document (a loader's segment of many millions)."""

    def __init__(self, start: int, n: int):
        self.start, self.n = int(start), int(n)

    def __len__(self) -> int:
        return self.n

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [self[j] for j in range(*i.indices(self.n))]
        if not -self.n <= i < self.n:
            raise IndexError(i)
        return str(self.start + (i % self.n))


class Uniform(Sequence):
    """One value for every document (no ``_source``, no stored fields)."""

    def __init__(self, value, n: int):
        self.value, self.n = value, int(n)

    def __len__(self) -> int:
        return self.n

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [self.value] * len(range(*i.indices(self.n)))
        if not -self.n <= i < self.n:
            raise IndexError(i)
        return self.value


class TpuSegment:
    """One immutable frozen segment."""

    _next_id = 0

    def __init__(
        self,
        num_docs: int,
        max_docs: int,
        inverted: Dict[str, InvertedField],
        numerics: Dict[str, NumericColumn],
        keywords: Dict[str, KeywordColumn],
        vectors: Dict[str, VectorColumn],
        sources: List[Optional[dict]],
        stored: List[dict],
        ids: List[str],
        id_map: Dict[str, int],
        field_lengths: Dict[str, Any],
        device: Any = None,
    ):
        TpuSegment._next_id += 1
        self.seg_id = TpuSegment._next_id
        # the chip this segment lives on: its shard's (SegmentBuilder
        # passes it), else the one its postings were put on by whoever
        # built it, else None — the default device, as a one-shard index
        # has it. Everything the segment and its fields place, now or
        # lazily (live mask, dense impact block, columns), goes there.
        if device is None:
            device = _committed_device(
                inv._doc_ids_raw for inv in inverted.values())
        self.device = device
        for fld in (*inverted.values(), *numerics.values(),
                    *keywords.values(), *vectors.values()):
            fld.device = device
        self.num_docs = num_docs
        self.max_docs = max_docs  # pow2 padded
        self.inverted = inverted
        self.numerics = numerics
        self.keywords = keywords
        self.vectors = vectors
        self.sources = sources
        self.stored = stored
        self.ids = ids
        self.id_map = id_map
        self.field_lengths = field_lengths  # field -> f32[max_docs] device
        # deletion state: host-authoritative, device copy refreshed on change
        self._live_host = np.zeros(max_docs, dtype=bool)
        self._live_host[:num_docs] = True
        self._live_dev = _device_put(self._live_host, device)
        self._live_dirty = False
        self.deleted_count = 0
        # block-join (set by SegmentBuilder.freeze when the segment holds
        # nested child docs; None = every doc is a root)
        self.metas: List[dict] = []
        self.parent_id_host: Optional[np.ndarray] = None
        self.nested_code_host: Optional[np.ndarray] = None
        self.nested_ord_host: Optional[np.ndarray] = None
        self.nested_paths: Dict[str, int] = {}
        self.roots_host: Optional[np.ndarray] = None
        self.parent_id_dev: Any = None
        self.nested_code_dev: Any = None
        self.roots_dev: Any = None
        self.root_id_host: Optional[np.ndarray] = None
        self.ancestors_host: Dict[int, np.ndarray] = {}
        self.root_id_dev: Any = None
        self.ancestors_dev: Dict[int, Any] = {}

    @property
    def has_nested(self) -> bool:
        return self.parent_id_dev is not None

    # -- deletes ---------------------------------------------------------------

    def delete_local(self, local_id: int) -> bool:
        if 0 <= local_id < self.num_docs and self._live_host[local_id]:
            self._live_host[local_id] = False
            self._live_dirty = True  # device copy refreshed lazily on next read
            self.deleted_count += 1
            # cascade to the whole block: nested children die with the root
            if self.parent_id_host is not None:
                stack = [local_id]
                while stack:
                    p = stack.pop()
                    kids = np.nonzero(self.parent_id_host[: self.num_docs] == p)[0]
                    for k in kids:
                        if self._live_host[k]:
                            self._live_host[k] = False
                            self.deleted_count += 1
                            stack.append(int(k))
            return True
        return False

    @property
    def live(self):
        if self._live_dirty:
            self._live_dev = _device_put(self._live_host, self.device)
            self._live_dirty = False
        return self._live_dev

    @property
    def live_i8(self):
        """The live mask as int8 on the segment's chip, for kernels that
        cannot read a bool array (ops/aggs.py): an evictable fielddata
        array like a doc-value column (_resident_field), placed under one
        lock on first use and again after a delete (keyed on
        ``deleted_count``; the replaced handle releases its charge when
        it is collected)."""
        lock = self.__dict__.setdefault("_live_i8_lock", threading.Lock())
        with lock:
            seen = self.deleted_count
            if self.__dict__.get("_live_i8_at") != seen:
                from elasticsearch_tpu import resources

                self._live_i8 = resources.RESIDENCY.put_array(
                    self._live_host.astype(np.int8),
                    label=f"segment:{self.seg_id}.live_i8",
                    tier="fielddata", device=self.device)
                self._live_i8_at = seen
            handle = self._live_i8
        return handle.get()

    @property
    def live_host(self) -> np.ndarray:
        return self._live_host

    @property
    def live_docs(self) -> int:
        return self.num_docs - self.deleted_count

    def memory_bytes(self) -> int:
        """Approximate ALWAYS-RESIDENT HBM footprint — the `segments`
        breaker charge at freeze (live mask + postings + a vector
        column's stored row term, VectorColumn.row_terms). Doc-value
        columns and vector slabs are NOT counted here: they load lazily
        into the evictable fielddata tier and charge the fielddata
        breaker on first touch (resources/residency.py)."""
        from elasticsearch_tpu.ops.knn import has_row_terms

        total = self.max_docs  # live mask
        for inv in self.inverted.values():
            total += inv.nnz_pad * (4 + 4 + 4 + 4)
        for vc in self.vectors.values():
            if has_row_terms(vc.similarity):
                total += 4 * self.max_docs
        return total

    def _column_iter(self):
        """(column, resident-field names) for every doc-value column."""
        for col in self.numerics.values():
            yield col, ("values", "exists", "hi", "lo", "code")
        for col in self.keywords.values():
            yield col, ("ords", "exists")
        for col in self.vectors.values():
            yield col, ("vecs", "exists")

    def fielddata_field_bytes(self) -> Dict[str, int]:
        """Per-field doc-value memory currently DEVICE-RESIDENT — the
        `fielddata` section of _stats (reference:
        index/fielddata/ShardFieldData.java per-field maps). Columns
        load lazily at first search and evict under HBM pressure, so
        like the reference this reports loaded bytes, not mapped bytes;
        for analyzed text the always-resident uninverted postings
        arrays play fielddata's sort/agg role and report in full."""
        out: Dict[str, int] = {}

        def add(name, b):
            if b:
                out[name] = out.get(name, 0) + b

        for col, fields in self._column_iter():
            add(col.name, _column_resident(col, fields)[0])
        for name, inv in self.inverted.items():
            if name in self.keywords or name in self.numerics \
                    or name.startswith("_"):
                continue
            add(name, inv.nnz_pad * 12)  # term_ids + doc_ids + tf
        return out

    def fielddata_evictions(self) -> Tuple[int, int]:
        """(evictions, rehydrations) over this segment's column and
        dense-impact residency handles — the once-zero-by-design
        `fielddata.evictions` counter is real now."""
        ev = rh = 0
        for col, fields in self._column_iter():
            _, e, r = _column_resident(col, fields)
            ev += e
            rh += r
        for inv in self.inverted.values():
            d = inv._dense
            if isinstance(d, tuple):
                ev += d[1].evictions
                rh += d[1].rehydrations
        return ev, rh


class SegmentBuilder:
    """Mutable in-memory indexing buffer; freeze() emits a TpuSegment.

    Mirrors the role of Lucene's IndexWriter RAM buffer + DWPT flush
    (reference: InternalEngine.refresh → Lucene flush), but the frozen form
    is device arrays rather than an on-disk codec.
    """

    def __init__(self, mappings: Mappings, device: Any = None):
        self.mappings = mappings
        # the owning shard's chip (None: the default device): where
        # freeze() places the segment
        self.device = device
        self.docs: List[ParsedDocument] = []
        # block-join metadata aligned with docs: immediate parent local id
        # (-1 for root docs) — children are emitted BEFORE their parent, the
        # Lucene block order (reference: nested docs in ParsedDocument.docs())
        self.parent_of: List[int] = []

    def add(self, parsed: ParsedDocument) -> int:
        """Append a doc block (descendants first, root last); returns the
        ROOT's local id."""
        child_locals: List[int] = []
        for child in parsed.children:
            child_locals.append(self.add(child))
        my_local = len(self.docs)
        self.docs.append(parsed)
        self.parent_of.append(-1)
        for cl in child_locals:
            self.parent_of[cl] = my_local
        return my_local

    def __len__(self) -> int:
        return len(self.docs)

    @property
    def num_docs(self) -> int:
        return len(self.docs)

    def freeze(self) -> Optional[TpuSegment]:
        if not self.docs:
            return None
        jnp = _jnp()
        n = len(self.docs)
        max_docs = pow2_bucket(n, minimum=64)

        # -- field discovery
        text_fields: Dict[str, None] = {}
        kw_fields: Dict[str, None] = {}
        num_fields: Dict[str, str] = {}
        vec_fields: Dict[str, Tuple[int, str]] = {}
        for d in self.docs:
            for f in d.text_tokens:
                text_fields.setdefault(f)
            for f, vec in d.vectors.items():
                fm = self.mappings.get(f)
                vec_fields.setdefault(f, (len(vec), fm.similarity if fm else "cosine"))
            for f, vals in d.doc_values.items():
                fm = self.mappings.get(f)
                kind = fm.type if fm else None
                if kind is None:
                    kind = "keyword" if isinstance(vals[0], str) else "double"
                if kind in ("keyword", "string_not_analyzed"):
                    kw_fields.setdefault(f)
                else:
                    num_fields[f] = kind

        inverted: Dict[str, InvertedField] = {}
        field_lengths: Dict[str, Any] = {}

        # -- text fields: build CSR postings with positions
        for fname in text_fields:
            inverted[fname] = self._build_inverted_text(fname, n, max_docs)
            lens = np.zeros(max_docs, dtype=np.float32)
            for i, d in enumerate(self.docs):
                lens[i] = d.field_length(fname)
            field_lengths[fname] = _device_put(lens, self.device)

        # -- keyword fields: inverted (for term filters + terms agg) + ords
        keywords: Dict[str, KeywordColumn] = {}
        for fname in kw_fields:
            inv, kwcol = self._build_keyword(fname, n, max_docs)
            inverted[fname] = inv
            keywords[fname] = kwcol

        # -- numeric-ish columns
        numerics: Dict[str, NumericColumn] = {}
        for fname, kind in num_fields.items():
            numerics[fname] = self._build_numeric(fname, kind, n, max_docs)

        # -- vectors
        vectors: Dict[str, VectorColumn] = {}
        for fname, (dims, sim) in vec_fields.items():
            mat = np.zeros((max_docs, dims), dtype=np.float32)
            exists = np.zeros(max_docs, dtype=bool)
            for i, d in enumerate(self.docs):
                v = d.vectors.get(fname)
                if v is not None:
                    mat[i] = np.asarray(v, dtype=np.float32)
                    exists[i] = True
            # host arrays: the device slab loads lazily into the
            # evictable fielddata tier on first touch (_resident_field)
            fm = self.mappings.get(fname)
            vc = VectorColumn(
                name=fname, vecs=mat, exists=exists,
                dims=dims, vecs_host=mat, exists_host=exists, similarity=sim,
                index_options=(getattr(fm, "index_options", None)
                               if fm is not None else None),
            )
            vc.build_ann(max_docs)
            vectors[fname] = vc

        ids = [d.doc_id for d in self.docs]
        seg = TpuSegment(
            num_docs=n,
            max_docs=max_docs,
            inverted=inverted,
            numerics=numerics,
            keywords=keywords,
            vectors=vectors,
            sources=[d.source for d in self.docs],
            stored=[d.stored for d in self.docs],
            ids=ids,
            id_map={doc_id: i for i, doc_id in enumerate(ids)},
            field_lengths=field_lengths,
            device=self.device,
        )
        seg.metas = [d.meta for d in self.docs]
        # block-join arrays (all-root fast path: leave device arrays None)
        if any(p >= 0 for p in self.parent_of):
            parent_id = np.full(max_docs, -1, dtype=np.int32)
            parent_id[:n] = np.asarray(self.parent_of, dtype=np.int32)
            nested_code = np.full(max_docs, -1, dtype=np.int32)
            nested_ord = np.full(max_docs, -1, dtype=np.int32)
            paths: Dict[str, int] = {}
            for i, d in enumerate(self.docs):
                if d.nested_path is not None:
                    code = paths.setdefault(d.nested_path, len(paths))
                    nested_code[i] = code
                    nested_ord[i] = d.nested_ord
            seg.parent_id_host = parent_id
            seg.nested_code_host = nested_code
            seg.nested_ord_host = nested_ord
            seg.nested_paths = paths
            roots = np.zeros(max_docs, dtype=bool)
            roots[:n] = parent_id[:n] < 0
            seg.roots_host = roots
            # transitive ancestors: root_id[d] = the block's root doc, and
            # per nested level L: ancestor_at[L][d] = d's ancestor whose
            # nested_code == L (-1 if none). Join targets for nested query /
            # reverse_nested at any depth, resolved by one device gather.
            root_id = np.arange(max_docs, dtype=np.int32)
            anc: Dict[int, np.ndarray] = {c: np.full(max_docs, -1, dtype=np.int32)
                                          for c in paths.values()}
            for i in range(n):
                # children precede parents, so walking up terminates fast
                j = i
                while parent_id[j] >= 0:
                    j = parent_id[j]
                    if nested_code[j] >= 0:
                        if anc[nested_code[j]][i] < 0:
                            anc[nested_code[j]][i] = j
                root_id[i] = j
                if nested_code[i] >= 0:
                    anc[nested_code[i]][i] = i  # a doc is its own level-ancestor
            seg.root_id_host = root_id
            seg.ancestors_host = anc
            put = partial(_device_put, device=self.device)
            seg.parent_id_dev = put(parent_id)
            seg.nested_code_dev = put(nested_code)
            seg.roots_dev = put(roots)
            seg.root_id_dev = put(root_id)
            seg.ancestors_dev = {c: put(a) for c, a in anc.items()}
        return seg

    # -- builders --------------------------------------------------------------

    def _postings_put(self, nnz: int):
        """Where freeze leaves a field's postings: on the shard's chip,
        or — a field one chip cannot hold whole, the rule
        ``InvertedField.postings_split`` asks too — on the host."""
        from elasticsearch_tpu.parallel.postings_shard import \
            chip_holds_whole

        if chip_holds_whole(nnz):
            return partial(_device_put, device=self.device)
        return lambda a: a

    def _build_inverted_text(self, fname: str, n: int, max_docs: int) -> InvertedField:
        # term -> list[(doc, tf, positions)]
        vocab: Dict[str, int] = {}
        terms: List[str] = []
        post: List[List[Tuple[int, int, List[int]]]] = []
        total_terms = 0
        for i, d in enumerate(self.docs):
            toks = d.text_tokens.get(fname)
            if not toks:
                continue
            total_terms += len(toks)
            per_term: Dict[int, List[int]] = {}
            for t, p in toks:
                tid = vocab.get(t)
                if tid is None:
                    tid = len(terms)
                    vocab[t] = tid
                    terms.append(t)
                    post.append([])
                per_term.setdefault(tid, []).append(p)
            for tid, poss in per_term.items():
                post[tid].append((i, len(poss), poss))

        V = len(terms)
        df = np.array([len(p) for p in post], dtype=np.int32) if V else np.zeros(0, np.int32)
        cf = np.array([sum(tf for _, tf, _ in p) for p in post], dtype=np.int64) if V else np.zeros(0, np.int64)
        nnz = int(df.sum())
        ndocs_with_field = int(sum(1 for d in self.docs if d.text_tokens.get(fname)))
        avg_len = (total_terms / ndocs_with_field) if ndocs_with_field else 1.0

        doc_ids = np.full(nnz, 0, dtype=np.int32)
        tf_arr = np.zeros(nnz, dtype=np.float32)
        term_ids = np.zeros(nnz, dtype=np.int32)
        offsets = np.zeros(V + 1, dtype=np.int64)
        pos_offsets = np.zeros(nnz + 1, dtype=np.int64)
        positions_flat: List[int] = []
        k = 0
        for tid in range(V):
            offsets[tid] = k
            for doc, tf, poss in post[tid]:
                doc_ids[k] = doc
                tf_arr[k] = tf
                term_ids[k] = tid
                positions_flat.extend(poss)
                pos_offsets[k + 1] = len(positions_flat)
                k += 1
        offsets[V] = k

        # precompute BM25 tf-normalization (k1/b fixed at index time, like
        # Lucene BM25Similarity norms; idf is applied at query time so global
        # dfs stats can override per-segment stats)
        dl = np.array([self.docs[i].field_length(fname) for i in doc_ids], dtype=np.float32) if nnz else np.zeros(0, np.float32)
        tfnorm = tf_arr * (K1 + 1.0) / (tf_arr + K1 * (1.0 - B + B * dl / max(avg_len, 1e-9)))

        nnz_pad = pow2_bucket(max(nnz, 1), minimum=8)
        # an OVERSIZED field must not allocate its full postings on one
        # device at freeze — scoring goes through the cross-device split;
        # the lazy accessors place these host arrays only if a fallback
        # path (phrase, terms agg) actually asks for the full copy
        put = self._postings_put(nnz)
        return InvertedField(
            name=fname,
            vocab=vocab,
            terms=terms,
            df=df,
            cf=cf,
            offsets=offsets,
            doc_ids=put(pad_to(doc_ids, nnz_pad, max_docs)),
            tf=put(pad_to(tf_arr, nnz_pad, 0.0)),
            tfnorm=put(pad_to(tfnorm.astype(np.float32), nnz_pad, 0.0)),
            term_ids=put(pad_to(term_ids, nnz_pad, V)),
            nnz=nnz,
            num_docs=ndocs_with_field,
            total_terms=total_terms,
            avg_len=avg_len,
            pos_offsets=pos_offsets,
            positions=np.array(positions_flat, dtype=np.int32),
            doc_ids_host=doc_ids,
            tfnorm_host=tfnorm.astype(np.float32),
            tf_host=tf_arr,
            max_docs=max_docs,
        )

    def _build_keyword(self, fname: str, n: int, max_docs: int):
        vocab: Dict[str, int] = {}
        terms: List[str] = []
        post: List[List[int]] = []
        ords = np.full(max_docs, -1, dtype=np.int32)
        exists = np.zeros(max_docs, dtype=bool)
        host_values: List[Optional[List[str]]] = [None] * max_docs
        for i, d in enumerate(self.docs):
            vals = d.doc_values.get(fname)
            if not vals:
                continue
            svals = [str(v) for v in vals]
            host_values[i] = svals
            exists[i] = True
            for v in svals:
                tid = vocab.get(v)
                if tid is None:
                    tid = len(terms)
                    vocab[v] = tid
                    terms.append(v)
                    post.append([])
                post[tid].append(i)
            if len(svals) == 1:
                ords[i] = vocab[svals[0]]

        V = len(terms)
        # sort terms lexicographically for deterministic ordinal order (ES
        # terms agg _term ordering relies on it)
        order = sorted(range(V), key=lambda t: terms[t])
        remap = {old: new for new, old in enumerate(order)}
        terms2 = [terms[o] for o in order]
        post2 = [sorted(set(post[o])) for o in order]
        vocab2 = {t: i for i, t in enumerate(terms2)}
        ords_re = np.where(ords >= 0, np.array([remap.get(o, -1) for o in range(V)] or [0], dtype=np.int32)[np.maximum(ords, 0)], -1).astype(np.int32) if V else ords

        df = np.array([len(p) for p in post2], dtype=np.int32) if V else np.zeros(0, np.int32)
        nnz = int(df.sum())
        doc_ids = np.zeros(nnz, dtype=np.int32)
        term_ids = np.zeros(nnz, dtype=np.int32)
        offsets = np.zeros(V + 1, dtype=np.int64)
        k = 0
        for tid in range(V):
            offsets[tid] = k
            for doc in post2[tid]:
                doc_ids[k] = doc
                term_ids[k] = tid
                k += 1
        offsets[V] = k
        nnz_pad = pow2_bucket(max(nnz, 1), minimum=8)
        ones = np.ones(nnz, dtype=np.float32)
        # same oversized-field treatment as _build_inverted_text
        put = self._postings_put(nnz)
        inv = InvertedField(
            name=fname,
            vocab=vocab2,
            terms=terms2,
            df=df,
            cf=df.astype(np.int64),
            offsets=offsets,
            doc_ids=put(pad_to(doc_ids, nnz_pad, max_docs)),
            tf=put(pad_to(ones, nnz_pad, 0.0)),
            tfnorm=put(pad_to(ones, nnz_pad, 0.0)),
            term_ids=put(pad_to(term_ids, nnz_pad, V)),
            nnz=nnz,
            num_docs=int(exists.sum()),
            total_terms=nnz,
            avg_len=1.0,
            doc_ids_host=doc_ids,
            tfnorm_host=ones,
            max_docs=max_docs,
        )
        kwcol = KeywordColumn(
            name=fname,
            ords=ords_re,  # host: lazy evictable device copy (fielddata)
            exists=exists,
            host_values=host_values,
            ords_host=ords_re,
            exists_host=exists,
        )
        return inv, kwcol

    def _build_numeric(self, fname: str, kind: str, n: int, max_docs: int) -> NumericColumn:
        exists = np.zeros(max_docs, dtype=bool)
        exact = np.zeros(max_docs, dtype=np.int64 if kind in EXACT_INT_KINDS
                         else np.float64)
        for i, d in enumerate(self.docs):
            vals = d.doc_values.get(fname)
            if not vals:
                continue
            exists[i] = True
            exact[i] = vals[0]  # multi-valued numerics: first value in the column (full set in _source)
        fm = self.mappings.get(fname)
        return numeric_column(fname, kind, exact, exists,
                              scaling_factor=fm.scaling_factor if fm else 1.0)
