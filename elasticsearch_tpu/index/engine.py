"""Index engine: the write path.

Reference: org/elasticsearch/index/engine/InternalEngine.java — in-memory
indexing buffer, near-real-time refresh, flush (durability handoff to
segments), versioned CRUD with optimistic concurrency, realtime GET served
from the not-yet-refreshed buffer, tombstone deletes, and merge scheduling.

TPU adaptation: "refresh" freezes the RAM buffer into an immutable
device-resident TpuSegment (instead of a Lucene flush-to-codec); deletes
flip bits in per-segment live masks; merge re-indexes live docs' _source
through the analysis chain into one new segment (equivalent output to a
postings-level merge because segments are derived purely from source+
mappings; noted deviation from Lucene's codec-level merge).
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from elasticsearch_tpu.analysis.registry import AnalysisRegistry
from elasticsearch_tpu.index.doc_parser import DocumentParser, ParsedDocument
from elasticsearch_tpu.index.mappings import Mappings
from elasticsearch_tpu.index.segment import SegmentBuilder, TpuSegment
from elasticsearch_tpu.index.seqno import (
    NO_OPS_PERFORMED,
    LocalCheckpointTracker,
)
from elasticsearch_tpu.index.translog import Translog
from elasticsearch_tpu.utils.errors import (
    DocumentMissingException,
    EngineFailedException,
    StalePrimaryException,
    VersionConflictException,
)
from elasticsearch_tpu.utils.faults import FAULTS


@dataclass
class DocLocation:
    version: int
    deleted: bool = False
    # "buffer" or a segment id; buffer docs re-resolve on refresh
    where: Any = "buffer"
    local_id: int = -1
    source: Optional[dict] = None  # for realtime get of buffered docs
    # _type/_parent meta preserved across partial updates & re-index
    doc_type: Optional[str] = None
    parent: Optional[str] = None
    routing: Optional[str] = None
    # resolved _timestamp (epoch millis) / _ttl expiry — served by GET
    # fields=_timestamp/_ttl without a segment lookup
    timestamp: Optional[int] = None
    ttl_expiry: Optional[int] = None
    # replication identity: the (primary term, seq no) the op that wrote
    # this state carried — recovery's full-copy path ships them so a
    # rebuilt copy keeps the same op lineage (index/seqno.py)
    seq_no: int = -2  # UNASSIGNED_SEQ_NO
    term: int = 0


@dataclass
class EngineStats:
    index_total: int = 0
    delete_total: int = 0
    get_total: int = 0
    refresh_total: int = 0
    flush_total: int = 0
    merge_total: int = 0
    index_time_ms: float = 0.0
    # per-doc-type indexing counters (reference: ShardIndexingService
    # typeStats feeding IndexingStats.Stats per type — the `types` scope
    # of _stats)
    types: Dict[str, Dict[str, int]] = field(default_factory=dict)

    def on_type(self, doc_type: Optional[str], op: str) -> None:
        ts = self.types.setdefault(doc_type or "_doc",
                                   {"index_total": 0, "delete_total": 0})
        ts[op] += 1


class Engine:
    """In-memory Lucene-equivalent: buffer → frozen TpuSegments, doc
    identity, versioning, translog durability.

    Lock order (verified acyclic by tpulint R013's interprocedural lock
    graph — keep it that way): ``Engine._lock`` is the OUTERMOST lock of
    the write path; under it we take ``Translog._lock`` (appends/fsync),
    ``LocalCheckpointTracker._lock`` (seqno advance), and the
    process-shared metrics/native locks. Nothing below may call back
    into an Engine public method while holding its own lock.
    """

    def __init__(
        self,
        mappings: Mappings,
        analysis: AnalysisRegistry,
        translog_path: Optional[str] = None,
        refresh_interval_docs: int = 0,
        merge_segment_count: int = 8,
        index_name: str = "",
        device=None,
    ):
        self.index_name = index_name  # for typed errors: "engine for [x]"
        # the shard's chip (None: the default device): every segment this
        # engine freezes or merges is placed there
        self.device = device
        self.mappings = mappings
        self.analysis = analysis
        self.parser = DocumentParser(mappings, analysis)
        self.translog = Translog(translog_path)
        self.buffer = SegmentBuilder(mappings)
        self.segments: List[TpuSegment] = []
        self._locations: Dict[str, DocLocation] = {}
        self._buffer_ids: Dict[str, int] = {}
        self._lock = threading.RLock()
        self.stats = EngineStats()
        # commit identity for the _stats shards level (reference: Lucene
        # SegmentInfos commit id/generation in CommitStats)
        import uuid as _uuid

        self.commit_id = _uuid.uuid4().hex
        self.merge_segment_count = merge_segment_count
        from elasticsearch_tpu.index.merge import TieredMergePolicy

        self.merge_policy = TieredMergePolicy(
            segments_per_tier=merge_segment_count)
        self._auto_id = 0
        # tragic-event state: non-None after a durability-critical IO
        # failure; every later write 503s (reference: failEngine)
        self.failed_reason: Optional[str] = None
        # replication safety (index/seqno.py): the term under which this
        # copy believes its shard's primary operates, the local-checkpoint
        # tracker, and the per-term max-seq-no history used for the
        # log-matching check peer recovery does before ops replay
        self.primary_term = 1
        self.seq = LocalCheckpointTracker()
        self._term_seq: Dict[int, int] = {}

    # -- primary terms / sequence numbers ---------------------------------------

    @property
    def local_checkpoint(self) -> int:
        return self.seq.checkpoint

    @property
    def max_seq_no(self) -> int:
        return self.seq.max_seq_no

    def bump_term(self, term: int) -> None:
        """Adopt a higher primary term (promotion, or learning the new
        term from a newer primary's op/recovery stream)."""
        with self._lock:
            if term > self.primary_term:
                self.primary_term = term

    def _fence_term(self, op_term: Optional[int],
                    history: bool = False) -> int:
        """Term handling for one op. LIVE ops (a primary's own writes,
        replica fanout) are FENCED: an op from a term older than this
        copy's current one comes from a demoted primary and is rejected;
        a newer term is adopted. HISTORY ops (translog replay, recovery
        streams) apply under their original recorded term without
        fencing — replaying a term-1 op onto a term-2 copy is the normal
        shape of catching up, not a zombie write (reference: the request-
        level term check in TransportReplicationAction fences live ops;
        recovery replays history below the current term freely). Must
        hold ``_lock``."""
        if op_term is None:
            return self.primary_term  # primary-local op: current term
        if history:
            return op_term
        if op_term < self.primary_term:
            raise StalePrimaryException(self.index_name, "?", op_term,
                                        self.primary_term)
        if op_term > self.primary_term:
            self.primary_term = op_term
        return op_term

    def _note_op(self, term: int, seq_no: int) -> None:
        """Record (term, seq no) into the per-term history and the local
        checkpoint tracker. Must hold ``_lock``."""
        if seq_no < 0:
            return
        self.seq.mark_processed(seq_no)
        cur = self._term_seq.get(term, NO_OPS_PERFORMED)
        if seq_no > cur:
            self._term_seq[term] = seq_no

    def term_at(self, seq_no: int) -> Optional[int]:
        """The primary term the op at ``seq_no`` ran under — the lowest
        term whose recorded max seq no covers it (term boundaries are
        strict: a new primary continues numbering past its predecessor).
        None when this engine holds no record of that seq no."""
        if seq_no < 0:
            return 0  # vacuous: an empty copy matches any history
        with self._lock:
            for term in sorted(self._term_seq):
                if self._term_seq[term] >= seq_no:
                    return term
        return None

    def seq_no_stats(self) -> dict:
        return {"max_seq_no": self.max_seq_no,
                "local_checkpoint": self.local_checkpoint,
                "primary_term": self.primary_term}

    def note_noop(self, seq_no: Optional[int], term: Optional[int]) -> None:
        """Mark an op's seq no processed WITHOUT applying it — the no-op
        path for a replayed/fanned op whose effect is already covered by
        newer state (version conflict, tombstoned doc). Without this, a
        skipped op leaves a permanent hole above the local checkpoint:
        the checkpoint (and hence the shard's global checkpoint) stalls
        forever and every later recovery re-replays from the hole — or,
        once the source flushes those ops away, falls back to full copies
        for good. Reference: InternalEngine records NOOP operations for
        exactly this (Engine.NoOp)."""
        if seq_no is None:
            return
        with self._lock:
            self._note_op(term if term is not None else self.primary_term,
                          seq_no)

    def adopt_seq_state(self, term_seq: Dict[int, int], checkpoint: int,
                        term: int) -> None:
        """Full-copy recovery target: the source shipped its complete
        state, so adopt its checkpoint and per-term history. Entries for
        terms BELOW the source's current term are REPLACED, not merged —
        a diverged copy's phantom ops (a zombie write that advanced its
        old-term max past the source's) would otherwise poison
        ``term_at`` and fail the log-matching check on every future
        handshake. Current-term entries max-merge: live fanout ops racing
        the copy legitimately extend that term past the snapshot."""
        with self._lock:
            fresh = {int(t): m for t, m in (term_seq or {}).items()}
            for t, m in self._term_seq.items():
                if t >= term and m > fresh.get(t, NO_OPS_PERFORMED):
                    fresh[t] = m
            self._term_seq = fresh
            self.seq.advance_to(checkpoint)
            if term > self.primary_term:
                self.primary_term = term

    def recovery_ops(self, checkpoint: int,
                     last_term: Optional[int] = None) -> Optional[list]:
        """Recovery source: the translog op suffix above the target's
        ``checkpoint``, or None when ops-based replay is unsafe and the
        caller must fall back to a full copy. Unsafe means: the target is
        ahead of us (diverged zombie copy), the target's history doesn't
        match ours at its checkpoint (log-matching check — the op at the
        target's checkpoint must carry the term the target says it does),
        or the retained translog no longer covers the whole suffix
        (generations dropped by a flush commit)."""
        with self._lock:
            if checkpoint > self.seq.checkpoint:
                return None  # target claims ops we never assigned/diverged
            if checkpoint >= 0 and last_term is not None:
                t = self.term_at(checkpoint)
                if t is None or t != last_term:
                    return None  # diverged history: full copy required
            # coverage is judged against the max seq no AT THIS POINT;
            # the log scan below runs OUTSIDE the engine lock so a
            # recovery handshake never stalls client writes — ops that
            # land during the scan reach the target via live fanout
            # (phase-2 semantics), exactly like ops landing after the
            # snapshot would
            upper = self.seq.max_seq_no
        by_seq: Dict[int, dict] = {}
        try:
            for op in self.translog.ops_above(checkpoint):
                s = op["seq_no"]
                prev = by_seq.get(s)
                if prev is None or op.get("term", 0) >= prev.get("term", 0):
                    by_seq[s] = op
        except OSError:
            return None  # unreadable log: full copy
        need = range(checkpoint + 1, upper + 1)
        if any(s not in by_seq for s in need):
            return None  # retention gap (flushed away): full copy
        return [by_seq[s] for s in sorted(by_seq) if s <= upper]

    # -- tragic events -----------------------------------------------------------

    @property
    def is_failed(self) -> bool:
        return self.failed_reason is not None

    def fail(self, reason: str) -> None:
        """Fail the engine closed after a tragic event. Idempotent; the
        translog channel is already closed by its own tragic handler,
        but close again defensively for non-translog callers."""
        with self._lock:
            if self.failed_reason is not None:
                return
            self.failed_reason = reason
            try:
                self.translog.close()
            except OSError:
                pass  # the channel is what failed; state flag is what matters
        # flight recorder (outside the engine lock — R013): a tragic
        # engine event is exactly the evidence that dies with the
        # process; engines have no node back-ref, so fan process-wide
        try:
            from elasticsearch_tpu.monitor import flight

            flight.record("engine_failures", index=self.index_name,
                          reason=reason)
        except Exception:  # tpulint: allow[R006] — recording must never
            pass           # compound a tragic event

    def _ensure_open(self) -> None:
        if self.failed_reason is not None:
            raise EngineFailedException(self.index_name, self.failed_reason)

    def _translog_append(self, entry: dict) -> None:
        """Append with tragic-event semantics: an IO/fsync failure fails
        the engine CLOSED and the triggering op is NOT acknowledged —
        so the set of acknowledged ops is exactly the set replay can
        reproduce (no silently-lost writes). The op's in-memory mutation
        is NOT rolled back (segment live-masks can't un-delete), so reads
        may see it until restart — a documented deviation from the
        reference, which closes reads too (docs/ROBUSTNESS.md)."""
        try:
            self.translog.append(entry)
        except OSError as e:
            self.fail(f"translog append failed: {e}")
            raise EngineFailedException(
                self.index_name, f"translog append failed: {e}") from e

    # -- write path ------------------------------------------------------------

    def index(
        self,
        doc_id: Optional[str],
        source: dict,
        version: Optional[int] = None,
        version_type: str = "internal",
        op_type: str = "index",
        routing: Optional[str] = None,
        doc_type: Optional[str] = None,
        parent: Optional[str] = None,
        timestamp: Optional[object] = None,
        ttl: Optional[object] = None,
        ttl_expiry: Optional[int] = None,
        seq_no: Optional[int] = None,
        primary_term: Optional[int] = None,
        _replay: bool = False,
        _history: bool = False,
    ) -> Tuple[str, int, bool]:
        """Index/create a document. Returns (id, new_version, created).

        Version semantics mirror InternalEngine.index: internal versioning
        requires the provided version to equal the current one; external
        requires it to be strictly greater. op_type=create fails if the doc
        exists (DocWriteRequest.OpType.CREATE).

        seq_no/primary_term: None on the primary (a fresh seq no is
        assigned under the engine's current term); replicas, translog
        replay, and recovery streams pass the primary-assigned identity
        through — and an op from a stale term is rejected with
        StalePrimaryException before any state mutates.
        """
        t0 = time.perf_counter()
        with self._lock:
            self._ensure_open()
            op_term = self._fence_term(primary_term, history=_history)
            if doc_id is None:
                self._auto_id += 1
                doc_id = f"auto_{self._auto_id}_{int(time.time() * 1000)}"
            doc_id = str(doc_id)
            loc = self._locations.get(doc_id)
            current = loc.version if (loc and not loc.deleted) else 0
            exists = loc is not None and not loc.deleted
            if op_type == "create" and exists:
                raise VersionConflictException(self.index_name, doc_id,
                                               current, 0)
            if version is not None:
                if version_type == "force":
                    # force: set the version unconditionally (reference:
                    # VersionType.FORCE, 2.0-era repair tool semantics)
                    new_version = version
                elif version_type in ("external", "external_gt", "external_gte"):
                    ok = (loc is None or version > loc.version
                          or (version_type == "external_gte" and version >= loc.version))
                    if not ok:
                        raise VersionConflictException("", doc_id, loc.version, version)
                    new_version = version
                else:
                    if current != version:
                        raise VersionConflictException("", doc_id, current, version)
                    new_version = current + 1
            else:
                new_version = (loc.version if loc else 0) + 1

            parsed = self.parser.parse(doc_id, source, routing=routing,
                                       doc_type=doc_type, parent=parent,
                                       timestamp=timestamp, ttl=ttl,
                                       ttl_expiry=ttl_expiry)
            # seq no assignment AFTER validation: a rejected op must not
            # consume a number (we keep the primary's stream contiguous
            # instead of logging no-ops for failures)
            if seq_no is None:
                seq_no = self.seq.generate()
            self._remove_existing(doc_id)
            local = self.buffer.add(parsed)
            self._buffer_ids[doc_id] = local
            self._locations[doc_id] = DocLocation(
                version=new_version, deleted=False, where="buffer", local_id=local,
                source=source, doc_type=doc_type, parent=parent, routing=routing,
                timestamp=parsed.meta.get("timestamp"),
                ttl_expiry=parsed.meta.get("ttl_expiry"),
                seq_no=seq_no, term=op_term,
            )
            if not _replay:
                entry = {"op": "index", "id": doc_id, "source": source,
                         "version": new_version, "routing": routing,
                         "seq_no": seq_no, "term": op_term}
                if doc_type:
                    entry["doc_type"] = doc_type
                if parent:
                    entry["parent"] = parent
                # resolved meta-field values: replay must reproduce them
                # exactly (re-resolving "now" later would drift)
                if "timestamp" in parsed.meta:
                    entry["timestamp"] = parsed.meta["timestamp"]
                if "ttl_expiry" in parsed.meta:
                    entry["ttl_expiry"] = parsed.meta["ttl_expiry"]
                self._translog_append(entry)
            # checkpoint advances only once durability settled: a tragic
            # append raised above and this op stays un-processed
            self._note_op(op_term, seq_no)
            self.stats.index_total += 1
            self.stats.on_type(doc_type, "index_total")
            self.stats.index_time_ms += (time.perf_counter() - t0) * 1000
            return doc_id, new_version, not exists

    def delete(self, doc_id: str, version: Optional[int] = None,
               version_type: str = "internal",
               seq_no: Optional[int] = None,
               primary_term: Optional[int] = None,
               _replay: bool = False,
               _history: bool = False) -> int:
        with self._lock:
            self._ensure_open()
            op_term = self._fence_term(primary_term, history=_history)
            doc_id = str(doc_id)
            loc = self._locations.get(doc_id)
            if loc is None or loc.deleted:
                raise DocumentMissingException("", doc_id)
            if version is not None:
                if version_type == "internal" and loc.version != version:
                    raise VersionConflictException("", doc_id, loc.version,
                                                   version)
                if version_type in ("external", "external_gt") \
                        and version <= loc.version:
                    raise VersionConflictException("", doc_id, loc.version,
                                                   version)
                if version_type == "external_gte" and version < loc.version:
                    raise VersionConflictException("", doc_id, loc.version,
                                                   version)
            if seq_no is None:
                seq_no = self.seq.generate()
            self._remove_existing(doc_id)
            if version is not None and version_type in (
                    "external", "external_gt", "external_gte", "force"):
                new_version = version  # external deletes stamp the version
            else:
                new_version = loc.version + 1
            self._locations[doc_id] = DocLocation(
                version=new_version, deleted=True, where=None,
                seq_no=seq_no, term=op_term)
            if not _replay:
                self._translog_append({"op": "delete", "id": doc_id,
                                       "version": new_version,
                                       "seq_no": seq_no, "term": op_term})
            self._note_op(op_term, seq_no)
            self.stats.delete_total += 1
            self.stats.on_type(loc.doc_type, "delete_total")
            return new_version

    def update(self, doc_id: str, partial: Optional[dict] = None,
               script: Optional[str] = None, script_params: Optional[dict] = None,
               upsert: Optional[dict] = None, doc_as_upsert: bool = False,
               scripted_upsert: bool = False,
               doc_type: Optional[str] = None, routing: Optional[str] = None,
               parent: Optional[str] = None, version: Optional[int] = None,
               version_type: str = "internal",
               timestamp: Optional[object] = None,
               ttl: Optional[object] = None,
               primary_term: Optional[int] = None) -> Tuple[int, bool]:
        """Partial update (RestUpdateAction semantics): merge `partial` into
        the current source, or create from `upsert` when missing. Only
        internal versioning applies (reference: UpdateRequest.validate
        rejects external version types)."""
        if version is not None and version_type not in ("internal",):
            from elasticsearch_tpu.utils.errors import \
                ActionRequestValidationException

            raise ActionRequestValidationException(
                f"version type [{version_type}] is not supported by the "
                f"update API")
        with self._lock:
            doc_id = str(doc_id)
            got = self.get(doc_id)
            if got is None:
                if version is not None:
                    # versioned update on a missing doc is a conflict, even
                    # with an upsert (TransportUpdateAction)
                    raise VersionConflictException("", doc_id, -1, version)
                if upsert is not None:
                    up = dict(upsert)
                    if scripted_upsert and script is not None:
                        # scripted_upsert: the script transforms the upsert
                        # doc before the insert (UpdateHelper.prepare)
                        up = self._run_update_script(
                            script, script_params or {}, up)
                    _, v, _ = self.index(doc_id, up, doc_type=doc_type,
                                         routing=routing, parent=parent,
                                         timestamp=timestamp, ttl=ttl,
                                         primary_term=primary_term)
                    return v, True
                if doc_as_upsert and partial is not None:
                    _, v, _ = self.index(doc_id, partial, doc_type=doc_type,
                                         routing=routing, parent=parent,
                                         timestamp=timestamp, ttl=ttl,
                                         primary_term=primary_term)
                    return v, True
                raise DocumentMissingException("", doc_id)
            if version is not None and got["_version"] != version:
                raise VersionConflictException("", doc_id, got["_version"],
                                               version)
            source = dict(got["_source"])
            if script is not None:
                source = self._run_update_script(script, script_params or {}, source)
            elif partial is not None:
                _deep_merge(source, partial)
            # carry _type/_parent/routing through the re-index, else a
            # partial update would sever the parent-child join
            loc = self._locations.get(doc_id)
            _, v, _ = self.index(
                doc_id, source,
                routing=(loc.routing if loc and loc.routing else routing),
                doc_type=loc.doc_type if loc else doc_type,
                parent=(loc.parent if loc and loc.parent else parent),
                timestamp=timestamp, ttl=ttl,
                primary_term=primary_term,
            )
            return v, False

    def _run_update_script(self, script: str, params: dict, source: dict) -> dict:
        """Update scripts mutate ctx._source; painless-lite is expression-only,
        so we support the common `ctx._source.<field> = <expr>` statement list.
        Groovy binds params as BARE variables (`ctx._source.foo = bar` with
        params {bar: ...}) — the expression compiler binds them directly
        (AST-level, so string literals equal to a param name are never
        touched)."""
        from elasticsearch_tpu.search.scripting import compile_script
        from elasticsearch_tpu.utils.errors import ScriptException

        reserved = {"doc", "params", "Math", "ctx", "_score", "_source",
                    "true", "false", "null"}
        extra = tuple(pn for pn in (params or {})
                      if pn.isidentifier() and pn not in reserved)
        for stmt in script.split(";"):
            stmt = stmt.strip()
            if not stmt:
                continue
            if "=" in stmt and "==" not in stmt.split("=", 1)[0]:
                lhs, _, rhs = stmt.partition("=")
                lhs = lhs.strip()
                prefix = "ctx._source."
                if not lhs.startswith(prefix):
                    raise ScriptException(f"update script must assign ctx._source.*: [{stmt}]")
                field = lhs[len(prefix):]
                rhs = rhs.strip()
                for fname, fval in source.items():
                    rhs = rhs.replace(f"ctx._source.{fname}", repr(fval))
                cs = compile_script(rhs, extra_vars=extra)
                val = cs.run(lambda f: None, params=params)
                if hasattr(val, "item"):
                    val = val.item()
                source[field] = val
            else:
                raise ScriptException(f"unsupported update script statement [{stmt}]")
        return source

    def _remove_existing(self, doc_id: str):
        loc = self._locations.get(doc_id)
        if loc is None or loc.deleted:
            return
        if loc.where == "buffer":
            # mark the buffered doc dead; freeze() skips tombstoned entries
            idx = self._buffer_ids.pop(doc_id, None)
            if idx is not None:
                self.buffer.docs[idx] = None  # type: ignore[assignment]
        else:
            for seg in self.segments:
                if seg.seg_id == loc.where:
                    seg.delete_local(loc.local_id)
                    break

    # -- read path -------------------------------------------------------------

    def get(self, doc_id: str, realtime: bool = True) -> Optional[dict]:
        """Realtime get: buffered docs are visible before refresh (ES serves
        these from the translog; we keep the source on the DocLocation)."""
        with self._lock:
            self.stats.get_total += 1
            doc_id = str(doc_id)
            loc = self._locations.get(doc_id)
            if loc is None or loc.deleted:
                return None
            if loc.where == "buffer":
                if not realtime:
                    return None
                return {"_id": doc_id, "_type": loc.doc_type or "_doc",
                        "_version": loc.version, "_source": loc.source,
                        "found": True}
            for seg in self.segments:
                if seg.seg_id == loc.where:
                    return {
                        "_id": doc_id,
                        "_type": loc.doc_type or "_doc",
                        "_version": loc.version,
                        "_source": seg.sources[loc.local_id],
                        "found": True,
                    }
            return None

    def exists(self, doc_id: str) -> bool:
        loc = self._locations.get(str(doc_id))
        return loc is not None and not loc.deleted

    @property
    def num_docs(self) -> int:
        with self._lock:
            return sum(1 for l in self._locations.values() if not l.deleted)

    # -- lifecycle -------------------------------------------------------------

    def purge_expired(self) -> int:
        """Delete docs whose _ttl expiry has passed (reference: indices/ttl/
        IndicesTTLService.java — the TTL purger; here it runs on refresh and
        merge). Expiry columns scan vectorized; deletes go through the
        normal tombstone path so versions/translog stay consistent."""
        if not getattr(self.mappings, "_ttl_enabled", False) \
                or self.failed_reason is not None:
            return 0  # a failed engine accepts no deletes (reads still serve)
        import numpy as np

        now = int(time.time() * 1000)
        expired: List[str] = []
        with self._lock:
            for seg in self.segments:
                col = seg.numerics.get("_ttl")
                if col is None or col.exact is None:
                    continue
                n = seg.num_docs
                hit = np.nonzero(seg.live_host[:n]
                                 & np.asarray(col.exists)[:n]
                                 & (col.exact[:n] < now))[0]
                expired.extend(seg.ids[int(i)] for i in hit)
            for d in self.buffer.docs:
                if (d is not None and d.doc_values.get("_ttl")
                        and d.doc_values["_ttl"][0] < now):
                    expired.append(d.doc_id)
            for doc_id in expired:
                try:
                    self.delete(doc_id)
                except DocumentMissingException:
                    pass
        return len(expired)

    def refresh(self) -> bool:
        """Freeze the buffer into a new searchable segment (NRT refresh)."""
        with self._lock:
            self.purge_expired()
            # roots only: tombstoned roots leave orphan children in the
            # buffer arrays; re-adding a root re-emits its block
            live_docs = [d for d, p in zip(self.buffer.docs, self.buffer.parent_of)
                         if d is not None and p == -1]
            if not live_docs:
                return False
            # refresh failure is RETRYABLE, not tragic: the buffer keeps
            # the docs and a later refresh serves them (unlike a translog
            # failure, nothing acknowledged is at risk)
            FAULTS.check("segment.freeze", index=self.index_name)
            fresh = SegmentBuilder(self.mappings, self.device)
            for d in live_docs:
                fresh.add(d)
            seg = fresh.freeze()
            try:
                self._charge_segment(seg)
            except Exception:
                # reclaim before giving up: merging away deleted docs is the
                # one path that frees breaker budget, and it would otherwise
                # be unreachable (maybe_merge only runs after a SUCCESSFUL
                # refresh) — a tripped breaker must not wedge forever
                self.maybe_merge()
                self._charge_segment(seg)
            self.segments.append(seg)
            for doc_id, local in list(seg.id_map.items()):
                loc = self._locations.get(doc_id)
                if loc is not None and loc.where == "buffer":
                    loc.where = seg.seg_id
                    loc.local_id = local
                    loc.source = None
            self.buffer = SegmentBuilder(self.mappings, self.device)
            self._buffer_ids.clear()
            self.stats.refresh_total += 1
            self.maybe_merge()
            return True

    def flush(self):
        """refresh + translog commit (durability handed to segments).

        NOTE: segments live in device/host memory; true on-disk segment
        persistence is the snapshot API's job (index/snapshots.py). Flush
        semantics here = translog generation rollover after refresh, same
        contract as InternalEngine.flush."""
        with self._lock:
            self.refresh()
            try:
                self.translog.commit()
            except OSError as e:
                # commit fsyncs before dropping generations — a failure
                # here is as tragic as a failed append
                self.fail(f"translog commit failed: {e}")
                raise EngineFailedException(
                    self.index_name, f"translog commit failed: {e}") from e
            self.stats.flush_total += 1

    def merge(self, max_segments: Optional[int] = None,
              subset: Optional[List[TpuSegment]] = None):
        """Merge segments by re-indexing live docs' source through the
        parser. With ``subset``: a policy-selected partial merge (tiered);
        without: force-merge everything down to one segment (optimize)."""
        with self._lock:
            self.purge_expired()
            if subset is None and len(self.segments) <= (max_segments or 1):
                return
            targets = subset if subset is not None else list(self.segments)
            target_ids = {s.seg_id for s in targets}
            builder = SegmentBuilder(self.mappings, self.device)
            from elasticsearch_tpu.tracing import check_cancelled

            for seg in targets:
                # cooperative cancellation between source segments: a
                # cancelled force-merge task (POST /_optimize) aborts
                # before the freeze — nothing committed, nothing lost
                check_cancelled()
                live = seg.live_host
                roots = seg.roots_host
                for local, doc_id in enumerate(seg.ids):
                    if live[local] and (roots is None or roots[local]):
                        meta = seg.metas[local] if local < len(seg.metas) else {}
                        builder.add(self.parser.parse(
                            doc_id, seg.sources[local],
                            routing=meta.get("routing"),
                            doc_type=meta.get("_type"), parent=meta.get("_parent"),
                            timestamp=meta.get("timestamp"),
                            ttl_expiry=meta.get("ttl_expiry")))
            merged = builder.freeze()
            keep = [s for s in self.segments if s.seg_id not in target_ids]
            # release-then-charge: a merge nets memory DOWN, so it charges
            # unconditionally (force) — only NEW data (refresh) can trip
            # the breaker
            from elasticsearch_tpu.index.segment import SEGMENT_HBM_BUDGET

            for s in targets:
                SEGMENT_HBM_BUDGET.release(getattr(s, "_hbm_charged", 0))
                s._hbm_charged = 0
            if merged is not None:
                merged._hbm_charged = merged.memory_bytes()
                SEGMENT_HBM_BUDGET.force(merged._hbm_charged)
                keep.append(merged)
                for doc_id, local in merged.id_map.items():
                    loc = self._locations.get(doc_id)
                    if loc is not None and not loc.deleted:
                        loc.where = merged.seg_id
                        loc.local_id = local
            self.segments[:] = keep  # in place: searchers share this list
            self.stats.merge_total += 1

    def maybe_merge(self):
        """Background-style merge check (reference: InternalEngine's
        maybeMerge via EsConcurrentMergeScheduler — synchronous here)."""
        with self._lock:
            found = self.merge_policy.find_merge(self.segments)
            if found and len(found) >= 1:
                self.merge(subset=found)

    def recover_from_translog(self) -> int:
        """Replay the translog (crash recovery / shard recovery). Frames
        carry (term, seq_no), so replay restores the seq-no tracker, the
        per-term history, AND the primary term itself — a term bump
        survives engine close/reopen. Returns ops replayed."""
        from elasticsearch_tpu.index.seqno import UNASSIGNED_SEQ_NO

        replayed = 0
        max_term = 0
        with self._lock:
            for op in self.translog.replay():
                max_term = max(max_term, op.get("term", 0))
                # legacy (pre-seqno) frames stay UNASSIGNED: minting a
                # fresh number here would fabricate checkpoint/term
                # history the primary never assigned, and a later
                # log-matching handshake could falsely pass on it
                seq = op.get("seq_no", UNASSIGNED_SEQ_NO)
                seq = UNASSIGNED_SEQ_NO if seq is None else seq
                if op["op"] == "index":
                    self.index(op["id"], op["source"], routing=op.get("routing"),
                               doc_type=op.get("doc_type"), parent=op.get("parent"),
                               timestamp=op.get("timestamp"),
                               ttl_expiry=op.get("ttl_expiry"),
                               seq_no=seq,
                               primary_term=op.get("term"),
                               _replay=True, _history=True)
                    self._locations[op["id"]].version = op["version"]
                    replayed += 1
                elif op["op"] == "delete":
                    try:
                        self.delete(op["id"], seq_no=seq,
                                    primary_term=op.get("term"),
                                    _replay=True, _history=True)
                        replayed += 1
                    except DocumentMissingException:
                        pass
            # the highest term in the log IS this copy's term: a bump
            # survives close/reopen
            self.bump_term(max_term)
        return replayed

    def apply_translog_op(self, op: dict) -> None:
        """Apply ONE foreign translog op (the ops-based peer-recovery
        stream): the op's own version rides external_gte so a newer state
        already on this copy (a racing live-fanout write) wins, and its
        (term, seq_no) identity is preserved. Raises VersionConflict /
        DocumentMissing for the caller to count as already-newer skips."""
        if op["op"] == "delete":
            self.delete(op["id"], version=op.get("version"),
                        version_type="external_gte" if op.get("version")
                        is not None else "internal",
                        seq_no=op.get("seq_no"), primary_term=op.get("term"),
                        _replay=True, _history=True)
            return
        self.index(op["id"], op["source"], version=op.get("version"),
                   version_type="external_gte" if op.get("version")
                   is not None else "internal",
                   routing=op.get("routing"), doc_type=op.get("doc_type"),
                   parent=op.get("parent"), timestamp=op.get("timestamp"),
                   ttl_expiry=op.get("ttl_expiry"),
                   seq_no=op.get("seq_no"), primary_term=op.get("term"),
                   _replay=True, _history=True)

    def _charge_segment(self, seg) -> None:
        """Charge a fresh segment against the node HBM breaker; raises
        CircuitBreakingException (429) when the budget would be exceeded —
        the refresh fails, buffered docs stay buffered, the node survives."""
        from elasticsearch_tpu.index.segment import SEGMENT_HBM_BUDGET
        from elasticsearch_tpu.utils.errors import CircuitBreakingException

        n = seg.memory_bytes()
        if not SEGMENT_HBM_BUDGET.reserve(n):
            raise CircuitBreakingException(
                f"[segments] data for new segment would be "
                f"[{SEGMENT_HBM_BUDGET.used + n}/{SEGMENT_HBM_BUDGET.total}]"
                f" bytes, which is larger than the limit")
        seg._hbm_charged = n

    def close(self):
        from elasticsearch_tpu.index.segment import SEGMENT_HBM_BUDGET

        for seg in self.segments:
            SEGMENT_HBM_BUDGET.release(getattr(seg, "_hbm_charged", 0))
            seg._hbm_charged = 0
        self.translog.close()


def _deep_merge(dst: dict, src: dict):
    for k, v in src.items():
        if isinstance(v, dict) and isinstance(dst.get(k), dict):
            _deep_merge(dst[k], v)
        else:
            dst[k] = v
