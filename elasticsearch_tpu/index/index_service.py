"""IndexService: one index = mappings + analysis + N shards + routing.

Reference: org/elasticsearch/index/IndexService.java plus the doc-routing
math of org/elasticsearch/cluster/routing/OperationRouting.java
(shard = murmur3(routing ?: id) % number_of_shards).
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

from elasticsearch_tpu.analysis.registry import AnalysisRegistry
from elasticsearch_tpu.index.mappings import Mappings
from elasticsearch_tpu.index.shard import IndexShard
from elasticsearch_tpu.search.context import GlobalStats
from elasticsearch_tpu.search.service import search_shards
from elasticsearch_tpu.utils.errors import DocumentMissingException


class IndexService:
    def __init__(
        self,
        name: str,
        settings: Optional[dict] = None,
        mappings_json: Optional[dict] = None,
        data_path: Optional[str] = None,
        validate_analysis: bool = True,
    ):
        """``validate_analysis=False`` skips the eager analysis-config
        build — gateway recovery uses it so a pre-validation on-disk index
        with a broken-but-unused component still re-opens (its analyzers
        stay lazy, the pre-r5 behavior) instead of silently vanishing."""
        self.name = name
        self.settings = settings or {}
        idx_settings = self.settings.get("index", self.settings)
        self.num_shards = int(idx_settings.get("number_of_shards", 1))
        self.num_replicas = int(idx_settings.get("number_of_replicas", 0))
        # multi-host: replicas are CROSS-HOST copies owned by other
        # processes; the internal _local_replicas=0 marker keeps this
        # process from ALSO materializing in-process replica groups while
        # num_replicas (settings echo, _shards math, cat columns) still
        # reports the declared count. Popped so it never leaks into the
        # settings echo.
        _local = idx_settings.pop("_local_replicas", None)
        self.local_replicas = (int(_local) if _local is not None
                               else self.num_replicas)
        self.analysis = AnalysisRegistry(self.settings)
        self.mappings = Mappings(mappings_json or {})
        self._validate_analyzers(self.mappings,
                                 eager_components=validate_analysis)
        self.aliases: Dict[str, dict] = {}
        self.data_path = data_path
        # recovery execution record feeding GET {index}/_recovery and
        # _cat/recovery (index/recovery.py::RecoveryRegistry) — created
        # before the shards so gateway recovery in __init__ can record
        from elasticsearch_tpu.index.recovery import RecoveryRegistry

        self.recoveries = RecoveryRegistry()
        self._device_table = None  # shard_device(): built on first ask
        self.shards: List[IndexShard] = [
            IndexShard(name, i, self.mappings, self.analysis, data_path,
                       device=self.shard_device(i))
            for i in range(self.num_shards)
        ]
        # replica copies + replication groups (reference: primary→replica
        # sync fanout in TransportShardReplicationOperationAction). Replicas
        # carry no translog — they re-sync from the primary via peer
        # recovery on open (recovery.recover_peer).
        from elasticsearch_tpu.cluster.replication import ReplicationGroup

        self.groups: List[ReplicationGroup] = []
        for i, primary in enumerate(self.shards):
            replicas = [IndexShard(name, i, self.mappings, self.analysis, None,
                                   device=self.shard_device(i, r + 1))
                        for r in range(self.local_replicas)]
            self.groups.append(ReplicationGroup(i, primary, replicas))
        self.closed = False
        self._percolator = None
        self._mesh_executor = None
        # shard query cache (reference: indices/cache/query/
        # IndicesQueryCache.java — opt-in via index.cache.query.enable,
        # size==0 requests only, keyed by reader identity + request body;
        # our "reader version" is the per-shard write/refresh counters,
        # which also capture instantly-visible deletes)
        from collections import OrderedDict as _OD
        import threading as _th

        self._query_cache: "_OD[tuple, dict]" = _OD()
        self._qc_lock = _th.Lock()  # ThreadingHTTPServer: searches race
        self.query_cache_stats = {"hits": 0, "misses": 0, "evictions": 0}
        self.warmers: Dict[str, dict] = {}
        # search/indexing slow logs (tracing/slowlog.py): thresholds read
        # from the LIVE settings each record, so dynamic updates through
        # update_index_settings apply immediately
        from elasticsearch_tpu.tracing.slowlog import IndexSlowLog

        self.slowlog = IndexSlowLog(name, lambda: self.settings)
        if data_path:
            # gateway recovery (reference: gateway/GatewayService +
            # IndexShardGateway): replay any existing translog on open
            self.recover()

    def shard_device(self, shard_id: int, replica: int = 0):
        """The chip a copy of a shard lives on — ``placement.allocate``'s
        table over this host's devices, asked here and nowhere else — or
        None (the default device, today's arrays) for a one-shard index
        and on a one-device host. Everything the copy's segments place,
        at freeze and lazily, goes there (``TpuSegment.device``)."""
        if self.num_shards < 2:
            return None
        table = self._device_table
        if table is None:
            import jax

            from elasticsearch_tpu.parallel.placement import (
                allocate, placement_table)

            devices = jax.devices()
            table = {} if len(devices) < 2 else {
                key[1:]: devices[ordinal]
                for key, ordinal in placement_table(allocate(
                    self.name, self.num_shards, self.local_replicas,
                    len(devices))).items()}
            self._device_table = table
        return table.get((shard_id, replica))

    def fail_shard(self, shard_id: int):
        """Primary failure → promote a replica (reference: shard failed →
        allocation promotes an in-sync copy; exposed for failure-injection
        tests and the future multi-host fault detector)."""
        group = self.groups[shard_id]
        new_primary = group.fail_primary()
        self.shards[shard_id] = new_primary
        return new_primary

    def recover(self):
        from elasticsearch_tpu.index.recovery import recover_peer
        from elasticsearch_tpu.search.percolator import PERCOLATOR_TYPE

        for shard in self.shards:
            entry = self.recoveries.start(shard.shard_id, "gateway")
            try:
                entry["stage"] = "translog"
                entry["ops_replayed"] = shard.recover()
                self.recoveries.finish(entry)
            except Exception:
                # a failed replay (chaos fault, tragic translog) must not
                # leave a ghost in-flight entry in ?active_only/gauges
                self.recoveries.finish(entry, ok=False)
                raise
        # replicas re-sync from the recovered primary (peer recovery)
        for group in self.groups:
            for replica in group.replicas:
                entry = self.recoveries.start(group.shard_id, "replica")
                try:
                    recover_peer(group.primary.engine, replica.engine,
                                 entry)
                    self.recoveries.finish(entry)
                except Exception:
                    self.recoveries.finish(entry, ok=False)
                    raise
        for shard in self.shards:
            # rebuild the in-memory percolator registry from recovered docs
            for doc_id, loc in shard.engine._locations.items():
                if loc.deleted or loc.doc_type != PERCOLATOR_TYPE:
                    continue
                got = shard.engine.get(doc_id)
                if got and got.get("_source"):
                    try:
                        self.percolator.register(doc_id, got["_source"])
                    except Exception:
                        # a legacy/corrupt percolator doc must not brick the
                        # whole index on open; it just doesn't participate
                        pass

    def _validate_analyzers(self, mappings: Mappings,
                            eager_components: bool = True):
        """Reject mappings naming analyzers the registry can't build —
        reference: MapperService fails index creation on unknown analyzers."""
        from elasticsearch_tpu.utils.errors import (IllegalArgumentException,
                                                    MapperParsingException)

        if eager_components:
            try:
                # every DECLARED analyzer must build, referenced or not
                # (reference: AnalysisService constructs all configured
                # analyzers; a broken settings.analysis fails the creation).
                # KeyError/TypeError cover malformed shared definitions (a
                # tokenizer entry missing "type", non-dict config values).
                self.analysis.validate()
            except (ValueError, KeyError, TypeError) as e:
                raise IllegalArgumentException(
                    f"failed to build analysis components: {e}") from e
        for name, fm in mappings.fields.items():
            if not getattr(fm, "is_text", False):
                continue
            for an in (fm.analyzer, fm.search_analyzer):
                if an is None:
                    continue
                try:
                    self.analysis.get(an)
                except ValueError as e:
                    raise MapperParsingException(
                        f"analyzer [{an}] not found for field [{name}]") from e

    # -- routing ---------------------------------------------------------------

    def route(self, doc_id: str, routing: Optional[str] = None) -> IndexShard:
        from elasticsearch_tpu.cluster.routing import shard_id_for

        return self.shards[shard_id_for(doc_id, self.num_shards, routing)]

    def group_for(self, doc_id: str, routing: Optional[str] = None):
        from elasticsearch_tpu.cluster.routing import shard_id_for

        return self.groups[shard_id_for(doc_id, self.num_shards, routing)]

    def _record_write_metric(self, op: str, seconds: float) -> None:
        """Write-path latency + op counters into the owning node's
        metrics registry (monitor/metrics.py). Library-embedded
        IndexServices have no node — then nothing records; the
        per-request numbers still exist in engine stats."""
        node = getattr(self, "_node", None)
        if node is None:
            return
        try:
            m = node.metrics
            m.histogram(
                "estpu_indexing_duration_seconds",
                "Write operation latency (engine + replication fanout)",
                ("op",)).labels(op).observe(seconds)
            m.counter(
                "estpu_indexing_operations_total",
                "Write operations by type", ("op",)).labels(op).inc()
        except Exception:  # tpulint: allow[R006] — a metrics failure
            pass           # must never fail the acked write

    # -- document ops ----------------------------------------------------------

    def index_doc(self, doc_id: Optional[str], source: dict, routing: Optional[str] = None,
                  **kw) -> dict:
        if doc_id is None:
            # auto-id: route after generation
            import uuid

            doc_id = uuid.uuid4().hex[:20]
        from elasticsearch_tpu.cluster.metadata import check_open

        check_open(self)
        self._check_routing_required(doc_id, kw.get("doc_type"),
                                     routing or kw.get("parent"))
        group = self.group_for(doc_id, routing)
        from elasticsearch_tpu.search.percolator import PERCOLATOR_TYPE

        is_perc = kw.get("doc_type") == PERCOLATOR_TYPE
        if is_perc:
            # validate BEFORE persisting: an unparseable percolator query
            # must never reach the translog (it would poison recovery)
            self.percolator.validate(source)
        t0 = time.perf_counter()
        rid, version, created, failed, seq_no, term = group.index(
            doc_id, source, routing=routing, **kw)
        if is_perc:
            self.percolator.register(rid, source)
        dt = time.perf_counter() - t0
        self.slowlog.on_index(dt * 1000, rid)
        self._record_write_metric("index", dt)
        return {
            "_index": self.name,
            "_type": kw.get("doc_type") or "_doc",
            "_id": rid,
            "_version": version,
            "_seq_no": seq_no,
            "_primary_term": term,
            "result": "created" if created else "updated",
            "created": created,
            "_shards": {"total": 1 + self.num_replicas,
                        "successful": 1 + len(group.replicas),
                        "failed": failed},
        }

    def _check_routing_required(self, doc_id, doc_type, routing) -> None:
        """Reference: MappingMetaData.routing().required() +
        `_parent` mappings make routing mandatory for that type."""
        if routing is not None:
            return
        from elasticsearch_tpu.utils.errors import RoutingMissingException

        if self.mappings.routing_required:
            raise RoutingMissingException(self.name, doc_type or "_doc",
                                          str(doc_id))
        if doc_type and doc_type in self.mappings.parent_types:
            raise RoutingMissingException(self.name, doc_type, str(doc_id))

    def get_doc(self, doc_id: str, routing: Optional[str] = None,
                realtime: bool = True, with_meta: bool = False) -> dict:
        from elasticsearch_tpu.cluster.metadata import check_open

        check_open(self, op="read")
        shard = self.route(doc_id, routing)
        got = shard.engine.get(doc_id, realtime=realtime)
        if got is None:
            return {"_index": self.name, "_type": "_doc", "_id": doc_id,
                    "found": False}
        got["_index"] = self.name
        if with_meta:
            # location meta rides the response for CROSS-HOST reads: the
            # coordinator's fields/_routing etc. extraction can't reach a
            # remote shard's location table
            loc = shard.engine._locations.get(str(doc_id))
            if loc is not None:
                got["_meta"] = {"routing": loc.routing,
                                "parent": loc.parent,
                                "timestamp": loc.timestamp,
                                "ttl_expiry": loc.ttl_expiry}
        return got

    def delete_doc(self, doc_id: str, routing: Optional[str] = None, **kw) -> dict:
        from elasticsearch_tpu.cluster.metadata import check_open

        check_open(self)
        group = self.group_for(doc_id, routing)
        loc = self.route(doc_id, routing).engine._locations.get(str(doc_id))
        dtype = (loc.doc_type if loc is not None and loc.doc_type
                 else "_doc")
        t0 = time.perf_counter()
        version, _failed, seq_no, term = group.delete(doc_id, **kw)
        self._record_write_metric("delete", time.perf_counter() - t0)
        if self._percolator is not None:
            self._percolator.unregister(str(doc_id))
        return {
            "_index": self.name,
            "_type": dtype,
            "_id": doc_id,
            "_version": version,
            "_seq_no": seq_no,
            "_primary_term": term,
            "result": "deleted",
            "found": True,
            "_shards": {"total": 1 + self.num_replicas,
                        "successful": 1 + len(group.replicas),
                        "failed": 0},
        }

    def update_doc(self, doc_id: str, body: dict, routing: Optional[str] = None,
                   doc_type: Optional[str] = None, **kw) -> dict:
        from elasticsearch_tpu.cluster.metadata import check_open

        check_open(self)
        shard = self.route(doc_id, routing)
        # percolator docs: validate the would-be merged query BEFORE the
        # engine persists anything, and re-register after (the plain index
        # path does the same; updates must not bypass it)
        from elasticsearch_tpu.search.percolator import PERCOLATOR_TYPE

        loc = shard.engine._locations.get(str(doc_id))
        is_perc = loc is not None and not loc.deleted and loc.doc_type == PERCOLATOR_TYPE
        if is_perc:
            if body.get("script") is not None:
                from elasticsearch_tpu.utils.errors import IllegalArgumentException

                raise IllegalArgumentException(
                    "percolator documents cannot be script-updated")
            from elasticsearch_tpu.index.engine import _deep_merge

            cur = shard.engine.get(str(doc_id))
            merged = dict(cur["_source"]) if cur else {}
            _deep_merge(merged, body.get("doc") or {})
            self.percolator.validate(merged)
        script = body.get("script")
        script_src, params = None, None
        if script is not None:
            from elasticsearch_tpu.search.scripting import script_source
            from elasticsearch_tpu.utils.errors import IllegalArgumentException

            lang = ((script.get("lang") if isinstance(script, dict) else None)
                    or body.get("lang") or "groovy")
            if lang not in ("groovy", "painless", "painless-lite",
                            "expression"):
                raise IllegalArgumentException(
                    f"script_lang not supported [{lang}]")
            script_src = script_source(script)
            if isinstance(script, dict):
                params = script.get("params")
            else:
                # 2.0-era form: a string script with SIBLING body params
                # ({"script": "...", "params": {...}, "lang": "groovy"})
                params = body.get("params")
        version, created = shard.engine.update(
            doc_id,
            partial=body.get("doc"),
            script=script_src,
            script_params=params,
            upsert=body.get("upsert"),
            doc_as_upsert=bool(body.get("doc_as_upsert", False)),
            scripted_upsert=bool(body.get("scripted_upsert", False)),
            doc_type=doc_type,
            routing=routing,
            **kw,
        )
        group = self.group_for(doc_id, routing)
        group.replicate_current(str(doc_id))
        if is_perc:
            got = shard.engine.get(str(doc_id))
            if got and got.get("_source"):
                self.percolator.register(str(doc_id), got["_source"])
        loc2 = shard.engine._locations.get(str(doc_id))
        return {
            "_index": self.name,
            "_type": (loc2.doc_type if loc2 is not None and loc2.doc_type
                      else "_doc"),
            "_id": doc_id,
            "_version": version,
            "result": "created" if created else "updated",
            "_shards": {"total": 1 + self.num_replicas,
                        "successful": 1 + len(group.replicas),
                        "failed": 0},
        }

    def mget(self, ids: List[str]) -> dict:
        return {"docs": [self.get_doc(i) for i in ids]}

    def find_doc_location(self, doc_id: str):
        """Locate a live doc's DocLocation without knowing its routing.

        By-query actions (delete/update-by-query) get ids back from search
        but not the custom routing the doc was indexed with; id-based
        routing would then target the wrong shard. Scan every shard's
        location table instead (reference: AbstractAsyncBulkByScrollAction
        carries each hit's routing through the scroll)."""
        locs = self.find_doc_locations(doc_id)
        return locs[0] if locs else None

    def find_doc_locations(self, doc_id: str) -> list:
        """All live copies of an id across shards — custom routing can place
        the same _id on several shards, and by-query actions must touch
        every copy, each with its own stored routing."""
        out = []
        for shard in self.shards:
            loc = shard.engine._locations.get(str(doc_id))
            if loc is not None and not loc.deleted:
                out.append(loc)
        return out

    # -- search ----------------------------------------------------------------

    def refresh(self):
        for g in self.groups:
            g.refresh()
        self._run_warmers()

    def _run_warmers(self):
        """Execute registered warmers against the fresh segments (reference:
        search/warmer + IndicesWarmer: warm new searchers on refresh). For a
        TPU segment 'warming' = triggering the XLA compile + building lazy
        acceleration structures (dense impact blocks) before user traffic."""
        for name, body in list(getattr(self, "warmers", {}).items()):
            try:
                # _search_inner: a warmer's whole point is pre-paying
                # compiles in the background — recording it through the
                # public wrapper would file deliberate warmer traffic
                # into estpu_search_duration_seconds{warmup="true"}, the
                # exact cold-start series it exists to empty
                self._search_inner(body or {"query": {"match_all": {}}})
            except Exception:
                pass  # a broken warmer must never fail the refresh

    def flush(self):
        for s in self.shards:
            s.engine.flush()

    def force_merge(self, max_num_segments: int = 1):
        for s in self.shards:
            s.engine.merge(max_segments=max_num_segments)

    def mesh_executor(self):
        """Lazy per-index MeshSearchExecutor: one ('shard',) mesh over
        min(num_shards, available devices); its device-array caches live as
        long as the index. None when the mesh can't be built — logged and
        counted (``mesh_build_failed``) once, after which every search of
        this index counts ``mesh_fallback_total`` on the host loop."""
        if self._mesh_executor is None:
            try:
                from elasticsearch_tpu.parallel.executor import MeshSearchExecutor
                from elasticsearch_tpu.parallel.mesh import shard_mesh

                mesh = shard_mesh(self.num_shards)
                # pass the live IndexShard objects, NOT a segment snapshot —
                # the executor must never pin merged-away segments in memory
                self._mesh_executor = MeshSearchExecutor(mesh, self.shards)
            except Exception:
                import logging

                from elasticsearch_tpu.monitor import kernels

                kernels.record("mesh_build_failed")
                logging.getLogger(__name__).exception(
                    "[%s] shard mesh could not be built; serving from the "
                    "host per-shard loop", self.name)
                self._mesh_executor = False
        return self._mesh_executor or None

    def _mesh_enabled(self) -> bool:
        import os

        if os.environ.get("ESTPU_DISABLE_MESH"):
            return False
        idx = self.settings.get("index", self.settings)
        return str(idx.get("search", {}).get("mesh", True)).lower() != "false"

    def replay_op(self, shard_ord: int, d: dict) -> None:
        """Apply ONE replayed op (the cross-host recovery stream's doc or
        tombstone) at engine level WITH percolator-registry maintenance.
        The whole decision runs under the engine lock: was-percolator is
        read pre-op, is-percolator re-read post-op, so a racing fanout
        write can neither leave a stale registration (doc re-created as a
        non-percolator type) nor lose one. Version conflicts propagate —
        the caller counts them as already-newer skips. Boot-time recovery
        instead bulk-rebuilds the registry in recover() above."""
        from elasticsearch_tpu.search.percolator import PERCOLATOR_TYPE

        engine = self.shards[shard_ord].engine
        with engine._lock:
            loc = engine._locations.get(d["id"])
            was_perc = (loc is not None and not loc.deleted
                        and loc.doc_type == PERCOLATOR_TYPE)
            if d.get("deleted"):
                # _history: a recovery stream replays recorded identity —
                # ops below the copy's current term are catch-up, not a
                # zombie write (the live-op fence lives in the replica
                # handler / engine fence for non-history ops)
                engine.delete(d["id"], version=d["version"],
                              version_type="external_gte",
                              seq_no=d.get("seq_no"),
                              primary_term=d.get("term"), _history=True)
            else:
                engine.index(d["id"], d["source"], version=d["version"],
                             version_type="external_gte",
                             doc_type=d.get("type"),
                             parent=d.get("parent"),
                             routing=d.get("routing"),
                             ttl_expiry=d.get("ttl_expiry"),
                             timestamp=d.get("timestamp"),
                             seq_no=d.get("seq_no"),
                             primary_term=d.get("term"),
                             _replay=True, _history=True)
            now = engine._locations.get(d["id"])
            is_perc = (now is not None and not now.deleted
                       and now.doc_type == PERCOLATOR_TYPE)
            if is_perc:
                try:
                    self.percolator.register(d["id"], d["source"])
                except Exception:
                    pass  # invalid legacy query: not registered
            elif was_perc:
                self.percolator.unregister(d["id"])

    def mlt_source(self, doc_id: str, routing=None, index=None):
        """Whole-index source lookup for doc-referencing queries (MLT
        liked ids, terms lookup, indexed_shape) — scans every shard (a
        routed doc doesn't live at its id-hash shard; the routing hint is
        unnecessary here). A reference naming a DIFFERENT index resolves
        through the owning node (terms lookup / indexed_shape registries
        usually live in their own index)."""
        if index is not None and index != self.name \
                and index not in self.aliases:
            node = getattr(self, "_node", None)
            if node is None:
                return None
            mh = getattr(node, "multihost", None)
            for nm in node.resolve_indices(index):
                if mh is not None and nm in mh.dist_indices:
                    # a DISTRIBUTED registry index: this host's local
                    # copy holds only its own shards — the lookup doc
                    # must come through the routed cross-host get
                    try:
                        got = mh.data.get_doc(nm, str(doc_id),
                                              routing=routing)
                    except Exception:
                        continue
                    if got.get("found"):
                        return got.get("_source")
                    continue
                svc = node.indices.get(nm)
                if svc is not None and svc is not self:
                    src = svc.mlt_source(doc_id, routing=routing)
                    if src is not None:
                        return src
            return None
        for sh in self.shards:
            got = sh.engine.get(str(doc_id))
            if got is not None:
                return got.get("_source")
        return None

    _QUERY_CACHE_CAP = 256

    def _query_cache_enabled(self) -> bool:
        idx = self.settings.get("index", self.settings)
        v = idx.get("cache.query.enable",
                    idx.get("index.cache.query.enable"))
        if v is None and isinstance(idx.get("cache"), dict):
            v = idx["cache"].get("query", {}).get("enable")
        return str(v).lower() in ("1", "true")

    def _query_cache_key(self, body: dict):
        """Cache key when this request is cacheable, else None (reference:
        IndicesQueryCache.canCache — size==0 only, no dfs, no scroll, no
        now-relative date math, enabled by setting or request override)."""
        import json as _json

        override = body.get("_query_cache")
        if override is False:
            return None
        if override is None and not self._query_cache_enabled():
            return None
        if int(body.get("size", 10)) != 0 or body.get("scroll"):
            return None
        if body.get("profile"):
            # a cached profile would replay the FIRST run's timings
            # (compile>0, retraces>0) for a request that ran nothing —
            # the reference excludes profiled requests from the request
            # cache for the same reason
            return None
        if body.get("search_type") in ("dfs_query_then_fetch", "scan"):
            return None
        try:
            blob = _json.dumps({k: v for k, v in body.items()
                                if k != "_query_cache"}, sort_keys=True)
        except TypeError:
            return None  # unserializable body: not cacheable
        import re as _re

        # now-relative date math ("now", "now-1d", "now/d") is
        # non-deterministic; plain words like "nowhere" must still cache
        if _re.search(r'"now(?:["+/\-]|\\)', blob, _re.IGNORECASE):
            return None
        gen = tuple((g.primary.engine.stats.index_total,
                     g.primary.engine.stats.delete_total,
                     g.primary.engine.stats.refresh_total)
                    for g in self.groups)
        return (gen, blob)

    def clear_query_cache(self) -> None:
        """POST /_cache/clear drops entries (counters keep their history)."""
        with self._qc_lock:
            self._query_cache.clear()

    def search(self, body: dict, dfs: bool = False,
               preference: Optional[str] = None) -> dict:
        """Index-level search entry. Wraps the body in the program
        observatory's index scope (per-index key census) and records the
        warmup-labeled latency: a request whose per-THREAD jit trace
        count moved paid a fresh compile — labeling it lets cold-start
        p99 separate from steady-state p99, the before/after number
        ROADMAP #6's zero-warmup acceptance needs."""
        from elasticsearch_tpu.monitor import programs
        from elasticsearch_tpu.serving import warmup as warmup_mod
        from elasticsearch_tpu.tracing import retrace

        t_req = time.perf_counter()
        snap = retrace.snapshot()
        prewarm = warmup_mod.in_prewarm()
        # pre-warm replays run OUTSIDE the census scope: a replay must
        # not bump the very key hit counts it was ordered by (max-merge
        # persistence would compound the inflation into a
        # self-reinforcing ranking every restart) — the programs still
        # register in the registry itself, which is what replay()'s
        # warm/missing verification reads
        with programs.index_scope(None if prewarm else self.name):
            resp = self._search_inner(body, dfs=dfs, preference=preference)
        delta = retrace.traces_since(snap)
        # pre-warm replays label "prewarm", not true/false: warmup's own
        # compiles must not pollute the cold-start acceptance series,
        # and a replay must not re-record its body into the census (it
        # would inflate its own work list's hit counts)
        if prewarm:
            warmup = "prewarm"
        else:
            warmup = "unknown" if delta < 0 \
                else ("true" if delta else "false")
            self._record_census_body(body)
        self._record_search_metric(time.perf_counter() - t_req, warmup)
        return resp

    #: census-body sampling: record every request for the first
    #: _CENSUS_FULL requests (building the replayable set wants full
    #: fidelity), then 1-in-_CENSUS_SAMPLE with weighted hits — the
    #: canonical json.dumps is the only per-search cost this feature
    #: adds, and for a steady workload whose bodies are already
    #: recorded it is pure counter maintenance
    _CENSUS_FULL = 256
    _CENSUS_SAMPLE = 8

    def _record_census_body(self, body: dict) -> None:
        """Feed the replayable census half (monitor/programs.py): the
        canonical JSON of an eligible body, so a restarted node can
        re-drive the same programs (serving/warmup.py). Profile bodies
        are excluded (they pin the host loop — replaying one would warm
        the wrong path); scroll bodies hold contexts."""
        import json as _json

        if not isinstance(body, dict) or body.get("profile") \
                or body.get("scroll"):
            return
        # GIL-atomic int bump; exact counts don't matter to a sampler
        self._census_seen = getattr(self, "_census_seen", 0) + 1
        weight = 1
        if self._census_seen > self._CENSUS_FULL:
            if self._census_seen % self._CENSUS_SAMPLE:
                return
            weight = self._CENSUS_SAMPLE
        try:
            canon = _json.dumps(
                {k: v for k, v in body.items()
                 if k not in ("_query_cache", "profile")},
                sort_keys=True)
        except (TypeError, ValueError):
            return  # unserializable body: not replayable
        try:
            from elasticsearch_tpu.monitor import programs

            programs.REGISTRY.record_body(self.name, canon, n=weight)
        except Exception:  # tpulint: allow[R006] — census recording
            pass           # must never fail the measured search

    def _record_search_metric(self, seconds: float, warmup: str) -> None:
        """Search latency with the warmup dimension. Library-embedded
        IndexServices have no node — then nothing records (the
        _record_write_metric discipline; a SHARED fallback would shadow
        the same-named per-node family in every node's exposition)."""
        node = getattr(self, "_node", None)
        if node is None:
            return
        try:
            node.metrics.histogram(
                "estpu_search_duration_seconds",
                "Search latency by index; warmup=true marks requests "
                "that paid a fresh jit compile (unknown = trace auditor "
                "absent; prewarm = census replay by serving/warmup.py)",
                ("index", "warmup"),
            ).labels(self.name, warmup).observe(seconds)
        except Exception:  # tpulint: allow[R006] — dropping one metric
            pass           # sample must never fail the measured search

    def _search_inner(self, body: dict, dfs: bool = False,
                      preference: Optional[str] = None) -> dict:
        from elasticsearch_tpu.cluster.metadata import check_open
        from elasticsearch_tpu.search.queries import rewrite_mlt_in_body

        check_open(self, op="read")
        body = body or {}
        t0 = time.perf_counter()
        qc_key = None if dfs else self._query_cache_key(body)
        if qc_key is not None:
            import copy as _copy

            with self._qc_lock:
                hit = self._query_cache.get(qc_key)
                if hit is not None:
                    self._query_cache.move_to_end(qc_key)
                    self.query_cache_stats["hits"] += 1
                else:
                    self.query_cache_stats["misses"] += 1
            if hit is not None:
                return _copy.deepcopy(hit)
        if "_query_cache" in body:
            body = {k: v for k, v in body.items() if k != "_query_cache"}
        if body.get("query"):
            # MLT liked ids resolve ONCE against the whole index before
            # the per-shard fan-out (queries.rewrite_mlt_in_body)
            q2 = rewrite_mlt_in_body(body["query"], self.mlt_source)
            if q2 is not body["query"]:
                body = dict(body, query=q2)
        global_stats = self.global_stats(body) if dfs else None
        # pick one in-sync copy per shard (preference: _primary | _replica |
        # default round-robin, reference: OperationRouting preference)
        readers = [g.reader(preference) for g in self.groups]
        searchers = [s.searcher for s in readers]
        resp = None
        if self._mesh_enabled():
            # DEFAULT path: the whole scatter/score/merge as one XLA program
            # over the shard mesh (SURVEY §3); host loop only for features
            # the compiler can't express. ?profile=true pins the host
            # per-segment loop via the mesh's _UNSUPPORTED_KEYS (ONE
            # mechanism — it also records the mesh_host_by_design
            # counter, which a second gate here would silently skip).
            from elasticsearch_tpu.parallel.mesh_service import try_mesh_search

            resp = try_mesh_search(self, searchers, body, global_stats)
        if resp is None:
            resp = search_shards(
                searchers, body, index_name=self.name,
                global_stats=global_stats,
            )
        if body.get("suggest"):
            resp["suggest"] = self.suggest(body["suggest"])
        self.slowlog.on_search((time.perf_counter() - t0) * 1000, body, resp)
        if qc_key is not None:
            import copy as _copy

            entry = _copy.deepcopy(resp)
            with self._qc_lock:
                self._query_cache[qc_key] = entry
                if len(self._query_cache) > self._QUERY_CACHE_CAP:
                    self._query_cache.popitem(last=False)
                    self.query_cache_stats["evictions"] += 1
        return resp

    def suggest(self, body: dict, shard_ids=None) -> dict:
        """Standalone suggest (reference: action/suggest/TransportSuggestAction
        + search-embedded SuggestPhase). `shard_ids` restricts to a shard
        subset — the multi-host fan-out targets each owner's PRIMARY
        shards only, so replica copies never double-count frequencies."""
        from elasticsearch_tpu.search.suggest import execute_suggest

        shards = (self.shards if shard_ids is None
                  else [self.shards[i] for i in shard_ids])
        for sh in shards:
            sh.searcher.stats.on_suggest()
        return execute_suggest(shards, body or {}, self.analysis,
                               mappings=self.mappings)

    # -- percolator ------------------------------------------------------------

    @property
    def percolator(self):
        from elasticsearch_tpu.search.percolator import PercolatorRegistry

        if self._percolator is None:
            self._percolator = PercolatorRegistry()
            self._percolator.doc_lookup = self.mlt_source
        return self._percolator

    def percolate(self, body: dict) -> dict:
        """Percolate a doc (reference: rest/action/percolate/RestPercolateAction
        → PercolatorService.percolate)."""
        from elasticsearch_tpu.search.percolator import (PERCOLATOR_TYPE,
                                                         percolate as _perc)

        doc = (body or {}).get("doc")
        if doc is None:
            raise DocumentMissingException(self.name, "_percolate requires [doc]")
        matches, _total, perc_ctx = _perc(self.percolator, [doc],
                                          self.mappings, self.analysis,
                                          return_ctx=True)
        full = matches[0]
        # percolate-request query/filter restricts WHICH registered queries
        # participate: it runs against the .percolator docs' own metadata
        # (reference: PercolateSourceBuilder query + PercolatorService's
        # percolateQueries filtering)
        restrict = (body or {}).get("query") or (body or {}).get("filter")
        if restrict is not None:
            # _search_inner: an internal sub-search of ONE user percolate
            # must not multiply estpu_search_duration_seconds samples
            r = self._search_inner({"query": {"bool": {
                "must": [restrict],
                "filter": [{"term": {"_type": PERCOLATOR_TYPE}}]}},
                "size": 10_000, "_source": False})
            allowed = {h["_id"] for h in r["hits"]["hits"]}
            full = [qid for qid in full if qid in allowed]
        size = (body or {}).get("size")
        listed = full if size is None else full[: int(size)]
        out = {
            "took": 0,
            "_shards": {"total": self.num_shards, "successful": self.num_shards,
                        "failed": 0},
            "total": len(full),  # total matched, even when size truncates
            "matches": [{"_index": self.name, "_id": qid} for qid in listed],
        }
        hl_spec = (body or {}).get("highlight")
        if hl_spec and listed:
            from elasticsearch_tpu.search.percolator import highlight_matches

            listed_set = set(listed)
            by_id = {qid: pair for qid, pair in self.percolator.items()
                     if qid in listed_set}
            hl = highlight_matches(doc, by_id, hl_spec, self.mappings,
                                   self.analysis, ctx=perc_ctx)
            for m in out["matches"]:
                if m["_id"] in hl:
                    m["highlight"] = hl[m["_id"]]
        aggs_spec = (body or {}).get("aggs") or (body or {}).get(
            "aggregations")
        if aggs_spec is not None:
            # aggregations run over the MATCHED .percolator docs' own
            # metadata fields (reference: PercolateSourceBuilder
            # aggregations / PercolatorService agg phase)
            r = self._search_inner({"query": {"bool": {"filter": [
                {"term": {"_type": PERCOLATOR_TYPE}},
                {"ids": {"values": full}}]}},
                "size": 0, "aggs": aggs_spec})
            out["aggregations"] = r.get("aggregations", {})
        return out

    def count(self, body: dict) -> dict:
        total = sum(s.searcher.count(body or {}) for s in self.shards)
        return {"count": total, "_shards": {"total": self.num_shards,
                                            "successful": self.num_shards, "failed": 0}}

    def global_stats(self, body: dict) -> GlobalStats:
        """dfs phase: collect cross-shard df/num_docs for consistent idf
        (reference: search/dfs/DfsPhase.java)."""
        num_docs: Dict[str, int] = {}
        df: Dict[Any, int] = {}
        for shard in self.shards:
            for seg in shard.segments:
                for fname, inv in seg.inverted.items():
                    num_docs[fname] = num_docs.get(fname, 0) + inv.num_docs
                    for term, tid in inv.vocab.items():
                        key = (fname, term)
                        df[key] = df.get(key, 0) + int(inv.df[tid])
        return GlobalStats(num_docs=num_docs, df=df)

    def stats(self) -> dict:
        shard_stats = [s.stats() for s in self.shards]
        # searches record on the round-robin reader's copy — fold replica
        # searcher counters into the primary's search section so _stats
        # reports the whole group (reference: stats aggregate every copy)
        for g, st in zip(self.groups, shard_stats):
            for c in g.copies:
                if c is g.primary:
                    continue
                _merge_counters(st["search"], c.searcher.stats.to_json())
            # the group-level global checkpoint joins the per-copy seq-no
            # stats (reference: SeqNoStats carries all three)
            st["seq_no"]["global_checkpoint"] = g.global_checkpoint
        total_docs = sum(st["docs"]["count"] for st in shard_stats)
        return {
            "primaries": {
                "docs": {"count": total_docs},
                "indexing": {
                    "index_total": sum(st["indexing"]["index_total"] for st in shard_stats)
                },
                "segments": {
                    "count": sum(st["segments"]["count"] for st in shard_stats),
                    "memory_in_bytes": sum(st["segments"]["memory_in_bytes"] for st in shard_stats),
                },
            },
            "shards": {str(i): st for i, st in enumerate(shard_stats)},
        }

    @property
    def num_docs(self) -> int:
        return sum(s.engine.num_docs for s in self.shards)

    def close(self):
        for g in self.groups:
            for c in g.copies + g.failed_replicas:
                c.close()
        self.closed = True


def _merge_counters(dst: dict, src: dict) -> None:
    """Sum numeric counters recursively (non-numeric keys first-wins)."""
    for k, v in src.items():
        if isinstance(v, dict):
            _merge_counters(dst.setdefault(k, {}), v)
        elif isinstance(v, (int, float)) and not isinstance(v, bool):
            dst[k] = dst.get(k, 0) + v
        else:
            dst.setdefault(k, v)
