"""Node: the top-level runtime holding indices, cluster state, templates.

Reference: org/elasticsearch/node/Node.java + node/internal/InternalNode.java
(service wiring), action/admin/indices/create/TransportCreateIndexAction.java
(template application order), action/bulk/TransportBulkAction.java (bulk
fan-out), action/search/TransportMultiSearchAction.java.
"""
from __future__ import annotations

import fnmatch
import json
import logging
import os
import re
import uuid
from typing import Any, Dict, List, Optional

from elasticsearch_tpu.cluster.state import ClusterState, DiscoveryNode, IndexMetadata
from elasticsearch_tpu.index.index_service import IndexService
from elasticsearch_tpu.utils.errors import (
    ElasticsearchTpuException,
    IllegalArgumentException,
    IndexAlreadyExistsException,
    IndexNotFoundException,
)
from elasticsearch_tpu import __version__

logger = logging.getLogger(__name__)

# exception types a fused _msearch attempt raised and the sequential
# loop absorbed: each is logged once a process, counted every time
# (estpu_span_errors_total{span="msearch.batch_attempt"})
_BATCH_ERRORS_LOGGED: set = set()


def _log_batch_error_once(e: BaseException) -> None:
    name = type(e).__name__
    if name not in _BATCH_ERRORS_LOGGED:
        _BATCH_ERRORS_LOGGED.add(name)
        logger.warning(
            "a fused _msearch batch raised %s (%s); its bodies ran one by "
            "one instead. Logged once a process for this type.", name, e)


class Node:
    def __init__(self, name: str = "node-1", data_path: Optional[str] = None,
                 cluster_name: str = "elasticsearch_tpu"):
        self.node_id = uuid.uuid4().hex[:12]
        self.name = name
        self.data_path = data_path
        self.indices: Dict[str, IndexService] = {}
        # stored search templates (reference keeps these in the .scripts
        # index; node-local registry here)
        self.search_templates: Dict[str, Any] = {}
        self.search_template_versions: Dict[str, int] = {}
        # snapshot repositories (reference: RepositoriesService)
        self.repositories: Dict[str, Any] = {}
        # dynamic cluster settings (reference: ClusterUpdateSettingsRequest
        # persistent/transient maps); stored keys are surfaced via
        # GET /_cluster/settings
        self.cluster_settings: Dict[str, Dict[str, Any]] = {
            "persistent": {}, "transient": {}}
        self.cluster_state = ClusterState(cluster_name)
        self.cluster_state.add_node(DiscoveryNode(self.node_id, name), master=True)
        # observability: span tracer + task registry (reference: the
        # TaskManager every TransportService carries; tracing/__init__.py)
        from elasticsearch_tpu.tracing import TaskRegistry, Tracer

        self.tasks = TaskRegistry(self.node_id)
        self.tracer = Tracer(self.node_id)
        # continuous metrics (monitor/metrics.py): a per-NODE registry —
        # REST latency, span histograms, indexing — plus scrape-time
        # collectors over the process-shared subsystems; every finished
        # span feeds a latency histogram via the tracer sink, so PR 4's
        # instrumentation became time-series without new call sites
        from elasticsearch_tpu.monitor.metrics import (MetricsRegistry,
                                                       span_sink)

        self.metrics = MetricsRegistry(include_shared=True)
        self.tracer.set_sink(span_sink(self.metrics))
        self._register_metric_collectors()
        # flight recorder + stall watchdog (monitor/flight.py,
        # monitor/watchdog.py): the recorder is registered with the
        # process fan so node-less subsystems (breakers, engines) reach
        # it; the watchdog's tick thread is lazy — serving entry points
        # (RestServer.start, cluster bootstrap) call ensure_started()
        from elasticsearch_tpu.monitor import flight as flight_mod
        from elasticsearch_tpu.monitor.watchdog import WatchdogService

        self.flight = flight_mod.FlightRecorder(self.node_id, name)
        flight_mod.register(self.flight)
        self.watchdog = WatchdogService(self)
        # serving front-end: cross-request micro-batching + per-tenant
        # QoS (serving/). Cheap to build — the drain thread is lazy, so
        # library-embedded Nodes that never coalesce don't pay for it.
        from elasticsearch_tpu.serving import ServingFrontend

        self.serving = ServingFrontend(self)
        # resource management: rehydration spans (tpu.rehydrate) land in
        # this node's tracer ring (process-shared registry — the device
        # is process-shared too; last in-process node wins)
        from elasticsearch_tpu import resources

        resources.RESIDENCY.set_tracer(self.tracer)
        # lazy: pools spin worker threads, so library-embedded Nodes that
        # never serve REST traffic don't pay for them
        self._thread_pool = None
        self._tp_lock = __import__("threading").Lock()
        self._ivf_dir = None
        if data_path:
            # durable ANN tier must be visible BEFORE replay freezes
            # segments, or recovery pays the k-means the cache holds
            from elasticsearch_tpu.index import ivf_cache

            self._ivf_dir = os.path.join(data_path, "_ivf")
            ivf_cache.register(self._ivf_dir)
            self._gateway_recover()

    @property
    def thread_pool(self):
        """Named request pools (reference: threadpool/ThreadPool.java).
        Double-checked under a lock — concurrent first REST requests must
        not each spin a registry of worker threads."""
        if self._thread_pool is None:
            from elasticsearch_tpu.utils.threadpool import ThreadPool

            with self._tp_lock:
                if self._thread_pool is None:
                    self._thread_pool = ThreadPool()
        return self._thread_pool

    def _register_metric_collectors(self) -> None:
        """Scrape-time gauge/counter families over state that is already
        counted elsewhere — threadpool queues, breaker bytes, residency
        tiers, kernel dispatch, jit traces. Re-counting these on every
        record would double-lock hot paths; reading them at scrape time
        costs one request per scrape instead."""
        m = self.metrics

        def _pools():
            tp = self._thread_pool
            return tp.stats().items() if tp is not None else ()

        m.collector("estpu_threadpool_queue_depth",
                    "Queued work items per named thread pool", ("pool",),
                    lambda: [((n,), st["queue"]) for n, st in _pools()])
        m.collector("estpu_threadpool_active",
                    "Active workers per named thread pool", ("pool",),
                    lambda: [((n,), st["active"]) for n, st in _pools()])
        m.collector("estpu_threadpool_rejected_total",
                    "Work rejected by a full queue, per pool", ("pool",),
                    lambda: [((n,), st["rejected"]) for n, st in _pools()],
                    kind="counter")
        m.collector("estpu_threadpool_completed_total",
                    "Work completed per named thread pool", ("pool",),
                    lambda: [((n,), st["completed"]) for n, st in _pools()],
                    kind="counter")

        def _breakers():
            from elasticsearch_tpu import resources

            return resources.BREAKERS.stats().items()

        m.collector("estpu_breaker_used_bytes",
                    "Estimated bytes held per circuit breaker",
                    ("breaker",),
                    lambda: [((n,), br["estimated_size_in_bytes"])
                             for n, br in _breakers()])
        m.collector("estpu_breaker_limit_bytes",
                    "Configured byte limit per circuit breaker",
                    ("breaker",),
                    lambda: [((n,), br["limit_size_in_bytes"])
                             for n, br in _breakers()])
        m.collector("estpu_breaker_tripped_total",
                    "Trips per circuit breaker", ("breaker",),
                    lambda: [((n,), br["tripped"]) for n, br in _breakers()],
                    kind="counter")

        def _tiers():
            from elasticsearch_tpu import resources

            return resources.RESIDENCY.stats()["tiers"].items()

        m.collector("estpu_residency_tier_bytes",
                    "Device-resident bytes per residency tier", ("tier",),
                    lambda: [((t,), st["resident_bytes"])
                             for t, st in _tiers()])
        m.collector("estpu_residency_evictions_total",
                    "Device-copy evictions per residency tier", ("tier",),
                    lambda: [((t,), st["evictions"]) for t, st in _tiers()],
                    kind="counter")
        m.collector("estpu_residency_rehydrations_total",
                    "Evicted-copy rehydrations per residency tier",
                    ("tier",),
                    lambda: [((t,), st["rehydrations"])
                             for t, st in _tiers()],
                    kind="counter")

        def _kernels():
            from elasticsearch_tpu.monitor import kernels

            return kernels.snapshot().items()

        m.collector("estpu_kernel_dispatch_total",
                    "Requests served per device kernel / dispatch "
                    "decision (monitor/kernels.py names)", ("kernel",),
                    lambda: [((k,), v) for k, v in _kernels()],
                    kind="counter")

        def _jit_traces():
            from elasticsearch_tpu.tracing import retrace

            a = retrace.auditor()
            # 0 when the auditor never installed: the exposition needs a
            # stable family; /_nodes profiles keep the honest -1 sentinel
            return [((), a.total() if a is not None else 0)]

        m.collector("estpu_jit_traces_total",
                    "jax.jit traces (compilations) recorded by the "
                    "trace auditor since process start", (),
                    _jit_traces, kind="counter")

        # device-program observatory (monitor/programs.py): per-key
        # compile/execute attribution. Cardinality is bounded by the
        # registry's own key cap (pow2 padding keeps the real universe
        # small; overflow collapses into the reserved _other_ row), so
        # these scrape-time families inherit the cap. The counters view
        # skips percentile math — the full snapshot() is for the REST
        # table, not a 15s-interval scrape — and a short memo lets ONE
        # registry walk serve all three families of a scrape (the three
        # collect() calls land within one render; counters may lag a
        # fraction of a second, which a 15s scrape cannot observe).
        _prog_memo = {"t": float("-inf"), "rows": ()}

        def _programs():
            import time as _time

            from elasticsearch_tpu.monitor import programs

            now = _time.monotonic()
            if now - _prog_memo["t"] > 0.2:
                _prog_memo["rows"] = programs.REGISTRY.counters_snapshot()
                _prog_memo["t"] = now
            return _prog_memo["rows"]

        m.collector("estpu_program_compiles_total",
                    "jit compiles per (program, shapes, backend) key",
                    ("program", "shapes", "backend"),
                    lambda: [((p, s, b), compiles)
                             for p, s, b, compiles, _cs, _es
                             in _programs()],
                    kind="counter")
        m.collector("estpu_program_compile_seconds",
                    "Wall seconds spent in calls that paid tracing + "
                    "compilation, per program key",
                    ("program", "shapes", "backend"),
                    lambda: [((p, s, b), cs)
                             for p, s, b, _c, cs, _es in _programs()],
                    kind="counter")
        m.collector("estpu_program_execute_seconds",
                    "Wall seconds spent executing cached programs, per "
                    "program key",
                    ("program", "shapes", "backend"),
                    lambda: [((p, s, b), es)
                             for p, s, b, _c, _cs, es in _programs()],
                    kind="counter")

        # AOT executable cache (parallel/aot.py via the jax-free counter
        # store monitor/compile_cache.py): per-source resolution counts —
        # aot_hit (deserialized blob, the zero-warmup path), xla_dir_hit
        # (fresh compile served by the persistent XLA dir), fresh (full
        # price), and the detected-miss/fallback taxonomy — plus phase
        # seconds. Fixed label vocabulary, cardinality bounded by
        # construction.
        def _cc_events():
            from elasticsearch_tpu.monitor import compile_cache

            return [((s,), v)
                    for s, v in compile_cache.events_snapshot().items()]

        def _cc_seconds():
            from elasticsearch_tpu.monitor import compile_cache

            return [((ph,), v)
                    for ph, v in compile_cache.seconds_snapshot().items()]

        m.collector("estpu_compile_cache_events_total",
                    "AOT executable-cache resolutions by source "
                    "(parallel/aot.py): aot_hit / xla_dir_hit / fresh, "
                    "plus detected corrupt/mismatch misses, store "
                    "outcomes, and call fallbacks", ("source",),
                    _cc_events, kind="counter")
        m.collector("estpu_compile_cache_seconds_total",
                    "Wall seconds in AOT cache phases: deserialize "
                    "(blob hit), compile (fresh lower+compile), "
                    "serialize (store)", ("phase",),
                    _cc_seconds, kind="counter")

    # -- gateway ---------------------------------------------------------------

    def _index_meta_path(self, name: str) -> str:
        return os.path.join(self.data_path, name, "_meta.json")

    def _persist_index_meta(self, name: str) -> None:
        """Durable index metadata (reference: gateway stores the cluster
        MetaData on disk — without it, translogs are orphans on restart)."""
        if not self.data_path or name not in self.indices:
            return
        svc = self.indices[name]
        path = self._index_meta_path(name)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"settings": svc.settings,
                       "mappings": svc.mappings.to_json(),
                       "aliases": svc.aliases,
                       "closed": bool(svc.closed)}, f)
        os.replace(tmp, path)

    def _gateway_recover(self) -> None:
        """Re-open every index found under data_path (reference:
        GatewayService + LocalGatewayMetaState on node start); each
        IndexService then replays its shards' translogs."""
        if not os.path.isdir(self.data_path):
            return
        for name in sorted(os.listdir(self.data_path)):
            meta_path = self._index_meta_path(name)
            if not os.path.isfile(meta_path):
                continue
            try:
                with open(meta_path) as f:
                    meta = json.load(f)
                svc = IndexService(
                    name, meta.get("settings"),
                    {"properties": {}} if not meta.get("mappings") else meta["mappings"],
                    data_path=self.data_path,
                    # a pre-validation index with a broken-but-unused
                    # analysis component must still re-open (lazy
                    # resolution, the behavior it was created under)
                    validate_analysis=False)
            except Exception:
                # one unrecoverable index (bad meta, failing replay) must
                # not stop the node from booting — it just stays absent
                # (red), reference: per-index recovery failures
                continue
            svc.aliases = dict(meta.get("aliases", {}))
            svc.closed = bool(meta.get("closed", False))
            svc._node = self  # foreign-index doc lookups (terms lookup)
            self.indices[name] = svc
            self.cluster_state.add_index(
                IndexMetadata(name, svc.settings, meta.get("mappings", {}),
                              svc.aliases),
                svc.num_shards, self.node_id)
            if svc.closed:
                m = self.cluster_state.indices.get(name)
                if m is not None:
                    m.state = "close"

    # -- index admin -----------------------------------------------------------

    def create_index(self, name: str, body: Optional[dict] = None) -> dict:
        if name in self.indices:
            raise IndexAlreadyExistsException(name)
        _validate_index_name(name)
        body = body or {}
        settings = dict(body.get("settings", {}))
        mappings = dict(body.get("mappings", {}))
        aliases = dict(body.get("aliases", {}))
        # apply matching templates, lowest order first (CreateIndexService)
        tmpls = sorted(
            (t for t in self.cluster_state.templates.values()
             if any(fnmatch.fnmatch(name, pat) for pat in t.get("index_patterns", [t.get("template", "")]))),
            key=lambda t: t.get("order", 0),
        )
        merged_settings: dict = {}
        merged_mappings: dict = {}
        for t in tmpls:
            _deep_merge(merged_settings, t.get("settings", {}))
            _deep_merge(merged_mappings, t.get("mappings", {}))
            aliases.update(t.get("aliases", {}))
        _deep_merge(merged_settings, settings)
        _deep_merge(merged_mappings, mappings)
        svc = IndexService(name, merged_settings, merged_mappings, data_path=self.data_path)
        svc._node = self  # foreign-index doc lookups (terms lookup)
        # aliases with `routing` fan it into index/search routing, like
        # IndicesAliasesRequest does
        for spec in aliases.values():
            if isinstance(spec, dict) and "routing" in spec:
                r = spec.pop("routing")
                spec.setdefault("index_routing", r)
                spec.setdefault("search_routing", r)
        svc.aliases = aliases
        for wname, wspec in dict(body.get("warmers", {})).items():
            svc.warmers[wname] = (wspec.get("source", wspec)
                                  if isinstance(wspec, dict) else wspec)
        self.indices[name] = svc
        self.cluster_state.add_index(
            IndexMetadata(name, merged_settings, merged_mappings, aliases),
            svc.num_shards, self.node_id,
        )
        self._persist_index_meta(name)
        return {"acknowledged": True, "shards_acknowledged": True, "index": name}

    def delete_index(self, name: str) -> dict:
        found = self.resolve_indices(name)
        if not found:
            raise IndexNotFoundException(name)
        mh = getattr(self, "multihost", None)
        for n in found:
            if mh is not None and n in mh.dist_indices:
                # cluster-wide: drop from the published metadata so peers
                # remove their copies (a local-only delete would be
                # resurrected by the next publish)
                mh.data.delete_index(n)
            else:
                self._delete_local_index(n)
        return {"acknowledged": True}

    def _delete_local_index(self, n: str) -> None:
        self.indices.pop(n).close()
        self.cluster_state.remove_index(n)
        if self.data_path:
            import shutil

            shutil.rmtree(os.path.join(self.data_path, n), ignore_errors=True)

    def index_exists(self, name: str) -> bool:
        return name in self.indices or bool(self._alias_targets(name))

    def resolve_indices(self, expr: Optional[str]) -> List[str]:
        """Resolve a name/alias/wildcard/csv expression to index names."""
        if expr in (None, "", "_all", "*"):
            return list(self.indices)
        out: List[str] = []
        for part in str(expr).split(","):
            part = part.strip()
            if "*" in part or "?" in part:
                out.extend(n for n in self.indices if fnmatch.fnmatch(n, part))
            elif part in self.indices:
                out.append(part)
            else:
                out.extend(self._alias_targets(part))
        seen = set()
        uniq = []
        for n in out:
            if n not in seen:
                seen.add(n)
                uniq.append(n)
        return uniq

    def _alias_targets(self, alias: str) -> List[str]:
        return [n for n, svc in self.indices.items() if alias in svc.aliases]

    def get_index(self, name: str) -> IndexService:
        names = self.resolve_indices(name)
        if not names:
            raise IndexNotFoundException(name)
        if len(names) > 1:
            raise ElasticsearchTpuException(
                f"alias/expression [{name}] resolves to multiple indices for a single-index op"
            )
        return self.indices[names[0]]

    def put_mapping(self, index: str, body: dict) -> dict:
        import copy

        names = self.resolve_indices(index)
        # validate the merged result on copies first: a rejected update must
        # leave every index untouched (all-or-nothing, like the reference's
        # MetaDataMappingService cluster-state update)
        for n in names:
            svc = self.indices[n]
            trial = copy.deepcopy(svc.mappings)
            trial.merge(body)
            svc._validate_analyzers(trial)
        for n in names:
            self.indices[n].mappings.merge(body)
            self._persist_index_meta(n)
        return {"acknowledged": True}

    def get_mapping(self, index: Optional[str] = None) -> dict:
        out = {}
        for n in self.resolve_indices(index):
            m = self.indices[n].mappings
            mj = m.to_json()
            # typed-mapping echo: indices that declared 2.0 type blocks
            # read back keyed by those names (single-type model underneath)
            out[n] = {"mappings": ({t: mj for t in m.type_names}
                                   if m.type_names else mj)}
        return out

    def update_aliases(self, actions: List[dict]) -> dict:
        mh = getattr(self, "multihost", None)
        if mh is not None:
            # alias changes are metadata: a headless node must fail them
            # typed 503 up front, not apply-and-ack a change the quorum's
            # master will overwrite on the next adopt
            mh.ensure_not_blocked("metadata_write")
        if mh is not None and not mh.is_master:
            # alias changes touching distributed indices are cluster state:
            # the master owns them (they ride the published metadata, so a
            # local-only change would be resurrected by the next publish).
            # SPLIT the batch at the per-INDEX level: expressions resolve
            # HERE (the master's index set differs), each resolved name
            # becomes an explicit single-index action, and only the
            # dist-index ones forward — so a wildcard spanning a local
            # and a distributed index updates both
            fwd: List[dict] = []
            local: List[dict] = []
            for action in actions:
                for op, spec in action.items():
                    for nm in (self.resolve_indices(
                            spec.get("index", spec.get("indices"))) or []):
                        single = {k: v for k, v in spec.items()
                                  if k not in ("index", "indices")}
                        single["index"] = nm
                        (fwd if nm in mh.dist_indices
                         else local).append({op: single})
            if fwd:
                from elasticsearch_tpu.cluster.search_action import \
                    ACTION_ALIASES

                mh.transport.send_remote(
                    mh.master_addr, ACTION_ALIASES, {"actions": fwd})
                actions = local
                if not actions:
                    return {"acknowledged": True}
        touched: List[str] = []
        for action in actions:
            for op, spec in action.items():
                idx_names = self.resolve_indices(spec.get("index", spec.get("indices")))
                alias = spec["alias"]
                for n in idx_names:
                    if op == "add":
                        meta = {k: v for k, v in spec.items()
                                if k not in ("index", "indices", "alias")}
                        if "routing" in meta:  # fans into both routings
                            r = str(meta.pop("routing"))
                            meta.setdefault("index_routing", r)
                            meta.setdefault("search_routing", r)
                        for rk in ("index_routing", "search_routing"):
                            if rk in meta:  # Settings are string maps
                                meta[rk] = str(meta[rk])
                        self.indices[n].aliases[alias] = meta
                    elif op == "remove":
                        self.indices[n].aliases.pop(alias, None)
                    self._persist_index_meta(n)
                    touched.append(n)
        if mh is not None and mh.is_master:
            # master: fold the new alias maps into the published dist
            # metadata (authoritative once present — _adopt_indices
            # REPLACES peers' local maps with it, so removals propagate
            # instead of being resurrected by the next publish)
            dist_touched = [n for n in touched if n in mh.dist_indices]
            if dist_touched:
                with mh._indices_lock:
                    prior = {n: dict(mh.dist_indices[n].get("aliases")
                                     or {}) for n in dist_touched}
                    for n in dist_touched:
                        mh.dist_indices[n]["aliases"] = dict(
                            self.indices[n].aliases)
                try:
                    mh.publish_indices()
                except Exception:
                    # not committed: restore BOTH halves (published map +
                    # local alias state) so this node doesn't diverge
                    # from what the quorum's master republishes, then
                    # fail the client typed
                    with mh._indices_lock:
                        for n, aliases in prior.items():
                            if n in mh.dist_indices:
                                mh.dist_indices[n]["aliases"] = \
                                    dict(aliases)
                            if n in self.indices:
                                self.indices[n].aliases = dict(aliases)
                                self._persist_index_meta(n)
                        mh._persist_dist_meta()
                    raise
        return {"acknowledged": True}

    def put_template(self, name: str, body: dict,
                     create: bool = False) -> dict:
        if create and name in self.cluster_state.templates:
            raise IndexAlreadyExistsException(name)
        body = dict(body)
        aliases = dict(body.get("aliases") or {})
        for spec in aliases.values():  # same routing fan-out as create
            if isinstance(spec, dict) and "routing" in spec:
                r = str(spec.pop("routing"))
                spec.setdefault("index_routing", r)
                spec.setdefault("search_routing", r)
        if aliases:
            body["aliases"] = aliases
        self.cluster_state.templates[name] = body
        return {"acknowledged": True}

    def delete_template(self, name: str) -> dict:
        if self.cluster_state.templates.pop(name, None) is None:
            raise IndexNotFoundException(name)
        return {"acknowledged": True}

    # -- documents -------------------------------------------------------------

    def bulk(self, operations: List[dict]) -> dict:
        """operations: parsed NDJSON pairs [{action}, {source}?, ...]."""
        items = []
        errors = False
        i = 0
        while i < len(operations):
            action_line = operations[i]
            (op, meta), = action_line.items()
            i += 1
            source = None
            if op in ("index", "create", "update"):
                source = operations[i]
                i += 1
            index_name = meta.get("_index")
            doc_id = meta.get("_id")
            parent = meta.get("parent", meta.get("_parent"))
            routing = meta.get("routing", meta.get("_routing")) or parent
            doc_type = meta.get("_type")
            try:
                # distributed index: every op hash-routes to its shard's
                # owner process (TransportBulkAction shard-bulk routing)
                mh = getattr(self, "multihost", None)
                data = (mh.data if mh is not None
                        and index_name in mh.dist_indices else None)
                svc = data or self.get_or_autocreate(index_name)
                args = (index_name,) if data is not None else ()
                if op in ("index", "create"):
                    kw = {}
                    if doc_type and doc_type != "_doc":
                        kw["doc_type"] = doc_type
                    if parent:
                        kw["parent"] = parent
                    r = svc.index_doc(*args, doc_id, source, routing=routing,
                                      op_type="create" if op == "create" else "index",
                                      **kw)
                    status = 201 if r.get("created") else 200
                elif op == "update":
                    r = svc.update_doc(*args, doc_id, source, routing=routing)
                    status = 200
                elif op == "delete":
                    r = svc.delete_doc(*args, doc_id, routing=routing)
                    status = 200
                else:
                    raise ElasticsearchTpuException(f"unknown bulk op [{op}]")
                items.append({op: {**r, "status": status}})
            except ElasticsearchTpuException as e:
                errors = True
                items.append({op: {
                    "_index": index_name, "_id": doc_id, "status": e.status,
                    "error": {"type": e.error_type, "reason": str(e)},
                }})
        return {"took": 0, "errors": errors, "items": items}

    def get_or_autocreate(self, name: str) -> IndexService:
        names = self.resolve_indices(name)
        if names:
            if len(names) == 1:
                return self.indices[names[0]]
            raise ElasticsearchTpuException(f"[{name}] resolves to multiple indices for a write")
        self.create_index(name)
        return self.indices[name]

    # -- search ----------------------------------------------------------------

    def search(self, index: Optional[str], body: dict,
               preference: Optional[str] = None) -> dict:
        # the container span of one search, whichever door it came
        # through: _search, _search_typed, _search_all, each body of an
        # _msearch, the Python client
        with self.tracer.span("search", index=index or "_all"):
            return self._search(index, body, preference)

    def _search(self, index: Optional[str], body: dict,
                preference: Optional[str]) -> dict:
        mh = getattr(self, "multihost", None)
        if mh is not None and index is not None:
            rname = mh.data.resolve_index(index)
            if rname in mh.dist_indices:
                # a distributed index (by name or alias) scatters
                # cross-host; multi-index expressions mixing local +
                # distributed stay local-scoped. Pass the RESOLVED name so
                # the data plane doesn't re-resolve.
                return mh.data.search(rname, body or {})
        if mh is not None and index in (None, "", "_all", "*"):
            # the all-indices spelling must ride the dist plane too: the
            # local-scoped fallback silently under-reports acked docs on
            # any member whose local copy of a shard is empty (a bare
            # GET /_search on a non-owner saw only its own shards)
            open_names = [nm for nm in self.resolve_indices(index)
                          if not self.indices[nm].closed]
            dist = [nm for nm in open_names if nm in mh.dist_indices]
            if len(dist) == 1 and len(open_names) == 1:
                return mh.data.search(dist[0], body or {})
            if dist:
                # multiple distributed indices, or distributed mixed
                # with local-only: a loud typed refusal beats the old
                # silently-local-scoped (under-reporting) answer
                from elasticsearch_tpu.utils.errors import \
                    IllegalArgumentException

                raise IllegalArgumentException(
                    "all-indices search over multiple (or mixed "
                    "local/distributed) indices is not supported in "
                    "coordinator mode; name one index (distributed "
                    f"here: {sorted(dist)})")
        names = self.resolve_indices(index)
        if not names and index not in (None, "", "_all", "*"):
            raise IndexNotFoundException(str(index))
        searchers = []
        alias_filters = []
        from elasticsearch_tpu.cluster.metadata import check_open

        # wildcard/_all expansion SKIPS closed indices; an explicitly named
        # closed index (directly or via an alias) is an error (reference:
        # IndicesOptions wildcard expansion defaults to open-only)
        explicit = set()
        for part in str(index or "").split(","):
            part = part.strip()
            if part and not any(c in part for c in "*?") and part not in ("_all",):
                explicit.update(self.resolve_indices(part) or [part])
        searched_names: List[str] = []
        for n in names:
            svc = self.indices[n]
            if svc.closed and n not in explicit:
                continue
            check_open(svc, op="read")
            searched_names.append(n)
        search_type = (body or {}).get("search_type")
        if len(searched_names) == 1:
            # single-index: delegate to the index service BEFORE building
            # searchers (reader() advances replica round-robin; calling it
            # twice per request would defeat replica rotation). The service
            # runs the mesh executor as the default product path.
            svc = self.indices[searched_names[0]]
            dfs = search_type == "dfs_query_then_fetch"

            def _run():
                return svc.search(body or {}, dfs=dfs,
                                  preference=preference)

            if not dfs and preference is None:
                # serving coalescer: eligible bodies of CONCURRENT
                # requests park briefly and execute as one fused batch
                # (serving/coalescer.py); lone requests and ineligible
                # bodies run the normal path unchanged
                out = self.serving.coalescer.execute(svc, body or {}, _run)
                if out is not None:
                    return out
            return _run()
        if (body or {}).get("query"):
            from elasticsearch_tpu.search.queries import rewrite_mlt_in_body

            def _lookup(doc_id, routing=None, index=None):
                # mlt_source's own index check handles aliases AND
                # delegates foreign names through the node, so one call
                # covers explicit-_index references; an explicitly-named
                # index never falls back to a different index's same-id
                # document
                if index:
                    return self.indices[searched_names[0]].mlt_source(
                        doc_id, routing=routing, index=index)
                for nm in searched_names:
                    src = self.indices[nm].mlt_source(doc_id,
                                                      routing=routing)
                    if src is not None:
                        return src
                return None

            q2 = rewrite_mlt_in_body(body["query"], _lookup)
            if q2 is not body["query"]:
                body = dict(body, query=q2)
        for n in searched_names:
            svc = self.indices[n]
            searchers.extend(g.reader(preference).searcher for g in svc.groups)
        if not searchers:
            return {
                "took": 0, "timed_out": False,
                "_shards": {"total": 0, "successful": 0, "failed": 0},
                "hits": {"total": 0, "max_score": None, "hits": []},
            }
        from elasticsearch_tpu.search.service import search_shards

        # NOTE: searcher.shard_ord is NOT renumbered here — search_shards
        # stamps candidates with positional ordinals itself, so persistent
        # searcher state stays untouched across multi-index searches
        gs = None
        if search_type == "dfs_query_then_fetch":
            # merge per-index dfs term stats so idf is consistent across
            # EVERY searched index (reference: search/dfs/DfsPhase collects
            # over all participating shards, not one index)
            from elasticsearch_tpu.search.context import GlobalStats

            num_docs: Dict[str, int] = {}
            df: Dict[Any, int] = {}
            for n2 in searched_names:
                g2 = self.indices[n2].global_stats(body)
                for k2, v2 in g2.num_docs.items():
                    num_docs[k2] = num_docs.get(k2, 0) + v2
                for k2, v2 in g2.df.items():
                    df[k2] = df.get(k2, 0) + v2
            gs = GlobalStats(num_docs=num_docs, df=df)
        resp = search_shards(searchers, body or {}, index_name=",".join(names), global_stats=gs)
        # hits already carry per-hit owning index (fetch_phase uses the
        # searcher's own index_name)
        return resp

    def msearch(self, pairs: List[tuple]) -> dict:
        # batched fast path: the ELIGIBLE SUBSET of a single-concrete-
        # index batch executes as ONE fused kernel per segment
        # (search/batch.py partial batching); ineligible items (aggs,
        # sort, off-shape queries) ride the sequential loop below, and
        # typed malformed-query items become per-item failures
        pre: List[Optional[dict]] = [None] * len(pairs)
        if len(pairs) >= 2:
            # index may be a list (valid msearch header syntax) — those and
            # mixed-index batches take the sequential path
            names = {h.get("index") if isinstance(h.get("index"), str)
                     else None for h, _ in pairs}
            if len(names) == 1 and None not in names:
                try:
                    resolved = self.resolve_indices(next(iter(names)))
                except ElasticsearchTpuException:
                    resolved = []
                mh = getattr(self, "multihost", None)
                if len(resolved) == 1 and not (
                        mh is not None
                        and resolved[0] in mh.dist_indices):
                    # a distributed index's LOCAL service holds only the
                    # locally-owned shards — the fused batch would return
                    # partial results; the sequential loop below routes
                    # each request through the cross-host data plane
                    from elasticsearch_tpu.cluster.metadata import check_open
                    from elasticsearch_tpu.search.batch import try_batched_msearch

                    svc = self.indices[resolved[0]]
                    with self.tracer.span("msearch.batch_attempt") as sp:
                        try:
                            check_open(svc, op="read")  # closed/blocked → sequential
                            out = try_batched_msearch(
                                svc, [b for _, b in pairs])
                            sp.tag(outcome="declined" if out is None
                                   else "fused")
                        except Exception as e:
                            out = None  # sequential path is always correct
                            # ... and the cause is on record: the span's
                            # error (estpu_span_errors_total) and one log
                            # line a process
                            sp.tag(outcome="error")
                            sp.error = f"{type(e).__name__}: {e}"
                            _log_batch_error_once(e)
                    if out is not None:
                        pre = out
        from elasticsearch_tpu.search.batch import msearch_error_entry

        responses = []
        for (header, body), served in zip(pairs, pre):
            if served is not None:
                # fused-batch response, or a typed per-item failure the
                # partial-batch split already shaped (2.0 msearch error
                # strings like "IndexMissingException[no such index]")
                responses.append(served)
                continue
            try:
                responses.append(self.search(header.get("index"), body))
            except ElasticsearchTpuException as e:
                responses.append(msearch_error_entry(e))
        return {"responses": responses}

    def nodes_stats(self) -> dict:
        from elasticsearch_tpu.monitor.stats import (TRANSLOG_RECOVERY,
                                                     aggregate_recovery,
                                                     aggregate_slowlog,
                                                     device_stats, os_stats,
                                                     process_stats)

        from elasticsearch_tpu.monitor.stats import SearchStats
        from elasticsearch_tpu.native import native_available

        # seed keys from SearchStats itself: one source of truth
        search = {k: 0 for k in SearchStats().to_json()}
        indexing = {"index_total": 0, "delete_total": 0, "index_time_in_millis": 0}
        seg_count = seg_mem = 0
        fd_mem = fd_ev = 0
        tl_frames = tl_bytes = 0
        for svc in self.indices.values():
            for g in svc.groups:
                for shard in g.copies:
                    ss = shard.searcher.stats.to_json()
                    for k in search:
                        search[k] += ss.get(k, 0)
                    # per-shard write/segment stats come from the shard's own
                    # stats() — single source of truth (index/shard.py)
                    st = shard.stats()
                    for k in indexing:
                        indexing[k] += st["indexing"][k]
                    seg_count += st["segments"]["count"]
                    seg_mem += st["segments"]["memory_in_bytes"]
                    fd_mem += st["fielddata"]["memory_size_in_bytes"]
                    fd_ev += st["fielddata"]["evictions"]
                    tl_frames += st["translog"].get("corrupt_tail_events", 0)
                    tl_bytes += st["translog"].get(
                        "corrupt_tail_bytes_dropped", 0)
        from elasticsearch_tpu.monitor import kernels

        # node-wide kernel dispatch counters (which device program served
        # each query component) + mesh-vs-host routing counts
        snap = kernels.snapshot()
        search["kernels"] = snap
        # first-class fallback gauges (r4 verdict weak #5): a product query
        # class silently living on the host-fallback path must be visible
        # without digging through the kernels map
        search["mesh_fallback_total"] = snap.get("mesh_fallback_total", 0)
        search["span_clause_truncated"] = snap.get("span_clause_truncated", 0)
        search["mesh_host_by_design"] = snap.get("mesh_host_by_design", 0)
        proc = process_stats()
        return {
            "cluster_name": self.cluster_state.cluster_name,
            "nodes": {
                self.node_id: {
                    "name": self.name,
                    "indices": {
                        "docs": {"count": sum(s.num_docs for s in self.indices.values())},
                        "search": search,
                        "indexing": indexing,
                        "segments": {"count": seg_count,
                                     "memory_in_bytes": seg_mem},
                        # resident fielddata + the once-zero eviction
                        # counter, real since columns became evictable
                        "fielddata": {"memory_size_in_bytes": fd_mem,
                                      "evictions": fd_ev},
                        # translog replay damage accounting, aggregated
                        # from THIS node's own shards (the process-global
                        # event log with per-path detail lives in
                        # monitor/stats.py::TRANSLOG_RECOVERY)
                        "translog_recovery": {
                            "corrupt_tail_frames_skipped": tl_frames,
                            "corrupt_tail_bytes_dropped": tl_bytes,
                            "events": [
                                e for e in
                                TRANSLOG_RECOVERY.to_json()["events"]
                                if self._owns_translog_path(e["path"])],
                        },
                        # recovery accounting: incremental (ops-replay)
                        # vs full-copy streams, from this node's own
                        # RecoveryRegistry entries
                        "recovery": aggregate_recovery(
                            self.indices.values()),
                    },
                    "process": proc,
                    "os": os_stats(),
                    # ES response-shape parity: dashboards read jvm.mem.*;
                    # the honest numbers are the Python process's
                    "jvm": {"mem": {"heap_used_in_bytes":
                                    proc["mem"]["resident_in_bytes"]}},
                    # don't force pool creation just to report stats — the
                    # section is empty until REST traffic spins the pools
                    "thread_pool": (self._thread_pool.stats()
                                    if self._thread_pool is not None else {}),
                    "breakers": self._breaker_stats(),
                    # residency tiers: resident bytes + evict/rehydrate
                    # counters + the device-put accounting choke point
                    "resources": self._residency_stats(),
                    # transport info (reference: NodeInfo transport section;
                    # profiles {} = no extra transport profiles configured)
                    "transport": self._transport_info(),
                    # observability: in-flight/completed tasks + span ring
                    # + per-NODE slow-op counters (this node's indices
                    # only — in-process multi-node setups must not bleed
                    # counts across nodes)
                    "tasks": self.tasks.stats(),
                    "tracing": self.tracer.stats(),
                    # continuous metrics: histogram percentile summaries
                    # + counter totals — the JSON view of the same
                    # numbers GET /_prometheus/metrics exposes
                    "metrics": self.metrics.summaries(),
                    # serving front-end: coalescer queue depth/config +
                    # per-tenant QoS shares (serving/)
                    "serving": self.serving.stats(),
                    "slowlog": aggregate_slowlog(self.indices.values()),
                    # device-program observatory totals (key count,
                    # compiles, compile/execute seconds); the per-key
                    # table lives at /_nodes/_local/xla/programs and
                    # /_cat/programs (monitor/programs.py)
                    "programs": self._program_stats(),
                    # flight recorder ring counts + watchdog trip totals;
                    # the full rings live at /_nodes/_local/flight and in
                    # the /_cluster/diagnostics bundle
                    "flight": self.flight.stats(),
                    "watchdog": self.watchdog.stats(),
                    # TPU-native extra: device kind + per-device HBM usage
                    "accelerator": device_stats(),
                    # which translog/postings codec serves: the C++ one
                    # built from native/codec.cpp, or the numpy fallback
                    "native": {"codec": ("native" if native_available()
                                         else "python")},
                }
            },
        }

    def _transport_info(self) -> dict:
        """Transport section of node info/stats (reference:
        transport/TransportInfo.java): addresses + configured profiles
        (always {} here — profiles are a netty-transport concept; the
        multi-host TCP transport has a single default binding)."""
        mh = getattr(self, "multihost", None)
        addr = "local[in-process]"
        if mh is not None:
            local = getattr(mh, "local", None)
            addr = getattr(local, "transport_address", None) or addr
        return {"bound_address": [addr], "publish_address": addr,
                "profiles": {}}

    def _owns_translog_path(self, path: str) -> bool:
        """True when a recovery event's translog path lives under THIS
        node's data_path — keeps per-node stats per-node when several
        in-process nodes share the global event log."""
        if not self.data_path:
            return False
        return os.path.abspath(path).startswith(
            os.path.abspath(self.data_path) + os.sep)

    @staticmethod
    def _residency_stats() -> dict:
        from elasticsearch_tpu import resources

        return resources.RESIDENCY.stats()

    @staticmethod
    def _program_stats() -> dict:
        from elasticsearch_tpu.monitor import programs

        return programs.REGISTRY.stats()

    @staticmethod
    def _breaker_stats() -> dict:
        """ES-shaped `/_nodes/stats/breaker`: parent + fielddata/request/
        in_flight_requests (+ the accelerator-extra `segments`), real
        estimated/tripped numbers (resources/breakers.py)."""
        from elasticsearch_tpu import resources

        return resources.BREAKERS.stats()

    def info(self) -> dict:
        import jax

        return {
            "name": self.name,
            "cluster_name": self.cluster_state.cluster_name,
            "version": {
                "number": __version__,
                "build_flavor": "tpu",
                "lucene_version": "n/a (device-resident segments)",
            },
            "tagline": "You Know, for Search — on TPU",
            "devices": [str(d) for d in jax.devices()],
        }

    def close(self):
        # stop the watchdog tick thread and leave the process fan before
        # teardown: a detector must not race the indices closing under it
        watchdog = getattr(self, "watchdog", None)
        if watchdog is not None:
            watchdog.close()
        flight_rec = getattr(self, "flight", None)
        if flight_rec is not None:
            from elasticsearch_tpu.monitor import flight as flight_mod

            flight_mod.unregister(flight_rec)
        # drain the serving coalescer FIRST: parked requests must resolve
        # (sequentially) before the indices they target close
        serving = getattr(self, "serving", None)
        if serving is not None:
            serving.close()
        for svc in self.indices.values():
            svc.close()
        if self._ivf_dir is not None:
            # persist each index's observed program-key census into the
            # durable blob tier BEFORE unregistering it, so the next
            # process over this data_path can read the exact program
            # universe this one served (resources/census.py; pre-warm
            # input for ROADMAP #6)
            from elasticsearch_tpu.resources import census

            for name in self.indices:
                try:
                    census.store_census(name)
                except Exception:
                    pass  # census persistence is best-effort: a failed
                    # write costs the next process a warmup, never a close
            from elasticsearch_tpu.index import ivf_cache

            ivf_cache.unregister(self._ivf_dir)
            self._ivf_dir = None
        if self._thread_pool is not None:
            self._thread_pool.shutdown()
            self._thread_pool = None


_INVALID_NAME = re.compile(r'[\\/*?"<>| ,#:A-Z]')


def _validate_index_name(name: str):
    if not name or name.startswith(("_", "-", "+")) or _INVALID_NAME.search(name):
        raise IllegalArgumentException(f"invalid index name [{name}]")


def _deep_merge(dst: dict, src: dict):
    for k, v in src.items():
        if isinstance(v, dict) and isinstance(dst.get(k), dict):
            _deep_merge(dst[k], v)
        else:
            dst[k] = v
