"""IndexShard: one shard = engine (write path) + searcher (read path).

Reference: org/elasticsearch/index/shard/IndexShard.java — lifecycle
(CREATED→RECOVERING→STARTED), stats, and the engine/searcher pairing.
"""
from __future__ import annotations

import os
from typing import Optional

from elasticsearch_tpu.analysis.registry import AnalysisRegistry
from elasticsearch_tpu.index.engine import Engine
from elasticsearch_tpu.index.mappings import Mappings
from elasticsearch_tpu.search.service import ShardSearcher


class IndexShard:
    def __init__(
        self,
        index_name: str,
        shard_id: int,
        mappings: Mappings,
        analysis: AnalysisRegistry,
        data_path: Optional[str] = None,
        device=None,
    ):
        self.index_name = index_name
        self.shard_id = shard_id
        # this copy's chip (parallel/placement.py), None on a one-shard
        # index or a one-device host: the default device
        self.device = device
        self.state = "CREATED"
        translog_path = None
        if data_path:
            translog_path = os.path.join(data_path, index_name, str(shard_id), "translog")
        self.engine = Engine(mappings, analysis, translog_path=translog_path,
                             index_name=index_name, device=device)
        self.searcher = ShardSearcher(self.engine.segments, mappings, analysis,
                                      shard_ord=shard_id, index_name=index_name)
        self.state = "STARTED"

    def recover(self) -> int:
        self.state = "RECOVERING"
        replayed = self.engine.recover_from_translog()
        self.engine.refresh()
        self.state = "STARTED"
        return replayed

    @property
    def segments(self):
        return self.engine.segments

    def refresh(self):
        self.engine.refresh()
        # searcher holds the same list object; refresh keeps it in sync
        self.searcher.segments = self.engine.segments

    def stats(self) -> dict:
        e = self.engine.stats
        segs = self.engine.segments
        fd_fields: dict = {}
        fd_evictions = fd_rehydrations = 0
        for seg in segs:
            for fname, b in seg.fielddata_field_bytes().items():
                fd_fields[fname] = fd_fields.get(fname, 0) + b
            ev, rh = seg.fielddata_evictions()
            fd_evictions += ev
            fd_rehydrations += rh
        comp_fields = self._completion_sizes(segs)
        indexing = {"index_total": e.index_total,
                    "delete_total": e.delete_total,
                    "index_time_in_millis": int(e.index_time_ms)}
        if e.types:
            indexing["types"] = {t: dict(ts) for t, ts in e.types.items()}
        return {
            "docs": {"count": self.engine.num_docs},
            "indexing": indexing,
            "get": {"total": e.get_total},
            "search": self.searcher.stats.to_json(),
            "refresh": {"total": e.refresh_total},
            "flush": {"total": e.flush_total},
            "merges": {"total": e.merge_total},
            "segments": {
                "count": len(segs),
                "memory_in_bytes": sum(s.memory_bytes() for s in segs),
            },
            # resident bytes + REAL evict/rehydrate counters: columns load
            # lazily into the evictable fielddata tier now
            # (resources/residency.py), so these move under HBM pressure
            "fielddata": {
                "memory_size_in_bytes": sum(fd_fields.values()),
                "evictions": fd_evictions,
                "rehydrations": fd_rehydrations,
                "fields": {f: {"memory_size_in_bytes": b}
                           for f, b in fd_fields.items()},
            },
            "completion": {
                "size_in_bytes": sum(comp_fields.values()),
                "fields": {f: {"size_in_bytes": b}
                           for f, b in comp_fields.items()},
            },
            # full TranslogStats shape (ops/generation/bytes/last_sync +
            # tragic/corruption accounting) for the monitor endpoint
            "translog": self.engine.translog.stats(),
            # replication safety (reference: SeqNoStats in the _stats
            # shards level): what checkpoint-based recovery negotiates on
            "seq_no": self.engine.seq_no_stats(),
            # Lucene CommitStats analogue: stable engine identity +
            # refresh/flush generation (the `shards` level echoes it)
            "commit": {"id": self.engine.commit_id,
                       "generation": e.refresh_total + e.flush_total + 1},
        }

    def _completion_sizes(self, segs) -> dict:
        """Per-field bytes held by the completion suggester's sorted
        prefix arrays (reference: CompletionStats per-field FST sizes)."""
        comp_names = [fm.name for fm in self.searcher.mappings.all_fields()
                      if getattr(fm, "type", None) == "completion"]
        if not comp_names:
            return {}
        from elasticsearch_tpu.search.suggest import _segment_completions

        out: dict = {}
        for seg in segs:
            for fname in comp_names:
                inputs, meta = _segment_completions(seg, fname)
                if not inputs:
                    continue
                b = sum(len(s.encode()) + 16 for s in inputs)
                b += sum(len(str(m[2]).encode()) for m in meta)
                out[fname] = out.get(fname, 0) + b
        return out

    def close(self):
        self.engine.close()
        self.state = "CLOSED"
