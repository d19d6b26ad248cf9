"""``keyword_terms_shard``: a small seeded shard of keyword fields, indexed
through ``_bulk``; a pool of ``size: 0`` searches, each a ``terms``
aggregation over one field under a ``term`` filter on another. An answer is
the reply's ``aggregations`` object; it is held, exactly, to a plain
``bincount`` of the generator's raw output. The control breaks the one
guarantee the configuration states: it answers from every document but
one."""
from __future__ import annotations

import json

import numpy as np

from benchmarks.loaders import Loaded, _sized


class KeywordShard(Loaded):
    def __init__(self, cfg: dict, seed: int, devices, rehearse: bool):
        from elasticsearch_tpu.node import Node
        from elasticsearch_tpu.rest.server import RestController

        cfg = _sized(cfg, rehearse)
        self.index = cfg["index"]
        self.fields = {f: int(c) for f, c in cfg["fields"].items()}
        self.filter_field, self.regions = (cfg["filter_field"],
                                           int(cfg["regions"]))
        n = int(cfg["documents"])
        rng = np.random.default_rng([int(seed), 0xA66])
        # the generator's raw output: one small integer a field a document
        self.raw = {f: rng.integers(0, c, n) for f, c in self.fields.items()}
        self.raw[self.filter_field] = rng.integers(0, self.regions, n)
        # one pool entry a (field, region); a shape is a field's entries
        self.pool = [(f, r) for f in self.fields for r in range(self.regions)]
        self.pool_size = len(self.pool)
        self.shapes = {f"terms.{f}": [i for i, (g, _) in enumerate(self.pool)
                                      if g == f] for f in self.fields}
        node = Node(name="bench", data_path=cfg.get("data_path"))
        node.create_index(self.index, {
            "settings": {"number_of_shards": 1},
            "mappings": {"properties": {
                f: {"type": "keyword"} for f in self.raw}}})
        lines = []
        for d in range(n):
            lines.append(json.dumps({"index": {"_index": self.index,
                                               "_id": str(d)}}))
            lines.append(json.dumps({f: f"{f}-{int(v[d])}"
                                     for f, v in self.raw.items()}))
        status, reply = RestController(node).dispatch(
            "POST", "/_bulk", {"refresh": "true"},
            ("\n".join(lines) + "\n").encode())
        if status != 200 or reply.get("errors"):
            raise RuntimeError(f"_bulk failed: {status} {str(reply)[:300]}")
        self.node = node
        self.info = {"documents": n, "fields": self.fields,
                     "regions": self.regions}

    def request(self, i: int) -> dict:
        field, region = self.pool[i]
        return {"size": 0,
                "query": {"term": {
                    self.filter_field: f"{self.filter_field}-{region}"}},
                "aggs": {"by": {"terms": {"field": field,
                                          "size": self.fields[field]}}}}

    def answer(self, reply: dict):
        if ("error" not in reply and not reply.get("timed_out")
                and isinstance(reply.get("aggregations"), dict)):
            return reply["aggregations"]
        return None

    def _counts(self, i: int, drop: int = -1) -> dict:
        """{bucket key: documents} by a plain count of the raw output;
        ``drop`` leaves one matching document out (the control)."""
        field, region = self.pool[i]
        rows = np.flatnonzero(self.raw[self.filter_field] == region)
        if drop >= 0:
            rows = np.delete(rows, drop)
        counts = np.bincount(self.raw[field][rows],
                             minlength=self.fields[field])
        return {f"{field}-{v}": int(c) for v, c in enumerate(counts) if c}

    def compare(self, sample: list) -> dict:
        wrong, faults = 0, []
        for i, aggs in sample:
            try:
                got = {b["key"]: int(b["doc_count"])
                       for b in aggs["by"]["buckets"]}
            except (KeyError, TypeError, ValueError) as e:
                got = f"malformed: {e!r}"
            if got != self._counts(i):
                wrong += 1
                if len(faults) < 5:
                    faults.append(f"pool entry {i}: {str(got)[:120]}")
        return {"numbers": {"wrong_buckets": wrong}, "faults": faults,
                "compared": len(sample)}

    def control(self, pool: list) -> list:
        return [(i, {"by": {"buckets": [
            {"key": k, "doc_count": c}
            for k, c in self._counts(i, drop=0).items()]}})
            for i in pool]

    def work(self, i: int) -> dict:
        # one pass over the two columns' ordinals
        n = len(self.raw[self.filter_field])
        return {"flop": 0.0, "bytes": 8.0 * n, "batch_bytes": 0.0}


load = KeywordShard
