"""``dense_vector_shard``: one frozen dense-vector segment, a pool of exact
kNN queries, the float64 distance reference (moved from ``loaders.py``,
body unchanged)."""
from __future__ import annotations

import numpy as np

from benchmarks.data import vectors as vector_data
from benchmarks.loaders import Loaded, _sized
from benchmarks.reference.knn import KnnReference


class VectorShards(Loaded):
    def __init__(self, cfg: dict, seed: int, devices, rehearse: bool):
        from elasticsearch_tpu.index.segment import TpuSegment, VectorColumn
        from elasticsearch_tpu.node import Node
        from elasticsearch_tpu.utils.shapes import pow2_bucket

        import jax

        cfg = _sized(cfg, rehearse)
        self.cfg = cfg
        self.index, self.field = cfg["index"], cfg["field"]
        self.k = int(cfg["k"])
        n, dims = int(cfg["vectors"]), int(cfg["dims"])
        if int(cfg["shards"]) != 1:
            raise ValueError("dense_vector_shard loads one shard")
        D = pow2_bucket(n, minimum=64)
        mix = cfg["mixture"]
        slab, queries = vector_data.make_vectors(
            n, D, dims, seed, clusters=mix["clusters"],
            spread=mix["spread"], n_queries=cfg["queries"]["pool"],
            decimals=cfg["queries"]["decimals"], device=devices[0])
        vecs_host = np.asarray(slab)  # the engine's host mirror
        self.queries = np.asarray(queries)
        self.pool_size = self.queries.shape[0]
        exists = np.zeros(D, bool)
        exists[:n] = True
        self.reference = KnnReference(vecs_host[:n], self.queries,
                                      cfg["similarity"])
        vc = VectorColumn(
            name=self.field, vecs=slab,
            exists=jax.device_put(exists, devices[0]), dims=dims,
            vecs_host=vecs_host, exists_host=exists,
            similarity=cfg["similarity"])
        seg = TpuSegment(
            num_docs=n, max_docs=D, inverted={}, numerics={}, keywords={},
            vectors={self.field: vc}, sources=[None] * n, stored=[None] * n,
            ids=[str(i) for i in range(n)], id_map={}, field_lengths={})
        node = Node(name="bench", data_path=cfg.get("data_path"))
        node.create_index(self.index, {
            "settings": {"number_of_shards": 1},
            "mappings": {"properties": {self.field: {
                "type": "dense_vector", "dims": dims,
                "similarity": cfg["similarity"]}}}})
        node.indices[self.index].shards[0].engine.segments.append(seg)
        self.node = node
        self.n, self.dims = n, dims
        self.slab_bytes = float(D * dims * slab.dtype.itemsize)
        self.info = {"shards": 1, "vectors": n, "slots": D, "dims": dims,
                     "slab_bytes": self.slab_bytes}

    def request(self, i: int) -> dict:
        return {"query": {"knn": {
            "field": self.field,
            "query_vector": [float(x) for x in self.queries[i].tolist()],
            "k": self.k, "ann": False}},
            "size": self.k, "_source": False}

    def work(self, i: int) -> dict:
        # 2*N*dims flop a query; the slab is read once a device batch
        return {"flop": 2.0 * self.n * self.dims, "bytes": 0.0,
                "batch_bytes": self.slab_bytes}


load = VectorShards
