"""``ann_vector_shard``: one frozen dense-vector segment mapped with an
approximate index (``index_options``), a pool of approximate kNN
queries, the exact reference of ``reference/ann.py``.

The slab is made on the device and stays there; the index is built at
set-up through the program's own index-time entry point
(``VectorColumn.build_ann``, what a segment freeze calls), so ``setup_s``
holds the build and no search pays for it. A program without that entry
point, or one that builds no index, fails here, at load. An answer is a
top-k of hits held to the exact neighbours by recall (ANN-Benchmarks'
rule) and by the exact score of every hit returned. The control is the
exact top-k with its second half swapped for the next ranks, its scores
rounded to bfloat16.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.data.descriptors import make_descriptors
from benchmarks.loaders import Loaded, _sized
from benchmarks.metrics import counters
from benchmarks.reference import ann

BUILD_SPANS = ("ann.build", "ann.kmeans", "ann.assign", "ann.pq_train",
               "ann.pq_encode")


class AnnVectorShard(Loaded):
    def __init__(self, cfg: dict, seed: int, devices, rehearse: bool):
        import jax

        from elasticsearch_tpu.index.segment import TpuSegment, VectorColumn
        from elasticsearch_tpu.node import Node
        from elasticsearch_tpu.utils.shapes import pow2_bucket

        cfg = _sized(cfg, rehearse)
        self.cfg = cfg
        self.index, self.field = cfg["index"], cfg["field"]
        self.k = int(cfg["k"])
        n, dims = int(cfg["vectors"]), int(cfg["dims"])
        if int(cfg["shards"]) != 1 or cfg["similarity"] != "cosine":
            raise ValueError("ann_vector_shard loads one cosine shard")
        opts = dict(cfg["index_options"])
        q = cfg["queries"]
        self.num_candidates = int(q["num_candidates"])
        D = pow2_bucket(n, minimum=64)
        mix = cfg["mixture"]
        device = devices[0] if devices else jax.devices()[0]
        slab, queries = make_descriptors(
            n, D, dims, seed, clusters=mix["clusters"], decay=mix["decay"],
            spread=mix["spread"], n_queries=q["pool"],
            decimals=q["decimals"], device=device)
        vecs_host = np.asarray(slab)  # the engine's host mirror
        self.queries = queries
        self.pool_size = queries.shape[0]
        exists = np.zeros(D, bool)
        exists[:n] = True
        self.reference = ann.AnnReference(vecs_host[:n], queries, self.k)
        vc = VectorColumn(
            name=self.field, vecs=slab,
            exists=jax.device_put(exists, device), dims=dims,
            vecs_host=vecs_host, exists_host=exists,
            similarity=cfg["similarity"], index_options=opts)
        seg = TpuSegment(
            num_docs=n, max_docs=D, inverted={}, numerics={}, keywords={},
            vectors={self.field: vc}, sources=[None] * n, stored=[None] * n,
            ids=[str(i) for i in range(n)], id_map={}, field_lengths={})
        node = Node(name="bench", data_path=cfg.get("data_path"))
        node.create_index(self.index, {
            "settings": {"number_of_shards": 1},
            "mappings": {"properties": {self.field: {
                "type": "dense_vector", "dims": dims,
                "similarity": cfg["similarity"], "index_options": opts}}}})
        t0 = time.monotonic()
        vc.build_ann(D, tracer=node.tracer)
        build_s = time.monotonic() - t0
        ivf, pq = vc.get_ivf(D), vc.get_pq(D)
        if ivf is None or pq is None:
            raise RuntimeError(f"the program built no IVF-PQ index over "
                               f"{n} vectors (ivf {ivf}, pq {pq})")
        node.indices[self.index].shards[0].engine.segments.append(seg)
        self.node = node
        self.n, self.dims = n, dims
        self.C, self.M, self.K = ivf.C, pq.M, pq.K
        self.nprobe = ivf.nprobe_for(self.num_candidates)
        self.fine_k = min(pow2_bucket(max(8 * self.k, 128)), D)
        self.real = self._probed_lengths(ivf)
        spans = counters.parse(node.metrics.expose())
        self.info = {
            "shards": 1, "vectors": n, "slots": D, "dims": dims,
            "lists": ivf.C, "Lmax": ivf.Lmax, "nprobe": self.nprobe,
            "candidate_slots": self.nprobe * ivf.Lmax,
            "candidates_mean": float(self.real.mean()),
            "pq": {"m": pq.M, "k": pq.K}, "build_s": round(build_s, 3),
            "build_spans_s": {name: round(counters.total(spans, [{
                "family": "estpu_span_duration_seconds_sum",
                "labels": {"span": name}}]), 3) for name in BUILD_SPANS}}

    def _probed_lengths(self, ivf) -> np.ndarray:
        """Real vectors in the lists each pool query probes: its nprobe
        nearest centroids by angle, as the program probes them."""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def probed(qs, cents, lens):
            cn = cents / jnp.maximum(
                jnp.linalg.norm(cents, axis=-1, keepdims=True), 1e-12)
            _, top = jax.lax.top_k(qs @ cn.T, self.nprobe)
            return jnp.sum(lens[top], axis=1)

        qs = self.queries.astype(np.float32)
        out = [np.asarray(probed(jnp.asarray(qs[lo:lo + 512]),
                                 ivf.centroids, ivf.list_lens))
               for lo in range(0, len(qs), 512)]
        return np.concatenate(out).astype(np.float64)

    def request(self, i: int) -> dict:
        return {"query": {"knn": {
            "field": self.field,
            "query_vector": [float(x) for x in self.queries[i].tolist()],
            "k": self.k, "num_candidates": self.num_candidates}},
            "size": self.k, "_source": False}

    def compare(self, sample: list) -> dict:
        return ann.compare(self.reference, sample)

    def control(self, pool: list) -> list:
        return self.reference.control(pool)

    def work(self, i: int) -> dict:
        """What the algorithm cannot do without for query ``i``: one read
        of the centroids and their 2 C dims flop; the ids and M-byte codes
        of the real vectors in the lists it probes, an add a code for the
        ADC's lookups, the table itself (2 K dims flop); the exact re-rank
        of ``fine_k`` survivors (their rows, 2 dims flop each). Not the
        padding of the lists, the [D]-wide expansion or the top-k over
        every slot: those show as the share they cost."""
        real = self.real[i]
        f32, i32 = 4.0, 4.0
        return {"flop": (2.0 * self.C * self.dims + real * self.M
                         + 2.0 * self.K * self.dims
                         + 2.0 * self.fine_k * self.dims),
                "bytes": (self.C * self.dims * f32 + real * (i32 + self.M)
                          + self.fine_k * self.dims * f32),
                "batch_bytes": 0.0}


load = AnnVectorShard
