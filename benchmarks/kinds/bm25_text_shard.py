"""``bm25_text_shard``: a frozen BM25 text segment a shard, a pool of match
queries, the float64 BM25 reference (moved from ``loaders.py``, body
unchanged)."""
from __future__ import annotations

import numpy as np

from benchmarks.data import text as text_data
from benchmarks.loaders import Loaded, _sized
from benchmarks.reference.bm25 import Bm25Reference


class TextShards(Loaded):
    def __init__(self, cfg: dict, seed: int, devices, rehearse: bool):
        import jax

        from elasticsearch_tpu.index.segment import InvertedField, TpuSegment
        from elasticsearch_tpu.node import Node
        from elasticsearch_tpu.utils.shapes import pad_to, pow2_bucket

        cfg = _sized(cfg, rehearse)
        self.cfg = cfg
        self.index, self.field = cfg["index"], cfg["field"]
        self.size = self.k = int(cfg["size"])
        n_shards = int(cfg["shards"])
        n_docs, vocab = int(cfg["documents_per_shard"]), int(cfg["vocab"])
        k1, b = float(cfg["bm25"]["k1"]), float(cfg["bm25"]["b"])
        self.shards = [
            text_data.make_corpus(
                n_docs, vocab, seed if s == 0 else seed * 4 + s,
                postings_per_doc=cfg["postings_per_doc"],
                exponent=cfg["zipf_exponent"],
                df_cap_share=cfg["df_cap_share"])
            for s in range(n_shards)]
        q = cfg["queries"]
        # the pool is the configuration's (fixed seed): term ids are ranks
        # of the df law, which no seed changes, so every run holds the same
        # set of queries over another corpus
        self.pool = text_data.make_queries(
            self.shards[0], q["pool_seed"], n_queries=q["pool"],
            min_terms=q["min_terms"], max_terms=q["max_terms"])
        self.pool_size = len(self.pool)
        self.reference = Bm25Reference(self.shards, k1, b, self.pool)

        terms = [f"t{t}" for t in range(vocab)]
        term_vocab = {t: i for i, t in enumerate(terms)}
        D = pow2_bucket(n_docs, minimum=64)
        node = Node(name="bench", data_path=cfg.get("data_path"))
        node.create_index(self.index, {
            "settings": {"number_of_shards": n_shards},
            "mappings": {"properties": {self.field: {"type": "text"}}}})
        self.posting_bytes = 0
        for s, c in enumerate(self.shards):
            dev = devices[s % len(devices)]
            put = lambda a: jax.device_put(a, dev)  # noqa: E731
            nnz_pad = pow2_bucket(c.nnz, minimum=8)
            tf = c.tf.astype(np.float32)
            avg = float(c.doc_len.mean())
            # the engine's own derived column (the reference derives its
            # own, in float64, from tf and doc_len)
            tfn = (tf * (k1 + 1.0) / (tf + k1 * (
                1.0 - b + b * c.doc_len[c.doc_ids].astype(np.float32) / avg))
                   ).astype(np.float32)
            term_ids = np.repeat(np.arange(vocab, dtype=np.int32), c.df)
            inv = InvertedField(
                name=self.field, vocab=term_vocab, terms=terms,
                df=c.df.astype(np.int32), cf=c.df.astype(np.int64),
                offsets=c.offsets,
                doc_ids=put(pad_to(c.doc_ids, nnz_pad, D)),
                tf=put(pad_to(tf, nnz_pad, 0.0)),
                tfnorm=put(pad_to(tfn, nnz_pad, 0.0)),
                term_ids=put(pad_to(term_ids, nnz_pad, vocab)),
                nnz=c.nnz, num_docs=n_docs,
                total_terms=int(c.doc_len.sum()), avg_len=avg,
                doc_ids_host=c.doc_ids, tfnorm_host=tfn, max_docs=D)
            self.posting_bytes = (inv.doc_ids.dtype.itemsize
                                  + inv.tfnorm.dtype.itemsize)
            lens = np.zeros(D, np.float32)
            lens[:n_docs] = c.doc_len
            base = s * n_docs
            seg = TpuSegment(
                num_docs=n_docs, max_docs=D,
                inverted={self.field: inv}, numerics={}, keywords={},
                vectors={}, sources=[None] * n_docs, stored=[None] * n_docs,
                ids=[str(base + i) for i in range(n_docs)], id_map={},
                field_lengths={self.field: put(lens)})
            node.indices[self.index].shards[s].engine.segments.append(seg)
        self.node = node
        self.score_bytes = 4  # one f32 score a live document for the top-k
        self.info = {"shards": n_shards, "documents_per_shard": n_docs,
                     "slots_per_shard": D, "vocab": vocab,
                     "postings": [c.nnz for c in self.shards],
                     "postings_padded": pow2_bucket(self.shards[0].nnz, 8),
                     "avg_len": float(self.shards[0].doc_len.mean())}

    def request(self, i: int) -> dict:
        return {"query": {"match": {self.field: " ".join(
            f"t{t}" for t in self.pool[i])}},
            "size": self.size, "_source": False}

    def group(self, name: str, arg) -> list:
        """``top_df_terms_only``: n -> the queries whose every term is among
        the n most frequent of shard 0 (ties to the lower term id): with n
        no more than the rows of the product's dense impact block, the
        queries that a coalesced batch scores by one matmul program."""
        if name != "top_df_terms_only":
            return super().group(name, arg)
        top = np.argsort(-self.shards[0].df, kind="stable")[:int(arg)]
        return [i for i, terms in enumerate(self.pool)
                if np.isin(terms, top).all()]

    def work(self, i: int) -> dict:
        terms = self.pool[i]
        postings = sum(int(c.df[terms].sum()) for c in self.shards)
        docs = sum(c.n_docs for c in self.shards)
        return {"flop": 2.0 * postings,
                "bytes": float(postings * self.posting_bytes
                               + docs * self.score_bytes),
                "batch_bytes": 0.0}


load = TextShards
