"""Vectorised loaders of an MS-MARCO-shaped text index and a SIFT-shaped
vector index, for ``chip_smoke.py``'s width phase.

The corpus loads through the product's own segment structures
(index.segment.InvertedField/TpuSegment) built vectorized — 1M docs through
the per-doc Python parser would take most of an hour — and is injected into
a real ``Node``, so every query then flows through the unmodified search
stack. The scenarios this file once held (closed loops round in-process
``Node.search``) are gone: the benchmark is ``benchmarks/run.py`` over the
table in ``BENCHMARK.json``; ``PERF.md`` and ``PERF_LEDGER.jsonl`` hold the
numbers.
"""
from __future__ import annotations

import os

import numpy as np

K1, B = 1.2, 0.75

#: git-ignored scratch inside the checkout: the corpus cache lives here
CACHE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         ".bench_cache")


# ---------------------------------------------------------------------------
# corpus
# ---------------------------------------------------------------------------

def build_corpus(n_docs: int, vocab: int, seed: int):
    """Term-major postings CSR for a Zipfian synthetic corpus
    (MS-MARCO-like: ~60-token passages, Zipf vocabulary). Deterministic in
    (n_docs, vocab, seed), so the ~2-minute build at the 1M default is
    disk-cached; a cache failure falls through to a fresh build."""
    # the version token guards the cache against generator/constant
    # changes (a K1/B or distribution tweak must not silently serve
    # corpora built by older code)
    ver = f"v1_k{K1}b{B}"
    cache = os.path.join(CACHE_DIR,
                         f"corpus_{ver}_{n_docs}_{vocab}_{seed}.npz")
    try:
        z = np.load(cache)
        return (z["u_doc"], z["tf"], z["tfn"], z["offsets"], z["df"],
                z["idf"], z["doc_len"])
    except Exception:
        pass
    rng = np.random.default_rng(seed)
    doc_len = np.clip(rng.normal(60, 15, n_docs), 20, 120).astype(np.int64)
    nnz_tok = int(doc_len.sum())
    terms = rng.zipf(1.15, nnz_tok).astype(np.int64)
    terms = np.where(terms >= vocab, rng.integers(1, vocab, nnz_tok), terms)
    docs = np.repeat(np.arange(n_docs, dtype=np.int64), doc_len)

    key = terms * n_docs + docs
    uniq, tf = np.unique(key, return_counts=True)
    u_term = (uniq // n_docs).astype(np.int32)
    u_doc = (uniq % n_docs).astype(np.int32)
    df = np.bincount(u_term, minlength=vocab).astype(np.int32)
    offsets = np.zeros(vocab + 1, np.int64)
    offsets[1:] = np.cumsum(df)

    avg = doc_len.mean()
    tfn = (tf * (K1 + 1) / (tf + K1 * (1 - B + B * doc_len[u_doc] / avg))
           ).astype(np.float32)
    idf = np.log(1 + (n_docs - df + 0.5) / (df + 0.5)).astype(np.float32)
    tf = tf.astype(np.float32)
    try:
        os.makedirs(os.path.dirname(cache), exist_ok=True)
        tmp = cache + f".{os.getpid()}.tmp.npz"  # savez keeps .npz names
        np.savez(tmp, u_doc=u_doc, tf=tf, tfn=tfn, offsets=offsets, df=df,
                 idf=idf, doc_len=doc_len)
        os.replace(tmp, cache)
    except Exception:
        pass  # cache is best-effort
    return u_doc, tf, tfn, offsets, df, idf, doc_len


def make_msmarco_node(u_doc, tf, tfn, offsets, df, doc_len, n_docs, vocab):
    """A real Node serving the corpus: the segment is built through the
    product's own structures (vectorized load) and injected into shard 0's
    engine; every query then flows through the unmodified search stack."""
    import jax

    from elasticsearch_tpu.index.segment import InvertedField, TpuSegment
    from elasticsearch_tpu.node import Node
    from elasticsearch_tpu.utils.shapes import pad_to, pow2_bucket

    D = pow2_bucket(n_docs, minimum=64)
    nnz = u_doc.shape[0]
    nnz_pad = pow2_bucket(nnz, minimum=8)
    term_ids = np.repeat(np.arange(vocab, dtype=np.int32), df)
    inv = InvertedField(
        name="body",
        vocab={f"t{t}": t for t in range(vocab)},
        terms=[f"t{t}" for t in range(vocab)],
        df=df,
        cf=df.astype(np.int64),
        offsets=offsets,
        doc_ids=jax.device_put(pad_to(u_doc, nnz_pad, D)),
        tf=jax.device_put(pad_to(tf, nnz_pad, 0.0)),
        tfnorm=jax.device_put(pad_to(tfn, nnz_pad, 0.0)),
        term_ids=jax.device_put(pad_to(term_ids, nnz_pad, vocab)),
        nnz=nnz,
        num_docs=n_docs,
        total_terms=int(doc_len.sum()),
        avg_len=float(doc_len.mean()),
        doc_ids_host=u_doc,
        tfnorm_host=tfn,
        max_docs=D,
    )
    lens = np.zeros(D, np.float32)
    lens[:n_docs] = doc_len
    seg = TpuSegment(
        num_docs=n_docs, max_docs=D,
        inverted={"body": inv}, numerics={}, keywords={}, vectors={},
        sources=[None] * n_docs, stored=[None] * n_docs,
        ids=[str(i) for i in range(n_docs)], id_map={},
        field_lengths={"body": jax.device_put(lens)},
    )
    node = Node(name="bench")
    node.create_index("msmarco", {
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {"body": {"type": "text"}}}})
    node.indices["msmarco"].shards[0].engine.segments.append(seg)
    return node, seg


def make_sift_node(n_vecs: int, dims: int, seed: int):
    import jax

    from elasticsearch_tpu.index.segment import TpuSegment, VectorColumn
    from elasticsearch_tpu.node import Node
    from elasticsearch_tpu.utils.shapes import pow2_bucket

    rng = np.random.default_rng(seed + 7)
    # SIFT-like: clustered enough that IVF probing is meaningful, with
    # within-cluster similarity gaps wide enough that bf16 MXU scoring
    # resolves true neighbors (SIFT1M's own gaps are comfortably > bf16 eps)
    n_clusters = 256
    cents = rng.standard_normal((n_clusters, dims)).astype(np.float32)
    assign = rng.integers(0, n_clusters, n_vecs)
    vecs = (cents[assign]
            + rng.standard_normal((n_vecs, dims)).astype(np.float32))
    D = pow2_bucket(n_vecs, minimum=64)
    vpad = np.zeros((D, dims), np.float32)
    vpad[:n_vecs] = vecs
    exists = np.zeros(D, bool)
    exists[:n_vecs] = True
    vc = VectorColumn(name="emb", vecs=jax.device_put(vpad),
                      exists=jax.device_put(exists), dims=dims,
                      vecs_host=vpad, exists_host=exists,
                      similarity="cosine")
    seg = TpuSegment(
        num_docs=n_vecs, max_docs=D,
        inverted={}, numerics={}, keywords={}, vectors={"emb": vc},
        sources=[None] * n_vecs, stored=[None] * n_vecs,
        ids=[str(i) for i in range(n_vecs)], id_map={},
        field_lengths={},
    )
    node = Node(name="bench-sift")
    node.create_index("sift", {
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {
            "emb": {"type": "dense_vector", "dims": dims,
                    "similarity": "cosine",
                    "index_options": {"type": "ivf"}}}}})
    node.indices["sift"].shards[0].engine.segments.append(seg)
    return node, seg, vecs


# ---------------------------------------------------------------------------
# queries
# ---------------------------------------------------------------------------

def make_queries(n_q: int, vocab: int, df: np.ndarray, seed: int,
                 terms_per_q: int = 4):
    """Mixed Zipfian queries as term-id lists."""
    rng = np.random.default_rng(seed + 1)
    qs = []
    for _ in range(n_q):
        npick = rng.integers(2, terms_per_q + 1)
        t = rng.zipf(1.3, npick).astype(np.int64)
        t = np.where((t >= vocab) | (df[np.clip(t, 0, vocab - 1)] == 0),
                     rng.integers(1, vocab, npick), t)
        qs.append(np.unique(t))
    return qs
