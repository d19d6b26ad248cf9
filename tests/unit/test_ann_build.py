"""The IVF-PQ index at a deployment's size, held to plain numpy at a small
one: the chunked, sampled k-means and the whole-set Lloyd steps, the list
fill and the loop it replaced, every vector in the list of its nearest
centroid and every code its nearest codeword, the mapping's ``nlist`` and
``m``, the ``ivf_pq_search`` program against a numpy IVF-PQ search over
the same index, and a ``_search`` through ``Node`` against brute force."""
import numpy as np
import pytest

from elasticsearch_tpu.ops import ivf as ivf_ops
from elasticsearch_tpu.ops.ivf import (build_ivf, fill_lists, kmeans,
                                        make_ivf_pq_search, sample_rows)
from elasticsearch_tpu.ops.pq import build_pq


def _clustered(n, dims, clusters, seed, spread=1.0):
    rng = np.random.default_rng(seed)
    cents = rng.standard_normal((clusters, dims)).astype(np.float32) * 3
    x = cents[rng.integers(0, clusters, n)] + spread * rng.standard_normal(
        (n, dims)).astype(np.float32)
    return x.astype(np.float32)


def _affinity(x, c, metric):
    x, c = x.astype(np.float64), c.astype(np.float64)
    if metric in ("l2", "l2_norm"):
        return x @ c.T - 0.5 * (c * c).sum(1)[None, :]
    return x @ (c / np.maximum(np.linalg.norm(c, axis=1,
                                              keepdims=True), 1e-12)).T


def _lloyd(x, C, iters, metric):
    """The whole-set form: every row in every step, one final pass."""
    cents = x[::max(len(x) // C, 1)][:C].astype(np.float64)
    for _ in range(iters):
        a = _affinity(x, cents, metric).argmax(1)
        for c in range(C):
            rows = x[a == c]
            if len(rows):
                cents[c] = rows.mean(0)
    return cents, _affinity(x, cents, metric).argmax(1)


@pytest.fixture
def small_chunks(monkeypatch):
    """Chunks of 64 rows: several a pass, the last one short."""
    monkeypatch.setattr(ivf_ops, "_CHUNK_CAP", 64)


@pytest.mark.parametrize("metric", ["cosine", "l2_norm"])
def test_chunked_kmeans_is_the_whole_set_form(small_chunks, metric):
    x = _clustered(1000, 8, 12, seed=3)
    assert ivf_ops.chunk_rows(12, 1000) == 64 and 1000 % 64
    cents, assign = kmeans(x, 12, iters=6, metric=metric)
    want_c, want_a = _lloyd(x, 12, 6, metric)
    np.testing.assert_allclose(cents, want_c, rtol=1e-4, atol=1e-4)
    assert (assign == want_a).mean() > 0.999


def test_a_sample_that_is_the_whole_set_trains_as_the_whole_set():
    x = _clustered(600, 8, 6, seed=4)
    a = kmeans(x, 6, iters=4, metric="l2", n_train=None)
    b = kmeans(x, 6, iters=4, metric="l2", n_train=600)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])


def test_the_sample_is_seeded_sorted_and_without_repeats():
    ids = np.arange(0, 5000, 3)
    s1, s2 = sample_rows(ids, 400, 7), sample_rows(ids, 400, 7)
    np.testing.assert_array_equal(s1, s2)
    assert len(set(s1.tolist())) == 400 and np.all(np.diff(s1) > 0)
    assert set(s1.tolist()) <= set(ids.tolist())
    assert not np.array_equal(s1, sample_rows(ids, 400, 8))
    np.testing.assert_array_equal(sample_rows(ids, 10 ** 6, 7), ids)


def test_the_vectorised_list_fill_is_the_loop():
    rng = np.random.default_rng(5)
    ids = np.sort(rng.choice(4000, 1500, replace=False)).astype(np.int32)
    assign = rng.integers(0, 37, ids.size)
    lists, counts = fill_lists(ids, assign, 37, sentinel=4096)
    Lmax = lists.shape[1]
    want = np.full((37, Lmax), 4096, np.int32)
    fill = np.zeros(37, np.int64)
    for i, a in zip(ids, assign):
        want[a, fill[a]] = i
        fill[a] += 1
    np.testing.assert_array_equal(lists, want)
    np.testing.assert_array_equal(counts, fill)


@pytest.mark.parametrize("metric", ["cosine", "l2_norm"])
def test_each_live_vector_sits_in_the_list_of_its_nearest_centroid(metric):
    import jax

    D, n, dims = 4096, 3000, 16
    vecs = np.zeros((D, dims), np.float32)
    vecs[:n] = _clustered(n, dims, 20, seed=6)
    exists = np.zeros(D, bool)
    exists[:n] = True
    exists[::7] = False  # deleted rows are in no list
    idx = build_ivf(jax.device_put(vecs), exists, D, C=24, metric=metric,
                    vecs_host=vecs)
    assert idx.C == 24
    lists = np.asarray(idx.lists)
    live = np.nonzero(exists)[0]
    listed = lists[lists < D]
    np.testing.assert_array_equal(np.sort(listed), live)
    where = {int(v): c for c in range(idx.C) for v in lists[c] if v < D}
    nearest = _affinity(vecs[live], np.asarray(idx.centroids),
                        metric).argmax(1)
    assert all(where[int(i)] == c for i, c in zip(live, nearest))


@pytest.mark.parametrize("metric", ["cosine", "l2_norm"])
def test_each_code_is_the_nearest_codeword_of_its_subspace(metric):
    import jax

    D, n, dims, M = 2048, 1500, 24, 6
    vecs = np.zeros((D, dims), np.float32)
    vecs[:n] = _clustered(n, dims, 10, seed=7)
    exists = np.zeros(D, bool)
    exists[:n] = True
    parts = build_pq(jax.device_put(vecs), exists, metric, M=M,
                     vecs_host=vecs)
    assert parts.M == M and parts.codes.shape == (D, M)
    x = vecs[:n].astype(np.float64)
    if metric == "cosine":
        x = x / np.linalg.norm(x, axis=1, keepdims=True)
    books = parts.codebooks.astype(np.float64)
    for m in range(M):
        sub = x[:, m * parts.dsub:(m + 1) * parts.dsub]
        d2 = ((sub[:, None, :] - books[m][None]) ** 2).sum(-1)
        got = parts.codes[:n, m].astype(np.int64)
        best = d2.min(1)
        # a tie between codewords may go either way
        np.testing.assert_allclose(d2[np.arange(n), got], best, rtol=1e-5,
                                   atol=1e-6)
    assert not parts.codes[n:].any()


def test_rows_read_by_lane_blocks_are_the_rows():
    import jax.numpy as jnp

    rng = np.random.default_rng(8)
    vecs = jnp.asarray(rng.standard_normal((1024, 96)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, 1024, 200).astype(np.int32))
    np.testing.assert_array_equal(
        np.asarray(ivf_ops._take_rows(vecs, ids, True)),
        np.asarray(vecs)[np.asarray(ids)])


def _numpy_ivf_pq(q, cents, lists, codebooks, codes, vecs, D, nprobe,
                  fine_k, k):
    """Probe by angle, ADC over the codes of the probed lists, exact
    cosine re-rank of the fine_k best: (ids, scores) of the top k."""
    qn = q.astype(np.float64) / np.linalg.norm(q)
    probe = np.argsort(-_affinity(qn[None], cents, "cosine")[0],
                       kind="stable")[:nprobe]
    cand = lists[probe].reshape(-1)
    cand = cand[cand < D]
    M, K, dsub = codebooks.shape
    lut = np.einsum("md,mkd->mk", qn.reshape(M, dsub),
                    codebooks.astype(np.float64))
    coarse = lut[np.arange(M)[None, :], codes[cand].astype(np.int64)].sum(1)
    fine = cand[np.argsort(-coarse, kind="stable")[:fine_k]]
    v = vecs[fine].astype(np.float64)
    cos = v @ qn / np.linalg.norm(v, axis=1)
    order = np.argsort(-cos, kind="stable")[:k]
    return fine[order], (1.0 + cos[order]) / 2.0


@pytest.mark.parametrize("lane_rows,scatter_free", [
    (False, False), (True, False), (True, True)])
def test_the_ivf_pq_program_is_a_numpy_ivf_pq_search(lane_rows,
                                                      scatter_free):
    import jax
    import jax.numpy as jnp

    D, n, dims, C, M = 4096, 3500, 32, 32, 8
    vecs = np.zeros((D, dims), np.float32)
    vecs[:n] = _clustered(n, dims, 16, seed=9)
    exists = np.zeros(D, bool)
    exists[:n] = True
    idx = build_ivf(jax.device_put(vecs), exists, D, C=C, vecs_host=vecs)
    parts = build_pq(jax.device_put(vecs), exists, "cosine", M=M,
                     vecs_host=vecs)
    nprobe, fine_k, k = 4, 64, 10
    prog = make_ivf_pq_search(
        idx.C, idx.Lmax, D, nprobe, "cosine", quantizer_metric="cosine",
        fine_k=fine_k, pq_meta=(parts.M, parts.K, parts.dsub, "cosine"),
        lane_rows=lane_rows, scatter_free=scatter_free)
    rng = np.random.default_rng(10)
    for _ in range(8):
        q = vecs[rng.integers(n)] + 0.3 * rng.standard_normal(dims).astype(
            np.float32)
        scores, mask = prog(jnp.asarray(q), idx.centroids, idx.lists,
                            jnp.asarray(vecs), jnp.asarray(parts.codes),
                            jnp.asarray(parts.codebooks))
        s = np.where(np.asarray(mask), np.asarray(scores), -np.inf)
        got = np.argsort(-s, kind="stable")[:k]
        want_ids, want_s = _numpy_ivf_pq(
            q, np.asarray(idx.centroids), np.asarray(idx.lists),
            parts.codebooks, parts.codes, vecs, D, nprobe, fine_k, k)
        np.testing.assert_array_equal(got, want_ids)
        np.testing.assert_allclose(s[got], want_s, rtol=1e-5)
        assert int(np.asarray(mask).sum()) == fine_k


# ---------------------------------------------------------------------------
# the mapping's nlist and m
# ---------------------------------------------------------------------------

def _vector_mapping(**opts):
    return {"properties": {"emb": {"type": "dense_vector", "dims": 12,
                                   "index_options": opts}}}


def test_nlist_and_m_are_kept_through_the_mapping_round_trip():
    from elasticsearch_tpu.index.mappings import Mappings

    m = Mappings(_vector_mapping(type="ivf_pq", nlist=64, m="4"))
    assert m.get("emb").index_options == {"type": "ivf_pq", "nlist": 64,
                                          "m": 4}
    again = Mappings(m.to_json())
    assert again.get("emb").index_options == m.get("emb").index_options
    assert Mappings(_vector_mapping(type="ivf")).get(
        "emb").index_options == {"type": "ivf"}


@pytest.mark.parametrize("opts,words", [
    ({"type": "ivf_pq", "m": 5}, "must divide dims"),
    ({"type": "ivf_pq", "m": 0}, "positive integer"),
    ({"type": "ivf", "nlist": -3}, "positive integer"),
    ({"type": "ivf", "nlist": 2.5}, "positive integer"),
    ({"type": "ivf", "nlist": "many"}, "positive integer"),
    ({"type": "ivf", "nlist": True}, "positive integer"),
    ({"type": "ivf", "m": 4}, "has no parameter [m]"),
    ({"type": "ivf_pq", "ef_construction": 4}, "has no parameter"),
    ({"type": "hnsw"}, "unsupported index_options type"),
])
def test_invalid_nlist_and_m_are_refused(opts, words):
    from elasticsearch_tpu.index.mappings import Mappings
    from elasticsearch_tpu.utils.errors import MapperParsingException

    with pytest.raises(MapperParsingException, match=words.replace(
            "[", r"\[").replace("]", r"\]")):
        Mappings(_vector_mapping(**opts))


def _ann_node(n_docs, dims, opts, seed, tags=4, clusters=4, spread=1.0):
    from elasticsearch_tpu.node import Node

    node = Node()
    node.create_index("v", {"mappings": {"properties": {
        "emb": {"type": "dense_vector", "dims": dims,
                "index_options": opts},
        "tag": {"type": "keyword"}}}})
    svc = node.indices["v"]
    rng = np.random.default_rng(seed)
    cents = rng.standard_normal((clusters, dims)).astype(np.float32) * 4
    x = np.empty((n_docs, dims), np.float32)
    for i in range(n_docs):
        x[i] = cents[i % clusters] + spread * rng.standard_normal(
            dims).astype(np.float32)
        svc.index_doc(str(i), {"emb": [float(v) for v in x[i]],
                               "tag": f"c{i % tags}"})
    svc.refresh()
    return node, svc, x


def test_a_search_through_node_honours_nlist_and_m_and_meets_its_recall():
    """Exact scores for every hit and recall@10 >= 0.9 against brute force
    over 1,600 documents in 16 lists of 8-subspace codes, served by the
    IVF-PQ program (counters), with its host work in search.ann."""
    from elasticsearch_tpu.monitor import kernels

    node, svc, x = _ann_node(1600, 32, {"type": "ivf_pq", "nlist": 16,
                                        "m": 8}, seed=11, clusters=64,
                             spread=0.5)
    vc = svc.shards[0].segments[0].vectors["emb"]
    assert vc._ivf.C == 16 and vc._pq.M == 8  # built at freeze
    xn = x / np.linalg.norm(x, axis=1, keepdims=True)
    rng = np.random.default_rng(12)
    before = kernels.snapshot()
    hits = 0
    for t in range(20):
        q = x[rng.integers(len(x))] + 0.2 * rng.standard_normal(32)
        r = node.search("v", {"query": {"knn": {
            "field": "emb", "query_vector": [float(v) for v in q],
            "k": 10, "num_candidates": 400}}, "size": 10})
        ids = np.asarray([int(h["_id"]) for h in r["hits"]["hits"]])
        got = np.asarray([h["_score"] for h in r["hits"]["hits"]])
        cos = xn[ids].astype(np.float64) @ (q / np.linalg.norm(q))
        np.testing.assert_allclose(got, (1.0 + cos) / 2.0, rtol=1e-5)
        exact = np.argsort(-(xn @ (q / np.linalg.norm(q))))[:10]
        hits += len(set(ids.tolist()) & set(exact.tolist()))
    assert hits / 200 >= 0.9, hits / 200
    after = kernels.snapshot()
    assert after.get("knn_ivf_pq", 0) - before.get("knn_ivf_pq", 0) == 20
    assert after.get("knn_ivf_pq_starved", 0) == before.get(
        "knn_ivf_pq_starved", 0)
    nprobe = vc._ivf.nprobe_for(400)
    assert (after["ann_candidate_slots"]
            - before.get("ann_candidate_slots", 0)) == \
        20 * nprobe * vc._ivf.Lmax
    text = node.metrics.expose()
    assert 'estpu_span_duration_seconds_count{span="search.ann"} 20' in text
    node.close()


def test_a_starved_filter_is_counted_and_answered_by_brute_force():
    from elasticsearch_tpu.monitor import kernels

    node, svc, x = _ann_node(800, 16, {"type": "ivf_pq", "nlist": 32,
                                       "m": 4}, seed=13)
    before = kernels.snapshot()
    q = [float(v) for v in x[1]]  # a doc of tag c1, filtered to c3 only
    r = node.search("v", {"query": {"knn": {
        "field": "emb", "query_vector": q, "k": 5, "num_candidates": 5,
        "filter": {"term": {"tag": "c3"}}}}, "size": 5})
    after = kernels.snapshot()
    ids = [int(h["_id"]) for h in r["hits"]["hits"]]
    assert len(ids) == 5 and all(i % 4 == 3 for i in ids)
    assert after.get("knn_ivf_pq_starved", 0) == \
        before.get("knn_ivf_pq_starved", 0) + 1
    assert after.get("knn_fused_topk", 0) > before.get("knn_fused_topk", 0)
    node.close()
