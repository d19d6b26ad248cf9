"""The stored per-row term of a kNN score (ops/knn.knn_row_terms, kept by
VectorColumn.row_terms): no kNN program reduces over the slab to rebuild
it, a coalesced batch answers what sequential searches answer and what
the float64 oracle says, and the term follows the segment's life (built
once a column, untouched by a delete, rebuilt by a refresh and a merge,
one a shard on the shard's own chip). Counts, placement and equality
only, never a time."""
import numpy as np
import pytest

from elasticsearch_tpu.monitor import kernels
from elasticsearch_tpu.node import Node

DIMS, K = 16, 10
# gist-960-exact.knn-steady's limits (benchmarks/cells/)
SCORE_ERR, RANK_GAP = 1e-05, 1e-05
METRICS = ("l2_norm", "cosine")


# ---- (a) the program: no reduction over the slab, one pass of bytes ---------

def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in eqn.params.values():
            for j in (sub if isinstance(sub, (list, tuple)) else [sub]):
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _bytes_accessed(lowered):
    cost = lowered.compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    return float(cost["bytes accessed"])


@pytest.mark.parametrize("use_bf16", [False, True], ids=["f32", "bf16"])
@pytest.mark.parametrize("Q", [1, 2, 8])
@pytest.mark.parametrize("metric", METRICS)
def test_a_knn_program_reads_the_slab_once_and_never_reduces_over_it(
        metric, Q, use_bf16):
    import jax
    import jax.numpy as jnp

    from elasticsearch_tpu.ops import knn

    D, dims = 2048, 960
    S = jax.ShapeDtypeStruct
    args = (S((Q, dims), jnp.float32), S((D, dims), jnp.float32),
            S((D,), jnp.float32), S((D,), jnp.bool_))
    kw = dict(k=K, use_bf16=use_bf16)
    traced = jax.make_jaxpr(
        lambda *a: knn.knn_topk_stored(*a, metric=metric, **kw))(*args)
    reductions = [
        eqn for eqn in _eqns(traced.jaxpr)
        if (eqn.primitive.name.startswith(("reduce_", "arg", "cum"))
            and any(getattr(v.aval, "shape", ())[-2:] in ((D, dims),
                                                           (dims, D))
                    for v in eqn.invars))]
    assert not reductions, reductions
    slab = D * dims * 4
    got = _bytes_accessed(knn.knn_topk_stored.lower(*args, metric=metric,
                                                    **kw))
    # a metric with a row term costs no more passes than the one without
    plain = _bytes_accessed(knn.knn_topk_stored.lower(
        args[0], args[1], None, args[3], metric="dot_product", **kw))
    assert got < plain + 0.25 * slab, (got / slab, plain / slab)
    if not use_bf16:  # (the bf16 sweep also writes and reads its cast copy)
        assert got < 1.5 * slab, got / slab
    # the three-array form the benchmark's compile check lowers builds the
    # term for the rows it is handed: a second pass, which is why nothing
    # that holds a slab calls it
    bare = _bytes_accessed(knn.knn_topk.lower(args[0], args[1], args[3],
                                              metric=metric, **kw))
    assert bare > got + 0.9 * slab, (bare / slab, got / slab)


def test_no_caller_in_the_product_takes_the_form_that_rebuilds_the_term():
    """`knn_topk` (three arrays) is kept for the benchmark's compile check
    alone; every program of the product reads a stored term."""
    import pathlib
    import re

    root = pathlib.Path(__file__).resolve().parents[2] / "elasticsearch_tpu"
    callers = []
    for path in root.rglob("*.py"):
        text = path.read_text()
        for m in re.finditer(r"\bknn_topk\(", text):
            line = text.count("\n", 0, m.start()) + 1
            if not (path.name == "knn.py" and text[:m.start()].endswith("def ")):
                callers.append(f"{path.relative_to(root)}:{line}")
    assert callers == [], callers


# ---- (b) a coalesced batch == sequential searches == the float64 oracle -----

def _oracle(metric, q, V):
    q64 = np.asarray(q, np.float32).astype(np.float64)
    V64 = V.astype(np.float64)
    if metric == "l2_norm":
        return 1.0 / (1.0 + ((V64 - q64) ** 2).sum(1))
    cos = (V64 @ q64) / (np.linalg.norm(V64, axis=1) * np.linalg.norm(q64))
    return (1.0 + cos) * 0.5


def _judge(metric, q, V, hits, live=None):
    """(score_err, rank_gap) of one answer, as benchmarks/reference/check.py
    reads them."""
    want_all = _oracle(metric, q, V)
    if live is not None:
        want_all = np.where(live, want_all, -np.inf)
    ids = np.asarray([int(h["_id"]) for h in hits])
    got = np.asarray([h["_score"] for h in hits], np.float64)
    assert len(ids) == K and len(set(ids.tolist())) == K
    assert np.all(np.diff(got) <= 0)
    want = want_all[ids]
    left = np.delete(want_all, ids).max()
    return (float(np.max(np.abs(got - want) / want)),
            max(0.0, float(left - want.min())) / float(want.min()))


def _body(q, field="emb"):
    return {"query": {"knn": {"field": field,
                              "query_vector": [float(x) for x in q],
                              "k": K, "ann": False}},
            "size": K, "_source": False}


def _vector_index(n, name, metric, V, shards=1, mesh=True):
    settings = {"number_of_shards": shards}
    if not mesh:
        settings["search"] = {"mesh": "false"}
    n.create_index(name, {"settings": {"index": settings}, "mappings": {
        "properties": {"emb": {"type": "dense_vector", "dims": DIMS,
                               "similarity": metric}}}})
    svc = n.indices[name]
    for i, v in enumerate(V):
        svc.index_doc(str(i), {"emb": [float(x) for x in v]})
    svc.refresh()
    return svc


@pytest.fixture(scope="module")
def world():
    rng = np.random.default_rng(34)
    V = rng.standard_normal((600, DIMS)).astype(np.float32)
    n = Node()
    for metric in METRICS:
        _vector_index(n, metric, metric, V)
    yield n, V
    n.close()


@pytest.mark.parametrize("count", [2, 3, 5])
@pytest.mark.parametrize("metric", METRICS)
def test_a_coalesced_batch_answers_what_sequential_searches_answer(
        world, metric, count):
    from elasticsearch_tpu.search.batch import execute_batch

    n, V = world
    rng = np.random.default_rng(100 + count)
    queries = rng.standard_normal((count, DIMS)).astype(np.float32)
    bodies = [_body(q) for q in queries]
    before = kernels.snapshot().get("knn_fused_batch", 0)
    # pad_pow2: the coalescer's flush shape
    batched = execute_batch(n.indices[metric], bodies, pad_pow2=True)
    assert batched is not None
    assert kernels.snapshot().get("knn_fused_batch", 0) - before >= count
    for q, body, reply in zip(queries, bodies, batched):
        alone = n.search(metric, body)["hits"]["hits"]
        hits = reply["hits"]["hits"]
        assert [h["_id"] for h in hits] == [h["_id"] for h in alone]
        np.testing.assert_allclose([h["_score"] for h in hits],
                                   [h["_score"] for h in alone], rtol=1e-6)
        for answer in (hits, alone):
            score_err, rank_gap = _judge(metric, q, V, answer)
            assert score_err <= SCORE_ERR and rank_gap <= RANK_GAP


# ---- (c) the term's life ----------------------------------------------------

def _columns(svc):
    return [seg.vectors["emb"] for g in svc.groups
            for seg in g.reader(None).searcher.segments]


def _builds():
    return kernels.snapshot().get("knn_row_terms_build", 0)


@pytest.mark.parametrize("metric", METRICS)
def test_the_term_is_built_once_kept_by_a_delete_rebuilt_by_refresh_and_merge(
        metric):
    from elasticsearch_tpu.ops.knn import knn_row_terms

    rng = np.random.default_rng(7)
    V = rng.standard_normal((300, DIMS)).astype(np.float32)
    q = rng.standard_normal(DIMS).astype(np.float32)
    n = Node()
    try:
        # pinned to the host loop: every search reads the column's own term
        svc = _vector_index(n, "life", metric, V[:200], mesh=False)
        (col,) = _columns(svc)
        assert col._row_terms is None  # lazy: nothing built at freeze
        b0 = _builds()
        first = n.search("life", _body(q))["hits"]["hits"]
        assert _builds() - b0 == 1
        term = col._row_terms
        want = (V[:200].astype(np.float64) ** 2).sum(1)
        if metric == "cosine":
            want = 1.0 / np.sqrt(want)
        np.testing.assert_allclose(np.asarray(term)[:200], want, rtol=1e-6)
        np.testing.assert_array_equal(
            np.asarray(term),
            np.asarray(knn_row_terms(col.vecs, metric=metric)))
        assert n.search("life", _body(q))["hits"]["hits"] == first
        assert _builds() - b0 == 1 and col._row_terms is term

        # a delete touches only `live`
        gone = first[0]["_id"]
        svc.delete_doc(gone)
        svc.refresh()
        after = n.search("life", _body(q))["hits"]["hits"]
        assert gone not in [h["_id"] for h in after]
        live = np.ones(200, bool)
        live[int(gone)] = False
        score_err, rank_gap = _judge(metric, q, V[:200], after, live)
        assert score_err <= SCORE_ERR and rank_gap <= RANK_GAP
        assert _columns(svc)[0] is col and col._row_terms is term
        assert _builds() - b0 == 1

        # a refresh freezes a new slab, which builds its own
        for i in range(200, 300):
            svc.index_doc(str(i), {"emb": [float(x) for x in V[i]]})
        svc.refresh()
        cols = _columns(svc)
        assert len(cols) == 2 and cols[0] is col
        n.search("life", _body(q))
        assert _builds() - b0 == 2 and cols[1]._row_terms is not None
        assert col._row_terms is term

        # a merge is a new slab again
        svc.force_merge(1)
        (merged,) = _columns(svc)
        assert merged is not col and merged._row_terms is None
        hits = n.search("life", _body(q))["hits"]["hits"]
        assert _builds() - b0 == 3
        live = np.ones(300, bool)
        live[int(gone)] = False
        score_err, rank_gap = _judge(metric, q, V, hits, live)
        assert score_err <= SCORE_ERR and rank_gap <= RANK_GAP
    finally:
        n.close()


def _one_segment(svc):
    (seg,) = [s for g in svc.groups
              for s in g.reader(None).searcher.segments]
    return seg


def test_a_dot_product_column_has_no_term_and_builds_none():
    rng = np.random.default_rng(9)
    V = rng.standard_normal((100, DIMS)).astype(np.float32)
    V /= np.linalg.norm(V, axis=1, keepdims=True)
    n = Node()
    try:
        svc = _vector_index(n, "dot", "dot_product", V, mesh=False)
        b0 = _builds()
        hits = n.search("dot", _body(V[3]))["hits"]["hits"]
        assert hits[0]["_id"] == "3"
        (col,) = _columns(svc)
        assert col.row_terms() is None and _builds() == b0
    finally:
        n.close()


def test_the_segments_breaker_is_charged_four_bytes_a_slot():
    rng = np.random.default_rng(10)
    V = rng.standard_normal((100, DIMS)).astype(np.float32)
    n = Node()
    try:
        plain = _one_segment(
            _vector_index(n, "dot", "dot_product", V, mesh=False))
        svc = _vector_index(n, "charge", "l2_norm", V, mesh=False)
        seg = _one_segment(svc)
        assert seg.max_docs == plain.max_docs
        assert seg.memory_bytes() - plain.memory_bytes() == 4 * seg.max_docs
        n.search("charge", _body(V[0]))
        (col,) = _columns(svc)
        assert col._row_terms.nbytes == 4 * seg.max_docs
    finally:
        n.close()


@pytest.mark.parametrize("mesh", [False, True], ids=["host_loop", "mesh"])
@pytest.mark.parametrize("metric", METRICS + ("dot_product",))
def test_four_shards_one_term_a_shard_on_the_shards_chip(
        eight_devices, metric, mesh):
    rng = np.random.default_rng(11)
    V = rng.standard_normal((400, DIMS)).astype(np.float32)
    if metric == "dot_product":
        V /= np.linalg.norm(V, axis=1, keepdims=True)
    q = V[5] + 0.1 * rng.standard_normal(DIMS).astype(np.float32)
    n = Node()
    try:
        svc = _vector_index(n, "four", metric, V, shards=4, mesh=mesh)
        b0 = _builds()
        hits = n.search("four", _body(q))["hits"]["hits"]
        if metric == "dot_product":
            want = (1.0 + V.astype(np.float64) @ q.astype(np.float64)) * 0.5
            assert [int(h["_id"]) for h in hits] == \
                np.argsort(-want)[:K].tolist()
            assert _builds() == b0
            return
        score_err, rank_gap = _judge(metric, q, V, hits)
        assert score_err <= SCORE_ERR and rank_gap <= RANK_GAP
        if mesh:  # the executor's stacked copy carries its own [S, D] term
            ex = svc.mesh_executor()
            terms = [v[0] for k, v in ex._data.items()
                     if k[0] == "vec_terms"]
            assert len(terms) == 1 and terms[0][0].shape[0] == 4
            assert len(terms[0][0].sharding.device_set) == 4
            return
        segs = [s for g in svc.groups
                for s in g.reader(None).searcher.segments]
        assert len(segs) == 4 and _builds() - b0 == 4
        chips = set()
        for seg in segs:
            term = seg.vectors["emb"]._row_terms
            assert seg.device is not None and term.devices() == {seg.device}
            chips |= term.devices()
        assert len(chips) == 4
    finally:
        n.close()
