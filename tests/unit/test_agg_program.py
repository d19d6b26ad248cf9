"""A size-0 aggregation tree as one ``agg_tree`` program a segment
(search/aggregations/program.py, ops/aggs.py), against a plain integer
reference on seeded data; the columnar codec (index/segment.
numeric_column) against the document-at-a-time builder."""
import json

import numpy as np
import pytest

from elasticsearch_tpu.index.segment import (CODE_MISSING, RangeIds,
                                             TpuSegment, Uniform,
                                             numeric_column)
from elasticsearch_tpu.monitor import kernels
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.utils.shapes import pow2_bucket

DAY = 86_400_000
EPOCH = 1_420_070_400_000  # 2015-01-01T00:00:00Z
MAPPINGS = {"ts": {"type": "date", "format": "yyyy-MM-dd HH:mm:ss"},
            "dist": {"type": "scaled_float", "scaling_factor": 100},
            "amt": {"type": "scaled_float", "scaling_factor": 100}}


def _node(ms, dist_cents, amt_cents, shards=1):
    """A node whose index holds the arrays as one frozen segment a shard
    (shard s takes every shards-th document), loaded as a loader does."""
    node = Node(name="agg-program")
    node.create_index("t", {"settings": {"number_of_shards": shards},
                            "mappings": {"properties": MAPPINGS}})
    for s in range(shards):
        part = slice(s, None, shards)
        n = len(ms[part])
        D = pow2_bucket(n, minimum=64)
        exists = np.zeros(D, bool)
        exists[:n] = True

        def pad(v, dt):
            out = np.zeros(D, dt)
            out[:n] = v
            return out

        seg = TpuSegment(
            num_docs=n, max_docs=D, inverted={}, keywords={}, vectors={},
            numerics={
                "ts": numeric_column("ts", "date", pad(ms[part], np.int64),
                                     exists),
                "dist": numeric_column(
                    "dist", "scaled_float",
                    pad(dist_cents[part] / 100.0, np.float64), exists,
                    scaling_factor=100),
                "amt": numeric_column(
                    "amt", "scaled_float",
                    pad(amt_cents[part] / 100.0, np.float64), exists,
                    scaling_factor=100)},
            sources=Uniform(None, n), stored=Uniform(None, n),
            ids=RangeIds(0, n), id_map={}, field_lengths={})
        node.indices["t"].shards[s].engine.segments.append(seg)
    return node


def _search(node, body):
    kernels.reset()
    out = node.search("t", body)
    return out, kernels.snapshot()


def _day_body(d0, d1):
    return {"size": 0,
            "query": {"range": {"ts": {"gte": EPOCH + d0 * DAY,
                                       "lte": EPOCH + d1 * DAY}}},
            "aggs": {"days": {"date_histogram": {"field": "ts",
                                                 "interval": "day"}}}}


def _mile_body(lo, hi):
    return {"size": 0,
            "query": {"bool": {"filter": {"range": {"dist": {
                "gte": lo, "lt": hi}}}}},
            "aggs": {"miles": {"histogram": {"field": "dist", "interval": 1},
                               "aggs": {"amt": {"stats": {
                                   "field": "amt"}}}}}}


def _ref_days(ms, d0, d1):
    sel = (ms >= EPOCH + d0 * DAY) & (ms <= EPOCH + d1 * DAY)
    keys, counts = np.unique(ms[sel] // DAY * DAY, return_counts=True)
    if not keys.size:
        return []
    full = np.arange(keys[0], keys[-1] + 1, DAY)
    got = dict(zip(keys.tolist(), counts.tolist()))
    return [(int(k), got.get(int(k), 0)) for k in full]


def _ref_miles(dist, amt, lo, hi):
    sel = (dist >= lo * 100) & (dist < hi * 100)
    key = dist[sel] // 100
    out = {}
    for k in np.unique(key).tolist():
        a = amt[sel][key == k] / 100.0
        out[float(k)] = (len(a), a.sum(), a.min(), a.max())
    return out


@pytest.fixture(scope="module")
def midnight():
    """Trips that end within a second of midnight on 40 days, and a few
    inside the days: the f32 channel of epoch millis cannot tell them
    apart (one ulp is 131 s)."""
    rng = np.random.default_rng(35)
    days = rng.integers(0, 40, 600)
    jitter = rng.choice([-1000, 0, 1000], 600)
    ms = EPOCH + days * DAY + jitter
    ms = np.concatenate([ms, EPOCH + rng.integers(0, 40 * DAY // 1000,
                                                  200) * 1000])
    ms = np.maximum(ms, EPOCH)
    n = ms.shape[0]
    dist = rng.integers(0, 3000, n)
    amt = rng.integers(250, 20000, n)
    return ms.astype(np.int64), dist, amt


def test_day_keys_are_exact_where_the_f32_channel_misbins(midnight):
    ms, dist, amt = midnight
    node = _node(ms, dist, amt)
    body = _day_body(2, 30)
    got, k = _search(node, body)
    assert k.get("agg_one_program") == 1 and not k.get("agg_declined")
    want = _ref_days(ms, 2, 30)
    assert [(b["key"], b["doc_count"]) for b in
            got["aggregations"]["days"]["buckets"]] == want
    assert got["hits"]["total"] == sum(c for _, c in want)
    # the device branch the program replaces: floor((f32 values + f32
    # offset) / interval) puts trips near midnight in the wrong day
    col = node.indices["t"].shards[0].engine.segments[0].numerics["ts"]
    vals = np.asarray(col.values)[:ms.shape[0]]
    f32_day = np.floor((vals + np.float32(col.offset)) / np.float32(DAY))
    assert (f32_day.astype(np.int64) != ms // DAY).sum() > 0


@pytest.mark.parametrize("lo,hi,B", [(0, 8, 8), (0, 9, 16), (3, 27, 24)])
def test_mile_histogram_with_stats_at_a_bucket_class_edge(midnight, lo, hi,
                                                          B):
    from elasticsearch_tpu.search.aggregations import parse_aggs, program
    from elasticsearch_tpu.search.context import SegmentContext
    from elasticsearch_tpu.search.queries import parse_query

    ms, dist, amt = midnight
    node = _node(ms, dist, amt)
    body = _mile_body(lo, hi)
    svc = node.indices["t"]
    seg = svc.shards[0].engine.segments[0]
    ctx = SegmentContext(seg, svc.mappings, svc.analysis)
    plan = program.plan(ctx, parse_query(body["query"]),
                        parse_aggs(body["aggs"]))
    assert plan.spec.B == B
    got, k = _search(node, body)
    assert k.get("agg_one_program") == 1
    want = _ref_miles(dist, amt, lo, hi)
    buckets = got["aggregations"]["miles"]["buckets"]
    assert [b["key"] for b in buckets] == sorted(want)
    for b in buckets:
        n, s, mn, mx = want[float(b["key"])]
        st = b["amt"]
        assert b["doc_count"] == st["count"] == n
        assert st["sum"] == pytest.approx(s, rel=1e-6)
        assert (st["min"], st["max"]) == (pytest.approx(mn),
                                          pytest.approx(mx))
        assert st["avg"] == pytest.approx(s / n, rel=1e-6)


def test_a_filter_that_selects_nothing(midnight):
    ms, dist, amt = midnight
    node = _node(ms, dist, amt)
    got, k = _search(node, _day_body(200, 210))
    assert k.get("agg_one_program") == 1
    assert got["hits"]["total"] == 0
    assert got["aggregations"]["days"]["buckets"] == []
    got, _ = _search(node, _mile_body(40, 45))
    assert got["aggregations"]["miles"]["buckets"] == []


def test_empty_interior_buckets_are_filled_as_the_host_path_fills_them():
    ms = EPOCH + np.array([0, 0, 3, 3, 3, 7], np.int64) * DAY + 5000
    dist = np.array([10, 120, 150, 410, 420, 990])  # miles 0, 1, 1, 4, 4, 9
    amt = np.arange(6) * 100 + 300
    node = _node(ms, dist, amt)
    for body in (_day_body(0, 8), _mile_body(0, 10)):
        got, k = _search(node, body)
        assert k.get("agg_one_program") == 1
        host = _host_answer(node, body)
        assert json.dumps(got["aggregations"], sort_keys=True) == \
            json.dumps(host, sort_keys=True)
    buckets = got["aggregations"]["miles"]["buckets"]
    assert [b["doc_count"] for b in buckets] == [1, 2, 0, 0, 2, 0, 0, 0,
                                                 0, 1]
    assert "amt" not in buckets[2] and buckets[4]["amt"]["count"] == 2


def _host_answer(node, body):
    """The same search through the host collectors (the program's plan
    declines everything)."""
    from elasticsearch_tpu.search.aggregations import program

    real = program.plan
    program.plan = lambda *a: None
    try:
        return node.search("t", body)["aggregations"]
    finally:
        program.plan = real


# a tree out of the program's shape stays on the mesh program
# (agg_declined_mesh); one in its shape whose plan a segment declines goes
# to the host loop's collectors (agg_declined)
@pytest.mark.parametrize("aggs,counter", [
    ({"x": {"extended_stats": {"field": "amt"}}}, "agg_declined_mesh"),
    ({"h": {"histogram": {"field": "dist", "interval": 1,
                          "extended_bounds": {"min": 0, "max": 5}}}},
     "agg_declined_mesh"),
    ({"h": {"histogram": {"field": "dist", "interval": 0.1}}},
     "agg_declined"),
    ({"d": {"date_histogram": {"field": "ts", "interval": "month"}}},
     "agg_declined"),
])
def test_a_declined_tree_falls_back_and_is_counted(midnight, aggs, counter):
    ms, dist, amt = midnight
    node = _node(ms, dist, amt)
    got, k = _search(node, {"size": 0, "aggs": aggs})
    assert k.get(counter) == 1 and not k.get("agg_one_program")
    assert set(got["aggregations"]) == set(aggs)


def test_metrics_alone_and_two_shards_on_the_mesh_default(midnight):
    ms, dist, amt = midnight
    node = _node(ms, dist, amt, shards=2)
    body = {"size": 0, "query": {"range": {"dist": {"lt": 20}}},
            "aggs": {"a": {"avg": {"field": "amt"}},
                     "m": {"max": {"field": "dist"}},
                     "c": {"value_count": {"field": "ts"}},
                     "s": {"sum": {"field": "amt"}},
                     "lo": {"min": {"field": "ts"}}}}
    got, k = _search(node, body)
    assert k.get("agg_one_program") == 2 and k.get("mesh_host_by_design")
    sel = dist < 2000
    a = got["aggregations"]
    assert a["c"]["value"] == got["hits"]["total"] == int(sel.sum())
    assert a["a"]["value"] == pytest.approx(amt[sel].mean() / 100, rel=1e-6)
    assert a["s"]["value"] == pytest.approx(amt[sel].sum() / 100, rel=1e-6)
    assert a["m"]["value"] == pytest.approx(dist[sel].max() / 100)
    assert a["lo"]["value"] == ms[sel].min()


def test_the_kernel_and_the_xla_program_agree():
    import jax.numpy as jnp

    from elasticsearch_tpu.ops.aggs import Metric, TreeSpec, agg_tree

    rng = np.random.default_rng(7)
    D = 8192
    key = rng.integers(0, 5000, D).astype(np.int32)
    key[rng.random(D) < 0.05] = CODE_MISSING
    val = rng.integers(-300, 100000, D).astype(np.int32)
    val[rng.random(D) < 0.1] = CODE_MISSING
    live = (rng.random(D) < 0.95).astype(np.int8)
    spec = TreeSpec(n_cols=2, filters=(0, 1), key_col=0, B=16,
                    metrics=(Metric(1, True, True, True, True),))
    params = jnp.asarray([100, 1399, -200, 90000, 100, 100], jnp.int32)
    args = (params, jnp.asarray(live), jnp.asarray(key), jnp.asarray(val))
    xla = np.asarray(agg_tree(*args, spec=spec))
    pallas = np.asarray(agg_tree(*args, spec=spec, kernel=True,
                                 interpret=True))
    sums = slice(1 + 2 * 16, 1 + 3 * 16)
    ints = np.ones(xla.shape, bool)
    ints[sums] = False
    assert (xla[ints] == pallas[ints]).all()
    np.testing.assert_allclose(xla[sums].view(np.float32),
                               pallas[sums].view(np.float32), rtol=1e-6)
    assert xla[0] == int(((live != 0) & (key >= 100) & (key <= 1399)
                          & (val >= -200) & (val <= 90000)).sum())


KINDS = {"long": ("long", [5, -3, None, 2 ** 40]),
         "date": ("date", ["2015-01-01 00:00:00", None,
                           "2015-03-01 12:00:01", "2015-01-01 00:00:01"]),
         "double": ("double", [1.5, None, -2.25, 1e10]),
         "scaled_float": ("scaled_float", [1.23, 4.5, None, 0.01])}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_builder_and_the_array_path_give_equal_columns(kind):
    from elasticsearch_tpu.client import Client

    typ, values = KINDS[kind]
    mapping = {"type": typ}
    if typ == "scaled_float":
        mapping["scaling_factor"] = 100
    if typ == "date":
        mapping["format"] = "yyyy-MM-dd HH:mm:ss"
    node = Node(name="codec")
    node.create_index("c", {"mappings": {"properties": {"f": mapping}}})
    client = Client(node)
    for i, v in enumerate(values):
        client.index("c", {} if v is None else {"f": v}, id=str(i))
    client.indices.refresh("c")
    (seg,) = node.indices["c"].shards[0].engine.segments
    built = seg.numerics["f"]
    arr = numeric_column("f", typ, built.exact.copy(),
                         np.asarray(built.exists_host).copy(),
                         scaling_factor=mapping.get("scaling_factor", 1.0))
    for name in ("values", "exists", "hi", "lo", "code"):
        a, b = getattr(built, name), getattr(arr, name)
        assert (a is None) == (b is None), name
        if a is not None:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for name in ("offset", "code_base", "code_step", "code_factor",
                 "code_min", "code_max", "value_count", "kind"):
        assert getattr(built, name) == getattr(arr, name), name
    np.testing.assert_array_equal(built.exact, arr.exact)
    # a long whose span passes CODE_LIMIT, and a double, have no code
    assert built.has_code == (typ in ("date", "scaled_float"))


def test_the_int8_live_mask_is_a_charged_column_refreshed_on_delete(midnight):
    """``live_i8`` is placed through the residency registry (fielddata
    tier, breaker-charged) once, and again only after a delete."""
    from elasticsearch_tpu import resources

    ms, dist, amt = midnight
    node = _node(ms, dist, amt)
    seg = node.indices["t"].shards[0].engine.segments[0]

    def loads():
        return resources.RESIDENCY.stats()["tiers"]["fielddata"]["loads"]

    before = loads()
    first = seg.live_i8
    assert seg.live_i8 is first and loads() == before + 1
    body = {"size": 0, "aggs": {"n": {"value_count": {"field": "amt"}}}}
    n0 = node.search("t", body)["aggregations"]["n"]["value"]
    before = loads()  # the search placed the amt column, not the mask
    seg.delete_local(0)
    assert int(np.asarray(seg.live_i8)[0]) == 0 and loads() == before + 1
    got, k = _search(node, body)
    assert k.get("agg_one_program") == 1
    assert got["aggregations"]["n"]["value"] == n0 - 1
