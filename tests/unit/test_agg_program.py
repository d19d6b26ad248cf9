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


def _plan(node, body):
    from elasticsearch_tpu.search.aggregations import parse_aggs, program
    from elasticsearch_tpu.search.context import SegmentContext
    from elasticsearch_tpu.search.queries import parse_query

    svc = node.indices["t"]
    ctx = SegmentContext(svc.shards[0].engine.segments[0], svc.mappings,
                         svc.analysis)
    return program.plan(ctx, parse_query(body["query"]),
                        parse_aggs(body["aggs"]))


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
    ms, dist, amt = midnight
    node = _node(ms, dist, amt)
    body = _mile_body(lo, hi)
    assert _plan(node, body).spec.B == B
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


# the kernel (interpret mode) against the XLA program. ``used`` is the
# segment's used-slot count (maxDoc): past it the live byte is 0, and the
# blocks past its last one hold live-looking documents that match, which
# the kernel must not read. ``nb`` is the request's real bucket count: the
# filter on the key keeps keys in [0, nb), and the buckets past it keep
# their initial values. ``rows`` is the block's rows of 128 slots (32 is
# one chunk a grid step, a drain each; 2048 is the real block: 64 chunks,
# two drains a step). ``data``: "random" codes; "bucket3" puts every used
# document, live and with a value, in bucket 3, so each lane's field of
# word 0 takes 4 documents a chunk and holds 128 (bits 24-31, the word
# negative) when drained; "at_limit" / "past_limit" do that with every
# value at the largest code the int32 partials admit, or one past it (the
# f32 Kahan path)
KERNEL_CASES = {
    # name: (D, used, tree, B, nb, deletes, rows, data)
    "the_first_case": (8192, 8192, "stats", 16, 13, False, 32, "random"),
    "used_ends_mid_block_nb_1": (16384, 2 * 4096 + 1234, "stats", 16, 1,
                                 False, 32, "random"),
    "used_ends_on_a_block_edge_nb_class_less_7": (16384, 3 * 4096, "stats",
                                                  16, 9, False, 32,
                                                  "random"),
    "used_ends_in_the_first_block_nb_class": (16384, 1000, "stats", 16, 16,
                                              False, 32, "random"),
    "deletes_in_the_last_used_block": (16384, 2 * 4096 + 3000, "stats", 24,
                                       17, True, 32, "random"),
    "count_only": (16384, 2 * 4096 + 77, "count", 16, 9, False, 32,
                   "random"),
    "bucketless": (16384, 3 * 4096 - 5, "none", 8, 1, False, 32, "random"),
    "fields_at_the_drain_limit": (2 * 2048 * 128, 2 * 2048 * 128, "stats",
                                  8, 4, False, 2048, "bucket3"),
    "int_sums_at_the_code_limit": (2 * 2048 * 128, 2 * 2048 * 128 - 4096,
                                   "stats", 8, 4, False, 2048, "at_limit"),
    "f32_sums_one_past_the_code_limit": (2 * 2048 * 128, 2 * 2048 * 128,
                                         "stats", 8, 4, False, 2048,
                                         "past_limit"),
}


def _kernel_case(D, used, tree, B, nb, deletes, rows, data):
    """(spec, params, live, cols) of a case, as a plan would give them,
    and the live mask a segment holds (0 past ``used``)."""
    from elasticsearch_tpu.ops.aggs import (LANE_DOCS, Metric, TreeSpec,
                                            int_sum_fits)

    rng = np.random.default_rng(7)
    key = rng.integers(0, 5000, D).astype(np.int32)
    key[rng.random(D) < 0.05] = CODE_MISSING
    val = rng.integers(-300, 100000, D).astype(np.int32)
    val[rng.random(D) < 0.1] = CODE_MISSING
    live = (rng.random(D) < 0.95).astype(np.int8)
    vlo, vhi = -200, 90000
    if data != "random":
        key[:] = rng.integers(400, 500, D)  # bucket 3 of [100, 100 nb + 99]
        live[:] = 1
        limit = (2 ** 31 - 1) // LANE_DOCS
        val = {"bucket3": val, "at_limit": np.full(D, limit),
               "past_limit": np.full(D, limit + 1)}[data].astype(np.int32)
        val[val == CODE_MISSING] = 7
        vlo, vhi = -limit - 1, limit + 1
    if deletes:
        live[used - 700:used:3] = 0
    live[used:] = 0
    has = val != CODE_MISSING
    fits = int_sum_fits(int(val[has].min()), int(val[has].max()))
    stats = (Metric(1, True, True, True, True, fits),)
    if tree == "none":
        spec = TreeSpec(n_cols=2, filters=(0, 1), key_col=-1, B=B,
                        metrics=stats)
        params = [100, 1399, vlo, vhi, 1, 1]
    else:
        # keys floor((code - 100) / 100) over codes [100, 100 nb + 99]
        spec = TreeSpec(n_cols=2, filters=(0, 1), key_col=0, B=B,
                        metrics=stats if tree == "stats" else ())
        params = [100, 100 * nb + 99, vlo, vhi, 100, 100]
    params += [(max(used, 1) - 1) // (rows * 128)]
    return spec, np.asarray(params, np.int32), live, (key, val)


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_the_kernel_and_the_xla_program_agree(case, monkeypatch):
    import jax.numpy as jnp

    from elasticsearch_tpu.ops import aggs

    D, used, tree, B, nb, deletes, rows, data = KERNEL_CASES[case]
    monkeypatch.setattr(aggs, "_BLOCK_ROWS", rows)
    spec, params, live, (key, val) = _kernel_case(*KERNEL_CASES[case])
    block = rows * 128
    last = int(params[-1])
    assert D // block >= 2 and last == aggs.last_block(D, used)
    assert all(m.int_sum == (data != "past_limit") for m in spec.metrics)
    xla = np.asarray(aggs.agg_tree(jnp.asarray(params), jnp.asarray(live),
                                   jnp.asarray(key), jnp.asarray(val),
                                   spec=spec))
    # past the last used block: documents that are live and match
    seen = live.copy()
    seen[(last + 1) * block:] = 1
    k2, v2 = key.copy(), val.copy()
    k2[(last + 1) * block:] = 150
    v2[(last + 1) * block:] = 7
    pallas = np.asarray(aggs._pallas_tree(
        jnp.asarray(params), jnp.asarray(seen),
        (jnp.asarray(k2), jnp.asarray(v2)), spec=spec, interpret=True))
    sums = np.zeros(xla.shape, bool)
    at = 1 + B
    for m in spec.metrics:
        at += B * m.count
        if m.sum:
            sums[at:at + B] = True
            at += B
        at += B * (m.min + m.max)
    assert at == xla.shape[0]
    assert (xla[~sums] == pallas[~sums]).all()
    np.testing.assert_allclose(xla[sums].view(np.float32),
                               pallas[sums].view(np.float32), rtol=1e-6)
    hi = 100 * nb + 99 if tree != "none" else 1399
    sel = ((live != 0) & (key >= 100) & (key <= hi)
           & (val >= params[2]) & (val <= params[3]))
    assert xla[0] == int(sel.sum()) > 0
    if data == "random":
        if tree != "none":
            assert (xla[1:1 + nb] > 0).all() and not xla[1 + nb:1 + B].any()
        return
    # every selected document in bucket 3: its count and values present
    # read the drained fields exactly, the other buckets nothing
    want = np.zeros(B, np.int64)
    want[3] = sel.sum()
    np.testing.assert_array_equal(pallas[1:1 + B], want)
    np.testing.assert_array_equal(pallas[1 + B:1 + 2 * B], want)
    # the sum against the exact int64 one, within f32's last place
    exact = np.float32(val[sel].astype(np.int64).sum())
    got = pallas[1 + 2 * B + 3:1 + 2 * B + 4].view(np.float32)[0]
    assert abs(float(got) - float(exact)) <= float(np.spacing(exact))


@pytest.fixture
def trips3k():
    """A node of 3,000 trips over 40 days (a segment of 4,096 slots: four
    kernel blocks of 1,024 under ``_BLOCK_ROWS`` 8) whose last 1,100
    documents are deleted: 1,900 live documents in 3,000 used slots."""
    rng = np.random.default_rng(36)
    n = 3000
    ms = EPOCH + rng.integers(0, 40 * DAY // 1000, n) * 1000
    dist = rng.integers(0, 3000, n)
    amt = rng.integers(250, 20000, n)
    node = _node(ms.astype(np.int64), dist, amt)
    seg = node.indices["t"].shards[0].engine.segments[0]
    for i in range(1900, n):
        seg.delete_local(i)
    assert (seg.max_docs, seg.num_docs, seg.live_docs) == (4096, n, 1900)
    node.live_trips = (ms[:1900], dist[:1900], amt[:1900])
    return node


@pytest.mark.parametrize("body,nb", [
    (_mile_body(0, 5), 5), (_mile_body(0, 20), 20), (_mile_body(0, 30), 30),
    (_day_body(2, 3), 2), (_day_body(2, 9), 8), (_day_body(2, 33), 32)])
def test_the_plan_bounds_the_kernels_grid(trips3k, monkeypatch, body, nb):
    """The last block from the used slots (maxDoc), not the live count;
    the class from the request's real bucket count ``kmax - kmin + 1``:
    ``gte: 0, lt hi`` whole miles is hi buckets, n days from midnight to
    midnight (``lte``) n + 1."""
    from elasticsearch_tpu.ops import aggs

    monkeypatch.setattr(aggs, "_BLOCK_ROWS", 8)
    plan = _plan(trips3k, body)
    last = int(plan.params[-1])
    assert last == 2 != aggs.last_block(4096, 1900)
    assert plan.spec.B == aggs.bucket_class(nb)
    step = DAY if "days" in body["aggs"] else 1
    assert plan.keys[nb - 1] == plan.keys[0] + (nb - 1) * step


@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("body", [_mile_body(0, 20), _day_body(2, 18)])
def test_agg_bucket_slots_rise_by_what_the_program_scans(trips3k,
                                                         monkeypatch, kernel,
                                                         body):
    """``estpu_kernel_dispatch_total{kernel="agg_bucket_slots"}`` rises
    by (slots scanned) x (bucket passes) a segment-search: D x B for the
    XLA program, (last block + 1) x block x B for the kernel (run here in
    interpret mode); the answer is the live documents' either way."""
    from elasticsearch_tpu.ops import aggs

    monkeypatch.setattr(aggs, "_BLOCK_ROWS", 8)
    if kernel:
        monkeypatch.setattr(aggs, "use_kernel", lambda D: True)
        monkeypatch.setattr(
            aggs, "agg_tree", lambda params, live, *cols, spec, kernel:
            aggs._pallas_tree(params, live, cols, spec=spec, interpret=True))
    plan = _plan(trips3k, body)
    assert plan.spec.B == 24
    got, k = _search(trips3k, body)
    assert k.get("agg_one_program") == 1
    want = (3 * 1024 if kernel else 4096) * 24
    assert k.get("agg_bucket_slots") == want
    ms, dist, amt = trips3k.live_trips
    (name, agg), = got["aggregations"].items()
    buckets = agg["buckets"]
    if name == "days":
        want = _ref_days(ms, 2, 18)
        assert [(b["key"], b["doc_count"]) for b in buckets] == want
        return
    want = _ref_miles(dist, amt, 0, 20)
    assert [b["key"] for b in buckets] == sorted(want)
    for b in buckets:
        n, total, lo, hi = want[b["key"]]
        st = b["amt"]
        assert b["doc_count"] == st["count"] == n
        assert st["sum"] == pytest.approx(total, rel=1e-6)
        assert (st["min"], st["max"]) == (pytest.approx(lo), pytest.approx(hi))


@pytest.fixture(scope="module")
def year3k():
    """3,000 trips over 2015: the dropoff second's code spans a year
    (~3.15e7), past what an int32 partial admits, the cents do not."""
    rng = np.random.default_rng(39)
    n = 3000
    ms = EPOCH + rng.integers(0, 365 * DAY // 1000, n) * 1000
    dist = rng.integers(0, 3000, n)
    amt = rng.integers(250, 20000, n)
    node = _node(ms.astype(np.int64), dist, amt)
    node.trips = (ms, dist, amt)
    return node


# (body, int-path sums the plan takes): the stats of the cents, two sums
# of cents, a tree that sums nothing, and the stats of a year of seconds
INT_SUM_TREES = {
    "mile_stats": (_mile_body(0, 20), 1),
    "two_sums_and_a_max": ({"size": 0, "query": {"match_all": {}},
                            "aggs": {"a": {"avg": {"field": "amt"}},
                                     "s": {"sum": {"field": "dist"}},
                                     "m": {"max": {"field": "ts"}}}}, 2),
    "count_only": (_day_body(2, 18), 0),
    "stats_of_a_year_of_seconds": ({"size": 0, "query": {"match_all": {}},
                                    "aggs": {"t": {"stats": {"field": "ts"}}}},
                                   0),
}


@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("tree", sorted(INT_SUM_TREES))
def test_agg_int_sums_rise_by_the_sums_the_kernel_takes_as_int32(
        year3k, monkeypatch, tree, kernel):
    """``estpu_kernel_dispatch_total{kernel="agg_int_sums"}`` rises by the
    metric sums a dispatch gives the kernel as exact int32 partials: none
    for a tree without a sum, or a column whose codes could overflow a
    partial, and none where the XLA program runs; the answer is the
    reference's either way."""
    from elasticsearch_tpu.ops import aggs

    body, want = INT_SUM_TREES[tree]
    monkeypatch.setattr(aggs, "_BLOCK_ROWS", 8)
    if kernel:
        monkeypatch.setattr(aggs, "use_kernel", lambda D: True)
        monkeypatch.setattr(
            aggs, "agg_tree", lambda params, live, *cols, spec, kernel:
            aggs._pallas_tree(params, live, cols, spec=spec, interpret=True))
    plan = _plan(year3k, body)
    assert sum(m.int_sum for m in plan.spec.metrics) == want
    got, k = _search(year3k, body)
    assert k.get("agg_one_program") == 1
    assert k.get("agg_int_sums", 0) == (want if kernel else 0)
    ms, dist, amt = year3k.trips
    a = got["aggregations"]
    if tree == "stats_of_a_year_of_seconds":
        st = a["t"]
        assert st["count"] == len(ms) and st["min"] == ms.min()
        assert st["max"] == ms.max()
        assert st["sum"] == pytest.approx(ms.sum(), rel=1e-6)
    elif tree == "two_sums_and_a_max":
        assert a["a"]["value"] == pytest.approx(amt.mean() / 100, rel=1e-6)
        assert a["s"]["value"] == pytest.approx(dist.sum() / 100, rel=1e-6)
        assert a["m"]["value"] == ms.max()
    elif tree == "mile_stats":
        want_m = _ref_miles(dist, amt, 0, 20)
        buckets = a["miles"]["buckets"]
        assert [b["key"] for b in buckets] == sorted(want_m)
        for b in buckets:
            n, total, lo, hi = want_m[b["key"]]
            assert b["amt"]["count"] == n
            assert b["amt"]["sum"] == pytest.approx(total, rel=1e-6)
    else:
        assert [(b["key"], b["doc_count"]) for b in a["days"]["buckets"]] \
            == _ref_days(ms, 2, 18)


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
