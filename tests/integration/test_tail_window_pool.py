"""The tail window over the text cell's own (rehearsal-sized) query pool:
how many ``(R, T, P)`` program classes its searches fall into, and what
the two counters ``term_group_topk`` brings say of a search's window.
Counts only, never a time."""
import json
import os

import pytest

from elasticsearch_tpu.monitor import kernels
from elasticsearch_tpu.search.context import (SegmentContext,
                                              chunk_count_bucket, tail_width)
from elasticsearch_tpu.search.queries import parse_query, term_group_topk

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SLOTS = 'estpu_kernel_dispatch_total{kernel="tail_window_slots"}'
POSTINGS = 'estpu_kernel_dispatch_total{kernel="tail_window_postings"}'


def _tail_runs(ctx, query):
    """The raw (start, len, weight) postings runs the tail of ``query``'s
    term group has to hold, by the segment's own tables: every term
    without a dense impact row (an absent term is a (0, 0) run where no
    term has a row, and dropped where one has: context.chunked_slices /
    hybrid_slices)."""
    from elasticsearch_tpu.search.queries import _fused_eligible_terms

    field, (terms, weights) = _fused_eligible_terms(ctx, query)
    inv = ctx.inv(field)
    block = inv.dense_block()
    tids = [inv.term_id(t) for t in terms]
    if block is None or not any(t >= 0 and block[0][t] >= 0 for t in tids):
        return [inv.term_slice(t) + (w,) for t, w in zip(terms, weights)]
    return [(int(inv.offsets[t]), int(inv.offsets[t + 1] - inv.offsets[t]), w)
            for t, w in zip(tids, weights) if t >= 0 and block[0][t] < 0]


@pytest.fixture(scope="module")
def pool():
    """(loaded cell at its rehearsal size, segment context, [(plan, raw
    tail runs)] for every query of the pool)."""
    import jax

    from benchmarks import loaders
    from elasticsearch_tpu.search.queries import plan_term_group

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "msmarco-passage-shard.json")) as fh:
        cfg = json.load(fh)
    loaded = loaders.load(cfg, 30, jax.devices()[:1], rehearse=True)
    searcher = loaded.node.indices[loaded.index].shards[0].searcher
    ctx = SegmentContext(searcher.segments[0], searcher.mappings,
                         searcher.analysis)
    queries = [parse_query(loaded.request(i)["query"])
               for i in range(loaded.pool_size)]
    plans = [(plan_term_group(ctx, q), _tail_runs(ctx, q)) for q in queries]
    yield loaded, ctx, plans
    loaded.node.close()


def _key(plan):
    return (0 if plan.impact is None else plan.qrows.shape[0],
            plan.starts.shape[0], plan.P)


def test_the_pool_falls_into_no_more_program_classes_than_before(pool):
    from tests.unit.test_tail_window import legacy_window

    _loaded, _ctx, plans = pool
    nnz_pad = plans[0][0].inv.nnz_pad
    now, before = set(), set()
    for plan, runs in plans:
        now.add(_key(plan))
        starts, _lens, _ws, P = legacy_window(runs)
        before.add((_key(plan)[0], starts.shape[0], P))
        # the runs' own width up to the cap, a chunk count off its
        # ladder, and never more slots than the old layout
        T = plan.starts.shape[0]
        assert plan.P == tail_width(nnz_pad, runs) <= P
        assert T == chunk_count_bucket(T, plan.P)
        assert T * plan.P <= starts.shape[0] * P
    assert len(plans) == 200 and 1 < len(now) <= len(before)


@pytest.mark.parametrize("i", [0, 1, 2, 3, 50, 120, 199])
def test_the_counters_rise_by_the_windows_slots_and_postings(pool, i):
    loaded, ctx, plans = pool
    plan, runs = plans[i]
    before = kernels.snapshot()
    term_group_topk(ctx, plan, loaded.k).block_until_ready()
    after = kernels.snapshot()
    rise = {name: after.get(name, 0) - before.get(name, 0)
            for name in ("tail_window_slots", "tail_window_postings",
                         "bm25_one_program")}
    assert rise == {
        "tail_window_slots": plan.starts.shape[0] * plan.P,
        "tail_window_postings": sum(ln for _s, ln, _w in runs),
        "bm25_one_program": 1}
    assert rise["tail_window_postings"] <= rise["tail_window_slots"]


def test_a_search_moves_both_prometheus_series(pool, monkeypatch):
    loaded, _ctx, plans = pool
    # the host loop, which serves a deployment-sized text shard (at the
    # rehearsal's thousand documents the mesh path would take the search)
    monkeypatch.setenv("ESTPU_DISABLE_MESH", "1")

    def read(text, series):
        rows = [ln for ln in text.splitlines() if ln.startswith(series)]
        return float(rows[0].rsplit(" ", 1)[1]) if rows else 0.0

    plan, runs = plans[7]
    metrics = loaded.node.metrics
    before = metrics.expose()
    out = loaded.node.search(loaded.index, loaded.request(7))
    after = metrics.expose()
    assert out["hits"]["hits"]
    assert read(after, SLOTS) - read(before, SLOTS) \
        == plan.starts.shape[0] * plan.P
    assert read(after, POSTINGS) - read(before, POSTINGS) \
        == sum(ln for _s, ln, _w in runs)


def test_the_full_size_pool_by_the_df_law_alone():
    """The cell's own 6,980 queries at its own size, from the
    configuration's df law (no corpus: a term's run is its df long, the 64
    highest-df terms have dense rows — the impact block's 1 GiB over
    4,194,304 slots of f32): the window halves, no query gets more slots
    than under the old layout, and the pool falls into no more classes."""
    import types

    from benchmarks.data.text import make_queries, zipf_df
    from elasticsearch_tpu.ops.scoring import DENSE_ROW_PAD
    from elasticsearch_tpu.search.context import chunk_table
    from elasticsearch_tpu.utils.shapes import pow2_bucket
    from tests.unit.test_tail_window import legacy_window

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "msmarco-passage-shard.json")) as fh:
        cfg = json.load(fh)
    df = zipf_df(cfg["documents_per_shard"], cfg["vocab"],
                 cfg["postings_per_doc"], cfg["zipf_exponent"],
                 cfg["df_cap_share"])
    q = cfg["queries"]
    queries = make_queries(
        types.SimpleNamespace(df=df, vocab=cfg["vocab"]), q["pool_seed"],
        n_queries=q["pool"], min_terms=q["min_terms"],
        max_terms=q["max_terms"])
    slots = {"now": [], "before": []}
    classes = {"now": set(), "before": set()}
    postings = 0
    for terms in queries:
        n_dense = int((terms < 64).sum())
        R = pow2_bucket(n_dense, minimum=DENSE_ROW_PAD) if n_dense else 0
        runs = [(0, int(df[t]), 1.0) for t in terms if t >= 64]
        postings += sum(ln for _s, ln, _w in runs)
        P = tail_width(1 << 27, runs)
        for name, (starts, _l, _w, width) in (
                ("now", chunk_table(runs, P) + (P,)),
                ("before", legacy_window(runs))):
            slots[name].append(starts.shape[0] * width)
            classes[name].add((R, starts.shape[0], width))
    n = len(queries)
    assert all(a <= b for a, b in zip(slots["now"], slots["before"]))
    assert 190_000 < sum(slots["before"]) / n < 200_000
    assert sum(slots["now"]) / n < 100_000
    assert postings / sum(slots["now"]) > 0.7
    assert len(classes["now"]) <= len(classes["before"])
    print("slots a query", sum(slots["before"]) / n, "->",
          sum(slots["now"]) / n, "fill", postings / sum(slots["now"]),
          "classes", len(classes["before"]), "->", len(classes["now"]))
