"""The one-chip cell ``nyc-taxis.agg-dashboard`` (PR 35): the table holds
the configuration and the cell, the cell's whole path rehearses traced and
untraced with a control that comes out not correct, the comparison finds
a planted fault of either kind, and the metrics the cell brings read a
number — 0 — from a program that lacks their series (the parent side of
the driver's check)."""
import argparse
import copy
import json
import math
import os

import numpy as np
import pytest

from benchmarks import contract, loaders
from benchmarks.metrics import counters, read_metric
from benchmarks.reference import check

CONFIG = "nyc-taxis"
CELL = "nyc-taxis.agg-dashboard"
NEW = ("agg_program.share.steady", "agg_roofline.steady",
       "aggs.span_ms.steady")


@pytest.fixture(scope="module")
def table():
    return contract.load_table()


def _file(*parts):
    with open(os.path.join(contract.BENCH_DIR, *parts)) as fh:
        return json.load(fh)


def test_the_table_holds_the_configuration_and_the_cell(table):
    cfg = contract.config_of(table, CONFIG)
    assert cfg["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert cfg["reduced"] == ["columns"] and "nyc_taxis" in cfg["source"]
    cell = contract.cell_of(table, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "agg-dashboard", 1)
    body = _file("configs", f"{CONFIG}.json")
    assert body["documents"] == 165_346_692 and body["shards"] == 1
    assert set(body["reduced"]) == {"columns"}
    for field in ("trip_distance", "total_amount"):
        assert body["mappings"][field] == {"type": "scaled_float",
                                           "scaling_factor": 100}
    own = _file("cells", f"{CELL}.json")
    assert own["rate_qps"] == pytest.approx(0.625 * own["knee_qps"])
    assert own["limits"]["wrong_buckets"] == 0
    assert own["limits"]["unanswered"] == 0
    assert 0 < own["limits"]["stat_err"] < 1e-3


def test_the_cell_is_listed_for_what_it_reports(table):
    e2e = {m["name"]: m for m in table["end_to_end"]}
    assert CELL in e2e["search_p50_ms"]["workloads"]
    assert CELL not in e2e["search_qps"]["workloads"]
    got = [m["name"] for m in contract.metrics_of(table, CELL, True)]
    assert set(NEW) <= set(got)
    # aggregated searches bypass the coalescer: its metrics are not read
    for name in ("coalescer.batch_mean", "coalescer.queue_wait_ms",
                 "span_ms.queue_wait.steady", "bm25_roofline.steady",
                 "knn_roofline.steady", "one_program.share.steady"):
        assert name not in got, name
    for name in NEW:
        (entry,) = [m for m in table["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL] and entry["moves"] == \
            "search_p50_ms"


@pytest.mark.parametrize("name,before,after,want", [
    ("agg_program.share.steady", "", "", 0.0),
    ("agg_program.share.steady",
     'estpu_kernel_dispatch_total{kernel="agg_one_program"} 3\n',
     'estpu_kernel_dispatch_total{kernel="agg_one_program"} 43\n', 100.0),
    ("aggs.span_ms.steady", "",
     'estpu_span_duration_seconds_sum{span="search.aggs"} 0.02\n', 0.5),
])
def test_a_new_metric_reads_a_number_from_a_counter_dump(name, before,
                                                         after, want):
    ctx = {"counters": {"window": (counters.parse(before),
                                   counters.parse(after))},
           "observed": {"answered": 40}}
    got = read_metric(name, ctx)
    assert isinstance(got, float) and got == pytest.approx(want)


@pytest.fixture(scope="module")
def rehearsed():
    from benchmarks import run as bench_run

    table = contract.load_table()
    cell = contract.cell_of(table, CELL)
    out = {}
    for trace in (0, 1):
        args = argparse.Namespace(seed=3500000035, seconds=2.0, trace=trace,
                                  control=1, sweep=None, describe_trace=False,
                                  keep_trace=False)
        done = bench_run.run_cell(args, table, CELL, True)
        contract.check_last_line(done["line"], cell, bool(trace), table)
        contract.dumps_line(done["line"])
        out[trace] = done
    return out


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_correct_with_a_control_that_is_not(rehearsed,
                                                              trace):
    done = rehearsed[trace]
    line = done["line"]
    assert line["correct"] is True and line["failed"] == 0
    assert done["record"]["control"]["correct"] is False
    assert done["record"]["control"]["numbers"]["stat_err"] > \
        line["compared"]["stat_err"]["limit"]
    for name, got in line["compared"].items():
        assert got["value"] <= got["limit"], name


def test_the_traced_rehearsal_serves_every_search_by_the_program(rehearsed,
                                                                 table):
    metrics = rehearsed[1]["line"]["metrics"]
    for m in contract.metrics_of(table, CELL, True):
        got = metrics[m["name"]]
        assert got["unit"] == m["unit"]
        assert math.isfinite(got["value"]) and got["value"] >= 0.0
    assert metrics["agg_program.share.steady"]["value"] == 100.0
    assert metrics["compiles_in_window.steady"]["value"] == 0.0


@pytest.fixture(scope="module")
def kind():
    table = contract.load_table()
    cfg = contract.config_of(table, CONFIG)
    with open(os.path.join(contract.ROOT, cfg["file"])) as fh:
        body = json.load(fh)
    loaded = loaders.load(body, 2 ** 31 + 35, None, rehearse=True)
    loaded.node.close()
    return loaded


def _reference_answers(kind, entries):
    """Replies as the program would give them, from the reference."""
    return [(i, kind._as_reply(i, kind.reference.answer(kind.pool[i])))
            for i in entries]


def _limits():
    return _file("cells", f"{CELL}.json")["limits"]


def _verdict(kind, sample):
    return check.verdict(dict(kind.compare(sample)["numbers"],
                              unanswered=0), _limits())


def test_the_reference_holds_to_a_plain_count(kind):
    trips = kind.reference.trips
    for i, e in enumerate(kind.pool):
        rows = kind.reference.answer(e)
        if e[0] == "distance":
            sel = ((trips["distance_cents"] >= 100 * e[1])
                   & (trips["distance_cents"] < 100 * e[2]))
        else:
            sel = ((trips["dropoff_s"] >= 86400 * e[1])
                   & (trips["dropoff_s"] <= 86400 * (e[1] + e[2])))
        assert sum(r["doc_count"] for r in rows) == int(sel.sum()), e


def test_the_pool_is_the_tracks_form(kind):
    """Mile bodies are the track's ``gte: 0`` band with an upper bound of
    5 to 50 miles; date windows are whole days inside 2015."""
    miles = [e for e in kind.pool if e[0] == "distance"]
    dates = [e for e in kind.pool if e[0] == "date"]
    assert len(miles) == len(dates) == kind.pool_size // 2
    assert all(e[1] == 0 and 5 <= e[2] <= 50 for e in miles)
    assert all(1 <= e[2] <= 31 and e[1] + e[2] <= 365 for e in dates)
    assert len(set(dates)) == len(dates)
    body = kind.request(kind.pool.index(miles[0]))
    rng = body["query"]["bool"]["filter"]["range"]["trip_distance"]
    assert rng == {"gte": 0, "lt": miles[0][2]}


def test_a_trip_moved_across_midnight_is_a_wrong_answer(kind):
    dates = [i for i, e in enumerate(kind.pool) if e[0] == "date"
             and len(kind.reference.answer(e)) >= 2]
    sample = _reference_answers(kind, dates[:8])
    assert _verdict(kind, sample)
    planted = copy.deepcopy(sample)
    buckets = planted[0][1]["dropoffs_over_time"]["buckets"]
    j = next(j for j in range(1, len(buckets)) if buckets[j]["doc_count"])
    buckets[j]["doc_count"] -= 1  # one trip of 00:00:00 ...
    buckets[j - 1]["doc_count"] += 1  # ... counted the day before
    assert kind.compare(planted)["numbers"]["wrong_buckets"] == 2
    assert not _verdict(kind, planted)


def test_a_sum_off_by_a_ten_thousandth_is_a_wrong_answer(kind):
    miles = [i for i, e in enumerate(kind.pool) if e[0] == "distance"]
    sample = _reference_answers(kind, miles[:8])
    planted = copy.deepcopy(sample)
    stats = next(b["total_amount_stats"]
                 for b in planted[0][1]["distance_histo"]["buckets"]
                 if b["doc_count"])
    stats["sum"] *= 1.0 + 1e-4
    numbers = kind.compare(planted)["numbers"]
    assert numbers["wrong_buckets"] == 0
    assert numbers["stat_err"] == pytest.approx(1e-4, rel=1e-3)
    assert not _verdict(kind, planted)


def test_the_control_is_not_correct(kind):
    pool = list(range(kind.pool_size))
    numbers = kind.compare(kind.control(pool))["numbers"]
    assert numbers["wrong_buckets"] == 0
    assert numbers["stat_err"] > _limits()["stat_err"]
    assert not _verdict(kind, kind.control(pool))
    assert np.isfinite(numbers["stat_err"])
