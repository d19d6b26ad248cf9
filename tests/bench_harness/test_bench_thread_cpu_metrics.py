"""The per-thread CPU metrics are data for a reader that was there: every
``benchmarks/metrics/thread_cpu_ms.*.json`` names ``delta_per_search`` over
``estpu_thread_cpu_seconds_total`` by ``group``, stands in BENCHMARK.json's
``per_layer`` after the entries that were there before it, in the cells
that report what it moves, and a rehearsal's traced line carries the three
``.steady`` ones, which add up to the line's ``host_cpu_ms.steady``."""
import argparse
import json
import math
import os

import pytest

from benchmarks import contract

METRICS_DIR = os.path.join(contract.BENCH_DIR, "metrics")
FAMILY = "estpu_thread_cpu_seconds_total"
STEADY = ["msmarco-passage-shard.match-steady", "gist-960-exact.knn-steady",
          "msmarco-passage-4shard.match-steady", "nyc-taxis.agg-dashboard"]
BATCH = ["msmarco-passage-shard.msearch-batch"]
# name: (groups summed, layer, moves, cells)
NEW = {}
for _kind, _cells, _moves in (("steady", STEADY, "search_p50_ms"),
                              ("batch", BATCH, "search_qps")):
    NEW[f"thread_cpu_ms.request.{_kind}"] = (
        ["request"], "REST front end and host path", _moves, _cells)
    NEW[f"thread_cpu_ms.runtime.{_kind}"] = (
        ["runtime"], "device programs", _moves, _cells)
    NEW[f"thread_cpu_ms.background.{_kind}"] = (
        ["background", "other"], "process", _moves, _cells)


def _spec(name):
    with open(os.path.join(METRICS_DIR, f"{name}.json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_metric_is_data_for_the_existing_reader(name):
    groups, layer, moves, cells = NEW[name]
    spec = _spec(name)
    assert spec["reader"] == "delta_per_search"
    assert os.path.exists(os.path.join(METRICS_DIR, "readers",
                                       "delta_per_search.py"))
    assert spec["scale"] == 1000.0  # seconds a search -> ms a search
    assert spec["series"] == [{"family": FAMILY, "labels": {"group": g}}
                              for g in groups]
    assert not name.startswith("span_")
    (entry,) = [m for m in contract.load_table()["per_layer"]
                if m["name"] == name]
    assert entry == {"name": name, "unit": "ms", "better": "lower",
                     "source": "program_counter", "layer": layer,
                     "moves": moves, "workloads": cells}


def test_the_entries_follow_those_that_were_there():
    listed = [m["name"] for m in contract.load_table()["per_layer"]]
    at = listed.index("agg_bucket_slots.steady") + 1
    assert listed[at:at + len(NEW)] == list(NEW)


def test_the_groups_summed_are_every_group_once():
    for kind in ("steady", "batch"):
        got = [g for part in ("request", "runtime", "background")
               for g in NEW[f"thread_cpu_ms.{part}.{kind}"][0]]
        assert sorted(got) == ["background", "other", "request", "runtime"]


@pytest.fixture(scope="module")
def traced_line():
    """The rehearsal's traced line of the one-chip match cell."""
    from benchmarks import run as bench_run

    table = contract.load_table()
    cell = contract.cell_of(table, STEADY[0])
    args = argparse.Namespace(seed=3_700_000_011, seconds=2.0, trace=1,
                              control=0, sweep=None, describe_trace=False,
                              keep_trace=False)
    line = bench_run.run_cell(args, table, cell["name"], True)["line"]
    contract.check_last_line(line, cell, True, table)
    return line


def test_a_rehearsed_traced_line_closes_host_cpu(traced_line):
    m = traced_line["metrics"]
    parts = [m[f"thread_cpu_ms.{g}.steady"]["value"]
             for g in ("request", "runtime", "background")]
    for v in parts:
        assert isinstance(v, float) and math.isfinite(v) and v >= 0.0
    host = m["host_cpu_ms.steady"]["value"]
    assert sum(parts) == pytest.approx(host, rel=0.05)
    # the spans run on the request threads: their CPU is inside the group's
    assert parts[0] >= m["span_cpu_ms.steady"]["value"]
