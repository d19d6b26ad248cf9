"""The span metrics are data for a reader that was there: every
``benchmarks/metrics/span_*.json`` names ``delta_per_search`` and spans of
``trace/span_names.json``, is listed in BENCHMARK.json, and the rehearsal's
traced line carries it as a finite number in every cell it lists."""
import argparse
import glob
import json
import math
import os

import pytest

from benchmarks import contract
from benchmarks.trace import host_spans

METRICS_DIR = os.path.join(contract.BENCH_DIR, "metrics")
SPAN_METRICS = sorted(
    os.path.basename(p)[:-len(".json")]
    for p in glob.glob(os.path.join(METRICS_DIR, "span_*.json")))
FAMILIES = {"estpu_span_duration_seconds_sum",
            "estpu_span_self_seconds_total",
            "estpu_span_cpu_seconds_total"}


def _spec(name):
    with open(os.path.join(METRICS_DIR, f"{name}.json")) as fh:
        return json.load(fh)


def test_there_are_the_span_metrics_the_table_lists():
    table = contract.load_table()
    listed = sorted(m["name"] for m in table["per_layer"]
                    if m["source"] == "program_span"
                    and m["name"].startswith("span_"))
    assert listed == SPAN_METRICS and len(SPAN_METRICS) == 20


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_span_metric_is_data_for_the_existing_reader(name):
    spec = _spec(name)
    assert spec["reader"] == "delta_per_search"
    assert os.path.exists(os.path.join(METRICS_DIR, "readers",
                                       "delta_per_search.py"))
    assert spec["scale"] == 1000.0  # seconds a search -> ms a search
    names = host_spans.load_names()
    known = (set(names["containers"]) | set(names["leaves"])
             | set(names["derived"]))
    assert spec["series"]
    for series in spec["series"]:
        assert series["family"] in FAMILIES
        labels = series.get("labels", {})
        assert set(labels) <= {"span"}
        if labels:
            assert labels["span"] in known
    # self seconds are read of containers only: a leaf's self is its wall
    for series in spec["series"]:
        if series["family"] == "estpu_span_self_seconds_total":
            assert series["labels"]["span"] in names["containers"]
    entry = [m for m in contract.load_table()["per_layer"]
             if m["name"] == name][0]
    assert entry["unit"] == "ms" and entry["better"] == "lower"
    kind = name.rsplit(".", 1)[1]
    assert entry["moves"] == {"steady": "search_p50_ms",
                              "batch": "search_qps"}[kind]


@pytest.fixture(scope="module")
def traced_lines():
    """The rehearsal's traced line of every cell, made once."""
    from benchmarks import run as bench_run

    table = contract.load_table()
    args = argparse.Namespace(seed=11, seconds=2.0, trace=1, control=0,
                              sweep=None, describe_trace=False,
                              keep_trace=False)
    lines = {}
    for cell in table["workloads"]:
        if cell["chips"] == 1:
            line = bench_run.run_cell(args, table, cell["name"],
                                      True)["line"]
            contract.check_last_line(line, cell, True, table)
            lines[cell["name"]] = line
    return lines


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_rehearsals_traced_line_carries_the_span_metric(traced_lines, name):
    entry = [m for m in contract.load_table()["per_layer"]
             if m["name"] == name][0]
    for workload in entry["workloads"]:
        got = traced_lines[workload]["metrics"][name]
        assert got["unit"] == "ms"
        assert isinstance(got["value"], float)
        assert math.isfinite(got["value"]) and got["value"] >= 0.0


def test_the_phases_of_a_rehearsed_cell_add_up_to_its_request(traced_lines):
    """In a cell with no coalesced batch the leaves and the containers'
    self time are the request's root span, read through the metrics."""
    m = traced_lines["msmarco-passage-shard.match-steady"]["metrics"]

    def v(name):
        return m[f"span_ms.{name}.steady"]["value"]

    assert v("request") > 0 and v("dispatch") > 0 and v("device_wait") > 0
    leaves = sum(v(n) for n in (
        "pool_wait", "body_json", "rewrite", "plan", "queue_wait",
        "dispatch", "device_wait", "fetch", "respond"))
    # a rehearsal's bursts may coalesce a few searches: their batch's
    # phases are counted beside their members' batch_wait
    assert leaves + v("unaccounted") == pytest.approx(v("request"),
                                                      rel=0.10)
    assert m["span_cpu_ms.steady"]["value"] <= \
        m["host_cpu_ms.steady"]["value"]
