"""The four-chip cell ``msmarco-passage-4shard.match-steady`` (PR 33): the
table holds the configuration and the cell as ISSUE 33 wrote them out, the
cell's whole path rehearses on 4 of the forced host devices traced and
untraced with a control that comes out not correct, and both metrics the
cell brings read 0 — a number — from a program that lacks their series
(the parent side of the driver's check: PR 26's refusal)."""
import argparse
import json
import math
import os

import pytest

from benchmarks import contract
from benchmarks.metrics import counters, read_metric

CONFIG = "msmarco-passage-4shard"
CELL = "msmarco-passage-4shard.match-steady"
ONE_CHIP = "msmarco-passage-shard"
NEW = {
    "sharded_program.share.steady": ("%", "higher", "bm25_sharded_program",
                                     100.0),
    "postings_split.per_search.steady": ("programs", "lower",
                                         "bm25_postings_sharded", 1.0),
}
# what both sides of a pair can read in this cell (ISSUE 33, step 1.3)
LISTED = ["loadgen.late_p95_ms", "host_cpu_ms.steady", "tail_p95_ms.steady",
          "coalescer.batch_mean", "coalescer.queue_wait_ms",
          "compiles_in_window.steady", "bm25_roofline.steady",
          "span_cpu_ms.steady"]


@pytest.fixture(scope="module")
def table():
    return contract.load_table()


def _file(*parts):
    with open(os.path.join(contract.BENCH_DIR, *parts)) as fh:
        return json.load(fh)


def test_the_table_holds_the_configuration_and_the_cell(table):
    cfg = contract.config_of(table, CONFIG)
    assert cfg["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert cfg["reduced"] == [] and "arXiv:1611.09268" in cfg["source"]
    cell = contract.cell_of(table, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "match-steady", 4)


def test_the_configuration_is_the_one_chip_shard_four_times(table):
    body, one = _file("configs", f"{CONFIG}.json"), _file(
        "configs", f"{ONE_CHIP}.json")
    assert body["shards"] == 4 and one["shards"] == 1
    assert body["reduced"] == {}
    for key in ("kind", "index", "field", "documents_per_shard", "vocab",
                "postings_per_doc", "zipf_exponent", "df_cap_share", "bm25",
                "queries", "size", "rehearsal"):
        assert body[key] == one[key], key
    assert 4 * body["documents_per_shard"] == 8_841_824
    assert "merge" in body["guarantees"]
    assert body["guarantees"]["exact"] == one["guarantees"]["exact"]
    for key, text in one["assumed"].items():
        assert body["assumed"][key] == text
    assert "second copy" in body["device_bytes_reckoned"]


def test_the_cell_has_the_one_chip_cells_limits_and_a_rate_under_the_knee():
    own = _file("cells", f"{CELL}.json")
    one = _file("cells", f"{ONE_CHIP}.match-steady.json")
    assert own["limits"] == one["limits"]
    assert own["sample"] == 96
    assert own["rate_qps"] == pytest.approx(0.625 * own["knee_qps"],
                                            rel=0.02)
    assert "parent" in own["knee_from"] and own["rate_from"]


def test_the_cell_is_listed_where_both_sides_of_a_pair_can_read(table):
    e2e = {m["name"]: m for m in table["end_to_end"]}
    assert CELL in e2e["search_p50_ms"]["workloads"]
    assert CELL not in e2e["search_qps"]["workloads"]
    per_layer = {m["name"]: m for m in table["per_layer"]}
    spans = [n for n in per_layer
             if n.startswith("span_ms.") and n.endswith(".steady")]
    assert len(spans) == 11
    for name in LISTED + spans:
        assert CELL in per_layer[name]["workloads"], name
    for name in per_layer:
        if name.endswith(".batch") or name in (
                "one_program.share.steady", "prep.hit_share",
                "program_call_ms.steady", "knn_roofline.steady"):
            assert CELL not in per_layer[name]["workloads"], name
    got = [m["name"] for m in contract.metrics_of(table, CELL, True)]
    assert set(NEW) <= set(got)
    assert [m["name"] for m in contract.metrics_of(table, CELL, False)] == [
        "search_p50_ms", "setup_s"]


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_metric_is_data_for_the_reader_that_sums_what_it_finds(
        table, name):
    unit, better, kernel, scale = NEW[name]
    spec = _file("metrics", f"{name}.json")
    assert spec["reader"] == "delta_per_search"  # never ratio_of_deltas
    assert spec["scale"] == scale
    assert spec["series"] == [{"family": "estpu_kernel_dispatch_total",
                               "labels": {"kernel": kernel}}]
    (entry,) = [m for m in table["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": "program_counter", "layer": "device programs",
                     "moves": "search_p50_ms", "workloads": [CELL]}


SHARDED = 'estpu_kernel_dispatch_total{kernel="bm25_sharded_program"}'
SPLIT = 'estpu_kernel_dispatch_total{kernel="bm25_postings_sharded"}'
OTHER = 'estpu_kernel_dispatch_total{kernel="bm25_hybrid"}'
DUMPS = {
    # name: (before, after, answered, share %, splits a search)
    "a_program_without_either_series": (
        f"{OTHER} 10\n", f"{OTHER} 170\n", 40, 0.0, 0.0),
    "the_parent_splits_every_shard_in_place": (
        f"{SPLIT} 400\n", f"{SPLIT} 560\n", 40, 0.0, 4.0),
    "the_change_serves_every_search_by_one_program": (
        f"{SHARDED} 7\n{SPLIT} 0\n", f"{SHARDED} 47\n{SPLIT} 0\n", 40,
        100.0, 0.0),
    "one_search_of_forty_falls_to_the_old_route": (
        f"{SHARDED} 0\n", f"{SHARDED} 39\n{OTHER} 4\n", 40, 97.5, 0.0),
}


@pytest.mark.parametrize("dump", sorted(DUMPS))
def test_the_new_metrics_read_a_number_from_a_counter_dump(dump):
    before, after, answered, share, splits = DUMPS[dump]
    ctx = {"counters": {"window": (counters.parse(before),
                                   counters.parse(after))},
           "observed": {"answered": answered}}
    for name, want in (("sharded_program.share.steady", share),
                       ("postings_split.per_search.steady", splits)):
        got = read_metric(name, ctx)
        assert isinstance(got, float) and got == pytest.approx(want)


@pytest.fixture(scope="module")
def rehearsed():
    """The cell's whole path at rehearsal size on 4 of the forced host
    devices, untraced and traced, the control read beside it."""
    from benchmarks import run as bench_run

    table = contract.load_table()
    cell = contract.cell_of(table, CELL)
    out = {}
    for trace in (0, 1):
        args = argparse.Namespace(seed=3300000033, seconds=2.0, trace=trace,
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
    assert line["device"]["count"] == 4
    assert done["record"]["loaded"]["shards"] == 4
    assert done["record"]["control"]["correct"] is False
    for name, got in line["compared"].items():
        assert got["value"] <= got["limit"], name


def test_the_traced_rehearsal_carries_every_metric_listed_for_the_cell(
        rehearsed, table):
    metrics = rehearsed[1]["line"]["metrics"]
    for m in contract.metrics_of(table, CELL, True):
        got = metrics[m["name"]]
        assert got["unit"] == m["unit"]
        assert isinstance(got["value"], float) and math.isfinite(
            got["value"]) and got["value"] >= 0.0
    assert "one_program.share.steady" not in metrics
    # (at rehearsal size the mesh DSL path serves the index: neither the
    # sharded program nor the in-place split runs, and both read 0)
    assert metrics["sharded_program.share.steady"]["value"] == 0.0
    assert metrics["postings_split.per_search.steady"]["value"] == 0.0
