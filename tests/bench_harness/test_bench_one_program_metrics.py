"""``one_program.share.*`` are data for a reader that was there
(``delta_per_search``): per 100 searches answered, how many search segments
the one-program term-group path served. A program without the counter (the
parent commit) reads 0: ``counters.delta`` sums what it finds."""
import json
import os

import pytest

from benchmarks import contract
from benchmarks.metrics import counters, read_metric

METRICS_DIR = os.path.join(contract.BENCH_DIR, "metrics")
NAMES = ["one_program.share.steady", "one_program.share.batch"]
SERIES = 'estpu_kernel_dispatch_total{kernel="bm25_one_program"}'

# /_prometheus/metrics text of a program that has the series and of one
# that has not (the other kernels' counts stand in both)
OTHERS = ('estpu_kernel_dispatch_total{kernel="bm25_hybrid"} %d\n'
          'estpu_kernel_dispatch_total{kernel="bm25_fused_topk"} %d\n'
          'estpu_kernel_dispatch_total{kernel="mesh_fallback_total"} %d\n')
DUMPS = {
    # name: (text before, text after, answered, the share read)
    "no_such_series": (OTHERS % (10, 2, 12), OTHERS % (250, 14, 266),
                       254, 0.0),
    "every_search": (OTHERS % (10, 2, 12) + f"{SERIES} 10\n",
                     OTHERS % (250, 2, 252) + f"{SERIES} 250\n", 240, 100.0),
    "all_but_the_all_dense": (
        OTHERS % (0, 0, 0) + f"{SERIES} 0\n",
        OTHERS % (190, 10, 200) + f"{SERIES} 190\n", 200, 95.0),
    "series_appears_in_the_window": (
        OTHERS % (0, 0, 0), OTHERS % (50, 0, 50) + f"{SERIES} 50\n",
        50, 100.0),
}


@pytest.mark.parametrize("name", NAMES)
def test_the_metric_is_data_for_the_existing_reader(name):
    with open(os.path.join(METRICS_DIR, f"{name}.json")) as fh:
        spec = json.load(fh)
    assert spec["reader"] == "delta_per_search"
    assert os.path.exists(os.path.join(METRICS_DIR, "readers",
                                       "delta_per_search.py"))
    assert spec["scale"] == 100.0  # segments a search -> per cent
    assert spec["series"] == [{"family": "estpu_kernel_dispatch_total",
                               "labels": {"kernel": "bm25_one_program"}}]
    (entry,) = [m for m in contract.load_table()["per_layer"]
                if m["name"] == name]
    kind = name.rsplit(".", 1)[1]
    assert entry == {
        "name": name, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "device programs",
        "moves": {"steady": "search_p50_ms", "batch": "search_qps"}[kind],
        "workloads": [{"steady": "msmarco-passage-shard.match-steady",
                       "batch": "msmarco-passage-shard.msearch-batch"}[kind]]}


def test_the_metrics_are_in_the_table_by_name_each_once():
    """Wherever in ``per_layer`` they stand: a later PR appends its own
    metrics after them."""
    listed = [m["name"] for m in contract.load_table()["per_layer"]]
    assert [listed.count(name) for name in NAMES] == [1, 1]


@pytest.mark.parametrize("dump", sorted(DUMPS))
@pytest.mark.parametrize("name", NAMES)
def test_the_share_read_from_a_counter_dump(name, dump):
    before, after, answered, want = DUMPS[dump]
    ctx = {"counters": {"window": (counters.parse(before),
                                   counters.parse(after))},
           "observed": {"answered": answered}}
    got = read_metric(name, ctx)
    assert isinstance(got, float) and got == pytest.approx(want)


@pytest.mark.parametrize("name", NAMES)
def test_no_search_answered_reads_nothing(name):
    ctx = {"counters": {"window": ({}, {})}, "observed": {"answered": 0}}
    assert read_metric(name, ctx) is None
