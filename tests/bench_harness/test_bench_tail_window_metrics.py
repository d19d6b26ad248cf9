"""``tail_window.slots.*`` are data for a reader that was there
(``delta_per_search``): slots of the tail's ``[T, P]`` postings window a
search answered, off ``estpu_kernel_dispatch_total{kernel=
"tail_window_slots"}`` (PR 30). A program without the series (a parent
before PR 30) reads 0, not nothing: ``counters.delta`` sums what it finds,
so the parent side of a pair still prints a line."""
import json
import os

import pytest

from benchmarks import contract
from benchmarks.metrics import counters, read_metric

METRICS_DIR = os.path.join(contract.BENCH_DIR, "metrics")
NAMES = ["tail_window.slots.steady", "tail_window.slots.batch"]
SLOTS = 'estpu_kernel_dispatch_total{kernel="tail_window_slots"}'
OTHERS = ('estpu_kernel_dispatch_total{kernel="tail_window_postings"} %d\n'
          'estpu_kernel_dispatch_total{kernel="bm25_one_program"} %d\n')
DUMPS = {
    # name: (text before, text after, answered, slots a search)
    "no_such_series": (
        'estpu_kernel_dispatch_total{kernel="bm25_one_program"} 10\n',
        'estpu_kernel_dispatch_total{kernel="bm25_one_program"} 210\n',
        200, 0.0),
    # PR 30's match-steady reading: 174,360,488 slots for 2,000 searches
    "a_window_of_match_steady": (
        OTHERS % (0, 0) + f"{SLOTS} 1000000\n",
        OTHERS % (126485221, 2000) + f"{SLOTS} 175360488\n",
        2000, 87180.244),
    "series_appears_in_the_window": (
        OTHERS % (0, 0), OTHERS % (300, 4) + f"{SLOTS} 32768\n", 4, 8192.0),
    "nothing_scattered": (
        OTHERS % (5, 5) + f"{SLOTS} 4096\n",
        OTHERS % (5, 9) + f"{SLOTS} 4096\n", 4, 0.0),
}


@pytest.mark.parametrize("name", NAMES)
def test_the_metric_is_data_for_the_existing_reader(name):
    with open(os.path.join(METRICS_DIR, f"{name}.json")) as fh:
        spec = json.load(fh)
    assert spec["reader"] == "delta_per_search"  # not ratio_of_deltas: PR 26
    assert spec["scale"] == 1.0
    assert spec["series"] == [{"family": "estpu_kernel_dispatch_total",
                               "labels": {"kernel": "tail_window_slots"}}]
    (entry,) = [m for m in contract.load_table()["per_layer"]
                if m["name"] == name]
    kind = name.rsplit(".", 1)[1]
    assert entry == {
        "name": name, "unit": "slots", "better": "lower",
        "source": "program_counter", "layer": "device programs",
        "moves": {"steady": "search_p50_ms", "batch": "search_qps"}[kind],
        "workloads": [{"steady": "msmarco-passage-shard.match-steady",
                       "batch": "msmarco-passage-shard.msearch-batch"}[kind]]}


@pytest.mark.parametrize("dump", sorted(DUMPS))
@pytest.mark.parametrize("name", NAMES)
def test_the_slots_read_from_a_counter_dump(name, dump):
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
