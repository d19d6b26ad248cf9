"""``agg_bucket_slots.steady`` is data for a reader that was there
(``delta_per_search``): slots the ``agg_tree`` program scanned times the
bucket passes it made, a search answered, off ``estpu_kernel_dispatch_total
{kernel="agg_bucket_slots"}``. A program without the series (a parent
whose kernel scans every slot) reads 0, not nothing:
``counters.delta`` sums what it finds, so the parent side of a pair still
prints a line."""
import json
import os

import pytest

from benchmarks import contract
from benchmarks.metrics import counters, read_metric

NAME = "agg_bucket_slots.steady"
CELL = "nyc-taxis.agg-dashboard"
SLOTS = 'estpu_kernel_dispatch_total{kernel="agg_bucket_slots"}'
ONE = 'estpu_kernel_dispatch_total{kernel="agg_one_program"} %d\n'
# 631 blocks of 262,144 slots hold the 165,346,692 trips of 2^28 slots
SCANNED = 631 * 262_144
DUMPS = {
    # name: (text before, text after, answered, slots a search)
    "no_such_series": (ONE % 10, ONE % 210, 200, 0.0),
    # a mile histogram of 28 buckets and a daily one of 17
    "two_searches": (ONE % 0 + f"{SLOTS} 0\n",
                     ONE % 2 + f"{SLOTS} {SCANNED * (28 + 17)}\n", 2,
                     SCANNED * 45 / 2),
    "series_appears_in_the_window": (ONE % 0, ONE % 4 + f"{SLOTS} 32768\n",
                                     4, 8192.0),
}


def test_the_metric_is_data_for_the_existing_reader():
    with open(os.path.join(contract.BENCH_DIR, "metrics",
                           f"{NAME}.json")) as fh:
        spec = json.load(fh)
    assert spec["reader"] == "delta_per_search"
    assert spec["scale"] == 1.0
    assert spec["series"] == [{"family": "estpu_kernel_dispatch_total",
                               "labels": {"kernel": "agg_bucket_slots"}}]
    (entry,) = [m for m in contract.load_table()["per_layer"]
                if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "slots", "better": "lower",
        "source": "program_counter", "layer": "device programs",
        "moves": "search_p50_ms", "workloads": [CELL]}


@pytest.mark.parametrize("dump", sorted(DUMPS))
def test_the_slots_read_from_a_counter_dump(dump):
    before, after, answered, want = DUMPS[dump]
    ctx = {"counters": {"window": (counters.parse(before),
                                   counters.parse(after))},
           "observed": {"answered": answered}}
    got = read_metric(NAME, ctx)
    assert isinstance(got, float) and got == pytest.approx(want)


def test_no_search_answered_reads_nothing():
    ctx = {"counters": {"window": ({}, {})}, "observed": {"answered": 0}}
    assert read_metric(NAME, ctx) is None
