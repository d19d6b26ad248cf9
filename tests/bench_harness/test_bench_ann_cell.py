"""The one-chip cell ``deep-image-96-ivfpq.ann-steady``: the table holds
the configuration and the cell, the cell's whole path rehearses traced and
untraced with a control that comes out not correct, the exact reference
holds to a brute-force loop, the comparison finds a planted fault of each
kind, and the metrics the cell brings read a number — 0 — from a program
that lacks their series (the parent side of a parent-against-change
comparison)."""
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

CONFIG = "deep-image-96-ivfpq"
CELL = "deep-image-96-ivfpq.ann-steady"
NEW = ("ann_program.share.steady", "adc_pallas.share.steady",
       "ann_candidate_slots.steady", "ann.span_ms.steady",
       "ivf_pq_roofline.steady")


@pytest.fixture(scope="module")
def table():
    return contract.load_table()


def _file(*parts):
    with open(os.path.join(contract.BENCH_DIR, *parts)) as fh:
        return json.load(fh)


def _limits():
    return _file("cells", f"{CELL}.json")["limits"]


def test_the_table_holds_the_configuration_and_the_cell(table):
    cfg = contract.config_of(table, CONFIG)
    assert cfg["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert cfg["reduced"] == [] and "deep-image-96-angular" in cfg["source"]
    cell = contract.cell_of(table, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "ann-steady", 1)
    body = _file("configs", f"{CONFIG}.json")
    assert (body["vectors"], body["dims"], body["similarity"], body["k"],
            body["shards"]) == (9_990_000, 96, "cosine", 10, 1)
    assert body["index_options"] == {"type": "ivf_pq", "nlist": 65536,
                                     "m": 16}
    assert body["queries"]["pool"] == 10_000
    assert body["queries"]["num_candidates"] in (1000, 2000, 5000, 10000)
    assert body["reduced"] == {}
    own = _file("cells", f"{CELL}.json")
    assert own["rate_qps"] == pytest.approx(0.625 * own["knee_qps"])
    assert own["limits"]["wrong_answers"] == 0
    assert own["limits"]["unanswered"] == 0
    assert own["limits"]["score_err"] == 1e-5
    assert 0 < own["limits"]["recall_miss"] <= 0.2
    assert "batches" not in _file("traffic", "ann-steady.json")["warmup"]


def test_the_cell_is_listed_for_what_it_reports(table):
    e2e = {m["name"]: m for m in table["end_to_end"]}
    assert CELL in e2e["search_p50_ms"]["workloads"]
    assert CELL not in e2e["search_qps"]["workloads"]
    got = [m["name"] for m in contract.metrics_of(table, CELL, True)]
    assert set(NEW) <= set(got)
    # an approximate search runs solo: the coalescer's metrics are not read
    for name in ("coalescer.batch_mean", "coalescer.queue_wait_ms",
                 "span_ms.queue_wait.steady", "knn_roofline.steady",
                 "prep.hit_share", "one_program.share.steady"):
        assert name not in got, name
    for name in NEW:
        (entry,) = [m for m in table["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "search_p50_ms"


@pytest.mark.parametrize("name,before,after,want", [
    ("ann_program.share.steady", "", "", 0.0),
    ("ann_program.share.steady",
     'estpu_kernel_dispatch_total{kernel="knn_ivf_pq"} 3\n',
     'estpu_kernel_dispatch_total{kernel="knn_ivf_pq"} 43\n', 100.0),
    ("adc_pallas.share.steady", "",
     'estpu_kernel_dispatch_total{kernel="adc_xla"} 40\n', 0.0),
    ("adc_pallas.share.steady", "",
     'estpu_kernel_dispatch_total{kernel="adc_pallas"} 10\n', 25.0),
    ("ann_candidate_slots.steady", "", "", 0.0),
    ("ann_candidate_slots.steady", "",
     'estpu_kernel_dispatch_total{kernel="ann_candidate_slots"} 286720\n',
     7168.0),
    ("ann.span_ms.steady", "", "", 0.0),
    ("ann.span_ms.steady", "",
     'estpu_span_duration_seconds_sum{span="search.ann"} 0.2\n', 5.0),
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
        args = argparse.Namespace(seed=3500000040, seconds=2.0, trace=trace,
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
    for name, got in line["compared"].items():
        assert got["value"] <= got["limit"], name
    ctl = done["record"]["control"]
    assert ctl["correct"] is False
    # it fails both ways: one precision down, one guarantee down
    assert ctl["numbers"]["score_err"] > _limits()["score_err"]
    assert ctl["numbers"]["recall_miss"] > _limits()["recall_miss"]
    assert ctl["numbers"]["wrong_answers"] == 0


def test_the_traced_rehearsal_serves_every_search_by_the_program(rehearsed,
                                                                 table):
    metrics = rehearsed[1]["line"]["metrics"]
    for m in contract.metrics_of(table, CELL, True):
        got = metrics[m["name"]]
        assert got["unit"] == m["unit"]
        assert math.isfinite(got["value"]) and got["value"] >= 0.0
    assert metrics["ann_program.share.steady"]["value"] == 100.0
    assert metrics["compiles_in_window.steady"]["value"] == 0.0
    assert metrics["ann_candidate_slots.steady"]["value"] > 0.0
    assert metrics["ann.span_ms.steady"]["value"] > 0.0
    info = rehearsed[0]["record"]["loaded"]
    assert info["lists"] == 128 and info["pq"] == {"m": 16, "k": 256}


@pytest.fixture(scope="module")
def kind():
    import jax

    table = contract.load_table()
    cfg = contract.config_of(table, CONFIG)
    with open(os.path.join(contract.ROOT, cfg["file"])) as fh:
        body = json.load(fh)
    loaded = loaders.load(body, 2 ** 31 + 40, jax.devices()[:1],
                          rehearse=True)
    loaded.node.close()
    return loaded


def _exact_answers(kind, entries):
    """Replies as an exact search would give them, from the reference."""
    out = []
    for i in entries:
        ids, cos = kind.reference.truth(i)
        out.append((i, [{"_id": str(int(d)), "_score": float((1 + c) / 2)}
                        for d, c in zip(ids[:kind.k], cos[:kind.k])]))
    return out


def _numbers(kind, sample):
    return kind.compare(sample)["numbers"]


def _verdict(kind, sample):
    return check.verdict(dict(_numbers(kind, sample), unanswered=0),
                         _limits())


def test_the_reference_is_a_brute_force_loop(kind):
    vecs = kind.reference.vecs.astype(np.float64)
    vn = vecs / np.linalg.norm(vecs, axis=1, keepdims=True)
    for i in range(0, kind.pool_size, 9):
        q = kind.queries[i].astype(np.float32).astype(np.float64)
        cos = vn @ (q / np.linalg.norm(q))
        want = np.argsort(-cos, kind="stable")[:kind.k]
        ids, got = kind.reference.truth(i)
        np.testing.assert_array_equal(ids[:kind.k], want)
        np.testing.assert_allclose(got[:kind.k], cos[want], rtol=1e-12)


def test_the_exact_answer_is_correct_and_planted_faults_are_not(kind):
    sample = _exact_answers(kind, range(12))
    assert _numbers(kind, sample) == {"wrong_answers": 0, "score_err":
                                      pytest.approx(0.0, abs=1e-12),
                                      "recall_miss": 0.0}
    assert _verdict(kind, sample)
    # a score off by a ten-thousandth
    planted = copy.deepcopy(sample)
    planted[0][1][3]["_score"] *= 1.0 + 1e-4
    planted[0][1].sort(key=lambda h: -h["_score"])
    assert _numbers(kind, planted)["score_err"] == pytest.approx(1e-4,
                                                                 rel=1e-3)
    assert not _verdict(kind, planted)
    # a document twice, and one hit short
    for fault in ("twice", "short"):
        planted = copy.deepcopy(sample)
        if fault == "twice":
            planted[1][1][-1] = dict(planted[1][1][-2])
        else:
            del planted[1][1][-1]
        assert _numbers(kind, planted)["wrong_answers"] == 1
        assert not _verdict(kind, planted)


def test_far_documents_in_place_of_neighbours_are_a_recall_miss(kind):
    """Every answer's last four hits replaced by the farthest documents
    (scores exact, order kept): recall 0.6, past the limit."""
    sample = _exact_answers(kind, range(12))
    planted = copy.deepcopy(sample)
    for i, hits in planted:
        q = kind.queries[i].astype(np.float32).astype(np.float64)
        v = kind.reference.vecs.astype(np.float64)
        cos = (v @ q) / (np.linalg.norm(v, axis=1) * np.linalg.norm(q))
        far = np.argsort(cos, kind="stable")[:4][::-1]
        hits[6:] = [{"_id": str(int(d)), "_score": float((1 + cos[d]) / 2)}
                    for d in far]
    got = _numbers(kind, planted)
    assert got["wrong_answers"] == 0 and got["score_err"] < 1e-12
    assert got["recall_miss"] == pytest.approx(0.4)
    assert not _verdict(kind, planted)


def test_the_control_is_not_correct(kind):
    pool = list(range(kind.pool_size))
    numbers = _numbers(kind, kind.control(pool))
    assert numbers["wrong_answers"] == 0
    assert numbers["score_err"] > _limits()["score_err"]
    assert numbers["recall_miss"] > _limits()["recall_miss"]
    assert not _verdict(kind, kind.control(pool))


def test_the_work_counts_the_probed_lists_and_no_padding(kind):
    w = [kind.work(i) for i in range(kind.pool_size)]
    assert all(x["batch_bytes"] == 0.0 for x in w)
    floor = kind.C * kind.dims * 4.0 + kind.fine_k * kind.dims * 4.0
    for i, x in enumerate(w):
        real = kind.real[i]
        assert 0 < real <= kind.nprobe * kind.info["Lmax"]
        assert x["bytes"] == pytest.approx(floor + real * (4 + kind.M))
