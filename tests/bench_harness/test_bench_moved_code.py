"""Moving the kinds into files moved no request and no sample. Recorded
from the parent of PR 31 (bd654ed) before any code moved, at rehearsal size
on the CPU: for each cell and two seeds the SHA-256 of the schedule
``schedule.build`` returns and the pool indices ``sample_answers`` picks
from a fixed synthetic window (``golden/parent_readings.json``). The moved
code is held to those values; and ``Loaded``'s base rules for what an
answer is are the rules ``run.py`` had."""
import hashlib
import json
import os

import pytest

from benchmarks import contract, loaders
from benchmarks import run as bench_run
from benchmarks.loadgen import schedule

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "golden", "parent_readings.json")) as fh:
    GOLDEN = json.load(fh)["readings"]


def reading(table: dict, workload: str, seed: int) -> dict:
    import jax

    files = bench_run.CellFiles(table, workload, True)
    loaded = loaders.load(files.config, seed, jax.devices()[:1], True)
    try:
        sched = schedule.build(files.traffic, seed, 2.0,
                               files.own.get("rate_qps"), loaded)
        flat = (sched["requests"] if sched["mode"] == "open"
                else [r for lst in sched["requests"] for r in lst])
        # a window in which every seventh search got no valid reply
        reqs = [{"pool": r["pool"],
                 "answers": [None if (n + j) % 7 == 0 else []
                             for j in range(len(r["pool"]))]}
                for n, r in enumerate(flat)]
        picked = [int(q) for q, _ in bench_run.sample_answers(
            reqs, loaded, int(files.own["sample"]), seed)]
    finally:
        loaded.node.close()
    return {"schedule_sha256": hashlib.sha256(json.dumps(
        sched, sort_keys=True).encode()).hexdigest(),
        "requests": len(flat), "sampled": picked}


@pytest.mark.parametrize("seed", [5, 2**31 + 12345])
@pytest.mark.parametrize("workload", sorted(GOLDEN))
def test_the_moved_code_sends_and_samples_what_the_parent_did(workload,
                                                              seed):
    got = reading(contract.load_table(), workload, seed)
    assert got == GOLDEN[workload][str(seed)]


# ---- what an answer is: the base class has run.py's old rule --------------------

HITS = [{"_id": "3", "_score": 1.5}]
REPLIES = [
    ({"hits": {"hits": HITS}}, HITS),
    ({"hits": {"hits": []}, "timed_out": False}, []),
    ({"hits": {"hits": HITS}, "timed_out": True}, None),
    ({"hits": {"hits": HITS}, "error": {"type": "x"}}, None),
    ({"hits": {"total": 3}}, None),
    ({"aggregations": {"by": {"buckets": []}}}, None),
    ({}, None),
]


@pytest.mark.parametrize("reply,want", REPLIES)
def test_the_base_answer_is_a_list_of_hits_or_nothing(reply, want):
    assert loaders.Loaded().answer(reply) == want


class Aggregated(loaders.Loaded):
    index, pool_size = "idx", 4

    def answer(self, reply):
        return reply.get("aggregations")


def _result(sched, texts, status=200):
    return {"records": [[i, 0.0, 0.0, 0.1, status, t]
                        for i, t in enumerate(texts)]}


def test_unpack_asks_the_loaded_what_an_answer_is():
    sched = {"mode": "open", "requests": [
        {"path": "/idx/_search", "pool": [0]},
        {"path": "/idx/_search", "pool": [1]},
        {"path": "/_msearch", "pool": [2, 3]},
        {"path": "/_msearch", "pool": [2, 3]}]}
    aggs = {"by": {"buckets": []}}
    texts = [json.dumps({"aggregations": aggs}),
             json.dumps({"hits": {"hits": HITS}}),
             json.dumps({"responses": [{"aggregations": aggs}, "oops"]}),
             json.dumps({"responses": [{"aggregations": aggs}]})]
    got = bench_run.unpack(sched, _result(sched, texts), Aggregated())
    assert [r["answers"] for r in got] == [[aggs], [None], [aggs, None],
                                           [None, None]]
    base = bench_run.unpack(sched, _result(sched, texts), loaders.Loaded())
    assert [r["answers"] for r in base] == [[None], [HITS], [None, None],
                                            [None, None]]
    failed = bench_run.unpack(sched, _result(sched, texts, 503), Aggregated())
    assert all(a is None for r in failed for a in r["answers"])
    assert failed[0]["error"] == texts[0][:300]


def test_the_pool_pass_sends_each_entry_where_the_loaded_says():
    class Elsewhere(Aggregated):
        def request(self, i):
            return {"n": i}

        def path(self, i):
            return "/idx/_count" if i % 2 else "/idx/_search"

    s = bench_run.singles(Elsewhere(), [0, 1, 2], 2)
    sent = sorted((r["pool"][0], r["path"]) for lst in s["requests"]
                  for r in lst)
    assert sent == [(0, "/idx/_search"), (1, "/idx/_count"),
                    (2, "/idx/_search")]
    with pytest.raises(ValueError, match="not a search"):
        schedule.build({"kind": "closed_loop_msearch", "clients": 1,
                        "bodies": 2}, 1, 1.0, None, Elsewhere())


# ---- a kind without a control cannot be loaded -----------------------------------

def test_a_kind_without_a_control_cannot_be_loaded(monkeypatch):
    import types

    class NoControl(loaders.Loaded):
        reference = object()  # no ``control`` on it either

    class OwnControl(NoControl):
        def control(self, pool):
            return []

    made = {}

    def module(package, name, what):
        return types.SimpleNamespace(load=lambda *a: made["cls"]())

    monkeypatch.setattr(loaders.byname, "module", module)
    made["cls"] = NoControl
    with pytest.raises(ValueError, match="has no control"):
        loaders.load({"kind": "k"}, 1, [], True)
    made["cls"] = OwnControl
    assert isinstance(loaders.load({"kind": "k"}, 1, [], True), OwnControl)
