"""The warm-up's batches formed on purpose (``warmup.batches`` of a traffic
file): under the program's own dynamic settings n searches due at once make
one batch of exactly n, so that every padded batch shape is compiled before
the window whatever thread timing does; the settings are back at their
defaults before the window. PR 31: 1 of 12 knn-steady runs first met the
17-32 shape inside its window and compiled there."""
import argparse
import json
import types

import pytest

from benchmarks import contract, loaders
from benchmarks import run as bench_run

CELL = "gist-960-exact.knn-steady"
HOLD = {"serving.coalescer.mode": "always",
        "serving.coalescer.max_wait": "2s"}
TRAFFIC = {"kind": "open_loop_singles", "connections": 8,
           "popularity": {"law": "uniform"}, "law_seed": 3,
           "warmup": {"pass_clients": 2, "seconds": 0.5, "max_rounds": 1,
                      "batches": {"sizes": [2, 5], "hold": HOLD,
                                  "size_setting": "max_batch"}}}


class Pool(loaders.Loaded):
    index, pool_size = "idx", 12

    def request(self, i):
        return {"q": i}


class FakeGen:
    """Answers every search; refuses a setting where ``refuse`` says so."""

    def __init__(self, refuse=lambda transient: False):
        self.sent, self.refuse = [], refuse

    def run(self, sched):
        self.sent.append(sched)
        flat = (list(enumerate(sched["requests"])) if sched["mode"] == "open"
                else [([c, j], r) for c, lst in enumerate(sched["requests"])
                      for j, r in enumerate(lst)])
        records = []
        for idx, r in flat:
            bad = (r["method"] == "PUT" and self.refuse(
                json.loads(r["body"])["transient"]))
            records.append([idx, 0.0, 0.0, 0.1, 400 if bad else 200,
                            json.dumps({"hits": {"hits": []}})])
        return {"t0": 0.0, "t_end": 1.0, "records": records}


def _warm(gen, traffic=TRAFFIC):
    files = types.SimpleNamespace(traffic=traffic)
    return bench_run.warm_up(files, gen, Pool(), 7, 1.0, 20.0, lambda: 0.0)


def _puts(gen):
    return [json.loads(s["requests"][0][0]["body"])["transient"]
            for s in gen.sent if s["mode"] == "closed"
            and s["requests"][0][0]["method"] == "PUT"]


def test_each_size_is_sent_at_once_under_its_own_setting_then_reset():
    gen = FakeGen()
    out = _warm(gen)
    names = [row["phase"] for row in out["phases"]]
    assert names == ["first touch", "pool pass", "batch of 2", "batch of 5",
                     "round 1"]
    assert _puts(gen) == [dict(HOLD, max_batch=2), dict(HOLD, max_batch=5),
                          {**dict.fromkeys(HOLD), "max_batch": None}]
    # PUT, burst, PUT, burst, reset: nothing else goes in between
    kinds = ["put" if s["mode"] == "closed"
             and s["requests"][0][0]["method"] == "PUT" else s["mode"]
             for s in gen.sent]
    assert kinds == ["closed", "closed", "put", "open", "put", "open", "put",
                     "open"]
    for n, sched in zip((2, 5), (gen.sent[3], gen.sent[5])):
        assert [r["due"] for r in sched["requests"]] == [0.0] * n
        assert len({r["pool"][0] for r in sched["requests"]}) == n
        assert sched["connections"] == 8


def test_a_traffic_file_without_batches_sends_none():
    traffic = dict(TRAFFIC, warmup={k: v for k, v in
                                    TRAFFIC["warmup"].items()
                                    if k != "batches"})
    gen = FakeGen()
    names = [row["phase"] for row in _warm(gen, traffic)["phases"]]
    assert names == ["first touch", "pool pass", "round 1"]
    assert _puts(gen) == []


def test_a_refused_setting_fails_the_set_up_and_is_still_reset():
    gen = FakeGen(refuse=lambda t: t.get("max_batch") == 5)
    with pytest.raises(RuntimeError, match="refused"):
        _warm(gen)
    assert _puts(gen)[-1] == {**dict.fromkeys(HOLD), "max_batch": None}
    assert gen.sent[-1]["mode"] == "closed"  # no round after the failure


class Grouped(Pool):
    def group(self, name, arg):
        if name != "odd":
            return super().group(name, arg)
        return [i for i in range(self.pool_size) if i % 2][:arg]


def _of(of, sizes=(2,)):
    warm = dict(TRAFFIC["warmup"], batches=dict(
        TRAFFIC["warmup"]["batches"], sizes=list(sizes), of=of))
    return dict(TRAFFIC, warmup=warm)


def test_a_batch_of_a_group_sends_the_entries_the_kind_lists():
    gen = FakeGen()
    files = types.SimpleNamespace(traffic=_of({"odd": 6}, sizes=(2, 3)))
    out = bench_run.warm_up(files, gen, Grouped(), 7, 1.0, 20.0, lambda: 0.0)
    assert [row["phase"] for row in out["phases"]][2:4] == [
        "batch of 2", "batch of 3"]
    sent = [s for s in gen.sent if s["mode"] == "open"][:2]
    assert [[r["pool"][0] for r in s["requests"]] for s in sent] == [
        [1, 3], [1, 3, 5]]


@pytest.mark.parametrize("of, loaded, error, match", [
    ({"odd": 1}, Grouped, RuntimeError, "more entries than"),
    ({"even": 4}, Grouped, ValueError, r"no group \[even\]"),
    ({"odd": 4}, Pool, ValueError, r"Pool has no group \[odd\]"),
])
def test_a_group_too_small_or_unknown_fails_the_set_up(of, loaded, error,
                                                       match):
    gen = FakeGen()
    files = types.SimpleNamespace(traffic=_of(of))
    with pytest.raises(error, match=match):
        bench_run.warm_up(files, gen, loaded(), 7, 1.0, 20.0, lambda: 0.0)
    # a setting that was put is put back; none was put before the failure
    assert all(None in t.values() for t in _puts(gen)[-1:])


def test_a_traffic_kind_without_connections_sends_a_batch_over_its_size():
    gen = FakeGen()
    traffic = {k: v for k, v in _of({"odd": 6}, sizes=(3,)).items()
               if k != "connections"}
    traffic.update(kind="closed_loop_msearch", clients=2, bodies=4)
    files = types.SimpleNamespace(traffic=traffic)
    bench_run.warm_up(files, gen, Grouped(), 7, 1.0, None, lambda: 0.0)
    batch = [s for s in gen.sent if s["mode"] == "open"][0]
    assert batch["connections"] == 3 and len(batch["requests"]) == 3


def test_the_text_kind_lists_the_queries_of_its_most_frequent_terms_only():
    import numpy as np

    from benchmarks.kinds.bm25_text_shard import TextShards

    shards = TextShards.__new__(TextShards)
    shards.shards = [types.SimpleNamespace(
        df=np.array([9, 9, 7, 7, 5, 3, 1]))]
    shards.pool = [np.array([0, 1]), np.array([1, 3]), np.array([2, 4]),
                   np.array([0]), np.array([5, 6])]
    assert shards.group("top_df_terms_only", 2) == [0, 3]
    assert shards.group("top_df_terms_only", 4) == [0, 1, 3]
    with pytest.raises(ValueError, match=r"no group \[rare\]"):
        shards.group("rare", 1)


def test_at_once_asks_the_loaded_for_the_path():
    class Elsewhere(Pool):
        def path(self, i):
            return "/idx/_count" if i % 2 else "/idx/_search"

    s = bench_run.at_once(Elsewhere(), [0, 1], 4)
    assert s["mode"] == "open" and s["connections"] == 4
    assert [(r["due"], r["path"], r["pool"], json.loads(r["body"]))
            for r in s["requests"]] == [
        (0.0, "/idx/_search", [0], {"q": 0}),
        (0.0, "/idx/_count", [1], {"q": 1})]


def test_the_rehearsed_text_cells_form_the_fused_pair_before_round_1(
        monkeypatch):
    """match-steady through the product's coalescer and batch tiers: the
    two searches of most frequent terms only flush as one full batch of 2,
    which the all-dense tier (``batch_bm25_fused``) serves."""
    from elasticsearch_tpu.monitor.programs import REGISTRY
    from elasticsearch_tpu.serving.coalescer import QueryCoalescer

    cell = "msmarco-passage-shard.match-steady"
    table = contract.load_table()
    if cell not in [c["name"] for c in table["workloads"]]:
        pytest.skip(f"{cell} is not in BENCHMARK.json")
    for c in table["workloads"]:
        if c["config"] == "msmarco-passage-shard":
            b = bench_run.CellFiles(table, c["name"], True).traffic[
                "warmup"]["batches"]
            assert b["sizes"] == [2] and b["of"] == {"top_df_terms_only": 32}
    seen, flush, record = [], QueryCoalescer._flush, REGISTRY.record_call

    def flushed(self, batch, reason):
        seen.append(("flush", len(batch), reason))
        return flush(self, batch, reason)

    def recorded(name, sig, *a, **kw):
        if name.startswith("batch_"):
            seen.append(("program", name, sig))
        return record(name, sig, *a, **kw)

    monkeypatch.setattr(QueryCoalescer, "_flush", flushed)
    monkeypatch.setattr(REGISTRY, "record_call", recorded)
    args = argparse.Namespace(seed=5, seconds=1.0, trace=0, control=0,
                              sweep=None, describe_trace=False,
                              keep_trace=False)
    done = bench_run.run_cell(args, table, cell, True)
    assert done["line"]["correct"] is True
    at = seen.index(("flush", 2, "full"))
    assert seen[at + 1][:2] == ("program", "batch_bm25_fused")
    assert "Q=2" in seen[at + 1][2]
    names = [row["phase"] for row in done["record"]["warmup"]["phases"]]
    assert names[2:4] == ["batch of 2", "round 1"]
    # the record keeps every answered request: [due s into the window, ms]
    kept = done["record"]["latencies"]
    assert len(kept) == done["line"]["attempted"]
    assert all(0.0 <= due < 1.0 and ms > 0.0 for due, ms in kept)


def test_the_rehearsed_cell_forms_its_batches_and_runs_on_the_defaults(
        monkeypatch):
    """Through the product's own coalescer: each asked size flushes as one
    full batch of that size, before the first round, and the window's
    coalescer is the default one again."""
    from elasticsearch_tpu.serving.coalescer import QueryCoalescer

    table = contract.load_table()
    if CELL not in [c["name"] for c in table["workloads"]]:
        pytest.skip(f"{CELL} is not in BENCHMARK.json")
    sizes = bench_run.CellFiles(table, CELL, True).traffic["warmup"][
        "batches"]["sizes"]
    seen, real = [], QueryCoalescer._flush

    def flush(self, batch, reason):
        seen.append((len(batch), reason, self))
        return real(self, batch, reason)

    monkeypatch.setattr(QueryCoalescer, "_flush", flush)
    args = argparse.Namespace(seed=5, seconds=1.5, trace=0, control=0,
                              sweep=None, describe_trace=False,
                              keep_trace=False)
    done = bench_run.run_cell(args, table, CELL, True)
    assert done["line"]["correct"] is True
    assert [n for n, reason, _ in seen if reason == "full"] == sizes
    names = [row["phase"] for row in done["record"]["warmup"]["phases"]]
    at = names.index("round 1")
    assert names[2:at] == [f"batch of {n}" for n in sizes]
    co = seen[-1][2]
    assert (co.mode, co.max_batch, co.max_wait_s, co.idle_gap_s) == (
        "adaptive", 256, 0.004, 0.001)
