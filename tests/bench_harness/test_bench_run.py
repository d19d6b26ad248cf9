"""run.py end to end without a chip: it refuses to measure on a CPU, its
rehearsal drives every one-chip cell's whole path (child generator, socket,
fd handling, trace reduction, comparison, check_last_line), and a timed
path broken underneath comes out as ``correct`` false."""
import argparse
import json
import os
import subprocess
import sys

import pytest

from benchmarks import contract

RUN = os.path.join(contract.ROOT, "benchmarks", "run.py")
ENV = dict(os.environ, JAX_PLATFORMS="cpu")
ENV.pop("XLA_FLAGS", None)


def test_run_on_a_cpu_exits_nonzero_with_empty_stdout():
    table = contract.load_table()
    p = subprocess.run(
        [sys.executable, RUN, "--workload", table["workloads"][0]["name"],
         "--seed", "2147483999", "--seconds", "1", "--trace", "0"],
        cwd=contract.ROOT, env=ENV, capture_output=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == b""
    assert b"TPU" in p.stderr


def test_unknown_workload_exits_nonzero_with_empty_stdout():
    p = subprocess.run(
        [sys.executable, RUN, "--workload", "no-such.cell", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=contract.ROOT, env=ENV, capture_output=True, timeout=300)
    assert p.returncode != 0 and p.stdout == b""


def test_rehearsal_passes_and_prints_no_line():
    """Every one-chip cell, untraced and traced; with ``--control 1`` a
    rehearsal also fails where a cell's control comes out correct."""
    p = subprocess.run([sys.executable, RUN, "--rehearse", "--control", "1"],
                       cwd=contract.ROOT, env=ENV, capture_output=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-3000:].decode("utf-8", "replace")
    assert p.stdout == b""
    assert b"rehearsal passed" in p.stderr
    assert p.stderr.count(b"'correct': False") == 2 * len(
        contract.load_table()["workloads"])


def test_load_generator_imports_nothing_of_jax_or_the_program():
    src = open(os.path.join(contract.ROOT, "benchmarks", "loadgen",
                            "client.py")).read()
    for word in ("import jax", "elasticsearch_tpu", "import numpy",
                 "from benchmarks"):
        assert word not in src


# ---- the timed path, broken underneath ------------------------------------------

def _alter(how):
    """A fault planted where the answer is produced: in the server's
    dispatch, below the socket."""
    def altered(payload):
        bodies = (payload.get("responses") if isinstance(payload, dict)
                  and "responses" in payload else [payload])
        for n, body in enumerate(bodies or []):
            hits = (body.get("hits", {}).get("hits")
                    if isinstance(body, dict) else None)
            if not hits:
                continue
            if how == "score_altered":
                hits[0]["_score"] = hits[0]["_score"] * 1.001
            elif how == "answer_dropped":
                del hits[0]
            elif how == "half_of_the_batch_left_out" and n % 2:
                del hits[:]
        return payload
    return altered


@pytest.mark.parametrize("workload,how", [
    ("msmarco-passage-shard.match-steady", "score_altered"),
    ("msmarco-passage-shard.match-steady", "answer_dropped"),
    ("msmarco-passage-shard.msearch-batch", "half_of_the_batch_left_out"),
    ("gist-960-exact.knn-steady", "score_altered"),
])
def test_a_broken_timed_path_comes_out_not_correct(monkeypatch, workload,
                                                   how):
    from benchmarks import run as bench_run
    from elasticsearch_tpu.rest.server import RestController

    table = contract.load_table()
    if workload not in [c["name"] for c in table["workloads"]]:
        pytest.skip(f"{workload} is not in BENCHMARK.json")
    real = RestController.dispatch
    alter = _alter(how)

    def dispatch(self, method, path, params, body, headers=None):
        status, payload = real(self, method, path, params, body,
                               headers=headers)
        if path.endswith("_search") or path.endswith("_msearch"):
            payload = alter(json.loads(json.dumps(payload, default=float)))
        return status, payload

    args = argparse.Namespace(seed=5, seconds=1.5, trace=0, control=0,
                              sweep=None, describe_trace=False,
                              keep_trace=False)
    sound = bench_run.run_cell(args, table, workload, True)["line"]
    assert sound["correct"] is True, sound["compared"]
    monkeypatch.setattr(RestController, "dispatch", dispatch)
    broken = bench_run.run_cell(args, table, workload, True)["line"]
    assert broken["correct"] is False, broken["compared"]
    over = [k for k, v in broken["compared"].items()
            if v["value"] > v["limit"]]
    assert over, broken["compared"]
