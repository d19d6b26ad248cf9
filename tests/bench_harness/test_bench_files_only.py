"""A deployment comes as files. A copy of ``benchmarks/`` and
``BENCHMARK.json`` takes the fixture ``fixtures/deployment_as_files`` — a
configuration kind, a traffic kind, a configuration, a traffic mix, a cell,
span names, a metric and its reader — as new files and as entries at the end
of the table's lists; no file that was there changes (held by hash), and the
new cell runs through ``run.py``'s own ``main``: an answer with no hit in
it, a comparison of its own, a control that comes out not correct."""
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmarks import contract

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixtures", "deployment_as_files")
CELL = "status-logs-tiny.agg-mix"
ENV = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=contract.ROOT)
ENV.pop("XLA_FLAGS", None)


def hashes(root: str) -> dict:
    """{relative path: SHA-256}, what a run leaves behind left out."""
    out = {}
    for base, dirs, files in os.walk(root):
        dirs[:] = [d for d in dirs if d != "__pycache__" and os.path.join(
            base, d) != os.path.join(root, "benchmarks", "out")]
        for f in files:
            path = os.path.join(base, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def append_entries(table: dict, entries: dict) -> dict:
    """The fixture's entries at the end of each list of a copy of the
    table; a cell joins an end-to-end metric at the end of its cells."""
    out = json.loads(json.dumps(table))
    for key in ("configs", "workloads", "per_layer"):
        out[key] += entries[key]
    for m in out["end_to_end"]:
        # (a metric with no list of cells is every cell's already)
        if "workloads" in m:
            m["workloads"] += entries["end_to_end_workloads"].get(
                m["name"], [])
    return out


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """(root of the copy, hashes before the fixture went in, the parent's
    table)."""
    root = str(tmp_path_factory.mktemp("files_only"))
    shutil.copytree(os.path.join(contract.ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "out"))
    shutil.copy(os.path.join(contract.ROOT, "BENCHMARK.json"), root)
    before = hashes(root)
    table = contract.load_table(os.path.join(root, "BENCHMARK.json"))
    added = []
    for base, _, files in os.walk(os.path.join(FIXTURE, "benchmarks")):
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), FIXTURE)
            assert rel not in before, f"the fixture would overwrite {rel}"
            os.makedirs(os.path.dirname(os.path.join(root, rel)),
                        exist_ok=True)
            shutil.copy(os.path.join(base, f), os.path.join(root, rel))
            added.append(rel)
    with open(os.path.join(FIXTURE, "entries.json")) as fh:
        entries = json.load(fh)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(append_entries(table, entries), fh, indent=1)
    return {"root": root, "before": before, "table": table, "added": added}


def run(copy, *args):
    return subprocess.run(
        [sys.executable, os.path.join("benchmarks", "run.py"), *args],
        cwd=copy["root"], env=ENV, capture_output=True, timeout=600)


def test_the_new_cell_rehearses_and_its_control_is_not_correct(copy):
    """``--rehearse`` drives the cell traced and untraced through
    ``run_cell`` and ``check_last_line``; with ``--control 1`` it also
    fails unless the control comes out not correct."""
    p = run(copy, "--rehearse", "--workload", CELL, "--control", "1")
    err = p.stderr.decode("utf-8", "replace")
    assert p.returncode == 0, err[-3000:]
    assert p.stdout == b""
    assert "rehearsal passed" in err
    controls = [row for row in err.splitlines() if "control (the" in row]
    assert len(controls) == 2, controls  # the untraced run and the traced
    for row in controls:
        assert "'correct': False" in row
        assert int(re.search(r"'wrong_buckets': (\d+)", row).group(1)) >= 12


def test_no_file_that_was_there_has_changed(copy):
    after = hashes(copy["root"])
    changed = [f for f, h in copy["before"].items()
               if f != "BENCHMARK.json" and after.get(f) != h]
    assert changed == []
    assert sorted(set(after) - set(copy["before"])) == sorted(copy["added"])
    # the table: every entry that was there, where it was, as it was; a
    # metric's list of cells may have grown at its end
    table = contract.load_table(os.path.join(copy["root"],
                                             "BENCHMARK.json"))
    was = copy["table"]
    for key in ("command", "paths", "run_seconds"):
        assert table[key] == was[key]
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for old, new in zip(was[key], table[key]):
            cells = new.get("workloads", [])
            if cells[-1:] == [CELL]:
                new = dict(new, workloads=cells[:-1])
            assert new == old
    assert [c["name"] for c in table["workloads"]][-1] == CELL


def test_the_fixtures_span_names_are_read_beside_the_tables(copy):
    from benchmarks.trace import host_spans

    names = host_spans.load_names(os.path.join(
        copy["root"], "benchmarks", "trace", "span_names.json"))
    base = host_spans.load_names()["leaves"]
    assert names["leaves"][:len(base)] == base  # what was there, first
    assert "fixture.aggregate" in names["leaves"][len(base):]
    assert names["root"] == "rest.request"


# the harness's tests that read the table and the directories (the ones
# that rehearse every cell are left to the run above: minutes)
REHEARSING = {"test_bench_files_only.py", "test_bench_run.py",
              "test_bench_span_metrics.py", "test_bench_tpu_compile.py"}


def test_the_harness_tests_pass_in_a_copy_that_holds_more(copy, tmp_path):
    """The benchmark's own tests are files a later PR may not edit either.
    Run as they stand against a copy that holds the fixture's deployment,
    a kind more of each sort (one sorting first, one last) and a later
    span-names file, they pass: none of them closes a set."""
    root = str(tmp_path / "copy")
    shutil.copytree(copy["root"], root,
                    ignore=shutil.ignore_patterns("__pycache__", "out"))
    tests = os.path.join(root, "tests")
    shutil.copytree(HERE, os.path.join(tests, "bench_harness"),
                    ignore=shutil.ignore_patterns("__pycache__",
                                                  *REHEARSING))
    shutil.copy(os.path.join(os.path.dirname(HERE), "conftest.py"), tests)
    for package in (("kinds",), ("loadgen", "kinds")):
        for name in ("aa_a_kind_more.py", "zz_a_kind_more.py"):
            with open(os.path.join(root, "benchmarks", *package, name),
                      "w") as fh:
                fh.write('"""A later PR\'s kind: a file more."""\n')
    with open(os.path.join(root, "benchmarks", "trace", "span_names",
                           "zz-later.json"), "w") as fh:
        json.dump({"what": "a later deployment's spans",
                   "containers": ["later.box"], "leaves": ["later.leaf"]},
                  fh)
    p = subprocess.run(
        [sys.executable, "-m", "pytest", os.path.join("tests",
                                                      "bench_harness"),
         "-q", "-p", "no:cacheprovider", "-p", "no:randomly", "-p",
         "no:xdist"],
        cwd=root, capture_output=True, timeout=600,
        env=dict(ENV, PYTHONPATH=os.pathsep.join([root, contract.ROOT])))
    out = p.stdout.decode("utf-8", "replace")
    assert p.returncode == 0, out[-4000:]
    # they ran against the copy, and there were many of them
    assert int(re.search(r"(\d+) passed", out).group(1)) >= 100
    probe = subprocess.run(
        [sys.executable, "-c", "import benchmarks; print(benchmarks.__file__)"],
        cwd=root, capture_output=True, timeout=60,
        env=dict(ENV, PYTHONPATH=os.pathsep.join([root, contract.ROOT])))
    assert probe.stdout.decode().strip().startswith(root)


@pytest.mark.parametrize("what,edit", [
    ("configuration kind", ("configs", "status-logs-tiny.json")),
    ("traffic kind", ("traffic", "agg-mix.json")),
])
def test_an_unknown_kind_names_the_kinds_found_and_prints_no_line(
        copy, what, edit, tmp_path):
    """Of a second copy, so that the first stays what its hashes say."""
    root = str(tmp_path / "copy")
    shutil.copytree(copy["root"], root,
                    ignore=shutil.ignore_patterns("__pycache__", "out"))
    path = os.path.join(root, "benchmarks", *edit)
    with open(path) as fh:
        body = json.load(fh)
    body["kind"] = "no_such_kind"
    with open(path, "w") as fh:
        json.dump(body, fh)
    p = run({"root": root}, "--rehearse", "--workload", CELL)
    err = p.stderr.decode("utf-8", "replace")
    assert p.returncode != 0 and p.stdout == b""
    assert f"unknown {what} [no_such_kind]; found: [" in err
    found = {"configuration kind": "'keyword_terms_shard'",
             "traffic kind": "'open_loop_weighted'"}[what]
    assert found in err.split("found: [")[1]
