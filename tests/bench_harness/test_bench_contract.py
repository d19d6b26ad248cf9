"""BENCHMARK.json and the last line: the form the driver checks, checked
here first. A good line passes; each of the ways PR 22's traced line can
have gone wrong is refused, with --trace 0 and 1, on 1 and 4 devices."""
import json
import os
import re

import pytest

from benchmarks import contract

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = os.path.join(contract.ROOT, "benchmarks")


@pytest.fixture(scope="module")
def table():
    return contract.load_table()


def toy_table(chips):
    return {
        "workloads": [{"name": "c.t", "config": "c", "traffic": "t",
                       "chips": chips, "why": "x"}],
        "end_to_end": [
            {"name": "search_p50_ms", "unit": "ms", "better": "lower",
             "bound": 0.05, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "host_cpu_ms", "unit": "ms", "better": "lower",
             "source": "host_clock", "layer": "host", "moves":
             "search_p50_ms"},
            {"name": "other_cell_only", "unit": "%", "better": "higher",
             "source": "device_trace", "layer": "k", "moves": "search_p50_ms",
             "workloads": ["d.t"]}]}


def good_line(chips, trace):
    metrics = ({"host_cpu_ms": {"value": 3.25, "unit": "ms"}} if trace else
               {"search_p50_ms": {"value": 81.5, "unit": "ms"},
                "setup_s": {"value": 71.0, "unit": "s"}})
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": chips,
           "memory_peak_bytes": 6_500_000_000}
    if trace:
        dev.update(window_s=3.0, busy_s=1.25)
    return {"correct": True, "attempted": 400, "failed": 0,
            "metrics": metrics, "device": dev}


MODES = [(c, t) for c in (1, 4) for t in (0, 1)]


@pytest.mark.parametrize("chips,trace", MODES)
def test_good_line_passes(chips, trace):
    t = toy_table(chips)
    line = good_line(chips, trace)
    contract.check_last_line(line, t["workloads"][0], bool(trace), t)
    # and it survives the strict dump and a parse
    assert json.loads(contract.dumps_line(line)) == line


def _busy_zero(line):       # 1: traced in a process that holds no chip
    line["device"]["busy_s"] = 0.0


def _busy_sum(line):        # 2: a sum over devices or over a plane's lines
    line["device"]["busy_s"] = line["device"]["window_s"] * 4


def _clipped_away(line):    # 3: clipped on the wrong clock: nothing left
    line["device"]["busy_s"] = 0
    line["device"]["window_s"] = 0


def _metric_null(line):     # 4a: a metric that could not be read
    next(iter(line["metrics"].values()))["value"] = None


def _metric_nan(line):      # 4b
    next(iter(line["metrics"].values()))["value"] = float("nan")


def _metric_absent(line):   # 4c
    line["metrics"].pop(next(iter(line["metrics"])))


def _no_peak(line):         # 5
    line["device"].pop("memory_peak_bytes")


def _no_window(line):       # 5
    line["device"].pop("window_s", None)
    line["device"].pop("busy_s", None)


TRACED_ONLY = (_busy_zero, _busy_sum, _clipped_away, _no_window)
FAULTS = [_busy_zero, _busy_sum, _clipped_away, _metric_null, _metric_nan,
          _metric_absent, _no_peak, _no_window]


@pytest.mark.parametrize("chips,trace", MODES)
@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__)
def test_faulty_line_is_refused(chips, trace, fault):
    t = toy_table(chips)
    line = good_line(chips, trace)
    if fault in TRACED_ONLY and not trace:
        # an untraced line carries neither key: it passes without them
        contract.check_last_line(line, t["workloads"][0], False, t)
        return
    fault(line)
    with pytest.raises(contract.ContractError):
        contract.check_last_line(line, t["workloads"][0], bool(trace), t)


def test_nan_is_never_printed():
    line = good_line(1, 0)
    line["metrics"]["setup_s"]["value"] = float("nan")
    with pytest.raises(ValueError):
        contract.dumps_line(line)


@pytest.mark.parametrize("key", ["correct", "attempted", "failed", "metrics",
                                 "device"])
def test_missing_key_is_refused(key):
    t = toy_table(1)
    line = good_line(1, 0)
    line.pop(key)
    with pytest.raises(contract.ContractError):
        contract.check_last_line(line, t["workloads"][0], False, t)


def test_wrong_device_count_and_unit_are_refused():
    t = toy_table(4)
    line = good_line(1, 0)
    with pytest.raises(contract.ContractError):
        contract.check_last_line(line, t["workloads"][0], False, t)
    line = good_line(4, 0)
    line["metrics"]["setup_s"]["unit"] = "ms"
    with pytest.raises(contract.ContractError):
        contract.check_last_line(line, t["workloads"][0], False, t)


def test_metrics_of_follows_workloads_and_moves():
    t = toy_table(1)
    assert [m["name"] for m in contract.metrics_of(t, "c.t", False)] == [
        "search_p50_ms", "setup_s"]
    assert [m["name"] for m in contract.metrics_of(t, "c.t", True)] == [
        "host_cpu_ms"]


# ---- the committed table ---------------------------------------------------

def test_table_has_exactly_the_contracts_keys(table):
    assert set(table) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= table["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(contract.ROOT, "BENCHMARK.json")
                           ) <= 64 * 1024
    assert table["command"][:2] == ["python3", "benchmarks/run.py"]
    for p in table["paths"]:
        assert os.path.isdir(os.path.join(contract.ROOT, p))


def test_every_name_and_unit_is_within_the_permitted_characters(table):
    names = []
    for cfg in table["configs"]:
        assert set(cfg) == {"name", "source", "file", "reduced", "why"}
        names.append(cfg["name"])
        assert len(cfg["reduced"]) <= 16
        for key in cfg["reduced"]:
            assert NAME.match(key)
            assert not key.endswith(("_dim", "_rank"))
    for cell in table["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        names += [cell["name"], cell["config"], cell["traffic"]]
        assert cell["chips"] in (1, 4)
    for m in table["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in table["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in table["end_to_end"] + table["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for n in names:
        assert NAME.match(n), n
    for group in ("configs", "workloads"):
        got = [x["name"] for x in table[group]]
        assert len(got) == len(set(got))
    metric_names = [m["name"] for m in table["end_to_end"]
                    + table["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    for text in ([c["why"] for c in table["workloads"]]
                 + [c["why"] for c in table["configs"]]
                 + [c["source"] for c in table["configs"]]
                 + [m["layer"] for m in table["per_layer"]]
                 + table["command"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_cells_metrics_and_moves_hang_together(table):
    cells = {c["name"] for c in table["workloads"]}
    e2e = {m["name"]: m for m in table["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    pairs = [(c["config"], c["traffic"]) for c in table["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(c["chips"] == 4 for c in table["workloads"])
    assert four <= max(1, len(cells) // 2)
    used = {c["config"] for c in table["workloads"]}
    assert used == {c["name"] for c in table["configs"]}
    for m in table["end_to_end"] + table["per_layer"]:
        assert set(m.get("workloads", [])) <= cells
    for m in table["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in moved.get("workloads", cells), (m["name"], cell)
    for cell in cells:
        got = [m["name"] for m in contract.metrics_of(table, cell, False)]
        assert "setup_s" in got and len(got) >= 2
        assert contract.metrics_of(table, cell, True)


def test_every_cells_files_exist(table):
    files = [c["file"] for c in table["configs"]]
    assert len(files) == len(set(files))
    for cfg in table["configs"]:
        assert any(cfg["file"].startswith(p + "/") for p in table["paths"])
        with open(os.path.join(contract.ROOT, cfg["file"])) as fh:
            body = json.load(fh)
        for key in ("kind", "source", "reduced", "assumed", "guarantees",
                    "device_bytes_reckoned", "rehearsal"):
            assert key in body, (cfg["name"], key)
        assert sorted(body["reduced"]) == sorted(cfg["reduced"])
    for cell in table["workloads"]:
        for part in (("traffic", cell["traffic"]), ("cells", cell["name"])):
            path = os.path.join(BENCH, part[0], part[1] + ".json")
            with open(path) as fh:
                json.load(fh)
    for m in table["per_layer"]:
        with open(os.path.join(BENCH, "metrics", m["name"] + ".json")) as fh:
            spec = json.load(fh)
        assert os.path.exists(os.path.join(
            BENCH, "metrics", "readers", spec["reader"] + ".py"))


def test_no_width_of_a_source_is_changed(table):
    cfgs = {c["name"]: json.load(open(os.path.join(contract.ROOT, c["file"])))
            for c in table["configs"]}
    gist = cfgs["gist-960-exact"]
    assert (gist["dims"], gist["vectors"], gist["similarity"], gist["k"]
            ) == (960, 1_000_000, "l2_norm", 10)
    for name, cfg in cfgs.items():
        if cfg["kind"] == "bm25_text_shard":
            assert cfg["bm25"] == {"k1": 1.2, "b": 0.75}
            assert cfg["documents_per_shard"] == -(-8_841_823 // 4)
            assert cfg["queries"]["pool"] == 6980
