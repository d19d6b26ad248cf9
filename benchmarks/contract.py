"""The benchmark's table (BENCHMARK.json) and the form of the last line.

``check_last_line`` is the one definition of what ``run.py`` may print as
its last line of standard output, for both modes; ``run.py`` calls it on
the object before it prints, and the tests call it on faulty objects.
"""
from __future__ import annotations

import json
import math
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmarks")


class ContractError(Exception):
    """The line (or the table) is not what the contract says."""


def load_table(path: str | None = None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def cell_of(table: dict, name: str) -> dict:
    for cell in table["workloads"]:
        if cell["name"] == name:
            return cell
    raise ContractError(
        f"no workload [{name}] in BENCHMARK.json; it has "
        f"{[c['name'] for c in table['workloads']]}")


def config_of(table: dict, name: str) -> dict:
    for cfg in table["configs"]:
        if cfg["name"] == name:
            return cfg
    raise ContractError(f"no configuration [{name}] in BENCHMARK.json")


def metrics_of(table: dict, cell_name: str, trace: bool) -> list:
    """The metrics a run of this cell has to report in this mode: its
    ``end_to_end`` metrics with ``--trace 0``, its ``per_layer`` metrics
    with ``--trace 1``. A metric with no ``workloads`` key belongs to every
    cell (per-layer: every cell that reports the metric it moves)."""
    e2e = [m for m in table["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in table["per_layer"]
            if (cell_name in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def _number(x) -> bool:
    return (isinstance(x, (int, float)) and not isinstance(x, bool)
            and math.isfinite(x))


def check_last_line(obj, cell: dict, trace: bool, table: dict) -> None:
    """Raise ContractError unless ``obj`` is a last line the driver takes
    for a run of ``cell`` (an entry of ``workloads``) in this mode."""
    if not isinstance(obj, dict):
        raise ContractError("the last line is not a JSON object")
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        if key not in obj:
            raise ContractError(f"key [{key}] is missing")
    if not isinstance(obj["correct"], bool):
        raise ContractError("[correct] is not true or false")
    for key in ("attempted", "failed"):
        if not _number(obj[key]) or obj[key] < 0 or int(obj[key]) != obj[key]:
            raise ContractError(f"[{key}] is not a count: {obj[key]!r}")
    if obj["attempted"] < 1 or obj["failed"] > obj["attempted"]:
        raise ContractError(
            f"attempted {obj['attempted']}, failed {obj['failed']}")
    metrics = obj["metrics"]
    if not isinstance(metrics, dict):
        raise ContractError("[metrics] is not an object")
    for m in metrics_of(table, cell["name"], trace):
        got = metrics.get(m["name"])
        if not isinstance(got, dict):
            raise ContractError(f"metric [{m['name']}] is missing")
        if not _number(got.get("value")):
            raise ContractError(
                f"metric [{m['name']}] has no finite value: "
                f"{got.get('value')!r}")
        if got.get("unit") != m["unit"]:
            raise ContractError(
                f"metric [{m['name']}] has unit [{got.get('unit')}], the "
                f"table says [{m['unit']}]")
    for name, got in metrics.items():  # a metric too many is still a number
        if not isinstance(got, dict) or not _number(got.get("value")) \
                or not isinstance(got.get("unit"), str):
            raise ContractError(f"metric [{name}] is malformed: {got!r}")
    dev = obj["device"]
    if not isinstance(dev, dict):
        raise ContractError("[device] is not an object")
    for key in ("platform", "kind"):
        if not isinstance(dev.get(key), str) or not dev[key]:
            raise ContractError(f"device.{key} is missing")
    if dev.get("count") != cell["chips"]:
        raise ContractError(
            f"device.count is {dev.get('count')!r}, the cell asks for "
            f"{cell['chips']}")
    if not _number(dev.get("memory_peak_bytes")) \
            or dev["memory_peak_bytes"] <= 0:
        raise ContractError(
            f"device.memory_peak_bytes is {dev.get('memory_peak_bytes')!r}")
    if trace:
        for key in ("window_s", "busy_s"):
            if not _number(dev.get(key)):
                raise ContractError(f"device.{key} is {dev.get(key)!r}")
        if not 0 < dev["busy_s"] <= dev["window_s"]:
            raise ContractError(
                f"device.busy_s {dev['busy_s']} is not above 0 and at most "
                f"device.window_s {dev['window_s']}")
        bd = obj.get("breakdown")
        if bd is not None:
            for key in ("device_ops", "idle_gaps"):
                rows = bd.get(key) if isinstance(bd, dict) else None
                if not isinstance(rows, list) or len(rows) > 10 or any(
                        not (isinstance(r, list) and len(r) == 2
                             and isinstance(r[0], str) and _number(r[1]))
                        for r in rows):
                    raise ContractError(f"breakdown.{key} is malformed")


def dumps_line(obj: dict) -> str:
    """One line, strict JSON (a NaN raises instead of printing ``NaN``)."""
    return json.dumps(obj, allow_nan=False, separators=(", ", ": "))
