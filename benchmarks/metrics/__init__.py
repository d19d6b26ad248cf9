"""Per-layer metrics as data: ``<name>.json`` says what to read (``reader``
names a module of ``readers/`` and the rest are that reader's parameters);
BENCHMARK.json says the unit, the layer, what it moves and in which cells.
A reader that finds nothing to read returns None, and ``run.py`` then
leaves the metric out of the line (which ``check_last_line`` refuses where
the cell is listed for it: a metric is never 0 by default)."""
from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def read_metric(name: str, ctx: dict):
    with open(os.path.join(HERE, f"{name}.json")) as fh:
        spec = json.load(fh)
    reader = importlib.import_module(
        f"benchmarks.metrics.readers.{spec['reader']}")
    return reader.read(ctx, spec)
