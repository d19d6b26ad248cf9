"""``/_prometheus/metrics`` text -> {family: {label text: value}}, and sums
of series over it (copied from ``chip_smoke.py``'s ``Server.metrics`` and
``by_label``; this copy is now the yardstick)."""
from __future__ import annotations


def parse(text: str) -> dict:
    out: dict = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        head, _, val = line.rpartition(" ")
        fam, _, labels = head.partition("{")
        try:
            out.setdefault(fam, {})[labels.rstrip("}")] = float(val)
        except ValueError:
            pass
    return out


def _labels(text: str) -> dict:
    out = {}
    for part in text.split(","):
        k, _, v = part.partition("=")
        if k:
            out[k.strip()] = v.strip().strip('"')
    return out


def total(snapshot: dict, series: list) -> float:
    """Sum of the series: each {"family": .., "labels": {..}?}; a series
    with labels takes the rows that carry all of them."""
    acc = 0.0
    for s in series:
        want = s.get("labels") or {}
        for labels, value in snapshot.get(s["family"], {}).items():
            have = _labels(labels)
            if all(have.get(k) == v for k, v in want.items()):
                acc += value
    return acc


def delta(pair, series: list) -> float:
    before, after = pair
    return total(after, series) - total(before, series)
