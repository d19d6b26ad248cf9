"""The plain reference of the nyc_taxis dashboard aggregations.

numpy, float64 and exact integers, from the generator's raw integers only
(``data/taxis.py``), importing nothing of the program. Two request shapes
of the Rally track, with the product's bucket semantics (a ``histogram``
or ``date_histogram`` with ``min_doc_count`` 0: every bucket from the
least to the greatest key that holds a document, empty ones included and
carrying no sub-aggregation):

- ``distance``: ``trip_distance`` in [lo, hi) whole miles, buckets of one
  mile keyed by the integer floor division of the raw cents by 100, each
  with ``stats`` of ``total_amount`` (count, sum, min, max, avg of the raw
  cents / 100);
- ``date``: ``dropoff_datetime`` from midnight of day d0 to midnight of
  day d0 + n, both inclusive, keyed by the integer floor division of the
  epoch milliseconds by 86,400,000 (so the last bucket holds the trips
  that end exactly at its midnight).

Every answer reads a table built once, block by block, over the whole
collection (per mile: count, sum, min, max of the amount; per day: trips,
and trips at midnight). ``bf16=True`` rounds each amount to bfloat16 first:
the control, one precision down.
"""
from __future__ import annotations

import numpy as np

from benchmarks.reference.bm25 import to_bf16

DAY_MS = 86_400_000
MILES = 50  # the dashboard's distance filter stops short of 50 miles
BLOCK = 1 << 24


class AggReference:
    def __init__(self, trips: dict, epoch_s: int, days: int):
        self.trips = trips
        self.epoch_ms = int(epoch_s) * 1000
        self.days = int(days)
        self._miles = {}
        self._days = None

    def _mile_table(self, bf16: bool) -> dict:
        """Per whole mile 0..49: count, sum, min, max of the amount in
        dollars (bfloat16-rounded where ``bf16``)."""
        if bf16 in self._miles:
            return self._miles[bf16]
        count = np.zeros(MILES, np.int64)
        total = np.zeros(MILES, np.float64)
        lo = np.full(MILES, np.inf)
        hi = np.full(MILES, -np.inf)
        d, a = self.trips["distance_cents"], self.trips["amount_cents"]
        for at in range(0, d.shape[0], BLOCK):
            key = d[at:at + BLOCK].astype(np.int64) // 100
            dollars = a[at:at + BLOCK].astype(np.float64) / 100.0
            if bf16:
                dollars = to_bf16(dollars).astype(np.float64)
            keep = key < MILES
            key, dollars = key[keep], dollars[keep]
            count += np.bincount(key, minlength=MILES)
            total += np.bincount(key, weights=dollars, minlength=MILES)
            np.minimum.at(lo, key, dollars)
            np.maximum.at(hi, key, dollars)
        self._miles[bf16] = {"count": count, "sum": total, "min": lo,
                             "max": hi}
        return self._miles[bf16]

    def _day_table(self) -> tuple:
        """(trips a day, trips ending exactly at a day's midnight), over
        the days of the year and 32 after it."""
        if self._days is None:
            n_days = self.days + 32
            per_day = np.zeros(n_days, np.int64)
            midnight = np.zeros(n_days, np.int64)
            s = self.trips["dropoff_s"]
            for at in range(0, s.shape[0], BLOCK):
                ms = self.epoch_ms + s[at:at + BLOCK].astype(np.int64) * 1000
                day = ms // DAY_MS - self.epoch_ms // DAY_MS
                per_day += np.bincount(day, minlength=n_days)
                midnight += np.bincount(day[ms % DAY_MS == 0],
                                        minlength=n_days)
            self._days = (per_day, midnight)
        return self._days

    def answer(self, entry: tuple, bf16: bool = False) -> list:
        """The expected buckets of one pool entry: [{"key", "doc_count"
        [, "stats": {count, sum, min, max, avg}]}]."""
        if entry[0] == "distance":
            _, lo, hi = entry
            t = self._mile_table(bf16)
            rows = []
            for k in range(lo, hi):
                n = int(t["count"][k])
                row = {"key": float(k), "doc_count": n}
                if n:
                    s = float(t["sum"][k])
                    row["stats"] = {"count": n, "sum": s,
                                    "min": float(t["min"][k]),
                                    "max": float(t["max"][k]),
                                    "avg": s / n}
                rows.append(row)
        else:
            _, d0, n_days = entry
            per_day, midnight = self._day_table()
            base = self.epoch_ms // DAY_MS
            rows = [{"key": (base + d) * DAY_MS,
                     "doc_count": int(per_day[d] if d < d0 + n_days
                                      else midnight[d])}
                    for d in range(d0, d0 + n_days + 1)]
        held = [i for i, r in enumerate(rows) if r["doc_count"]]
        return rows[held[0]:held[-1] + 1] if held else []
