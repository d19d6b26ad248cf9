"""Seeded NYC taxi trips of 2015: the three columns the nyc_taxis
dashboard cell reads, as raw integers, made in bulk on the host.

- ``dropoff_s``: whole seconds since 2015-01-01T00:00:00Z, a day of the
  year weighted by its weekday and an hour of the day by a diurnal
  profile, the second uniform in the hour (so some trips end exactly at
  midnight);
- ``distance_cents``: trip_distance in hundredths of a mile, log-normal
  (median 1.8 mi, a tail past 50 mi) with a share of zero-length trips;
- ``amount_cents``: total_amount in cents, tied to the distance (a flag
  fall, a rate a mile with a spread) plus a tip and surcharges.

These raw integers are what the plain reference (``reference/aggs.py``)
reads; the kind turns them into the mapped values (``scaled_float``
factor 100, epoch-millisecond dates) through the product's own codec.
"""
from __future__ import annotations

import os

import numpy as np

EPOCH_2015_S = 1420070400  # 2015-01-01T00:00:00Z
DAYS = 365
BLOCK = 1 << 22
# Monday .. Sunday; 2015-01-01 was a Thursday
WEEKDAY = np.array([0.92, 0.97, 1.0, 1.05, 1.12, 1.13, 0.95])
# trips ending in each hour of the day (TLC 2015 yellow cabs, rounded)
HOURLY = np.array([3.9, 2.9, 2.1, 1.5, 1.1, 1.0, 2.1, 3.6, 4.5, 4.6, 4.4,
                   4.5, 4.7, 4.7, 4.9, 4.9, 4.4, 5.0, 6.0, 6.4, 5.9, 5.7,
                   5.5, 4.8])


def _cdf() -> np.ndarray:
    day = WEEKDAY[(np.arange(DAYS) + 3) % 7]
    p = np.outer(day, HOURLY).ravel()
    return np.cumsum(p / p.sum())


def _block(out: dict, cdf: np.ndarray, seed: int, b: int, at: int, m: int):
    rng = np.random.default_rng([int(seed) & (2 ** 63 - 1), 0x7A11, b])
    cell = np.searchsorted(cdf, rng.random(m), side="right")
    cell = np.minimum(cell, DAYS * 24 - 1)
    out["dropoff_s"][at:at + m] = cell * 3600 + rng.integers(0, 3600, m)
    miles = rng.lognormal(0.6, 0.85, m)
    miles[rng.random(m) < 0.01] = 0.0
    cents = np.minimum(np.rint(miles * 100.0), 2_000_000)
    out["distance_cents"][at:at + m] = cents
    fare = 330.0 + 250.0 * (cents / 100.0) * rng.normal(1.0, 0.08, m)
    extra = rng.exponential(180.0, m) + 50.0 * rng.integers(0, 3, m)
    out["amount_cents"][at:at + m] = np.maximum(np.rint(fare + extra), 0)


def make_trips(n: int, seed: int) -> dict:
    """{"dropoff_s": int32[n], "distance_cents": int32[n],
    "amount_cents": int32[n]} from ``seed`` (any int up to 2**63). Blocks
    draw from their own streams, on a few threads (numpy's generators
    release the interpreter), so the data does not depend on how many."""
    from concurrent.futures import ThreadPoolExecutor

    cdf = _cdf()
    out = {k: np.empty(n, np.int32)
           for k in ("dropoff_s", "distance_cents", "amount_cents")}
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as ex:
        for f in [ex.submit(_block, out, cdf, seed, b, at, min(BLOCK, n - at))
                  for b, at in enumerate(range(0, n, BLOCK))]:
            f.result()
    return out
