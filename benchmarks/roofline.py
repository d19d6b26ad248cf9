"""The least time the chip could take for the searches it answered.

The roofline reads the work, not the implementation: its numerator comes
from the requests answered in the traced interval and the index as it is
resident (``Loaded.work``), never from which kernel served them; its
denominator is ``busy_s`` of the same interval — all the device did, under
whatever names. So a share cannot pass 100% unless the program leaves work
out, and a later PR that swaps a kernel moves the share without making the
count stale.

``match``: the postings of the query's terms (sum of df x the bytes of one
posting's doc id and impact, from the resident arrays' dtypes) plus the
top-k's read of one score a live document; 2 flop a posting. Exact kNN:
2 x N x dims flop a query, and one read of the resident slab for each
device batch, where a batch is what the serving layer formed (coalescer
flushes plus bypasses), not what a kernel chose to launch.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str, path: str | None = None) -> dict:
    with open(path or os.path.join(HERE, "peaks.json")) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind [{device_kind}] in peaks.json "
            f"(it has {sorted(table)}); a device that is not in the table "
            f"is an error, not a default")
    return table[device_kind]


def least_seconds(flop: float, nbytes: float, peaks: dict) -> dict:
    t_flop = flop / peaks["flop_per_s_bf16"]
    t_bytes = nbytes / peaks["bytes_per_s"]
    return {"seconds": max(t_flop, t_bytes),
            "bound": "flop" if t_flop >= t_bytes else "bytes"}


def work_of(works: list, batches: float) -> tuple:
    """(flop, bytes) of answered searches: each one's own flop and bytes,
    plus ``batch_bytes`` once a device batch."""
    flop = sum(w["flop"] for w in works)
    nbytes = sum(w["bytes"] for w in works)
    per_batch = max((w["batch_bytes"] for w in works), default=0.0)
    return flop, nbytes + per_batch * batches


def share_pct(works: list, batches: float, busy_s: float, chips: int,
              peaks: dict):
    """Roofline share in %, or None where there is nothing to read (no
    search answered in the interval, or the device never busy)."""
    if not works or not busy_s > 0:
        return None
    flop, nbytes = work_of(works, batches)
    least = least_seconds(flop / chips, nbytes / chips, peaks)
    return 100.0 * least["seconds"] / busy_s
