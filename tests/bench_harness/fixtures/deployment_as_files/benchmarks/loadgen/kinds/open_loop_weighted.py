"""``open_loop_weighted``: ``rate_qps`` x ``seconds`` single requests at
Poisson arrivals, each of a request shape drawn by the ``mix``'s weights
and, inside the shape, a pool entry drawn uniformly. The shapes are the
configuration kind's (``loaded.shapes``: {shape: [pool indices]}). Every
seed gets the same quota of each shape and of each entry, in another
order."""
from __future__ import annotations

import json

import numpy as np

from benchmarks.loadgen.schedule import _ranks, poisson_dues


def build(traffic: dict, seed: int, seconds: float, rate_qps: float,
          loaded) -> dict:
    n = max(1, int(round(rate_qps * seconds)))
    law_rng = np.random.default_rng([int(traffic["law_seed"]), n])
    rng = np.random.default_rng([int(seed), 0x3E16])
    due = poisson_dues(n, seconds, law_rng, rng)
    weights = np.asarray([float(m["weight"]) for m in traffic["mix"]])
    want = n * weights / weights.sum()
    quota = np.floor(want).astype(np.int64)
    quota[np.argsort(-(want - quota), kind="stable")[:n - quota.sum()]] += 1
    picks = []
    for m, k in zip(traffic["mix"], quota):
        members = np.asarray(loaded.shapes[m["shape"]])
        picks.append(members[_ranks(int(k), len(members),
                                    {"law": "uniform"}, law_rng)])
    ranks = np.concatenate(picks)
    rng.shuffle(ranks)
    return {
        "mode": "open", "seconds": seconds,
        "connections": int(traffic["connections"]),
        "reply_timeout_s": float(traffic.get("reply_timeout_s", 60.0)),
        "requests": [{"due": float(d), "method": "POST",
                      "path": loaded.path(int(q)),
                      "body": json.dumps(loaded.request(int(q))),
                      "pool": [int(q)]}
                     for d, q in zip(due, ranks)]}
