"""``open_loop_singles``: ``rate_qps`` x ``seconds`` single requests, due at
Poisson arrivals, each drawing a pool entry by the ``popularity`` law
(``zipf`` with its exponent, or ``uniform``), over ``connections``
connections (moved from ``schedule.py``, unchanged)."""
from __future__ import annotations

import json

import numpy as np

from benchmarks.loadgen.schedule import _ranks, poisson_dues


def build(traffic: dict, seed: int, seconds: float, rate_qps: float,
          loaded) -> dict:
    n = max(1, int(round(rate_qps * seconds)))
    law_rng = np.random.default_rng([int(traffic["law_seed"]), n])
    rng = np.random.default_rng([int(seed), 0x10AD])
    due = poisson_dues(n, seconds, law_rng, rng)
    ranks = _ranks(n, loaded.pool_size, traffic["popularity"], law_rng)
    rng.shuffle(ranks)
    # a warm-up may ask for bursts first (``bursts``: so many requests due
    # at once, a second apart), so that the coalescer's batch shapes are
    # compiled before the window; the measured window has none
    bursts = [int(b) for b in traffic.get("bursts", [])]
    if bursts:
        extra = _ranks(sum(bursts), loaded.pool_size, traffic["popularity"],
                       law_rng)
        rng.shuffle(extra)
        at = np.repeat(np.arange(len(bursts), dtype=np.float64), bursts)
        due = np.concatenate([at, due + len(bursts)])
        ranks = np.concatenate([extra, ranks])
    return {
        "mode": "open", "seconds": seconds,
        "connections": int(traffic["connections"]),
        "reply_timeout_s": float(traffic.get("reply_timeout_s", 60.0)),
        "requests": [{"due": float(d), "method": "POST",
                      "path": loaded.path(int(q)),
                      "body": json.dumps(loaded.request(int(q))),
                      "pool": [int(q)]}
                     for d, q in zip(due, ranks)]}
