"""``closed_loop_msearch``: ``clients`` callers, each sending ``_msearch``
requests of ``bodies`` searches back to back; the pool is cut into such
requests in a seeded order without repeats and dealt round the clients
(moved from ``schedule.py``, unchanged)."""
from __future__ import annotations

import json

import numpy as np


def build(traffic: dict, seed: int, seconds: float, rate_qps,
          loaded) -> dict:
    bodies, clients = int(traffic["bodies"]), int(traffic["clients"])
    rng = np.random.default_rng([int(seed), 0xC105])
    order = rng.permutation(loaded.pool_size)
    n_req = len(order) // bodies
    if n_req < clients:
        raise ValueError(
            f"a pool of {loaded.pool_size} does not give {clients} clients "
            f"a request of {bodies} bodies each")
    lists = [[] for _ in range(clients)]
    for r in range(n_req):
        pool = order[r * bodies:(r + 1) * bodies]
        lines = []
        for q in pool:
            lines.append(json.dumps({"index": _index_of(loaded, int(q))}))
            lines.append(json.dumps(loaded.request(int(q))))
        lists[r % clients].append({
            "method": "POST", "path": "/_msearch",
            "body": "\n".join(lines) + "\n",
            "pool": [int(q) for q in pool]})
    return {"mode": "closed", "seconds": seconds,
            "reply_timeout_s": float(traffic.get("reply_timeout_s", 120.0)),
            "requests": lists}


def _index_of(loaded, i: int) -> str:
    """The index an ``_msearch`` header names for pool entry ``i``: the one
    its single request's path searches."""
    index, _, verb = loaded.path(i).strip("/").partition("/")
    if verb != "_search":
        raise ValueError(
            f"pool entry {i} goes to [{loaded.path(i)}]: not a search, so "
            f"no body of an _msearch")
    return index
