"""The one general traffic generator: a traffic file's parameters and a
seed in, a schedule for ``client.py`` out.

Every seed gets the same set of arrival gaps and the same set of pool
ranks (both fixed by the traffic file's ``law_seed``), in another order:
the seed changes which request comes when, not how much work a run holds.

Kinds (the ``kind`` of the traffic file):

``open_loop_singles``   ``rate_qps`` x ``seconds`` single searches, due at
                        Poisson arrivals, each drawing a pool query by the
                        ``popularity`` law (``zipf`` with its exponent, or
                        ``uniform``), over ``connections`` connections.
``closed_loop_msearch`` ``clients`` callers, each sending ``_msearch``
                        requests of ``bodies`` searches back to back; the
                        pool is cut into such requests in a seeded order
                        without repeats and dealt round the clients.
"""
from __future__ import annotations

import json

import numpy as np


def _ranks(n: int, pool: int, popularity: dict, law_rng) -> np.ndarray:
    """``n`` pool indices whose multiset follows the law exactly (largest
    remainders), in no particular order."""
    law = popularity.get("law", "uniform")
    if law == "zipf":
        p = np.arange(1, pool + 1, dtype=np.float64) ** -float(
            popularity["exponent"])
    elif law == "uniform":
        p = np.ones(pool, np.float64)
    else:
        raise ValueError(f"unknown popularity law [{law}]")
    want = n * p / p.sum()
    counts = np.floor(want).astype(np.int64)
    short = n - int(counts.sum())
    if short:
        counts[np.argsort(-(want - counts), kind="stable")[:short]] += 1
    # which pool member holds which rank: fixed by the law's seed
    member = law_rng.permutation(pool)
    return np.repeat(member, counts)


def open_loop_singles(traffic: dict, seed: int, seconds: float,
                      rate_qps: float, loaded) -> dict:
    n = max(1, int(round(rate_qps * seconds)))
    law_rng = np.random.default_rng([int(traffic["law_seed"]), n])
    rng = np.random.default_rng([int(seed), 0x10AD])
    gaps = law_rng.standard_exponential(n + 1)
    ranks = _ranks(n, loaded.pool_size, traffic["popularity"], law_rng)
    rng.shuffle(gaps[:n])  # the closing spacing stays where it is
    rng.shuffle(ranks)
    due = np.cumsum(gaps)[:n] / gaps.sum() * seconds
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
    path = f"/{loaded.index}/_search"
    return {
        "mode": "open", "seconds": seconds,
        "connections": int(traffic["connections"]),
        "reply_timeout_s": float(traffic.get("reply_timeout_s", 60.0)),
        "requests": [{"due": float(d), "method": "POST", "path": path,
                      "body": json.dumps(loaded.request(int(q))),
                      "pool": [int(q)]}
                     for d, q in zip(due, ranks)]}


def closed_loop_msearch(traffic: dict, seed: int, seconds: float,
                        rate_qps, loaded) -> dict:
    bodies, clients = int(traffic["bodies"]), int(traffic["clients"])
    rng = np.random.default_rng([int(seed), 0xC105])
    order = rng.permutation(loaded.pool_size)
    n_req = len(order) // bodies
    if n_req < clients:
        raise ValueError(
            f"a pool of {loaded.pool_size} does not give {clients} clients "
            f"a request of {bodies} bodies each")
    head = json.dumps({"index": loaded.index})
    lists = [[] for _ in range(clients)]
    for r in range(n_req):
        pool = order[r * bodies:(r + 1) * bodies]
        lines = []
        for q in pool:
            lines.append(head)
            lines.append(json.dumps(loaded.request(int(q))))
        lists[r % clients].append({
            "method": "POST", "path": "/_msearch",
            "body": "\n".join(lines) + "\n",
            "pool": [int(q) for q in pool]})
    return {"mode": "closed", "seconds": seconds,
            "reply_timeout_s": float(traffic.get("reply_timeout_s", 120.0)),
            "requests": lists}


KINDS = {"open_loop_singles": open_loop_singles,
         "closed_loop_msearch": closed_loop_msearch}


def build(traffic: dict, seed: int, seconds: float, rate_qps,
          loaded) -> dict:
    try:
        kind = KINDS[traffic["kind"]]
    except KeyError:
        raise ValueError(f"unknown traffic kind [{traffic.get('kind')}]; "
                         f"known: {sorted(KINDS)}") from None
    return kind(traffic, seed, seconds, rate_qps, loaded)
