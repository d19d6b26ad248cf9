"""The one general traffic generator: a traffic file's parameters and a
seed in, a schedule for ``client.py`` out.

Every seed gets the same set of arrival gaps and the same set of pool
ranks (both fixed by the traffic file's ``law_seed``), in another order:
the seed changes which request comes when, not how much work a run holds.

The ``kind`` of the traffic file names a module of ``loadgen/kinds/``
that gives ``build(traffic, seed, seconds, rate_qps, loaded) -> schedule``;
what the kinds share — the law of pool ranks and of arrival gaps — is here.
A schedule is what ``client.py`` reads: ``mode`` ``open`` (``requests``:
one list, each with its ``due`` second, over ``connections``) or ``closed``
(``requests``: one list a client, sent back to back), each request with
``method``, ``path``, ``body`` and ``pool`` (the pool entries it carries).
"""
from __future__ import annotations

import numpy as np

from benchmarks import byname


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


def poisson_dues(n: int, seconds: float, law_rng, rng) -> np.ndarray:
    """``n`` due times inside ``seconds``: the law's seed fixes the set of
    gaps, the run's seed their order (the closing spacing stays where it
    is)."""
    gaps = law_rng.standard_exponential(n + 1)
    rng.shuffle(gaps[:n])
    return np.cumsum(gaps)[:n] / gaps.sum() * seconds


def build(traffic: dict, seed: int, seconds: float, rate_qps,
          loaded) -> dict:
    kind = byname.module("benchmarks.loadgen.kinds", traffic.get("kind"),
                         "traffic kind")
    return kind.build(traffic, seed, seconds, rate_qps, loaded)
