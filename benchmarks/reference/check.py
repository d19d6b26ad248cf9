"""The comparison that decides ``correct``.

Copied from ``chip_smoke.py`` (``check_topk``) and turned from pass/fail
at a fixed tolerance into numbers, each held to a limit of its own (the
cell's file gives the limits; PERF.md gives the readings they were set
from). This copy is now the yardstick.

For every sampled answer, against the plain reference's scores:

``wrong_answers``  answers that are not a top-k at all: not as many hits as
                   there should be, a document twice, a document that does
                   not match, scores out of order, no parseable reply.
                   Exact: the limit is 0.
``score_err``      the widest gap between a returned ``_score`` and the
                   reference's score of that document, as a share of it.
``rank_gap``       the widest margin by which a document left out beats the
                   last one returned, by the reference's scores, as a share
                   of that score (0 where the top-k is the reference's, up
                   to ties).
"""
from __future__ import annotations

import numpy as np

NUMBERS = ("wrong_answers", "score_err", "rank_gap")


def compare_answer(reference, pool_index: int, hits, k: int) -> dict:
    """One answer (``hits``: the ``hits.hits`` list of a reply, or None
    where the reply had none) -> {"fault": str | None, "score_err",
    "rank_gap"}."""
    if not isinstance(hits, list):
        return {"fault": "no hits in the reply", "score_err": 0.0,
                "rank_gap": 0.0}
    try:
        ids = np.asarray([int(h["_id"]) for h in hits], np.int64)
        got = np.asarray([float(h["_score"]) for h in hits], np.float64)
    except (KeyError, TypeError, ValueError) as e:
        return {"fault": f"malformed hit: {e!r}", "score_err": 0.0,
                "rank_gap": 0.0}
    j = reference.judge(pool_index, ids)
    fault = None
    want_n = min(k, j["n_eligible"])
    if len(ids) != want_n:
        fault = f"{len(ids)} hits, the reference has {want_n}"
    elif len(set(ids.tolist())) != len(ids):
        fault = f"a document twice: {ids.tolist()}"
    elif not j["eligible"].all():
        fault = (f"a document the reference excludes: "
                 f"{ids[~j['eligible']].tolist()}")
    elif not np.all(np.isfinite(got)):
        fault = "a score that is not a number"
    elif len(got) > 1 and np.any(np.diff(got) > 0):
        fault = "hits not in descending order of score"
    if fault or not len(ids):
        return {"fault": fault, "score_err": 0.0, "rank_gap": 0.0}
    want = j["want"]
    score_err = float(np.max(np.abs(got - want) / np.maximum(want, 1e-30)))
    last = float(want.min())
    rank_gap = 0.0
    if len(ids) == k:  # fewer than k hits: nothing was left out
        rank_gap = max(0.0, j["best_left"] - last) / max(last, 1e-30)
    return {"fault": None, "score_err": score_err, "rank_gap": rank_gap}


def compare(reference, answers: list, k: int) -> dict:
    """``answers``: (pool index, hits) pairs. The cell's numbers, with the
    first few faults in words."""
    reference.prepare([i for i, _ in answers])
    out = {"wrong_answers": 0, "score_err": 0.0, "rank_gap": 0.0}
    faults = []
    for i, hits in answers:
        r = compare_answer(reference, i, hits, k)
        if r["fault"]:
            out["wrong_answers"] += 1
            if len(faults) < 5:
                faults.append(f"pool query {i}: {r['fault']}")
        out["score_err"] = max(out["score_err"], r["score_err"])
        out["rank_gap"] = max(out["rank_gap"], r["rank_gap"])
    return {"numbers": out, "faults": faults, "compared": len(answers)}


def control_answers(reference, pool: list, k: int) -> list:
    """The lower-precision reference's answers, in the form of replies."""
    pool = list(pool)
    return [(i, [{"_id": str(int(d)), "_score": float(s)}
                 for d, s in zip(ids, scores)])
            for i, (ids, scores) in zip(pool, reference.control(pool, k))]


def verdict(numbers: dict, limits: dict) -> bool:
    """Every number compared is at or under its limit; a number with no
    limit is an error of the cell's file, not a pass."""
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"the cell's file gives no limit for [{name}]")
        if not value <= limits[name]:
            return False
    return True
