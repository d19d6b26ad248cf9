"""Plain exact nearest neighbours by angle, over the generator's raw
vectors (numpy), and the comparison of an approximate top-k with them.

Elasticsearch's ``cosine`` similarity scores (1 + cos) / 2. A float32
matrix product over the slab, in blocks, finds each sampled query's
nearest candidates; their cosines, and those of every returned document,
are then worked out again in float64, so every number compared is exact.

An approximate search is held to what ANN-Benchmarks holds one to
(arXiv:1807.05614, ``knn_threshold``): a returned document counts toward
recall@k when its distance (1 - cos) is within that of the k-th true
neighbour plus 1e-3, so that a tie beyond the k-th is not a miss. The
numbers, each held to a limit of the cell's file:

``wrong_answers``  answers that are no top-k at all: not k hits, a
                   document twice, a document that is not live, a score
                   that is not a number or out of order, no reply.
``score_err``      the widest gap between a returned ``_score`` and the
                   exact score of that document, as a share of it: the
                   program re-ranks its survivors exactly.
``recall_miss``    1 - recall@k over the answers that are top-ks.
"""
from __future__ import annotations

import numpy as np

from benchmarks.reference.bm25 import to_bf16

CANDIDATES = 64
ROWS = 131072  # rows a block
SLACK = 1e-3  # ANN-Benchmarks' epsilon on the k-th distance


class AnnReference:
    def __init__(self, vecs: np.ndarray, queries: np.ndarray, k: int):
        self.vecs = vecs                          # float32[N, dims]
        self.queries = queries                    # float64[Q, dims], rounded
        self.k = int(k)
        self._inv = None
        self._near: dict = {}                     # i -> (ids, cos) best first

    def _query32(self, i: int) -> np.ndarray:
        # the server parses the JSON numbers and holds them as float32
        return self.queries[i].astype(np.float32)

    def _cos(self, i: int, rows: np.ndarray) -> np.ndarray:
        v = self.vecs[rows].astype(np.float64)
        q = self._query32(i).astype(np.float64)
        return (v @ q) / (np.linalg.norm(v, axis=1) * np.linalg.norm(q))

    def prepare(self, pool: list) -> None:
        """The exact nearest of every sampled query, in one pass."""
        todo = sorted({int(i) for i in pool} - set(self._near))
        if not todo:
            return
        if self._inv is None:
            self._inv = np.empty(self.vecs.shape[0], np.float32)
            for lo in range(0, self.vecs.shape[0], ROWS):
                v = self.vecs[lo:lo + ROWS]
                self._inv[lo:lo + ROWS] = 1.0 / np.maximum(
                    np.sqrt(np.einsum("nd,nd->n", v, v)), 1e-30)
        qs = np.stack([self._query32(i) for i in todo])
        qs /= np.linalg.norm(qs, axis=1, keepdims=True)
        n = self.vecs.shape[0]
        kth = min(CANDIDATES, n) - 1
        best = np.empty((len(todo), 0), np.int64)
        for lo in range(0, n, ROWS):
            cos = (qs @ self.vecs[lo:lo + ROWS].T) * self._inv[None, lo:lo + ROWS]
            part = np.argpartition(-cos, min(kth, cos.shape[1] - 1),
                                   axis=1)[:, :kth + 1] + lo
            best = np.concatenate([best, part], axis=1)
        for row, i in zip(best, todo):
            cos = self._cos(i, row)
            order = np.lexsort((row, -cos))
            self._near[i] = (row[order], cos[order])

    def truth(self, i: int):
        """(ids, cos) of the exact nearest, best first."""
        self.prepare([i])
        return self._near[i]

    def judge(self, i: int, hits) -> dict:
        """One answer -> {"fault": str | None, "score_err", "recall"}."""
        if not isinstance(hits, list):
            return {"fault": "no hits in the reply"}
        try:
            ids = np.asarray([int(h["_id"]) for h in hits], np.int64)
            got = np.asarray([float(h["_score"]) for h in hits], np.float64)
        except (KeyError, TypeError, ValueError) as e:
            return {"fault": f"malformed hit: {e!r}"}
        n = self.vecs.shape[0]
        if len(ids) != min(self.k, n):
            return {"fault": f"{len(ids)} hits, not {min(self.k, n)}"}
        if len(set(ids.tolist())) != len(ids):
            return {"fault": f"a document twice: {ids.tolist()}"}
        if np.any((ids < 0) | (ids >= n)):
            return {"fault": f"a document that is not live: {ids.tolist()}"}
        if not np.all(np.isfinite(got)):
            return {"fault": "a score that is not a number"}
        if len(got) > 1 and np.any(np.diff(got) > 0):
            return {"fault": "hits not in descending order of score"}
        cos = self._cos(i, ids)
        want = (1.0 + cos) / 2.0
        _, true_cos = self.truth(i)
        limit = (1.0 - true_cos[self.k - 1]) + SLACK
        return {"fault": None,
                "score_err": float(np.max(np.abs(got - want) / want)),
                "recall": float(np.mean((1.0 - cos) <= limit))}

    def control(self, pool: list) -> list:
        """The reference one guarantee and one precision down, as replies:
        the exact top-k with its second half swapped for the next ranks
        (ranks 6-10 for 11-15 at k 10) and every score rounded to
        bfloat16."""
        half = self.k // 2
        out = []
        for i in pool:
            ids, cos = self.truth(i)
            pick = np.r_[np.arange(half), np.arange(self.k, 2 * self.k - half)]
            scores = to_bf16(((1.0 + cos[pick]) / 2.0).astype(np.float32))
            out.append((i, [{"_id": str(int(d)), "_score": float(s)}
                            for d, s in zip(ids[pick], scores)]))
        return out


def compare(reference: AnnReference, answers: list) -> dict:
    """``answers``: (pool index, hits) pairs -> the cell's numbers, with the
    first few faults in words."""
    reference.prepare([i for i, _ in answers])
    wrong, err, recalls, faults = 0, 0.0, [], []
    for i, hits in answers:
        r = reference.judge(i, hits)
        if r["fault"]:
            wrong += 1
            if len(faults) < 5:
                faults.append(f"pool query {i}: {r['fault']}")
            continue
        err = max(err, r["score_err"])
        recalls.append(r["recall"])
    miss = 1.0 - float(np.mean(recalls)) if recalls else 1.0
    return {"numbers": {"wrong_answers": wrong, "score_err": err,
                        "recall_miss": miss},
            "faults": faults, "compared": len(answers)}
