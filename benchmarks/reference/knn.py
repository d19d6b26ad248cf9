"""Plain exact nearest neighbours over the generator's raw vectors (numpy).

Copied from ``chip_smoke.py:width_child`` (``ref_knn``), for Elasticsearch's
``l2_norm`` similarity: score = 1 / (1 + |q - v|^2). This copy is now the
yardstick. A float32 matrix product over the whole slab finds each sampled
query's nearest candidates; their distances, and those of every returned
document, are then worked out again in float64, so every number compared
is exact.
"""
from __future__ import annotations

import numpy as np

from benchmarks.reference.bm25 import to_bf16

CANDIDATES = 64
ROWS = 131072  # rows a block


class KnnReference:
    def __init__(self, vecs: np.ndarray, queries: np.ndarray,
                 similarity: str):
        if similarity != "l2_norm":
            raise ValueError(f"no plain reference for [{similarity}]")
        self.vecs = vecs                          # float32[N, dims]
        self.queries = queries                    # float64[Q, dims], rounded
        self._near: dict = {}

    def _query32(self, i: int) -> np.ndarray:
        # the server parses the JSON numbers and holds them as float32
        return self.queries[i].astype(np.float32)

    def _d2(self, qs: np.ndarray, lower: bool) -> np.ndarray:
        """float32[len(qs), N] squared distances by the norm expansion;
        ``lower`` rounds both sides of the product to bfloat16."""
        out = np.empty((qs.shape[0], self.vecs.shape[0]), np.float32)
        q2 = (qs.astype(np.float64) ** 2).sum(1).astype(np.float32)
        qm = to_bf16(qs) if lower else qs
        for lo in range(0, self.vecs.shape[0], ROWS):
            v = self.vecs[lo:lo + ROWS]
            v2 = np.einsum("nd,nd->n", v, v)
            vm = to_bf16(v) if lower else v
            out[:, lo:lo + ROWS] = q2[:, None] - 2.0 * (qm @ vm.T) + v2[None]
        return out

    def prepare(self, pool: list) -> None:
        """Nearest candidates of every sampled query, in one pass."""
        todo = sorted({int(i) for i in pool} - set(self._near))
        if not todo:
            return
        d2 = self._d2(np.stack([self._query32(i) for i in todo]), False)
        kth = min(CANDIDATES, d2.shape[1] - 1)
        for row, i in zip(d2, todo):
            self._near[i] = np.argpartition(row, kth)[:kth + 1]

    def _exact(self, i: int, rows: np.ndarray) -> np.ndarray:
        diff = (self.vecs[rows].astype(np.float64)
                - self._query32(i).astype(np.float64))
        return 1.0 / (1.0 + (diff * diff).sum(1))

    def judge(self, i: int, ids: np.ndarray) -> dict:
        self.prepare([i])
        n = self.vecs.shape[0]
        ok = (ids >= 0) & (ids < n)
        want = np.zeros(ids.shape[0], np.float64)
        want[ok] = self._exact(i, ids[ok])
        left = np.setdiff1d(self._near[i], ids[ok])
        return {"want": want, "eligible": ok, "n_eligible": n,
                "best_left": float(self._exact(i, left).max())}

    def control(self, pool: list, k: int) -> list:
        """The reference in the program's place, one precision down: the
        product of the norm expansion on bfloat16 inputs, summed in
        float32. One pass over the slab for all of ``pool``; (ids, scores)
        for each."""
        d2 = self._d2(np.stack([self._query32(i) for i in pool]), True)
        out = []
        for row in d2:
            top = np.argpartition(row, min(k, row.size - 1))[:k]
            top = top[np.lexsort((top, row[top]))]
            out.append((top, 1.0 / (1.0 + np.maximum(row[top], 0.0)
                                    .astype(np.float64))))
        return out
