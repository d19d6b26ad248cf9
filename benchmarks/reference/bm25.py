"""Plain BM25 over the generator's raw output (numpy, float64).

Copied from ``chip_smoke.py:width_child`` (``ref_bm25``) and its
``Reference.bm25``; this copy is now the yardstick. Independent of the
engine: it derives its own idf and tf-normalisation from raw tf and
document lengths, per shard (Elasticsearch scores a shard with the shard's
own statistics), and never reads an array the loader derived.

    idf(t)    = ln(1 + (N - df + 0.5) / (df + 0.5))
    score(d)  = sum_t idf(t) * tf * (k1 + 1) / (tf + k1 * (1 - b + b * len(d) / avg))
"""
from __future__ import annotations

import numpy as np


def to_bf16(x: np.ndarray) -> np.ndarray:
    """float32 values rounded to bfloat16 (nearest even), still float32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))
         ) & np.uint32(0xFFFF0000)
    return u.view(np.float32)


class Bm25Reference:
    def __init__(self, shards: list, k1: float, b: float, pool: list):
        self.shards, self.k1, self.b = shards, float(k1), float(b)
        self.pool = pool  # term-id lists; answers are asked for by index
        self.avg_len = [c.doc_len.sum() / c.n_docs for c in shards]
        self.n_docs = sum(c.n_docs for c in shards)

    def _impacts(self, si: int, t: int):
        """(doc ids, idf, tf-normalisation) of term ``t`` in shard ``si``."""
        c = self.shards[si]
        s, e = int(c.offsets[t]), int(c.offsets[t + 1])
        docs = c.doc_ids[s:e]
        tf = c.tf[s:e].astype(np.float64)
        df = float(c.df[t])
        idf = np.log(1.0 + (c.n_docs - df + 0.5) / (df + 0.5))
        tfn = tf * (self.k1 + 1.0) / (tf + self.k1 * (
            1.0 - self.b + self.b * c.doc_len[docs] / self.avg_len[si]))
        return docs, idf, tfn

    def scores(self, terms) -> np.ndarray:
        """float64[n_docs]: document ``shard * docs_per_shard + local``."""
        out = np.zeros(self.n_docs, np.float64)
        base = 0
        for si, c in enumerate(self.shards):
            part = out[base:base + c.n_docs]
            for t in terms:
                docs, idf, tfn = self._impacts(si, int(t))
                part[docs] += idf * tfn
            base += c.n_docs
        return out

    def prepare(self, pool: list) -> None:
        """Nothing to share between queries here."""

    def judge(self, i: int, ids: np.ndarray) -> dict:
        """What the comparison needs of one answer: the reference's score
        of each returned document, whether it may be returned at all, how
        many documents match, and the best score among those left out."""
        sc = self.scores(self.pool[i])
        ok = (ids >= 0) & (ids < sc.size)
        want = np.where(ok, sc[np.where(ok, ids, 0)], 0.0)
        n_match = int(np.count_nonzero(sc))
        sc[ids[ok]] = 0.0
        return {"want": want, "eligible": ok & (want > 0.0),
                "n_eligible": n_match, "best_left": float(sc.max())}

    def control(self, pool: list, k: int) -> list:
        """The reference in the program's place, one precision down: idf
        and tf-normalisation rounded to bfloat16, products summed in
        float32 (what a bf16 pass of the matrix unit gives). (ids, scores)
        for each query of ``pool``."""
        answers = []
        for i in pool:
            out = np.zeros(self.n_docs, np.float32)
            base = 0
            for si, c in enumerate(self.shards):
                part = out[base:base + c.n_docs]
                for t in self.pool[i]:
                    docs, idf, tfn = self._impacts(si, int(t))
                    part[docs] += (to_bf16(np.float32(idf))
                                   * to_bf16(tfn.astype(np.float32)))
                base += c.n_docs
            top = np.argpartition(-out, min(k, out.size - 1))[:k]
            top = top[np.lexsort((top, -out[top]))]
            top = top[out[top] > 0]
            answers.append((top, out[top].astype(np.float64)))
        return answers
