"""Seeded text corpus, straight into term-major CSR (numpy, host).

The yardstick's generator: the idea of ``bench.build_corpus`` (Zipf
passages) at a deployment's sizes, without its sort/``unique`` over every
token. Per term a document frequency from a capped Zipf law (the law is
the configuration's, so every seed has the same set of run lengths and
therefore the same amount of work), doc ids as uniform order statistics
(normalised cumulative exponential spacings, shifted by rank so that they
come sorted and distinct), tf from a small discrete law, document lengths
from the tf column sums. No Python loop over terms or documents.

Raw output only: doc ids, tf, document lengths. BM25's tf-normalisation
and idf are derived twice, independently: by ``benchmarks/loaders.py`` for
the engine and by ``benchmarks/reference/bm25.py`` for the comparison.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class TextCorpus:
    n_docs: int
    vocab: int
    df: np.ndarray        # int64[V]
    offsets: np.ndarray   # int64[V+1]
    doc_ids: np.ndarray   # int32[nnz], sorted and distinct inside a term
    tf: np.ndarray        # uint8[nnz]
    doc_len: np.ndarray   # int64[n_docs] (sum of tf over the doc's terms)

    @property
    def nnz(self) -> int:
        return int(self.offsets[-1])


def zipf_df(n_docs: int, vocab: int, postings_per_doc: float,
            exponent: float, df_cap_share: float) -> np.ndarray:
    """df(rank) = n_docs * min(cap, a / rank**s), at least 1, with ``a``
    solved so that the postings come to ``postings_per_doc`` a document.
    Deterministic: the law belongs to the configuration, not the seed."""
    w = np.arange(1, vocab + 1, dtype=np.float64) ** -float(exponent)
    lo, hi = 0.0, float(vocab)
    for _ in range(80):
        a = 0.5 * (lo + hi)
        if np.minimum(df_cap_share, a * w).sum() < postings_per_doc:
            lo = a
        else:
            hi = a
    share = np.minimum(df_cap_share, 0.5 * (lo + hi) * w)
    return np.clip(np.rint(share * n_docs), 1, n_docs).astype(np.int64)


#: P(tf = 1, 2, 3, 4) = 205/256, 41/256, 8/256, 2/256 (mean 1.246)
_TF_CUTS = (205, 246, 254)


def make_corpus(n_docs: int, vocab: int, seed: int, *,
                postings_per_doc: float, exponent: float,
                df_cap_share: float) -> TextCorpus:
    df = zipf_df(n_docs, vocab, postings_per_doc, exponent, df_cap_share)
    offsets = np.zeros(vocab + 1, np.int64)
    np.cumsum(df, out=offsets[1:])
    nnz = int(offsets[-1])
    rng = np.random.default_rng([int(seed), 0x7E47])

    # sorted uniforms per term: cumulative spacings over the term's total
    c = np.cumsum(rng.standard_exponential(nnz, dtype=np.float32),
                  dtype=np.float64)
    base = np.zeros(vocab, np.float64)
    base[1:] = c[offsets[1:-1] - 1]
    total = c[offsets[1:] - 1] - base + rng.standard_exponential(vocab) + 1e-9
    c -= np.repeat(base, df)
    c /= np.repeat(total, df)
    # a sorted sample WITH repeats from [0, n_docs - df] plus the rank in
    # the run is a sorted sample WITHOUT repeats from [0, n_docs)
    slack = (n_docs - df).astype(np.float64)
    c *= np.repeat(slack + 1.0, df)
    np.floor(c, out=c)
    np.minimum(c, np.repeat(slack, df), out=c)
    doc_ids = c.astype(np.int32)
    del c
    rank = np.arange(nnz, dtype=np.int64)
    rank -= np.repeat(offsets[:-1], df)
    doc_ids += rank.astype(np.int32)
    del rank

    b = rng.integers(0, 256, nnz, dtype=np.uint8)
    tf = np.ones(nnz, np.uint8)
    for cut in _TF_CUTS:
        tf += b >= cut
    doc_len = np.bincount(doc_ids, weights=tf, minlength=n_docs
                          ).astype(np.int64)
    return TextCorpus(n_docs=n_docs, vocab=vocab, df=df, offsets=offsets,
                      doc_ids=doc_ids, tf=tf, doc_len=doc_len)


def make_queries(corpus: TextCorpus, seed: int, *, n_queries: int,
                 min_terms: int, max_terms: int) -> list:
    """``n_queries`` term-id lists. The number of terms per query is a
    fixed quota over [min_terms, max_terms] (the same multiset for every
    seed, shuffled); each term is a token drawn from running text, i.e.
    with probability proportional to its df, so function words come as
    often as they do in the passages (the analyzer keeps them)."""
    rng = np.random.default_rng([int(seed), 0x9E27])
    span = max_terms - min_terms + 1
    sizes = min_terms + (np.arange(n_queries) % span)
    rng.shuffle(sizes)
    cdf = np.cumsum(corpus.df, dtype=np.float64)
    cdf /= cdf[-1]
    flat = np.searchsorted(cdf, rng.random(int(sizes.sum())), side="right")
    flat = np.minimum(flat, corpus.vocab - 1)
    out, at = [], 0
    for n in sizes.tolist():
        # a repeated word is one term to a `match` query
        out.append(np.unique(flat[at:at + n]).astype(np.int64))
        at += n
    return out
