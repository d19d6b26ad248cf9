"""The layout of the tail's ``[T, P]`` postings window (search/context.py
``split_runs`` / ``chunk_table`` / ``tail_width``, utils/shapes.py
``half_step_bucket``): every run cut at one width — the pow2 bucket of
the longest run, at most ``TAIL_W`` — and the chunk count padded to a
half-step bucket. Counts and equality only, never a time."""
import math

import numpy as np
import pytest

from elasticsearch_tpu.search import context
from elasticsearch_tpu.utils.shapes import half_step_bucket, pow2_bucket

W = context.TAIL_W
LADDER = [1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64, 96, 128]
EDGE_LENGTHS = [0, 1, W - 1, W, W + 1, 2 * W, (1 << 15) + 1, 141_909]


def test_the_ladder_is_the_powers_of_two_and_their_halfway_steps():
    assert sorted({half_step_bucket(n) for n in range(129)}) == LADDER
    assert half_step_bucket(0) == 1 and half_step_bucket(5, minimum=8) == 8


@pytest.mark.parametrize("top", [1, 2, 3, 9, 100, 4097, 70_000])
def test_the_bucket_is_monotone_tight_and_has_few_values(top):
    got = [half_step_bucket(n) for n in range(1, top + 1)]
    assert all(b >= n for n, b in enumerate(got, 1))
    assert all(b < 1.5 * n or b == n for n, b in enumerate(got, 1))
    assert got == sorted(got)
    assert len({b for b in got if b <= top}) <= 2 * math.log2(top) + 2


@pytest.mark.parametrize("n", [0, 1, 3, 5, 9, 21, 700, 1000])
def test_the_chunk_count_takes_the_finer_ladder_only_at_the_caps_width(n):
    # a padding chunk costs W slots at the cap; under it the window is
    # the old layout's, a pow2 of chunks
    assert context.chunk_count_bucket(n, W) == half_step_bucket(n)
    assert context.chunk_count_bucket(n, 2 * W) == half_step_bucket(n)
    assert context.chunk_count_bucket(n, W // 2) == pow2_bucket(n, minimum=1)
    assert context.chunk_count_bucket(n, 8, minimum=8) == pow2_bucket(n)
    assert context.chunk_count_bucket(n, W, minimum=8) == max(
        half_step_bucket(n), 8)


def _runs(lengths, rng):
    """Runs of the given lengths, laid end to end with gaps, in a random
    order (a query's terms come in no order of their postings)."""
    at, runs = int(rng.integers(0, 50)), []
    for i, ln in enumerate(lengths):
        runs.append((at, int(ln), 1.0 + 0.5 * i))
        at += int(ln) + int(rng.integers(0, 3 * W))
    return [runs[i] for i in rng.permutation(len(runs))]


@pytest.mark.parametrize("seed", range(12))
def test_every_posting_is_in_exactly_one_chunk_of_the_fixed_width(seed):
    rng = np.random.default_rng(seed)
    lengths = list(rng.choice(EDGE_LENGTHS, size=rng.integers(1, 5))) + list(
        rng.integers(0, 3 * W, size=rng.integers(0, 6)))
    runs = _runs(lengths, rng)
    starts, lens, ws = context.split_runs(runs, W)
    n_chunks = sum(max(-(-ln // W), 1) for _s, ln, _w in runs)
    assert len(starts) == len(lens) == len(ws) == n_chunks
    at = 0
    for s, ln, w in runs:  # a run's chunks: adjacent, in order, its weight
        n = max(-(-ln // W), 1)
        assert starts[at:at + n] == [s + j * W for j in range(n)]
        assert lens[at:at + n - 1] == [W] * (n - 1)
        assert sum(lens[at:at + n]) == ln and 0 <= lens[at + n - 1] <= W
        assert ln == 0 or lens[at + n - 1] > 0
        assert ws[at:at + n] == [w] * n
        at += n
    covered = np.concatenate([np.arange(s, s + ln)
                              for s, ln in zip(starts, lens)] + [[]])
    wanted = np.concatenate([np.arange(s, s + ln) for s, ln, _w in runs]
                            + [[]])
    assert np.array_equal(np.sort(covered), np.sort(wanted))

    t_starts, t_lens, t_ws = context.chunk_table(runs, W)
    T = t_starts.shape[0]
    assert T in LADDER + [192, 256] and n_chunks <= T < max(1.5 * n_chunks, 2)
    assert (t_starts.dtype, t_lens.dtype, t_ws.dtype) == (
        np.int32, np.int32, np.float32)
    assert t_starts[:n_chunks].tolist() == starts
    assert t_lens[:n_chunks].tolist() == lens and not t_lens[n_chunks:].any()
    assert not t_ws[n_chunks:].any()


def test_no_run_is_one_empty_chunk():
    starts, lens, ws = context.chunk_table([], W)
    assert starts.tolist() == lens.tolist() == [0] and ws.tolist() == [0.0]


def legacy_window(runs):
    """(starts i32[T], lens i32[T], ws f32[T], P) of raw runs as PR 29
    laid the window out: runs cut at 2^15, P the pow2 of the longest
    chunk, T a pow2."""
    starts, lens, ws = context.split_runs(runs, 1 << 15)
    pad = pow2_bucket(len(starts), minimum=1) - len(starts)
    return (np.asarray(starts + [0] * pad, np.int32),
            np.asarray(lens + [0] * pad, np.int32),
            np.asarray(ws + [0.0] * pad, np.float32),
            pow2_bucket(max(lens, default=1)))


@pytest.mark.parametrize("nnz_pad,longest,want", [
    (8, 3, 8), (4, 3, 4), (W // 2, 10 * W, W // 2), (W, W, W),
    (1 << 27, 141_909, W), (1 << 27, W + 1, W), (1 << 27, W // 2 + 1, W),
    (1 << 27, W // 2, W // 2), (1 << 27, 9, 16), (1 << 27, 1, 8),
    (1 << 27, 0, 8)])
def test_the_width_is_the_longest_runs_bucket_up_to_the_cap(nnz_pad, longest,
                                                            want):
    # (never wider than the postings either: lax.dynamic_slice cannot cut
    # a window wider than its operand)
    runs = [(0, longest, 1.0), (7, min(longest, 2), 2.0)]
    assert context.tail_width(nnz_pad, runs) == want
    assert context.tail_width(nnz_pad, []) == min(8, nnz_pad)


@pytest.mark.parametrize("n_terms,df", [(1000, 1), (1000, 7), (65_536, 1),
                                        (300, 100), (1000, 0)])
def test_many_short_runs_make_no_more_slots_than_the_old_layout(n_terms, df):
    """A terms filter of a thousand ids of a keyword field (df 1) is a
    [1024, 8] window as it was before the fixed width, not a [1024, 4096]
    one: the width follows the runs up to the cap."""
    runs = [(11 * i, df, 1.0) for i in range(n_terms)]
    P = context.tail_width(1 << 27, runs)
    starts, lens, ws = context.chunk_table(runs, P)
    old_starts, old_lens, old_ws, old_P = legacy_window(runs)
    # under the cap the window IS the old layout's, chunk for chunk
    assert P == old_P == pow2_bucket(df) < W
    assert np.array_equal(starts, old_starts)
    assert np.array_equal(lens, old_lens) and np.array_equal(ws, old_ws)


@pytest.mark.parametrize("seed", range(8))
def test_no_window_has_more_slots_than_the_old_layouts(seed):
    rng = np.random.default_rng(1000 + seed)
    lengths = list(rng.integers(0, [5, 300, 3 * W, 200_000][seed % 4] + 1,
                                size=rng.integers(1, 40)))
    runs = _runs(lengths, rng)
    P = context.tail_width(1 << 27, runs)
    starts, lens, _ws = context.chunk_table(runs, P)
    old_starts, old_lens, _w, old_P = legacy_window(runs)
    assert int(lens.sum()) == int(old_lens.sum()) == sum(lengths)
    assert starts.shape[0] * P <= old_starts.shape[0] * old_P


def _corpus(seed, n_docs=2048, vocab=48):
    from elasticsearch_tpu.index.segment import build_dense_impact

    rng = np.random.default_rng(seed)
    D = pow2_bucket(n_docs)
    doc_lists = [np.sort(rng.choice(n_docs, size=max(1, n_docs // (t + 1)),
                                    replace=False)) for t in range(vocab)]
    df = np.array([len(d) for d in doc_lists], np.int32)
    offsets = np.zeros(vocab + 1, np.int64)
    offsets[1:] = np.cumsum(df)
    nnz = int(df.sum())
    docs = np.concatenate(doc_lists).astype(np.int32)
    tfn = ((rng.random(nnz) + 0.5) * 8).round().astype(np.float32) / 8
    dense_rows, impact = build_dense_impact(docs, tfn, offsets, df, D,
                                            df_threshold=512)
    nnz_pad = pow2_bucket(nnz)
    d_doc = np.full(nnz_pad, D, np.int32)
    d_doc[:nnz] = docs
    d_tfn = np.zeros(nnz_pad, np.float32)
    d_tfn[:nnz] = tfn
    return dict(D=D, n_docs=n_docs, vocab=vocab, dense_rows=dense_rows,
                impact=impact, offsets=offsets, df=df, d_doc=d_doc,
                d_tfn=d_tfn, nnz_pad=nnz_pad)


@pytest.mark.parametrize("seed", [3, 5, 8])
@pytest.mark.parametrize("nested", [False, True])
@pytest.mark.parametrize("dense", [False, True])
def test_the_one_program_is_bitwise_the_old_layouts(dense, nested, seed):
    """``bm25_term_group_topk`` packs the same words under PR 29's window
    (runs cut at 2^15, P the pow2 of the longest, T a pow2) and under the
    fixed-width one: a term's chunks stay adjacent and in term order, so a
    document's contributions arrive in the same order. (XLA:CPU's scatter
    adds in window order. XLA:TPU's does not keep it — there a fifth of
    the pool's queries differ in a score's last bit: PERF.md §6, PR 30.)"""
    import jax.numpy as jnp

    from elasticsearch_tpu.ops.scoring import (bm25_term_group_topk,
                                               pack_dense_rows,
                                               pack_term_group_words)
    c = _corpus(seed)
    rng = np.random.default_rng(100 + seed)
    # terms 0..3 have a dense row (df >= 512), 4..12 a run longer than
    # the new width, the rest short runs
    qterms = np.unique(np.concatenate([
        rng.choice(4, size=rng.integers(1, 3), replace=False),
        rng.choice(np.arange(4, 13), size=rng.integers(1, 4), replace=False),
        rng.choice(np.arange(13, c["vocab"]), size=rng.integers(0, 5),
                   replace=False)]))
    rng.shuffle(qterms)
    row_w, runs = {}, []
    for i, t in enumerate(qterms):
        w = 1.0 + 0.37 * i
        row = int(c["dense_rows"][t]) if dense else -1
        if row >= 0:
            row_w[row] = row_w.get(row, 0.0) + w
        else:
            runs.append((int(c["offsets"][t]), int(c["df"][t]), w))
    assert bool(row_w) == dense and runs
    qrows, qrw = pack_dense_rows(row_w) if row_w else (None, None)
    live = np.zeros(c["D"], bool)
    live[:c["n_docs"]] = True
    live[rng.choice(c["n_docs"], size=100, replace=False)] = False
    roots = None
    if nested:
        roots = np.zeros(c["D"], bool)
        roots[:c["n_docs"]:3] = True
        roots = jnp.asarray(roots)

    def packed(starts, lens, ws, P):
        return np.asarray(bm25_term_group_topk(
            c["impact"] if dense else None, c["d_doc"], c["d_tfn"],
            jnp.asarray(live), roots,
            pack_term_group_words(qrows, qrw, starts, lens, ws),
            R=qrows.shape[0] if dense else 0, T=starts.shape[0], P=P,
            D=c["D"], k=10, topk_block=0))

    old = legacy_window(runs)
    new_w = 128  # far under the long runs here, as 4096 is under a shard's
    new = context.chunk_table(runs, new_w) + (new_w,)
    assert new[0].shape[0] > old[0].shape[0]  # the runs really were cut
    assert np.array_equal(packed(*old), packed(*new))
