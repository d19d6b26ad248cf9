"""A single query reads its own dense rows of the impact block, never the
block (ops/scoring.py ``_dense_rows``; PR 28).

(a) Compiled for a described v5e with no chip attached (on-chip-measurement,
    section 2): the whole one-program search holds no ``f32[64,32768]`` column
    piece of the block — the advanced-index gather's lowering, which streamed
    all 64 rows a search — and the score program's bytes and temporaries are
    those of a row at a time. A compile is not a chip run and gives no time.
    The topology is described inside a fixture, never at import.
(b) On the CPU: the in-order f32 row sum against a float64 numpy sum, and the
    one program's packed words against the staged sequence, bit for bit.
(c) The selecting and comparing sites give what the index form gave, exactly.
"""
import os

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

MIB = 1 << 20
F_TEXT, SLOTS_TEXT, NNZ_TEXT = 64, 1 << 22, 1 << 27


# -- (a) compiled for a described v5e ----------------------------------------

@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    import jax
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield make
    jax.config.update("jax_enable_compilation_cache", was)


def test_one_program_search_holds_no_piece_of_the_whole_block(shape):
    import jax.numpy as jnp

    from elasticsearch_tpu.ops import scoring

    R, T, P, k = 8, 8, 32768, 10
    text = scoring.bm25_term_group_topk.lower(
        shape((F_TEXT, SLOTS_TEXT), jnp.float32),
        shape((NNZ_TEXT,), jnp.int32), shape((NNZ_TEXT,), jnp.float32),
        shape((SLOTS_TEXT,), jnp.bool_), None,
        shape((2 * R + 3 * T,), jnp.int32),
        R=R, T=T, P=P, D=SLOTS_TEXT, k=k, topk_block=8192).compile().as_text()
    # the gather's lowering cut all 64 rows into 128 column pieces
    assert f"f32[{F_TEXT},32768]" not in text
    # nor is there a copy of the query's rows: one loop over the real
    # rows, each sliced from the block inside the fusion that adds it
    assert f"f32[{R},{SLOTS_TEXT}]" not in text
    sliced = [ln for ln in text.splitlines()
              if f" = f32[1,{SLOTS_TEXT}]" in ln and " dynamic-slice(" in ln]
    assert len(sliced) == 1 and "while/body" in sliced[0]


@pytest.mark.parametrize("block", ["float32", "bfloat16"])
def test_score_program_reads_r_rows_for_v5e(shape, block):
    import jax.numpy as jnp

    from elasticsearch_tpu.ops import scoring

    R = 8
    compiled = scoring.bm25_score_hybrid_gather.lower(
        shape((F_TEXT, SLOTS_TEXT), jnp.dtype(block)),
        shape((R,), jnp.int32), shape((R,), jnp.float32),
        shape((NNZ_TEXT,), jnp.int32), shape((NNZ_TEXT,), jnp.float32),
        shape((1,), jnp.int32), shape((1,), jnp.int32),
        shape((1,), jnp.float32), P=8, D=SLOTS_TEXT).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    # the loop's body counted once: a row, the score vector in and out;
    # the gather form read 3.86 GB
    assert cost["bytes accessed"] < 0.5e9
    # under 1 MiB; the gather form held the pieces and an [R, D] copy, 316 MiB
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * MIB


# -- (b), (c) on the CPU -------------------------------------------------------

D, NNZ = 4096, 2048


def _block(dtype: str, seed: int):
    """(impact[F, D] in ``dtype`` with zeros where a term is absent, its
    float64 twin — the stored values, exactly)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    dense = rng.random((32, D)) * 2.0 + 0.25
    dense[rng.random((32, D)) < 0.6] = 0.0
    impact = jnp.asarray(dense, jnp.dtype(dtype))
    return impact, np.asarray(impact.astype(jnp.float32), np.float64)


def _rows(R: int, n_real: int, seed: int):
    """Duplicate-free sorted rows padded to R with -1, weights with 0 —
    what ``pack_dense_rows`` makes."""
    from elasticsearch_tpu.ops.scoring import pack_dense_rows

    rng = np.random.default_rng(seed)
    picked = rng.choice(32, size=n_real, replace=False)
    qrows, qrw = pack_dense_rows(
        {int(r): float(w) for r, w in zip(picked, rng.random(n_real) * 6 + 0.5)})
    assert qrows.shape == (R,) and (qrows[n_real:] == -1).all()
    assert len(set(qrows[:n_real].tolist())) == n_real
    return qrows, qrw


def _tail(seed: int, T: int = 2, P: int = 256):
    """A CSR tail: T runs of distinct documents each, padded postings."""
    rng = np.random.default_rng(seed)
    doc_ids = np.full(NNZ, D, np.int32)
    tfnorm = np.zeros(NNZ, np.float32)
    starts = np.arange(T, dtype=np.int32) * P
    lens = rng.integers(P // 2, P, T).astype(np.int32)
    for s, n in zip(starts, lens):
        doc_ids[s:s + n] = np.sort(rng.choice(D, size=n, replace=False))
        tfnorm[s:s + n] = rng.random(n).astype(np.float32) + 0.5
    ws = (rng.random(T) * 8 + 1).astype(np.float32)
    return doc_ids, tfnorm, starts, lens, ws, P


ROW_CASES = [(8, 1), (8, 3), (8, 8), (16, 9), (16, 16)]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("R,n_real", ROW_CASES)
def test_row_sum_is_the_float64_sum_to_f32_rounding(R, n_real, dtype):
    from elasticsearch_tpu.ops.scoring import bm25_score_hybrid_gather

    impact, stored = _block(dtype, seed=R + n_real)
    qrows, qrw = _rows(R, n_real, seed=7 * R + n_real)
    doc_ids, tfnorm, starts, lens, ws, P = _tail(seed=n_real)
    got = np.asarray(bm25_score_hybrid_gather(
        impact, qrows, qrw, doc_ids, tfnorm, starts, lens, ws, P=P, D=D))
    want = (qrw[:n_real].astype(np.float64)[:, None]
            * stored[qrows[:n_real]]).sum(axis=0)
    for s, n, w in zip(starts, lens, ws):
        np.add.at(want, doc_ids[s:s + n],
                  np.float64(w) * tfnorm[s:s + n].astype(np.float64))
    assert got.dtype == np.float32 and (want > 0).sum() > D // 4
    gap = np.abs(got - want) / np.maximum(want, 1e-30)
    assert gap.max() <= 1e-6
    # a document no row and no run touches scores exactly 0
    assert (got[want == 0] == 0).all()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("R,n_real", ROW_CASES)
def test_one_program_is_bitwise_the_staged_row_read(R, n_real, dtype):
    import jax.numpy as jnp

    from elasticsearch_tpu.ops.scoring import (
        bm25_score_hybrid_gather, bm25_term_group_topk, finish_topk,
        pack_term_group_words, topk_block_config, unpack_topk_result)

    impact, _ = _block(dtype, seed=R + n_real)
    qrows, qrw = _rows(R, n_real, seed=7 * R + n_real)
    doc_ids, tfnorm, starts, lens, ws, P = _tail(seed=n_real)
    live = np.ones(D, bool)
    live[np.random.default_rng(5).choice(D, size=300, replace=False)] = False
    live = jnp.asarray(live)
    k, blk = 10, topk_block_config()
    scores = bm25_score_hybrid_gather(impact, qrows, qrw, doc_ids, tfnorm,
                                      starts, lens, ws, P=P, D=D)
    want = np.asarray(finish_topk(scores, scores > 0, live, k=k,
                                  topk_block=blk)[0])
    got = np.asarray(bm25_term_group_topk(
        impact, doc_ids, tfnorm, live, None,
        pack_term_group_words(qrows, qrw, starts, lens, ws),
        R=R, T=len(ws), P=P, D=D, k=k, topk_block=blk))
    np.testing.assert_array_equal(got, want)  # i32 words: bitwise
    vals, _idx, total = unpack_topk_result(got, k)
    assert np.isfinite(vals).all() and total > k


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("R,n_real", ROW_CASES)
def test_selecting_sites_equal_the_index_form(R, n_real, dtype):
    """match_count_hybrid_gather and term_mask_hybrid_gather only select
    and compare: what ``impact[max(qrows, 0)]`` gave, they give."""
    from elasticsearch_tpu.ops.scoring import (match_count_hybrid_gather,
                                               match_count_segment,
                                               term_mask,
                                               term_mask_hybrid_gather)

    impact, _ = _block(dtype, seed=R + n_real)
    qrows, _qrw = _rows(R, n_real, seed=7 * R + n_real)
    doc_ids, _tfn, starts, lens, _ws, P = _tail(seed=n_real)
    old_rows = np.asarray(impact)[np.maximum(qrows, 0)]  # [R, D]
    old_present = (old_rows != 0) & (qrows >= 0)[:, None]

    tail_count = np.asarray(match_count_segment(doc_ids, starts, lens,
                                                P=P, D=D))
    got_count = np.asarray(match_count_hybrid_gather(
        impact, qrows, doc_ids, starts, lens, P=P, D=D))
    assert got_count.dtype == np.int32
    np.testing.assert_array_equal(
        got_count, old_present.sum(axis=0).astype(np.int32) + tail_count)

    tail_mask = np.asarray(term_mask(doc_ids, starts, lens, P=P, D=D))
    got_mask = np.asarray(term_mask_hybrid_gather(
        impact, qrows, doc_ids, starts, lens, P=P, D=D))
    assert got_mask.dtype == bool
    np.testing.assert_array_equal(got_mask,
                                  old_present.any(axis=0) | tail_mask)
    # padding rows read row 0 and must not count it
    assert got_count.max() <= n_real + len(starts)
