"""The cells' widest programs, compiled for a described v5e with no chip
attached (on-chip-measurement, section 2): the 960-d exact kNN scan over
1,048,576 slots and the match scoring over 4,194,304 slots with 2^27
postings. A shape the chip's compiler refuses is found here, before a
chip call. A compile that passes is not a chip run and gives no time.

The topology is described inside a fixture, never at import, and every
such test of the benchmark lives in this one file."""
import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

GIB = 1 << 30
SLOTS_TEXT, NNZ = 1 << 22, 1 << 27
SLOTS_VEC, DIMS = 1 << 20, 960


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


def fits(compiled, resident_gib):
    m = compiled.memory_analysis()
    total = m.temp_size_in_bytes + m.argument_size_in_bytes \
        + m.output_size_in_bytes
    assert total < (16 - resident_gib) * GIB, (
        f"{total / GIB:.2f} GiB beside {resident_gib} GiB resident")


@pytest.mark.parametrize("use_bf16,k", [(False, 10), (True, 40)])
def test_knn_960_scan_compiles_for_v5e(shape, use_bf16, k):
    import jax.numpy as jnp

    from elasticsearch_tpu.ops import knn

    compiled = knn.knn_topk.lower(
        shape((8, DIMS), jnp.float32), shape((SLOTS_VEC, DIMS), jnp.float32),
        shape((SLOTS_VEC,), jnp.bool_), k=k, metric="l2_norm",
        use_bf16=use_bf16).compile()
    fits(compiled, resident_gib=4)      # the executor's second slab


def test_knn_960_rescore_compiles_for_v5e(shape):
    import jax.numpy as jnp

    from elasticsearch_tpu.ops import knn

    knn.exact_rescore_topk.lower(
        shape((8, DIMS), jnp.float32), shape((SLOTS_VEC, DIMS), jnp.float32),
        shape((8, 40), jnp.float32), shape((8, 40), jnp.int32),
        metric="l2_norm").compile()


def test_match_over_4m_slots_compiles_for_v5e(shape):
    import jax.numpy as jnp

    from elasticsearch_tpu.ops import scoring

    compiled = scoring.bm25_score_hybrid_gather.lower(
        shape((64, SLOTS_TEXT), jnp.float32), shape((8,), jnp.int32),
        shape((8,), jnp.float32), shape((NNZ,), jnp.int32),
        shape((NNZ,), jnp.float32), shape((16,), jnp.int32),
        shape((16,), jnp.int32), shape((16,), jnp.float32),
        P=1 << 17, D=SLOTS_TEXT).compile()
    fits(compiled, resident_gib=3)      # the other copy of block + postings


def test_batched_match_block_and_topk_compile_for_v5e(shape):
    """256 bodies: the [256, 64] x [64, 4M] impact product and a top-k
    over the [256, 4M] scores it leaves (4 GiB of them)."""
    import jax
    import jax.numpy as jnp

    from elasticsearch_tpu.ops import scoring

    def batched(qw, impact):
        return jax.lax.top_k(scoring._dense_dot(qw, impact, "highest"), 10)

    compiled = jax.jit(batched).lower(
        shape((256, 64), jnp.float32),
        shape((64, SLOTS_TEXT), jnp.float32)).compile()
    fits(compiled, resident_gib=5)
