"""Seeded dense vectors, made on the device in one jitted call.

A clustered Gaussian mixture (the argument of ``bench.make_sift_node``:
neighbours inside a cluster are separated by more than bf16 resolves, so
exact search has one right answer). The slab comes out already padded to
the segment's slots, block by block, so the peak on the device is the slab
plus one block's temporaries. Queries are further draws from the same
mixture, rounded to ``decimals`` so that a request's JSON is the size a
client would send and parses back to the same float32.
"""
from __future__ import annotations

import numpy as np

BLOCK = 65536


def _key(seed: int):
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def make_vectors(n: int, slots: int, dims: int, seed: int, *, clusters: int,
                 spread: float, n_queries: int, decimals: int, device):
    """(f32[slots, dims] on ``device`` with rows >= n zero, float64
    [n_queries, dims] on the host, rounded)."""
    import jax
    import jax.numpy as jnp

    block = min(BLOCK, slots)
    if slots % block:
        raise ValueError(f"{slots} slots are not whole blocks of {block}")

    def draw(key, cents, rows):
        ka, kn = jax.random.split(key)
        assign = jax.random.randint(ka, (rows,), 0, clusters)
        noise = jax.random.normal(kn, (rows, dims), jnp.float32)
        return cents[assign] + jnp.float32(spread) * noise

    @jax.jit
    def make(key):
        kc, kb, kq = jax.random.split(key, 3)
        cents = jax.random.normal(kc, (clusters, dims), jnp.float32)

        def one(b):
            rows = draw(jax.random.fold_in(kb, b), cents, block)
            live = (b * block + jnp.arange(block)) < n
            return jnp.where(live[:, None], rows, 0.0)

        slab = jax.lax.map(one, jnp.arange(slots // block))
        return slab.reshape(slots, dims), draw(kq, cents, n_queries)

    with jax.default_device(device):
        slab, queries = make(_key(seed))
    queries = np.round(np.asarray(queries, np.float64), int(decimals))
    return slab, queries
