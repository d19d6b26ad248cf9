"""Seeded image descriptors, made on the device in one jitted call.

ANN-Benchmarks' ``deep-image-96-angular`` holds DEEP1B descriptors: the
last layer of an image CNN, PCA-compressed to 96 dimensions and
L2-normalized. There is no network here for the real file, so this draws
a mixture with the same traits: rows around ``clusters`` centres, the
variance of the j-th dimension falling as (j + 1) ** -decay in the
centres and in the spread around them alike (the spectrum of a PCA
basis, largest component first), then normalized to unit length. With
isotropic noise in 96 dimensions every neighbour would be nearly as far
as every other and no ANN index would mean anything.

The slab comes out padded to the segment's slots, block by block, so the
peak on the device is the slab plus one block's temporaries. Queries are
further draws of the same law, rounded to ``decimals`` so that a request
is the size a client sends and parses back to the float32 the reference
uses.
"""
from __future__ import annotations

import numpy as np

from benchmarks.data.vectors import BLOCK, _key


def make_descriptors(n: int, slots: int, dims: int, seed: int, *,
                     clusters: int, decay: float, spread: float,
                     n_queries: int, decimals: int, device):
    """(f32[slots, dims] on ``device``, unit rows, rows >= n zero; float64
    [n_queries, dims] on the host, rounded)."""
    import jax
    import jax.numpy as jnp

    block = min(BLOCK, slots)
    if slots % block:
        raise ValueError(f"{slots} slots are not whole blocks of {block}")
    scale = jnp.asarray((np.arange(dims) + 1.0) ** (-0.5 * decay),
                        jnp.float32)

    def draw(key, cents, rows):
        ka, kn = jax.random.split(key)
        assign = jax.random.randint(ka, (rows,), 0, clusters)
        noise = jax.random.normal(kn, (rows, dims), jnp.float32)
        x = cents[assign] + jnp.float32(spread) * noise * scale
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    @jax.jit
    def make(key):
        kc, kb, kq = jax.random.split(key, 3)
        cents = jax.random.normal(kc, (clusters, dims), jnp.float32) * scale

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
