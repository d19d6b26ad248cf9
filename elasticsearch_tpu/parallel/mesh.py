"""Device-mesh construction for the distributed layers.

Reference counterpart: none — this replaces the *deployment topology* of
org/elasticsearch/cluster/routing/ (shards spread over nodes connected by
netty transport) with a `jax.sharding.Mesh`. Shards map to devices along a
``shard`` axis; search collectives (all_gather of per-shard top-k, psum of
agg partials / term stats) ride ICI instead of the transport layer.

Two mesh flavors:

- ``shard_mesh(n)``: 1-D ('shard',) mesh for search/indexing data placement.
- ``training_mesh(n)``: 2-D ('dp', 'tp') mesh for the dual-encoder model
  (models/dual_encoder.py) — batch data-parallel × tensor-parallel, the
  standard TPU layout where tp collectives stay on the fastest ICI axis.

Both accept fewer devices than requested shards by wrapping (multiple
shards per device), mirroring ES packing multiple shards per node.
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np


def _jax():
    import jax

    return jax


def shard_mesh(n_shards: Optional[int] = None, devices: Optional[Sequence] = None):
    """1-D Mesh over ('shard',). Uses min(n_shards, n_devices) devices."""
    jax = _jax()
    from jax.sharding import Mesh

    devs = list(devices) if devices is not None else jax.devices()
    n = len(devs) if n_shards is None else min(n_shards, len(devs))
    return Mesh(np.asarray(devs[:n]), ("shard",))


def training_mesh(n_devices: Optional[int] = None, devices: Optional[Sequence] = None,
                  tp: Optional[int] = None):
    """2-D Mesh over ('dp', 'tp').

    tp defaults to the largest power of two ≤ min(n, 4) that divides n —
    keeps tensor-parallel groups small (tp collectives are latency-bound)
    while giving data parallelism the rest.
    """
    jax = _jax()
    from jax.sharding import Mesh

    devs = list(devices) if devices is not None else jax.devices()
    n = min(n_devices, len(devs)) if n_devices is not None else len(devs)
    devs = devs[:n]
    if tp is None:
        tp = 1
        while tp * 2 <= min(n, 4) and n % (tp * 2) == 0:
            tp *= 2
    assert n % tp == 0, f"tp={tp} must divide n={n}"
    return Mesh(np.asarray(devs).reshape(n // tp, tp), ("dp", "tp"))


def mesh_size(mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))
