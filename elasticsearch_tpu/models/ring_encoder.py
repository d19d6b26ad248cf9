"""Sequence-parallel (ring attention) encode for the dual encoder.

Long-context passages blow up attention memory quadratically: at L tokens a
single chip holds [B, H, L, L] scores. This module runs the SAME dual
encoder (same param pytree, same numerics up to bf16 matmul order) with the
sequence dimension sharded over an ``('sp',)`` mesh axis:

- activations are [B, L/S, D] per device; LayerNorm/MLP/projections are
  position-wise, so they run locally with replicated params;
- attention is a RING: each device keeps its query block and passes its
  key/value/mask block around the 'sp' ring with ``lax.ppermute``,
  accumulating the exact softmax with the online (flash-attention style)
  max/sum rescaling — no [L, L] score matrix ever materializes, per-device
  peak is [B, H, L/S, L/S];
- the masked mean-pool is a local partial sum + one ``psum``; the final
  projection runs replicated, so every device returns the identical
  [B, embed_dim] output.

This is the 'sp' axis of the framework's tp/dp/sp story (SURVEY §2.12:
"sequence/ep-style sharding"; the reference has no model counterpart — ES
2.0 predates dense retrieval). Exactness: the ring accumulation computes
the same softmax as the dense mask-where attention (same masking, full
numerator/denominator), so outputs match `model.apply` to bf16 tolerance —
asserted by tests/unit/test_ring_encoder.py.
"""
from __future__ import annotations

import math
from typing import Any

import numpy as np

from elasticsearch_tpu.models.dual_encoder import DualEncoderConfig


def _jax():
    import jax
    import jax.numpy as jnp

    return jax, jnp


def build_sp_mesh(n_devices: int):
    import jax
    from jax.sharding import Mesh

    devs = jax.devices()[:n_devices]
    return Mesh(np.asarray(devs), ("sp",))


def _layer_norm(x, scale, bias, jnp):
    # flax LayerNorm numerics: stats in f32, eps 1e-6, then back to x.dtype
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    y = (xf - mu) / jnp.sqrt(var + 1e-6)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _ring_attention(q, k, v, mask_local, S, jnp, lax):
    """Exact softmax attention with K/V sharded over the 'sp' ring.

    q/k/v: [B, H, Lloc, Dh] (this device's blocks), mask_local: [B, Lloc].
    Returns [B, H, Lloc, Dh] = softmax(QK^T / sqrt(Dh), over the FULL L) V,
    via S ppermute hops with online max/sum rescaling.
    """
    B, H, Lloc, Dh = q.shape
    qf = q.astype(jnp.float32) / math.sqrt(Dh)
    perm = [(i, (i + 1) % S) for i in range(S)]
    neg = jnp.float32(-1e30)

    def step(carry, _):
        k_blk, v_blk, m_blk, m_acc, l_acc, o_acc = carry
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_blk.astype(jnp.float32))
        s = jnp.where(m_blk[:, None, None, :] > 0, s, neg)
        m_new = jnp.maximum(m_acc, s.max(-1))
        alpha = jnp.exp(m_acc - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l_acc * alpha + p.sum(-1)
        o_new = (o_acc * alpha[..., None]
                 + jnp.einsum("bhqk,bhkd->bhqd", p,
                              v_blk.astype(jnp.float32)))
        k_blk = lax.ppermute(k_blk, "sp", perm)
        v_blk = lax.ppermute(v_blk, "sp", perm)
        m_blk = lax.ppermute(m_blk, "sp", perm)
        return (k_blk, v_blk, m_blk, m_new, l_new, o_new), None

    m0 = jnp.full((B, H, Lloc), neg)
    l0 = jnp.zeros((B, H, Lloc), jnp.float32)
    o0 = jnp.zeros((B, H, Lloc, Dh), jnp.float32)
    (_, _, _, _, l_fin, o_fin), _ = lax.scan(
        step, (k, v, mask_local, m0, l0, o0), None, length=S)
    out = o_fin / jnp.maximum(l_fin[..., None], 1e-30)
    return out.astype(q.dtype)


def _forward_local(cfg: DualEncoderConfig, p: Any, ids_local, mask_local,
                   S: int, jnp, lax):
    """One device's slice of the encoder forward (params replicated).

    Mirrors models/dual_encoder.build_model layer by layer — every
    position-wise op runs on the local [B, Lloc, D] slice; attention is the
    ring; the pool is a psum. Cited parity test: test_ring_encoder.py.
    """
    dtype = cfg.dtype or jnp.bfloat16
    B, Lloc = ids_local.shape
    shard = lax.axis_index("sp")
    H, D = cfg.n_heads, cfg.d_model
    Dh = D // H

    x = p["tok_emb"]["embedding"].astype(dtype)[ids_local]
    # clip covers ring padding past max_len: those positions are mask-0,
    # their embedding never reaches the pool
    pos_ids = jnp.clip(shard * Lloc + jnp.arange(Lloc), 0, cfg.max_len - 1)
    x = x + p["pos_emb"]["embedding"].astype(dtype)[pos_ids][None, :, :]
    m = mask_local.astype(jnp.float32)

    for i in range(cfg.n_layers):
        blk = p[f"block_{i}"]
        h = _layer_norm(x, blk["ln1"]["scale"], blk["ln1"]["bias"], jnp)
        a = blk["attn"]

        def qkv(name):
            w = a[name]["kernel"].astype(dtype)  # [D, H, Dh]
            b = a[name]["bias"].astype(dtype)  # [H, Dh]
            y = jnp.einsum("bld,dhk->bhlk", h, w) + b[None, :, None, :]
            return y

        q, k, v = qkv("query"), qkv("key"), qkv("value")
        o = _ring_attention(q, k, v, mask_local, S, jnp, lax)
        wo = a["out"]["kernel"].astype(dtype)  # [H, Dh, D]
        attn_out = jnp.einsum("bhlk,hkd->bld", o, wo) \
            + a["out"]["bias"].astype(dtype)
        x = x + attn_out
        h = _layer_norm(x, blk["ln2"]["scale"], blk["ln2"]["bias"], jnp)
        h = h @ blk["wi"]["kernel"].astype(dtype) \
            + blk["wi"]["bias"].astype(dtype)
        import jax.nn as jnn

        h = jnn.gelu(h)  # approximate=True, matching flax nn.gelu
        h = h @ blk["wo"]["kernel"].astype(dtype) \
            + blk["wo"]["bias"].astype(dtype)
        x = x + h

    x = _layer_norm(x, p["ln_f"]["scale"], p["ln_f"]["bias"], jnp)
    # masked mean-pool: local partials + one psum each
    num = lax.psum(jnp.sum(x * m[:, :, None].astype(x.dtype), axis=1), "sp")
    den = lax.psum(jnp.sum(m, axis=1), "sp")
    pooled = num / jnp.maximum(den, 1.0)[:, None].astype(x.dtype)
    z = pooled @ p["proj"]["kernel"].astype(dtype) \
        + p["proj"]["bias"].astype(dtype)
    z = z.astype(jnp.float32)
    return z / jnp.maximum(jnp.linalg.norm(z, axis=-1, keepdims=True), 1e-6)


# jitted forward per (cfg, mesh): jax.jit's cache is keyed on function
# identity, so a fresh closure every call would re-trace (and re-COMPILE)
# the whole encoder per encode
_FWD_CACHE: dict = {}


def _jitted_fwd(cfg: DualEncoderConfig, mesh, S: int):
    jax, jnp = _jax()
    from jax import lax
    from jax.sharding import PartitionSpec as PS

    key = (cfg.vocab_size, cfg.max_len, cfg.d_model, cfg.n_heads,
           cfg.n_layers, cfg.d_ff, cfg.embed_dim, str(cfg.dtype),
           tuple(d.id for d in mesh.devices.flat), S)
    fn = _FWD_CACHE.get(key)
    if fn is None:
        fn = jax.jit(jax.shard_map(
            lambda p, i, m: _forward_local(cfg, p, i, m, S, jnp, lax),
            mesh=mesh,
            in_specs=(PS(), PS(None, "sp"), PS(None, "sp")),
            out_specs=PS(),
            check_vma=False,
        ))
        _FWD_CACHE[key] = fn
    return fn


def ring_encode(cfg: DualEncoderConfig, params, token_ids, attn_mask, mesh):
    """Sequence-parallel encode: f32[B, embed_dim], unit-norm, equal to
    `model.apply(params, ...)` up to bf16 tolerance.

    token_ids/attn_mask are host or device [B, L] with L <= cfg.max_len;
    L is right-padded (mask 0, clipped position ids) to a multiple of the
    mesh's 'sp' size before sharding.
    """
    jax, _jnp = _jax()
    from jax.sharding import NamedSharding, PartitionSpec as PS

    S = int(mesh.shape["sp"])
    ids = np.asarray(token_ids)
    msk = np.asarray(attn_mask, np.float32)
    B, L = ids.shape
    if L > cfg.max_len:
        raise ValueError(f"sequence {L} exceeds cfg.max_len {cfg.max_len}")
    Lp = ((L + S - 1) // S) * S
    if Lp != L:
        ids = np.pad(ids, ((0, 0), (0, Lp - L)))
        msk = np.pad(msk, ((0, 0), (0, Lp - L)))

    fwd = _jitted_fwd(cfg, mesh, S)
    seq_sh = NamedSharding(mesh, PS(None, "sp"))
    rep = NamedSharding(mesh, PS())
    # offbudget: per-call encode inputs + caller-owned model params (the
    # encoder is stateless here — weight residency belongs to the caller)
    pt = jax.device_put(  # tpulint: offbudget
        params["params"] if "params" in params else params, rep)
    return fwd(pt, jax.device_put(ids, seq_sh),  # tpulint: offbudget
               jax.device_put(msk, seq_sh))  # tpulint: offbudget
