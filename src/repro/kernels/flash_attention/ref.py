"""Pure-jnp oracle for the flash attention kernel."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        window: int | None = None) -> jnp.ndarray:
    """q: (BH, Sq, hd); k/v: (BHkv, Sk, hd). GQA via head-group repeat."""
    bh, sq, hd = q.shape
    bh_kv, sk, _ = k.shape
    group = bh // bh_kv
    if group > 1:
        k = jnp.repeat(k, group, axis=0)
        v = jnp.repeat(v, group, axis=0)
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(hd)
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)) \
        .astype(q.dtype)


def paged_decode_attention_ref(q, k_new, v_new, k_pool, v_pool, layer,
                               tables, lengths) -> jnp.ndarray:
    """Oracle for the paged decode op: gather the layer's pages into a
    dense cache view, append the current token, and mask-and-softmax like
    dense decode.  q (B, H, hd); k_new/v_new (B, Hkv, hd);
    k_pool/v_pool (L, P, ps, W >= Hkv*hd); layer int; tables (B, npp) i32;
    lengths (B,) i32 tokens in the pool (the current token excluded)."""
    b, h, hd = q.shape
    hkv = k_new.shape[1]
    npp = tables.shape[1]
    ps = k_pool.shape[2]

    def dense(pool, new):                  # (B, npp*ps + 1, Hkv, hd)
        g = jnp.take(pool[layer], tables, axis=0)[..., :hkv * hd]
        g = g.reshape(b, npp * ps, hkv, hd)
        return jnp.concatenate([g, new[:, None].astype(g.dtype)], axis=1)

    k, v = dense(k_pool, k_new), dense(v_pool, v_new)
    group = h // hkv
    if group > 1:
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bhd,bchd->bhc", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(hd)
    kpos = jnp.arange(npp * ps + 1)[None, :]
    mask = (kpos < lengths[:, None]) | (kpos == npp * ps)
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhc,bchd->bhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


# pre-PR-6 name, kept importable
attention_ref = flash_attention_ref
