"""Fused RMSNorm(+residual) Pallas TPU kernel.

The serving policy's ``fused_norm`` flag maps onto this kernel: the
residual add and the RMSNorm that follows it run as ONE kernel, so the
summed residual stream makes a single HBM round-trip instead of three
(add out, norm in, norm out):

    s = x + res                 (residual variant only)
    y = rmsnorm(s) * (1 + scale)

Grid: (token_blocks,) — each step loads a (bt, d) row tile, reduces the
mean-of-squares on the VPU, and writes the normalized tile (plus the
summed stream for the residual variant).  Numerics follow
``models.common.rmsnorm``: the reduction and scaling happen in float32
and the result is cast back to the input dtype (agreement is to within
float32 rounding of the XLA-fused reference, ~1 ulp).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rmsnorm_kernel(x_ref, g_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)  # (bt, d)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    g = g_ref[...].astype(jnp.float32)  # (1, d)
    o_ref[...] = (y * (1.0 + g)).astype(o_ref.dtype)


def _rmsnorm_residual_kernel(x_ref, r_ref, g_ref, s_ref, o_ref, *, eps: float):
    s = x_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
    # the unfused reference adds in model dtype and norms the ROUNDED sum;
    # round-trip through the output dtype so numerics match it exactly
    s_out = s.astype(s_ref.dtype)
    s_ref[...] = s_out
    sf = s_out.astype(jnp.float32)
    var = jnp.mean(jnp.square(sf), axis=-1, keepdims=True)
    y = sf * jax.lax.rsqrt(var + eps)
    g = g_ref[...].astype(jnp.float32)
    o_ref[...] = (y * (1.0 + g)).astype(o_ref.dtype)


def fused_rmsnorm_pallas(
    x, scale, *, eps: float = 1e-6, bt: int = 256, interpret: bool = False
):
    """x: (N, d); scale: (d,).  Returns rmsnorm(x) * (1 + scale)."""
    n, d = x.shape
    bt = min(bt, n)
    pad_n = (-n) % bt
    if pad_n:
        x = jnp.pad(x, ((0, pad_n), (0, 0)))
    nt = x.shape[0] // bt
    g = scale.reshape(1, d)

    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((bt, d), lambda it: (it, 0)),
            pl.BlockSpec((1, d), lambda it: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bt, d), lambda it: (it, 0)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], d), x.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, g)
    return out[:n]


def fused_rmsnorm_residual_pallas(
    x, res, scale, *, eps: float = 1e-6, bt: int = 256, interpret: bool = False
):
    """x/res: (N, d); scale: (d,).  Returns (x + res,
    rmsnorm(x + res) * (1 + scale)) in one pass."""
    n, d = x.shape
    bt = min(bt, n)
    pad_n = (-n) % bt
    if pad_n:
        x = jnp.pad(x, ((0, pad_n), (0, 0)))
        res = jnp.pad(res, ((0, pad_n), (0, 0)))
    nt = x.shape[0] // bt
    g = scale.reshape(1, d)

    s, out = pl.pallas_call(
        functools.partial(_rmsnorm_residual_kernel, eps=eps),
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((bt, d), lambda it: (it, 0)),
            pl.BlockSpec((bt, d), lambda it: (it, 0)),
            pl.BlockSpec((1, d), lambda it: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bt, d), lambda it: (it, 0)),
            pl.BlockSpec((bt, d), lambda it: (it, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((x.shape[0], d), x.dtype),
            jax.ShapeDtypeStruct((x.shape[0], d), x.dtype),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, res, g)
    return s[:n], out[:n]
