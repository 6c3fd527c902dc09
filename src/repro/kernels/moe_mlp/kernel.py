"""Fused grouped expert-MLP Pallas TPU kernel (grouped GEMM + activation
fusion — the paper's tensor-fusion technique on the MoE hot path).

Computes, per expert e over its capacity buffer:

    out[e] = (silu(x[e] @ wg[e]) * (x[e] @ wi[e])) @ wo[e]

Grid: (experts, token_blocks, ff_blocks); the ff axis is sequential and
the (bt, d) output tile accumulates in VMEM scratch — the (C, F) hidden
activation never exists in HBM.  This is simultaneously the grouped-GEMM
kernel: expert weight tiles are selected by the grid's expert index, so
one kernel serves both dense MLP (E=1) and MoE (E>1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_mlp.kernel import VMEM_LIMIT_BYTES, fit_ff_block


def _moe_mlp_kernel(x_ref, wg_ref, wi_ref, wo_ref, o_ref, acc_ref, *,
                    n_ff_blocks: int, swiglu: bool):
    jf = pl.program_id(2)

    @pl.when(jf == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0].astype(jnp.float32)                  # (bt, d)
    wi = wi_ref[0].astype(jnp.float32)                # (d, bf)
    h = jax.lax.dot_general(x, wi, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if swiglu:
        wg = wg_ref[0].astype(jnp.float32)
        g = jax.lax.dot_general(x, wg, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        h = (g * jax.nn.sigmoid(g)) * h               # silu(g) * h
    else:
        h = jax.nn.gelu(h)
    wo = wo_ref[0].astype(jnp.float32)                # (bf, d)
    acc_ref[...] += jax.lax.dot_general(h, wo, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(jf == n_ff_blocks - 1)
    def _finalize():
        o_ref[0, :, :] = acc_ref[...].astype(o_ref.dtype)


def moe_mlp_pallas(x, wg, wi, wo, *, swiglu: bool = True, bt: int = 128,
                   bf: int = 512, interpret: bool = False):
    """x: (E, C, d); wg/wi: (E, d, F); wo: (E, F, d). Returns (E, C, d)."""
    e, c, d = x.shape
    f = wi.shape[-1]
    bt = min(bt, c)
    bf = min(fit_ff_block(bf, bt, d, x.dtype.itemsize), f)
    pad_c = (-c) % bt
    pad_f = (-f) % bf
    if pad_c:
        x = jnp.pad(x, ((0, 0), (0, pad_c), (0, 0)))
    if pad_f:
        wi = jnp.pad(wi, ((0, 0), (0, 0), (0, pad_f)))
        wg = jnp.pad(wg, ((0, 0), (0, 0), (0, pad_f)))
        wo = jnp.pad(wo, ((0, 0), (0, pad_f), (0, 0)))
    nt, nf = x.shape[1] // bt, wi.shape[-1] // bf

    out = pl.pallas_call(
        functools.partial(_moe_mlp_kernel, n_ff_blocks=nf, swiglu=swiglu),
        grid=(e, nt, nf),
        in_specs=[
            pl.BlockSpec((1, bt, d), lambda ie, it, jf: (ie, it, 0)),
            pl.BlockSpec((1, d, bf), lambda ie, it, jf: (ie, 0, jf)),
            pl.BlockSpec((1, d, bf), lambda ie, it, jf: (ie, 0, jf)),
            pl.BlockSpec((1, bf, d), lambda ie, it, jf: (ie, jf, 0)),
        ],
        out_specs=pl.BlockSpec((1, bt, d), lambda ie, it, jf: (ie, it, 0)),
        out_shape=jax.ShapeDtypeStruct((e, x.shape[1], d), x.dtype),
        scratch_shapes=[pltpu.VMEM((bt, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(x, wg, wi, wo)
    return out[:, :c]
