"""Fused dense gated-MLP (SwiGLU) Pallas TPU kernel.

The serving policy's ``fused_mlp`` flag maps onto this kernel: the whole
MLP block

    out = (silu(x @ wg) * (x @ wi)) @ wo        (swiglu)
    out = gelu(x @ wi) @ wo                     (plain gelu MLP)

runs as one kernel, so the (N, F) hidden activation never exists in HBM
(the paper's tensor-fusion technique applied to the projection hot path).

Grid: (token_blocks, ff_blocks); the ff axis is sequential and the
(bt, d) output tile accumulates in VMEM scratch.  This is the dense
single-expert sibling of the grouped ``moe_mlp`` kernel — dense serving
MLPs have no expert dim, so the grid drops to two axes and the weight
tiles are shared across all token blocks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Mosaic's default scoped-VMEM limit is 16 MiB; the MLP kernels ask for more
# and size their ff block to fit it (v5e has 128 MiB of VMEM per core).
VMEM_LIMIT_BYTES = 64 * 2**20


def fit_ff_block(bf: int, bt: int, d: int, itemsize: int) -> int:
    """Halve the ff block (not below 128) until the kernel's double-buffered
    tiles (x, the gate/up/down weight tiles, the output) and its f32
    accumulator take at most three quarters of VMEM_LIMIT_BYTES, leaving the
    rest to Mosaic's own scratch."""

    def footprint(bf):
        return 2 * itemsize * (2 * bt * d + 3 * d * bf) + 4 * bt * d

    while bf > 128 and footprint(bf) > VMEM_LIMIT_BYTES * 3 // 4:
        bf //= 2
    return bf


def _accumulate(o_ref, acc_ref, h, wo_ref, jf, n_ff_blocks):
    @pl.when(jf == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    wo = wo_ref[...].astype(jnp.float32)  # (bf, d)
    acc_ref[...] += jax.lax.dot_general(
        h, wo, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(jf == n_ff_blocks - 1)
    def _finalize():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _swiglu_mlp_kernel(
    x_ref, wg_ref, wi_ref, wo_ref, o_ref, acc_ref, *, n_ff_blocks: int
):
    jf = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)  # (bt, d)
    wi = wi_ref[...].astype(jnp.float32)  # (d, bf)
    h = jax.lax.dot_general(
        x, wi, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    wg = wg_ref[...].astype(jnp.float32)
    g = jax.lax.dot_general(
        x, wg, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    h = (g * jax.nn.sigmoid(g)) * h  # silu(g) * h
    _accumulate(o_ref, acc_ref, h, wo_ref, jf, n_ff_blocks)


def _gelu_mlp_kernel(x_ref, wi_ref, wo_ref, o_ref, acc_ref, *, n_ff_blocks: int):
    # no gate: wg never enters VMEM, halving up-projection weight traffic
    jf = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)
    wi = wi_ref[...].astype(jnp.float32)
    h = jax.lax.dot_general(
        x, wi, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    h = jax.nn.gelu(h)
    _accumulate(o_ref, acc_ref, h, wo_ref, jf, n_ff_blocks)


def fused_mlp_pallas(
    x,
    wg,
    wi,
    wo,
    *,
    swiglu: bool = True,
    bt: int = 128,
    bf: int = 512,
    interpret: bool = False,
):
    """x: (N, d); wg/wi: (d, F); wo: (F, d).  Returns (N, d).  With
    swiglu=False the gate is skipped entirely (wg may be None)."""
    n, d = x.shape
    f = wi.shape[-1]
    bt = min(bt, n)
    bf = min(fit_ff_block(bf, bt, d, x.dtype.itemsize), f)
    pad_n = (-n) % bt
    pad_f = (-f) % bf
    if pad_n:
        x = jnp.pad(x, ((0, pad_n), (0, 0)))
    if pad_f:
        wi = jnp.pad(wi, ((0, 0), (0, pad_f)))
        wo = jnp.pad(wo, ((0, pad_f), (0, 0)))
        if swiglu:
            wg = jnp.pad(wg, ((0, 0), (0, pad_f)))
    nt, nf = x.shape[0] // bt, wi.shape[-1] // bf

    x_spec = pl.BlockSpec((bt, d), lambda it, jf: (it, 0))
    up_spec = pl.BlockSpec((d, bf), lambda it, jf: (0, jf))
    down_spec = pl.BlockSpec((bf, d), lambda it, jf: (jf, 0))
    if swiglu:
        kernel = functools.partial(_swiglu_mlp_kernel, n_ff_blocks=nf)
        operands = (x, wg, wi, wo)
        in_specs = [x_spec, up_spec, up_spec, down_spec]
    else:
        kernel = functools.partial(_gelu_mlp_kernel, n_ff_blocks=nf)
        operands = (x, wi, wo)
        in_specs = [x_spec, up_spec, down_spec]

    out = pl.pallas_call(
        kernel,
        grid=(nt, nf),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bt, d), lambda it, jf: (it, 0)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], d), x.dtype),
        scratch_shapes=[pltpu.VMEM((bt, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(*operands)
    return out[:n]
