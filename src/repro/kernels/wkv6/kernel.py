"""RWKV6 (Finch) WKV recurrence as a chunked Pallas TPU kernel.

Per head (k-dim i, v-dim j), with data-dependent per-channel decay w_t:

    o_t[j]   = sum_i r_t[i] * (S_{t-1}[i,j] + u[i] k_t[i] v_t[j])
    S_t[i,j] = w_t[i] * S_{t-1}[i,j] + k_t[i] v_t[j]

Grid: (batch*heads, time_chunks); the chunk axis is sequential and the
(D, D) state matrix lives in VMEM scratch.  Within a chunk the recurrence
is evaluated in closed form (GLA-style): pairwise decay factors
exp(Lp[t]-L[s]) have non-positive exponents, so the chunked form is exact
and overflow-safe, and all FLOPs are MXU matmuls rather than a hidden
sequential loop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, o_ref, s_ref,
                *, chunk: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        s_ref[...] = s0_ref[0].astype(jnp.float32)

    r = r_ref[0].astype(jnp.float32)          # (C, D)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)        # log decay, <= 0
    u = u_ref[0].astype(jnp.float32)          # (1, D) bonus
    state = s_ref[...]                        # (D, Dv)

    c = chunk
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    # inclusive prefix sum over time as a lower-triangular matmul
    # (Mosaic has no cumsum)
    L = jax.lax.dot_general(jnp.where(row >= col, 1.0, 0.0), lw,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    Lp = L - lw                               # exclusive
    # inter-chunk
    o = jax.lax.dot_general(r * jnp.exp(Lp), state,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # intra-chunk: A[t,s] = sum_i r[t,i] k[s,i] exp(Lp[t,i]-L[s,i]), s<t
    P = jnp.exp(jnp.clip(Lp[:, None, :] - L[None, :, :], -60.0, 0.0))
    tmask = row > col
    A = jnp.sum(r[:, None, :] * k[None, :, :] * P, axis=-1)
    A = jnp.where(tmask, A, 0.0)
    diag = jnp.sum(r * u * k, axis=1)         # (C,)
    A = A + jnp.where(row == col, diag[:, None], 0.0)
    o = o + jax.lax.dot_general(A, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    o_ref[0, :, :] = o.astype(o_ref.dtype)
    # carry state
    l_end = L[c - 1:c]                        # (1, D)
    # the chunk's total log decay as a (D, 1) column: lw^T @ ones
    decay_all = jnp.exp(jax.lax.dot_general(
        lw, jnp.ones((c, 1), jnp.float32), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32))
    decay_tail = jnp.exp(jnp.clip(l_end - L, -60.0, 0.0))
    s_new = state * decay_all + jax.lax.dot_general(
        (k * decay_tail), v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    s_ref[...] = s_new


def wkv6_pallas(r, k, v, logw, u, s0, *, chunk: int = 64,
                interpret: bool = False):
    """r/k/v/logw: (BH, S, D); u: (BH, 1, D); s0: (BH, D, Dv).
    Returns (o (BH, S, Dv), s_final is NOT returned — use ref for state).
    """
    bh, s, d = r.shape
    dv = v.shape[-1]
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        z = lambda t: jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
        r, k, v = z(r), z(k), z(v)
        logw = jnp.pad(logw, ((0, 0), (0, pad), (0, 0)))
    nc = r.shape[1] // chunk

    out = pl.pallas_call(
        functools.partial(_wkv_kernel, chunk=chunk),
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, d), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, d), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dv), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, d), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, d), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((1, d, dv), lambda b, c: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, dv), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, r.shape[1], dv), r.dtype),
        scratch_shapes=[pltpu.VMEM((d, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, logw, u, s0)
    return out[:, :s]
