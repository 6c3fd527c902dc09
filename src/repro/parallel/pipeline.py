"""GPipe-style pipeline parallelism over a named mesh axis via shard_map +
collective_permute (the paper's deep-pipeline architecture template,
Fig. 4, mapped onto jax-native constructs per DESIGN.md).

The model's repeated layer stack is split into `n_stages` contiguous
stages placed along the `pp` mesh axis; microbatches stream through with
a steady-state schedule of depth n_stages + n_micro - 1.  Double
buffering in the paper maps to XLA's overlap of the collective_permute
with the next microbatch's compute.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Params = Any


def split_stages(stacked_params: Params, n_stages: int) -> Params:
    """(L, ...) stacked layer params -> (n_stages, L/n_stages, ...)."""
    def re(x):
        l = x.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return x.reshape(n_stages, l // n_stages, *x.shape[1:])
    return jax.tree.map(re, stacked_params)


def pipeline_apply(layer_fn: Callable, stage_params: Params, x,
                   *, mesh: Mesh, axis: str = "pp"):
    """Run x (n_micro, mb, ...) through the pipeline on `axis`.

    layer_fn(params_slice, h) -> h applies this stage's layer block.
    stage_params leaves have leading dim n_stages (sharded over `axis`).
    Returns outputs in microbatch order, (n_micro, mb, ...).
    """
    n_stages = mesh.shape[axis]

    def staged(params, xs):
        # params: this stage's slice (leading dim 1); xs: all microbatches
        params = jax.tree.map(lambda a: a[0], params)
        stage = jax.lax.axis_index(axis)
        n_micro = xs.shape[0]
        total = n_micro + n_stages - 1
        buf = jnp.zeros_like(xs)

        def tick(t, carry):
            buf, cur = carry
            # stage 0 injects microbatch t (when valid)
            inject = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False)
            h_in = jnp.where(stage == 0, inject, cur)
            h_out = layer_fn(params, h_in)
            # last stage collects its result at position t - (n_stages-1)
            pos = t - (n_stages - 1)
            valid = (pos >= 0) & (stage == n_stages - 1)
            buf = jax.lax.cond(
                valid,
                lambda b: jax.lax.dynamic_update_index_in_dim(
                    b, h_out, jnp.clip(pos, 0, n_micro - 1), 0),
                lambda b: b, buf)
            # shift activations downstream
            nxt = jax.lax.ppermute(
                h_out, axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return buf, nxt

        buf, _ = jax.lax.fori_loop(
            0, total, tick, (buf, jnp.zeros_like(xs[0])))
        # broadcast the last stage's buffer to all stages
        out = jax.lax.psum(
            jnp.where(stage == n_stages - 1, buf, jnp.zeros_like(buf)),
            axis)
        return out

    pspec = jax.tree.map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(staged, mesh=mesh,
                       in_specs=(pspec, P()), out_specs=P(),
                       check_vma=False)
    return fn(stage_params, x)
