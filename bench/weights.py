"""Random weights from the seed, made on the device in one jitted call.

The layout is the program's parameter pytree (its shapes are read with
`jax.eval_shape` of the program's own initialiser, which allocates
nothing); the values are drawn here, leaf by leaf inside one program, in
the dtype they are served in.  The same seed gives the same weights, so
the reference can make them again after the program's state is freed.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key from any non-negative integer seed (wider than 32 bits
    is fine): the seed is hashed to two 32-bit words."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, dtype=np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32), impl="threefry2x32")


VECTOR_STD = 0.1


def _std(path: str, shape: tuple, n_layers: int) -> float:
    """The spread of one leaf: layers are stacked on a leading axis of
    every leaf under `segments`."""
    if path.endswith("scale"):
        return 0.1                      # norm weights spread around 1
    if path.startswith("embed") or path.startswith("head"):
        return 0.02
    if len(shape) == 1 + path.startswith("segments"):
        # a vector (per layer), such as a QKV bias or a router's selection
        # bias: a tenth of the unit-scale activations it is added to
        return VECTOR_STD
    fan_in = shape[-2]
    std = 1.0 / math.sqrt(fan_in)
    if path.endswith("wo") or path.endswith("w_out"):
        std /= math.sqrt(2 * n_layers)
    return std


def leaf_paths(tree) -> list[str]:
    """The path of each leaf of a parameter pytree, keys joined by `/`."""
    return ["/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


@functools.lru_cache(maxsize=4)
def _weights_fn(cfg, device):
    """One jitted program that draws every leaf of `cfg`'s params."""
    from repro.models import api

    shapes = jax.eval_shape(lambda: api.init_params(cfg, jax.random.PRNGKey(0)))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = leaf_paths(shapes)

    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, path, (_, sd) in zip(keys, paths, leaves):
            std = _std(path, sd.shape, cfg.n_layers)
            out.append((jax.random.normal(k, sd.shape, jnp.float32) * std).astype(sd.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build, out_shardings=jax.sharding.SingleDeviceSharding(device))


def make(cfg, seed: int, device=None):
    """The program's params for `cfg`, random from `seed`, on `device`."""
    return _weights_fn(cfg, device or jax.devices()[0])(seed_key(seed))
