"""Token sampling: greedy / temperature / top-k / top-p."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def sample(logits, key, *, temperature: float = 0.0, top_k: int = 0,
           top_p: float = 1.0):
    """logits: (B, V) -> (B,) int32."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    lf = logits.astype(jnp.float32) / temperature
    if top_k > 0:
        kth = jax.lax.top_k(lf, top_k)[0][..., -1:]
        lf = jnp.where(lf < kth, -1e30, lf)
    if top_p < 1.0:
        sorted_l = jnp.sort(lf, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_l, axis=-1)
        csum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.sum(csum < top_p, axis=-1, keepdims=True)
        cutoff = jnp.take_along_axis(sorted_l, cutoff_idx, axis=-1)
        lf = jnp.where(lf < cutoff, -1e30, lf)
    return jax.random.categorical(key, lf, axis=-1).astype(jnp.int32)


def _sample_tokens(logits, lanes, temperature, n, key):
    """Sample one token for each of the first `n` of W lanes in one program.

    logits: (B, T, V), sampled at the last position; lanes: (W,) int32,
    lane i's logits row; temperature: (W,) float32; n: int32 scalar, the
    live lanes (lanes i >= n are padding); key: the PRNG key.  Returns
    `(tokens (W,) int32, all_finite, new_key)`.

    Token for token and key for key the same as the per-lane loop
    `key, k = split(key); sample(logits[lanes[i], -1:], k, temperature=t)`
    over the live lanes in order: the key splits once per live lane and
    never on padding.  Greedy rows take the argmax in the logits' own
    dtype, and the categorical draw runs only when some live row has a
    temperature.  `all_finite` is the NaN guard over the whole of
    `logits`.
    """
    live = jnp.arange(lanes.shape[0]) < n

    def split(key, on):
        nxt, k = jax.random.split(key)
        return jnp.where(on, nxt, key), k

    new_key, keys = jax.lax.scan(split, key, live, unroll=True)
    rows = logits[lanes, -1]                                  # (W, V)
    greedy = jnp.argmax(rows, axis=-1).astype(jnp.int32)
    hot = live & (temperature > 0)

    def draw(_):
        t = jnp.where(hot, temperature, 1.0)

        def one(k, row, t):
            # `sample`'s temperature branch on a (1, V) row
            lf = row[None].astype(jnp.float32) / t
            return jax.random.categorical(k, lf, axis=-1)[0]

        return jnp.where(hot, jax.vmap(one)(keys, rows, t), greedy)

    tokens = jax.lax.cond(hot.any(), draw, lambda _: greedy, None)
    return tokens, jnp.isfinite(logits).all(), new_key


# one executable per (W, logits shape, dtype), shared by every engine;
# profiles show it as `jit_sample_tokens`
_sample_tokens.__name__ = "sample_tokens"
sample_tokens = jax.jit(_sample_tokens)
