"""Plain float32 reference of a dense GQA decoder (InternLM2, SmolLM).

It follows the published architecture (pre-norm RMSNorm, rotary
positions on the first and second halves of each head, grouped-query
causal attention, SwiGLU MLP, final RMSNorm, output head or tied
embeddings) and reads the sizes from the benchmark's configuration file.
It imports nothing of the program.  The weights are the pytree the
benchmark made from the seed (`bench.weights`), in the layout the
program is served with; two conventions of that layout are mapped here:
a norm's weight is stored as `scale` with weight = 1 + scale, and the
layers are stacked on a leading axis.

`precision="fp8"` is the control: every matmul operand is rounded to
fp8 e4m3 (per-tensor scaled, float32 accumulation), the step below the
configuration's bfloat16 that a later change could be tempted to take.

It is the default `reference` of a configuration (`bench/harness.py`
says what a reference module provides).  It attends over the whole
padded sequence at once: the scores of 16 heads over 2048 positions
take 268 MB, which fits beside InternLM2-1.8B's weights; a configuration
with many more heads or positions brings a reference that works in
blocks.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 224.0  # reduce_precision's e4m3 (IEEE-style) saturates at 240
# every weight leaf `forward` reads, each the end of a leaf's path
READS = ("embed", "head", "final_norm/scale", "norm1/scale", "norm2/scale", "attn/wq", "attn/wk",
         "attn/wv", "attn/wo", "mlp/w_gate", "mlp/w_in", "mlp/w_out")


def _fp8(x):
    """Round to fp8 e4m3 with a per-tensor scale, back in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    q = jax.lax.reduce_precision(x / scale, exponent_bits=4, mantissa_bits=3)
    return q * scale


def _mm(a, b, precision: str):
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _rope(x, pos, theta):
    """x: (S, H, hd); rotate the first half against the second half."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(model: dict, w, tokens, precision: str = "f32"):
    """Logits (S, V) in float32 for one token sequence (S,)."""
    nh, kvh = model["n_heads"], model["kv_heads"]
    hd = model.get("head_dim") or model["d_model"] // nh
    eps, theta = model["norm_eps"], model["rope_theta"]
    s = tokens.shape[0]
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731

    x = f32(w["embed"])[tokens]
    (layers,) = [next(iter(seg.values())) for seg in w["segments"]]

    def layer(x, lp):
        lp = jax.tree.map(f32, lp)
        at = lp["attn"]
        h = _rms(x, lp["norm1"]["scale"], eps)
        q = _rope(_mm(h, at["wq"], precision).reshape(s, nh, hd), pos, theta)
        k = _rope(_mm(h, at["wk"], precision).reshape(s, kvh, hd), pos, theta)
        v = _mm(h, at["wv"], precision).reshape(s, kvh, hd)
        g = nh // kvh
        qg = q.reshape(s, kvh, g, hd).transpose(1, 2, 0, 3)  # (kvh, g, S, hd)
        kt = k.transpose(1, 2, 0)[:, None]                    # (kvh, 1, hd, S)
        sc = _mm(qg, kt, precision) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = _mm(p, v.transpose(1, 0, 2)[:, None], precision)  # (kvh, g, S, hd)
        o = o.transpose(2, 0, 1, 3).reshape(s, nh * hd)
        x = x + _mm(o, at["wo"], precision)
        h = _rms(x, lp["norm2"]["scale"], eps)
        mlp = lp["mlp"]
        a = jax.nn.silu(_mm(h, mlp["w_gate"], precision)) * _mm(h, mlp["w_in"], precision)
        return x + _mm(a, mlp["w_out"], precision), None

    x, _ = jax.lax.scan(layer, x, layers)
    x = _rms(x, f32(w["final_norm"]["scale"]), eps)
    head = f32(w["embed"]).T if model.get("tie_embeddings") else f32(w["head"])
    return _mm(x, head, precision)


def gap_check(forward):
    """`(served_gaps, lower_gaps)`, the output check that `bench/harness.py`
    asks of a reference module, for the reference whose logits are
    `forward(model, w, tokens, precision)`: (S, V) in float32 for one
    token sequence (S,), with every matmul operand in fp8 under
    `precision="fp8"`.  A reference of another architecture writes its
    own `forward` and takes these two from here."""

    @functools.partial(jax.jit, static_argnums=(0, 4))
    def gaps(model_items, w, tokens, targets, control: bool):
        """Per position: how far the reference's logit of `targets` lies
        below the reference's best.  With `control`, the target at each
        position is what the fp8 forward puts first instead."""
        model = dict(model_items)
        ref = forward(model, w, tokens, "f32")
        if control:
            targets = jnp.argmax(forward(model, w, tokens, "fp8"), axis=-1)
        picked = jnp.take_along_axis(ref, targets[:, None], axis=-1)[:, 0]
        return jnp.max(ref, axis=-1) - picked

    def served_gaps(model: dict, w, prompt, served, pad_to: int, control: bool = False) -> np.ndarray:
        """Gaps of each served token of one request (greedy): the reference
        runs once over prompt + served tokens, padded to `pad_to` so that one
        executable serves every request (causal attention keeps the padding
        out of every real position)."""
        prompt = np.asarray(prompt, np.int32)
        served = np.asarray(served, np.int32)
        seq = np.concatenate([prompt, served[:-1]])
        if len(seq) > pad_to:
            raise ValueError(f"request of {len(seq)} tokens exceeds the reference length {pad_to}")
        toks = np.zeros(pad_to, np.int32)
        toks[: len(seq)] = seq
        targets = np.zeros(pad_to, np.int32)
        first = len(prompt) - 1
        targets[first: first + len(served)] = served
        with jax.default_matmul_precision("highest"):
            g = gaps(_items(model), w, jnp.asarray(toks), jnp.asarray(targets), control)
        return np.asarray(g)[first: first + len(served)]

    def lower_gaps(model: dict, w, tokens):
        """The program `served_gaps` runs, lowered for weights `w` and a
        padded token vector `tokens` (arrays or `ShapeDtypeStruct`s), to
        compile without a chip."""
        with jax.default_matmul_precision("highest"):
            return gaps.lower(_items(model), w, tokens, tokens, False)

    return served_gaps, lower_gaps


def _items(model: dict) -> tuple:
    """The model's scalar sizes, hashable, as the gap program's static argument."""
    return tuple(sorted((k, v) for k, v in model.items() if not isinstance(v, (dict, list))))


served_gaps, lower_gaps = gap_check(forward)
