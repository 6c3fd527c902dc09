"""Plain float32 reference of a dense GQA decoder with a bias on its
query, key and value projections (Qwen2.5), for the tests' second tiny
configuration.

It is `bench/reference.py`'s decoder with `h @ W + b` in place of
`h @ W` for q, k and v, and imports nothing of the program; the check
around its `forward` is the dense reference's (`gap_check`).  The tests
copy it into their copy of the benchmark as a file of its own, which the
configuration names by its `"reference"` key.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench import reference as dense

READS = dense.READS + ("attn/bq", "attn/bk", "attn/bv")


def forward(model: dict, w, tokens, precision: str = "f32"):
    """Logits (S, V) in float32 for one token sequence (S,)."""
    nh, kvh = model["n_heads"], model["kv_heads"]
    hd = model.get("head_dim") or model["d_model"] // nh
    eps, theta = model["norm_eps"], model["rope_theta"]
    s = tokens.shape[0]
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    mm = functools.partial(dense._mm, precision=precision)

    x = f32(w["embed"])[tokens]
    (layers,) = [next(iter(seg.values())) for seg in w["segments"]]

    def layer(x, lp):
        lp = jax.tree.map(f32, lp)
        at = lp["attn"]
        h = dense._rms(x, lp["norm1"]["scale"], eps)
        q = dense._rope((mm(h, at["wq"]) + at["bq"]).reshape(s, nh, hd), pos, theta)
        k = dense._rope((mm(h, at["wk"]) + at["bk"]).reshape(s, kvh, hd), pos, theta)
        v = (mm(h, at["wv"]) + at["bv"]).reshape(s, kvh, hd)
        g = nh // kvh
        qg = q.reshape(s, kvh, g, hd).transpose(1, 2, 0, 3)
        sc = mm(qg, k.transpose(1, 2, 0)[:, None]) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = mm(p, v.transpose(1, 0, 2)[:, None]).transpose(2, 0, 1, 3).reshape(s, nh * hd)
        x = x + mm(o, at["wo"])
        h = dense._rms(x, lp["norm2"]["scale"], eps)
        mlp = lp["mlp"]
        a = jax.nn.silu(mm(h, mlp["w_gate"])) * mm(h, mlp["w_in"])
        return x + mm(a, mlp["w_out"]), None

    x, _ = jax.lax.scan(layer, x, layers)
    x = dense._rms(x, f32(w["final_norm"]["scale"]), eps)
    head = f32(w["embed"]).T if model.get("tie_embeddings") else f32(w["head"])
    return mm(x, head)


served_gaps, lower_gaps = dense.gap_check(forward)
