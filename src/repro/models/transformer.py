"""Decoder-only transformer family: covers h2o-danube (SWA), smollm,
internlm2, qwen2.5 (QKV bias), mixtral (MoE+SWA), deepseek-v3 (MLA + MoE
shared/routed + MTP), qwen2-vl (M-RoPE + vision-stub prefix).

Pure JAX; params are nested dicts; repeated layers are stacked on a
leading axis and executed with lax.scan (MaxText-style) for compile-time
sanity at 61-64 layers.  KV caches support plain, sliding-window (ring)
and MLA-latent layouts.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from .common import (apply_mrope, apply_norm, apply_norm_residual,
                     apply_rope, attention, attn_einsum, cross_entropy,
                     dense_init, embed_init, init_norm, maybe_remat,
                     mlp_block)
from .config import ModelConfig

Params = Any


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_attn(cfg: ModelConfig, key) -> Params:
    ks = jax.random.split(key, 8)
    d, qd, kvd, hd = cfg.d_model, cfg.q_dim, cfg.kv_dim, cfg.hd
    pd = cfg.jparam_dtype
    out_scale = 0.02 / math.sqrt(2 * cfg.n_layers)
    if cfg.use_mla:
        rd, qr, kvr = cfg.mla_rope_dim, cfg.mla_q_rank, cfg.mla_kv_rank
        return {
            "wdq": dense_init(ks[0], (d, qr), pd),
            "q_norm": {"scale": jnp.zeros((qr,), pd)},
            "wuq": dense_init(ks[1], (qr, cfg.n_heads * (hd + rd)), pd),
            "wdkv": dense_init(ks[2], (d, kvr + rd), pd),
            "kv_norm": {"scale": jnp.zeros((kvr,), pd)},
            "wuk": dense_init(ks[3], (kvr, cfg.n_heads * hd), pd),
            "wuv": dense_init(ks[4], (kvr, cfg.n_heads * hd), pd),
            "wo": dense_init(ks[5], (qd, d), pd, scale=out_scale),
        }
    p = {
        "wq": dense_init(ks[0], (d, qd), pd),
        "wk": dense_init(ks[1], (d, kvd), pd),
        "wv": dense_init(ks[2], (d, kvd), pd),
        "wo": dense_init(ks[3], (qd, d), pd, scale=out_scale),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((qd,), pd)
        p["bk"] = jnp.zeros((kvd,), pd)
        p["bv"] = jnp.zeros((kvd,), pd)
    return p


def _init_mlp(cfg: ModelConfig, key, d_ff: int | None = None,
              mult: int = 1) -> Params:
    ks = jax.random.split(key, 3)
    d, f = cfg.d_model, (d_ff or cfg.d_ff) * mult
    pd = cfg.jparam_dtype
    out_scale = 0.02 / math.sqrt(2 * cfg.n_layers)
    p = {"w_in": dense_init(ks[0], (d, f), pd),
         "w_out": dense_init(ks[1], (f, d), pd, scale=out_scale)}
    if cfg.swiglu:
        p["w_gate"] = dense_init(ks[2], (d, f), pd)
    return p


def _init_moe(cfg: ModelConfig, key) -> Params:
    ks = jax.random.split(key, 5)
    d, f, e = cfg.d_model, cfg.routed_ff, cfg.n_experts
    pd = cfg.jparam_dtype
    out_scale = 0.02 / math.sqrt(2 * cfg.n_layers)
    p = {
        "router": dense_init(ks[0], (d, e), pd),
        "experts_in": dense_init(ks[1], (e, d, f), pd),
        "experts_out": dense_init(ks[2], (e, f, d), pd, scale=out_scale),
    }
    if cfg.swiglu:
        p["experts_gate"] = dense_init(ks[3], (e, d, f), pd)
    if cfg.n_shared_experts:
        p["shared"] = _init_mlp(cfg, ks[4], d_ff=cfg.routed_ff,
                                mult=cfg.n_shared_experts)
    return p


def _init_layer(cfg: ModelConfig, key, kind: str) -> Params:
    ks = jax.random.split(key, 4)
    p = {"norm1": init_norm(cfg, ks[0]),
         "attn": _init_attn(cfg, ks[1]),
         "norm2": init_norm(cfg, ks[2])}
    if kind == "moe":
        p["moe"] = _init_moe(cfg, ks[3])
    else:
        p["mlp"] = _init_mlp(cfg, ks[3])
    return p


def layer_segments(cfg: ModelConfig) -> list[tuple[str, int]]:
    """[(layer_kind, count)] — contiguous runs of identical structure."""
    if cfg.use_moe and cfg.first_dense_layers:
        return [("dense", cfg.first_dense_layers),
                ("moe", cfg.n_layers - cfg.first_dense_layers)]
    if cfg.use_moe:
        return [("moe", cfg.n_layers)]
    return [("dense", cfg.n_layers)]


def init_params(cfg: ModelConfig, key) -> Params:
    keys = jax.random.split(key, 8)
    pd = cfg.jparam_dtype
    params: Params = {
        "embed": embed_init(keys[0], (cfg.vocab, cfg.d_model), pd),
        "final_norm": init_norm(cfg, keys[1]),
        "segments": [],
    }
    if not cfg.tie_embeddings:
        params["head"] = dense_init(keys[2], (cfg.d_model, cfg.vocab), pd,
                                    scale=0.02)
    kseg = jax.random.split(keys[3], len(layer_segments(cfg)))
    for (kind, count), k in zip(layer_segments(cfg), kseg):
        lkeys = jax.random.split(k, count)
        stacked = jax.vmap(lambda kk: _init_layer(cfg, kk, kind))(lkeys)
        params["segments"].append({"kind_" + kind: stacked})
    if cfg.mtp:
        params["mtp"] = {
            "proj": dense_init(keys[4], (2 * cfg.d_model, cfg.d_model), pd),
            "norm": init_norm(cfg, keys[5]),
            "layer": _init_layer(cfg, keys[6], "dense"),
        }
    return params


def segment_kind(seg: Params) -> str:
    return next(iter(seg.keys())).removeprefix("kind_")


def segment_params(seg: Params) -> Params:
    return next(iter(seg.values()))


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _mesh_axis_names() -> tuple:
    return tuple(jax.sharding.get_abstract_mesh().axis_names)


def _mesh_axis_size(name: str) -> int:
    return int(jax.sharding.get_abstract_mesh().shape.get(name, 1))


def _wsc(x, *spec):
    """with_sharding_constraint if a mesh is visible; no-op otherwise."""
    names = _mesh_axis_names()
    if "model" not in names:
        return x
    from jax.sharding import PartitionSpec as _P
    fixed = tuple(s if (s is None or all(
        n in names for n in (s if isinstance(s, tuple) else (s,))))
        else None for s in spec)
    return jax.lax.with_sharding_constraint(x, _P(*fixed))


def moe_block_grouped(cfg: ModelConfig, p: Params, x):
    """§Perf variant: two-hop expert dispatch.

    The naive scatter into an expert-sharded buffer forces SPMD to
    all-gather the whole token stream (data-dependent routing is opaque
    to the partitioner).  Instead: (1) group tokens by their DATA shard
    and scatter into per-group capacity buffers — entirely shard-local;
    (2) transpose (G, E, cap, d) -> (E, G*cap, d), an explicit layout
    change the partitioner lowers to ONE all-to-all of the routed
    activations; (3) EP expert compute; (4) inverse all-to-all + local
    combine.  Collective volume drops from O(tokens x d x devices) to
    O(tokens x d x top_k x cf)."""
    bsz, s, d = x.shape
    n = bsz * s
    g = cfg.moe_groups
    assert g > 0 and n % g == 0, (n, g)
    m = n // g
    k, e = cfg.top_k, cfg.n_experts
    dp = ("pod", "data") if "pod" in _mesh_axis_names() else "data"
    xf = x.reshape(g, m, d)
    logits = (xf @ p["router"].astype(jnp.float32)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    w, idx = jax.lax.top_k(probs, k)                        # (g, m, k)
    w = (w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)).astype(cfg.jdtype)

    cap = int(math.ceil(m * k / e * cfg.capacity_factor))
    cap = max(8, min(cap, m))
    cap = (cap + 7) // 8 * 8

    flat_idx = idx.reshape(g, m * k)
    onehot = jax.nn.one_hot(flat_idx, e, dtype=jnp.int32)   # (g, m*k, e)
    pos = jnp.cumsum(onehot, axis=1) - 1
    slot = jnp.take_along_axis(pos, flat_idx[..., None], 2)[..., 0]
    keep = slot < cap
    slot = jnp.where(keep, slot, cap - 1)

    xrep = jnp.repeat(xf, k, axis=1)                        # (g, m*k, d)
    vals = jnp.where(keep[..., None], xrep, 0).astype(cfg.jdtype)
    vals = _wsc(vals, dp, None, None)
    gix = jnp.arange(g)[:, None]
    buf = jnp.zeros((g, e, cap, d), cfg.jdtype)
    buf = buf.at[gix, flat_idx, slot].add(vals)             # shard-local
    buf = _wsc(buf, dp, None, None, None)

    # hop 2: regroup expert-major — ONE all-to-all
    bufe = buf.transpose(1, 0, 2, 3).reshape(e, g * cap, d)
    bufe = _wsc(bufe, "model", None, None)
    h = jnp.einsum("ecd,edf->ecf", bufe, p["experts_in"].astype(cfg.jdtype))
    if cfg.swiglu:
        gg = jnp.einsum("ecd,edf->ecf", bufe,
                        p["experts_gate"].astype(cfg.jdtype))
        h = jax.nn.silu(gg) * h
    else:
        h = jax.nn.gelu(h)
    oute = jnp.einsum("ecf,efd->ecd", h,
                      p["experts_out"].astype(cfg.jdtype))
    oute = _wsc(oute, "model", None, None)
    outg = oute.reshape(e, g, cap, d).transpose(1, 0, 2, 3)
    outg = _wsc(outg, dp, None, None, None)

    gathered = outg[gix, flat_idx, slot]                    # shard-local
    gathered = jnp.where(keep[..., None], gathered, 0)
    combined = (gathered.reshape(g, m, k, d)
                * w[..., None]).sum(2).astype(cfg.jdtype)
    y = combined.reshape(bsz, s, d)
    if cfg.n_shared_experts:
        y = y + mlp_block(cfg, p["shared"], x)
    return y


def moe_block_shard_map(cfg: ModelConfig, p: Params, x):
    """§Perf variant: EXPLICIT expert parallelism.

    pjit cannot turn a data-dependent scatter into routed communication
    (it all-gathers the token stream: the dominant collective term in the
    deepseek-v3 train baseline).  shard_map makes the routing explicit:
    tokens are fully sharded over (dp x model); each device builds local
    per-expert capacity buffers (zero communication), ONE
    lax.all_to_all ships each expert's rows to its owner (volume =
    tokens x d x top_k x cf / devices), local expert GEMMs run, and the
    inverse all_to_all returns the outputs.
    """
    from jax.sharding import PartitionSpec as _P

    mesh = jax.sharding.get_abstract_mesh()
    names = tuple(mesh.axis_names)
    ep_axes = tuple(a for a in ("data", "model") if a in names)
    all_axes = tuple(a for a in ("pod", "data", "model") if a in names)
    n_ep = 1
    for a in ep_axes:
        n_ep *= int(mesh.shape[a])
    n_tot = 1
    for a in all_axes:
        n_tot *= int(mesh.shape[a])
    bsz, s, d = x.shape
    n = bsz * s
    k, e = cfg.top_k, cfg.n_experts
    if e % n_ep or n % n_tot:
        return moe_block(cfg.replace(moe_shard_map=False), p, x)
    el = e // n_ep
    nl = n // n_tot
    cap_l = max(1, int(math.ceil(nl * k / e * cfg.capacity_factor)))

    dt = cfg.jdtype

    def local_fn(xl, router, win, wgate, wout):
        # xl: (nl, d); win/wgate: (el, d, f); wout: (el, f, d)
        logits = (xl @ router.astype(jnp.float32)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, -1)
        w, idx = jax.lax.top_k(probs, k)                    # (nl, k)
        w = (w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)).astype(dt)
        flat_idx = idx.reshape(-1)
        onehot = jax.nn.one_hot(flat_idx, e, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - 1
        slot = jnp.take_along_axis(pos, flat_idx[:, None], 1)[:, 0]
        keep = slot < cap_l
        slot = jnp.where(keep, slot, cap_l - 1)
        xrep = jnp.repeat(xl, k, axis=0)
        buf = jnp.zeros((e, cap_l, d), dt)
        buf = buf.at[flat_idx, slot].add(
            jnp.where(keep[:, None], xrep, 0).astype(dt))   # LOCAL
        # ship expert rows to their owners: ONE all-to-all
        buf2 = jax.lax.all_to_all(buf, ep_axes, split_axis=0,
                                  concat_axis=1, tiled=True)
        # (el, cap_l * n_ep, d) — this device's experts, everyone's rows
        h = jnp.einsum("ecd,edf->ecf", buf2, win.astype(dt))
        if cfg.swiglu:
            g = jnp.einsum("ecd,edf->ecf", buf2, wgate.astype(dt))
            h = jax.nn.silu(g) * h
        else:
            h = jax.nn.gelu(h)
        oute = jnp.einsum("ecf,efd->ecd", h, wout.astype(dt))
        back = jax.lax.all_to_all(oute, ep_axes, split_axis=1,
                                  concat_axis=0, tiled=True)
        gathered = back[flat_idx, slot]                     # LOCAL
        gathered = jnp.where(keep[:, None], gathered, 0)
        y = (gathered.reshape(nl, k, d) * w[..., None]).sum(1)
        return y.astype(dt)

    xf = x.reshape(n, d)
    wg = p.get("experts_gate", p["experts_in"])
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(_P(all_axes, None), _P(None, None),
                  _P(ep_axes, None, None), _P(ep_axes, None, None),
                  _P(ep_axes, None, None)),
        out_specs=_P(all_axes, None),
        check_vma=False)
    y = fn(xf, p["router"], p["experts_in"], wg, p["experts_out"])
    y = y.reshape(bsz, s, d)
    # re-pin a clean batch-sharded layout (the reshape of a fully
    # token-sharded vector otherwise yields an unparseable GSPMD split)
    dp = ("pod", "data") if "pod" in names else "data"
    y = _wsc(y, dp, None, None)
    if cfg.n_shared_experts:
        y = y + mlp_block(cfg, p["shared"], x)
    return y


def moe_block(cfg: ModelConfig, p: Params, x):
    """Capacity-based top-k MoE (Switch-style dense dispatch): static
    shapes, shards experts over the model axis, all-to-all under SPMD."""
    if cfg.moe_shard_map and "model" in _mesh_axis_names():
        return moe_block_shard_map(cfg, p, x)
    if cfg.moe_groups > 0 and (x.shape[0] * x.shape[1]) \
            % cfg.moe_groups == 0:
        return moe_block_grouped(cfg, p, x)
    bsz, s, d = x.shape
    n = bsz * s
    k, e = cfg.top_k, cfg.n_experts
    xf = x.reshape(n, d)
    logits = (xf @ p["router"].astype(jnp.float32)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    w, idx = jax.lax.top_k(probs, k)                       # (n, k)
    w = (w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)).astype(cfg.jdtype)

    cap = int(math.ceil(n * k / e * cfg.capacity_factor))
    cap = max(8, min(cap, n))
    cap = (cap + 7) // 8 * 8

    # position of each (token, slot) inside its expert's buffer
    flat_idx = idx.reshape(-1)                             # (n*k,)
    onehot = jax.nn.one_hot(flat_idx, e, dtype=jnp.int32)  # (n*k, e)
    pos = jnp.cumsum(onehot, axis=0) - 1
    slot = jnp.take_along_axis(pos, flat_idx[:, None], 1)[:, 0]
    keep = slot < cap
    slot = jnp.where(keep, slot, cap - 1)

    xrep = jnp.repeat(xf, k, axis=0)                       # (n*k, d)
    vals = jnp.where(keep[:, None], xrep, 0).astype(cfg.jdtype)
    hints = cfg.shard_hints and "model" in _mesh_axis_names()
    if hints:
        # §Perf variant: pin the dispatch layout so SPMD lowers the
        # scatter to an all-to-all (tokens: DP-sharded -> buffers:
        # expert-sharded) instead of all-gathering the token stream.
        from jax.sharding import PartitionSpec as _P
        vals = jax.lax.with_sharding_constraint(
            vals, _P(("pod", "data") if "pod" in
                     _mesh_axis_names() else "data", None))
    buf = jnp.zeros((e, cap, d), cfg.jdtype)
    buf = buf.at[flat_idx, slot].add(vals)
    if hints:
        from jax.sharding import PartitionSpec as _P
        espec = "model" if e % _mesh_axis_size("model") == 0 else None
        buf = jax.lax.with_sharding_constraint(buf,
                                               _P(espec, None, None))

    h = jnp.einsum("ecd,edf->ecf", buf, p["experts_in"].astype(cfg.jdtype))
    if cfg.swiglu:
        g = jnp.einsum("ecd,edf->ecf", buf,
                       p["experts_gate"].astype(cfg.jdtype))
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    out = jnp.einsum("ecf,efd->ecd", h, p["experts_out"].astype(cfg.jdtype))

    gathered = out[flat_idx, slot]                         # (n*k, d)
    if hints:
        from jax.sharding import PartitionSpec as _P
        gathered = jax.lax.with_sharding_constraint(
            gathered, _P(("pod", "data") if "pod" in
                         _mesh_axis_names() else "data", None))
    gathered = jnp.where(keep[:, None], gathered, 0)
    combined = (gathered.reshape(n, k, d)
                * w[..., None]).sum(1).astype(cfg.jdtype)
    y = combined.reshape(bsz, s, d)
    if cfg.n_shared_experts:
        y = y + mlp_block(cfg, p["shared"], x)
    return y


def _rope_qk(cfg: ModelConfig, q, k, positions, mrope_positions=None):
    if cfg.mrope_sections is not None:
        mp = mrope_positions
        if mp is None:
            mp = jnp.broadcast_to(positions[None], (3,) + positions.shape)
        return (apply_mrope(q, mp, cfg.rope_theta, cfg.mrope_sections),
                apply_mrope(k, mp, cfg.rope_theta, cfg.mrope_sections))
    return (apply_rope(q, positions, cfg.rope_theta),
            apply_rope(k, positions, cfg.rope_theta))


def attn_block(cfg: ModelConfig, p: Params, x, positions,
               mrope_positions=None):
    """Full-sequence (train/prefill) attention, returns (out, (k, v)) —
    k/v in cache layout for prefill reuse."""
    bsz, s, d = x.shape
    dt = cfg.jdtype
    if cfg.use_mla:
        rd, hd = cfg.mla_rope_dim, cfg.hd
        cq = rmsnorm_latent(x @ p["wdq"].astype(dt), p["q_norm"], cfg)
        q = (cq @ p["wuq"].astype(dt)).reshape(bsz, s, cfg.n_heads, hd + rd)
        ckv_full = x @ p["wdkv"].astype(dt)
        ckv, k_rope = ckv_full[..., :cfg.mla_kv_rank], \
            ckv_full[..., cfg.mla_kv_rank:]
        ckv = rmsnorm_latent(ckv, p["kv_norm"], cfg)
        k_nope = (ckv @ p["wuk"].astype(dt)).reshape(bsz, s, cfg.n_heads, hd)
        v = (ckv @ p["wuv"].astype(dt)).reshape(bsz, s, cfg.n_heads, hd)
        q_nope, q_rope = q[..., :hd], q[..., hd:]
        q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
        k_rope = apply_rope(k_rope[:, :, None, :], positions,
                            cfg.rope_theta)
        qf = jnp.concatenate([q_nope, q_rope], -1)
        kf = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope,
                                      (bsz, s, cfg.n_heads, rd))], -1)
        o = attention(cfg, qf, kf, v, causal=True)
        out = o.reshape(bsz, s, cfg.q_dim) @ p["wo"].astype(dt)
        cache_kv = jnp.concatenate([ckv, k_rope[:, :, 0, :]], -1)
        return out, (cache_kv, None)

    q = x @ p["wq"].astype(dt)
    k = x @ p["wk"].astype(dt)
    v = x @ p["wv"].astype(dt)
    if cfg.qkv_bias:
        q, k, v = q + p["bq"].astype(dt), k + p["bk"].astype(dt), \
            v + p["bv"].astype(dt)
    q = q.reshape(bsz, s, cfg.n_heads, cfg.hd)
    k = k.reshape(bsz, s, cfg.kv_heads, cfg.hd)
    v = v.reshape(bsz, s, cfg.kv_heads, cfg.hd)
    q, k = _rope_qk(cfg, q, k, positions, mrope_positions)
    o = attention(cfg, q, k, v, causal=True)
    out = o.reshape(bsz, s, cfg.q_dim) @ p["wo"].astype(dt)
    return out, (k, v)


def rmsnorm_latent(x, p, cfg: ModelConfig):
    from .common import rmsnorm
    return rmsnorm(x, p["scale"], cfg.norm_eps)


def layer_fwd(cfg: ModelConfig, kind: str, p: Params, x, positions,
              mrope_positions=None):
    a, kv = attn_block(cfg, p["attn"], apply_norm(cfg, p["norm1"], x),
                       positions, mrope_positions)
    # fused norm_impl runs the attn-residual add + norm2 as one kernel
    x, h = apply_norm_residual(cfg, p["norm2"], x, a)
    if kind == "moe":
        x = x + moe_block(cfg, p["moe"], h)
    else:
        x = x + mlp_block(cfg, p["mlp"], h)
    return x, kv


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def embed_tokens(cfg: ModelConfig, params: Params, tokens):
    return jnp.take(params["embed"].astype(cfg.jdtype), tokens, axis=0)


def unembed(cfg: ModelConfig, params: Params, x):
    if cfg.tie_embeddings:
        return x @ params["embed"].astype(cfg.jdtype).T
    return x @ params["head"].astype(cfg.jdtype)


def _run_segments(cfg: ModelConfig, params: Params, x, positions,
                  mrope_positions=None, collect_kv: bool = False):
    """Run all layer segments; optionally collect per-layer (k, v) stacks
    (prefill).  Returns (x, list_of_kv_stacks_per_segment)."""
    kvs = []
    for seg in params["segments"]:
        kind = segment_kind(seg)
        sp = segment_params(seg)
        count = jax.tree_util.tree_leaves(sp)[0].shape[0]

        def body(h, lp):
            h2, kv = layer_fwd(cfg, kind, lp, h, positions, mrope_positions)
            return h2, (kv if collect_kv else None)

        body = maybe_remat(body, cfg)
        if cfg.scan_layers and count >= cfg.scan_min_layers:
            x, kv = jax.lax.scan(body, x, sp)
        else:
            kv_list = []
            for i in range(count):
                lp = jax.tree.map(lambda a: a[i], sp)
                x, kvi = body(x, lp)
                kv_list.append(kvi)
            kv = (jax.tree.map(lambda *xs: jnp.stack(xs), *kv_list)
                  if collect_kv else None)
        kvs.append(kv)
    return x, kvs


def forward(cfg: ModelConfig, params: Params, tokens=None, *,
            embeds=None, positions=None, mrope_positions=None,
            collect_kv: bool = False, return_hidden: bool = False):
    """Logits for a full sequence. `embeds` (B,S,d) may replace/augment
    tokens for modality-stub prefixes (vision/audio)."""
    if tokens is not None:
        x = embed_tokens(cfg, params, tokens)
        if embeds is not None:           # vision prefix + text suffix
            x = jnp.concatenate([embeds.astype(cfg.jdtype), x], axis=1)
    else:
        x = embeds.astype(cfg.jdtype)
    bsz, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (bsz, s))
    x, kvs = _run_segments(cfg, params, x, positions, mrope_positions,
                           collect_kv=collect_kv)
    x = apply_norm(cfg, params["final_norm"], x)
    if return_hidden and not collect_kv:
        return None, x, kvs
    logits = unembed(cfg, params, x)
    if collect_kv:
        return logits, x, kvs
    return logits


def chunked_cross_entropy(cfg: ModelConfig, params: Params, hidden,
                          labels, chunk: int = 512) -> jnp.ndarray:
    """§Perf variant (fused_ce): the (B, S, V) fp32 logits tensor is the
    training-memory hot spot for small-d/large-V archs; stream the
    unembed + CE over sequence chunks so only (B, chunk, V) is ever
    live."""
    b, s, d = hidden.shape
    pad = (-s) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    n = hidden.shape[1] // chunk
    hc = hidden.reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
    yc = labels.reshape(b, n, chunk).transpose(1, 0, 2)

    def body(carry, xs):
        h, y = xs
        lf = unembed(cfg, params, h).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(lf, axis=-1)
        ll = jnp.take_along_axis(lf, y[..., None].clip(0), -1)[..., 0]
        valid = (y != -1).astype(jnp.float32)
        return (carry[0] + ((lse - ll) * valid).sum(),
                carry[1] + valid.sum()), None

    (num, den), _ = jax.lax.scan(body, (0.0, 0.0), (hc, yc))
    return num / jnp.maximum(den, 1.0)


def loss_fn(cfg: ModelConfig, params: Params, batch) -> jnp.ndarray:
    """Cross-entropy LM loss; adds the MTP auxiliary loss when enabled
    (DeepSeek-V3-style single-depth MTP)."""
    tokens, labels = batch["tokens"], batch["labels"]
    embeds = batch.get("embeds")
    if cfg.fused_ce and not cfg.mtp:
        _, h, _ = forward(cfg, params, tokens, embeds=embeds,
                          collect_kv=False, return_hidden=True)
        if embeds is not None:
            h = h[:, embeds.shape[1]:]
        return chunked_cross_entropy(cfg, params, h, labels)
    if cfg.mtp:
        logits, h, _ = forward(cfg, params, tokens, embeds=embeds,
                               collect_kv=True)
    else:
        logits = forward(cfg, params, tokens, embeds=embeds)
    if embeds is not None:   # prefix positions carry no labels
        logits = logits[:, embeds.shape[1]:]
    loss = cross_entropy(logits, labels)
    if cfg.mtp:
        mp = params["mtp"]
        emb_next = embed_tokens(cfg, params,
                                jnp.pad(tokens[:, 1:], ((0, 0), (0, 1))))
        hh = jnp.concatenate([h, emb_next], -1) @ mp["proj"].astype(cfg.jdtype)
        bsz, s, _ = hh.shape
        pos = jnp.broadcast_to(jnp.arange(s)[None], (bsz, s))
        hh, _ = layer_fwd(cfg, "dense", mp["layer"], hh, pos)
        hh = apply_norm(cfg, mp["norm"], hh)
        mtp_logits = unembed(cfg, params, hh)
        mtp_labels = jnp.pad(labels[:, 1:], ((0, 0), (0, 1)),
                             constant_values=-1)
        loss = loss + 0.3 * cross_entropy(mtp_logits, mtp_labels)
    return loss


# ---------------------------------------------------------------------------
# KV cache + decode
# ---------------------------------------------------------------------------

def cache_len(cfg: ModelConfig, max_len: int) -> int:
    """Ring-buffer length: SWA archs only ever need `window` slots."""
    return min(max_len, cfg.window) if cfg.window else max_len


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=None) -> Params:
    dt = dtype or cfg.jdtype
    clen = cache_len(cfg, max_len)
    segs = []
    for kind, count in layer_segments(cfg):
        if cfg.use_mla:
            kv = jnp.zeros((count, batch, clen,
                            cfg.mla_kv_rank + cfg.mla_rope_dim), dt)
            segs.append({"latent": kv})
        else:
            segs.append({
                "k": jnp.zeros((count, batch, clen, cfg.kv_heads, cfg.hd), dt),
                "v": jnp.zeros((count, batch, clen, cfg.kv_heads, cfg.hd), dt),
            })
    return {"segments": segs, "index": jnp.zeros((), jnp.int32)}


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                     dtype=None) -> list:
    """Per-segment KV page pools: the paged analogue of `init_cache`'s
    (L, B, C, ...) slabs with the (B, C) rectangle replaced by a shared
    (num_pages, page_size) pool.  Page 0 is reserved as the null page
    every unused page-table entry points at; its contents are never read
    (decode masks by per-slot length).  Slot ownership / page tables live
    with the serving engine (`repro.serving.paged.PagePool`)."""
    dt = dtype or cfg.jdtype
    segs = []
    for kind, count in layer_segments(cfg):
        if cfg.use_mla:
            segs.append({"latent": jnp.zeros(
                (count, num_pages, page_size,
                 cfg.mla_kv_rank + cfg.mla_rope_dim), dt)})
        else:
            segs.append({
                "k": jnp.zeros((count, num_pages, page_size,
                                cfg.kv_heads, cfg.hd), dt),
                "v": jnp.zeros((count, num_pages, page_size,
                                cfg.kv_heads, cfg.hd), dt),
            })
    return segs


def _ring_slot(cfg: ModelConfig, index, clen: int):
    return index % clen if cfg.window else index


def _cache_positions(cfg: ModelConfig, index, clen: int):
    """Absolute position held by each cache slot (ring-aware); -1 invalid.
    index: (B,) vector -> returns (B, clen)."""
    j = jnp.arange(clen)[None, :]
    idx = index[:, None]
    if cfg.window:
        # slot j holds the largest p <= index with p % clen == j
        p = idx - ((idx - j) % clen)
        return jnp.where(p >= 0, p, -1)
    return jnp.where(j <= idx, j, -1)


def _scatter_slot(cache_arr, new_entry, slot):
    """cache_arr (B, C, ...) <- new_entry (B, 1, ...) at per-batch slot."""
    b = cache_arr.shape[0]
    return cache_arr.at[jnp.arange(b), slot].set(
        new_entry[:, 0].astype(cache_arr.dtype))


def _decode_attn(cfg: ModelConfig, p: Params, x, seg_cache, index):
    """One-token attention against the cache. x: (B,1,d); index: (B,)."""
    bsz = x.shape[0]
    dt = cfg.jdtype
    pos1 = index[:, None].astype(jnp.int32)

    if cfg.use_mla:
        rd, hd, kvr = cfg.mla_rope_dim, cfg.hd, cfg.mla_kv_rank
        cq = rmsnorm_latent(x @ p["wdq"].astype(dt), p["q_norm"], cfg)
        q = (cq @ p["wuq"].astype(dt)).reshape(bsz, 1, cfg.n_heads, hd + rd)
        q_nope, q_rope = q[..., :hd], q[..., hd:]
        q_rope = apply_rope(q_rope, pos1, cfg.rope_theta)
        ckv_full = x @ p["wdkv"].astype(dt)
        ckv, k_rope = ckv_full[..., :kvr], ckv_full[..., kvr:]
        ckv = rmsnorm_latent(ckv, p["kv_norm"], cfg)
        k_rope = apply_rope(k_rope[:, :, None, :], pos1, cfg.rope_theta)
        new_entry = jnp.concatenate([ckv, k_rope[:, :, 0, :]], -1)  # (B,1,D)
        clen = seg_cache["latent"].shape[1]
        slot = _ring_slot(cfg, index, clen)
        cache = _scatter_slot(seg_cache["latent"], new_entry, slot)
        # (B, C, kvr+rd)
        lat, lat_rope = cache[..., :kvr], cache[..., kvr:]
        # absorbed attention: q_nope^T W_uk c_kv
        wuk = p["wuk"].astype(dt).reshape(kvr, cfg.n_heads, hd)
        q_abs = jnp.einsum("bqhd,khd->bqhk", q_nope, wuk)     # (B,1,H,kvr)
        s_n = jnp.einsum("bqhk,bck->bhqc", q_abs, lat.astype(dt))
        s_r = jnp.einsum("bqhd,bcd->bhqc", q_rope, lat_rope.astype(dt))
        scores = (s_n + s_r).astype(jnp.float32) / math.sqrt(hd + rd)
        kpos = _cache_positions(cfg, index, lat.shape[1])     # (B, C)
        mask = (kpos >= 0) & (kpos <= index[:, None])
        if cfg.window:
            mask &= kpos > index[:, None] - cfg.window
        scores = jnp.where(mask[:, None, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores, -1).astype(dt)
        o_lat = jnp.einsum("bhqc,bck->bqhk", probs, lat.astype(dt))
        wuv = p["wuv"].astype(dt).reshape(kvr, cfg.n_heads, hd)
        o = jnp.einsum("bqhk,khd->bqhd", o_lat, wuv)
        out = o.reshape(bsz, 1, cfg.q_dim) @ p["wo"].astype(dt)
        return out, {"latent": cache}

    q = x @ p["wq"].astype(dt)
    k = x @ p["wk"].astype(dt)
    v = x @ p["wv"].astype(dt)
    if cfg.qkv_bias:
        q, k, v = q + p["bq"].astype(dt), k + p["bk"].astype(dt), \
            v + p["bv"].astype(dt)
    q = q.reshape(bsz, 1, cfg.n_heads, cfg.hd)
    k = k.reshape(bsz, 1, cfg.kv_heads, cfg.hd)
    v = v.reshape(bsz, 1, cfg.kv_heads, cfg.hd)
    q, k = _rope_qk(cfg, q, k, pos1)
    K, V = seg_cache["k"], seg_cache["v"]           # (B, C, kvh, hd)
    clen = K.shape[1]
    slot = _ring_slot(cfg, index, clen)
    K = _scatter_slot(K, k, slot)
    V = _scatter_slot(V, v, slot)
    n_rep = cfg.n_heads // cfg.kv_heads
    kpos = _cache_positions(cfg, index, clen)       # (B, C)
    mask = (kpos >= 0) & (kpos <= index[:, None])
    if cfg.window:
        mask &= kpos > index[:, None] - cfg.window

    if cfg.gqa_einsum and n_rep > 1:
        # §Perf variant: grouped attention — contract each query-head
        # group against its kv head directly; the cache is read ONCE
        # instead of materializing an n_rep-times-expanded copy.
        qg = q.reshape(bsz, 1, cfg.kv_heads, n_rep, cfg.hd)
        scores = jnp.einsum("bqkgd,bckd->bkgqc", qg, K.astype(dt)) \
            .astype(jnp.float32) / math.sqrt(cfg.hd)
        scores = jnp.where(mask[:, None, None, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores, -1).astype(dt)
        o = jnp.einsum("bkgqc,bckd->bqkgd", probs, V.astype(dt))
        o = o.reshape(bsz, 1, cfg.n_heads, cfg.hd)
    else:
        Kr = jnp.repeat(K.astype(dt), n_rep, axis=2) if n_rep > 1 \
            else K.astype(dt)
        Vr = jnp.repeat(V.astype(dt), n_rep, axis=2) if n_rep > 1 \
            else V.astype(dt)
        scores = jnp.einsum("bqhd,bchd->bhqc", q, Kr) \
            .astype(jnp.float32) / math.sqrt(cfg.hd)
        scores = jnp.where(mask[:, None, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores, -1).astype(dt)
        o = jnp.einsum("bhqc,bchd->bqhd", probs, Vr)
    out = o.reshape(bsz, 1, cfg.q_dim) @ p["wo"].astype(dt)
    return out, {"k": K, "v": V}


def _decode_layer(cfg: ModelConfig, kind: str, p: Params, x, seg_cache,
                  index):
    a, new_cache = _decode_attn(cfg, p["attn"],
                                apply_norm(cfg, p["norm1"], x),
                                seg_cache, index)
    x, h = apply_norm_residual(cfg, p["norm2"], x, a)
    if kind == "moe":
        x = x + moe_block(cfg, p["moe"], h)
    else:
        x = x + mlp_block(cfg, p["mlp"], h)
    return x, new_cache


def decode_step(cfg: ModelConfig, params: Params, tokens, cache):
    """One decode step. tokens: (B, 1) int32. Returns (logits, cache).
    cache["index"] may be a scalar (uniform lengths) or a (B,) vector
    (continuous batching with mixed-length slots)."""
    raw_index = jnp.asarray(cache["index"])
    index = raw_index if raw_index.ndim == 1 \
        else jnp.full((tokens.shape[0],), raw_index, jnp.int32)
    x = embed_tokens(cfg, params, tokens)
    new_segs = []
    for seg, seg_cache in zip(params["segments"], cache["segments"]):
        kind = segment_kind(seg)
        sp = segment_params(seg)
        count = jax.tree_util.tree_leaves(sp)[0].shape[0]

        def body(h, xs):
            lp, lc = xs
            h2, nc = _decode_layer(cfg, kind, lp, h, lc, index)
            return h2, nc

        if cfg.scan_layers and count >= cfg.scan_min_layers:
            x, nc = jax.lax.scan(body, x, (sp, seg_cache))
        else:
            ncs = []
            for i in range(count):
                lp = jax.tree.map(lambda a: a[i], sp)
                lc = jax.tree.map(lambda a: a[i], seg_cache)
                x, nci = body(x, (lp, lc))
                ncs.append(nci)
            nc = jax.tree.map(lambda *xs: jnp.stack(xs), *ncs)
        new_segs.append(nc)
    x = apply_norm(cfg, params["final_norm"], x)
    logits = unembed(cfg, params, x)
    return logits, {"segments": new_segs, "index": raw_index + 1}


def _window_attn(cfg: ModelConfig, p: Params, x, seg_cache, pos):
    """W-token cached attention (spec-decode verify). x: (B, W, d);
    pos: (B, W) absolute positions.  Plain (non-MLA, non-ring) path:
    position p writes cache slot p directly and attends causally to
    every slot <= its own position."""
    bsz, w = x.shape[0], x.shape[1]
    dt = cfg.jdtype
    q = x @ p["wq"].astype(dt)
    k = x @ p["wk"].astype(dt)
    v = x @ p["wv"].astype(dt)
    if cfg.qkv_bias:
        q, k, v = q + p["bq"].astype(dt), k + p["bk"].astype(dt), \
            v + p["bv"].astype(dt)
    q = q.reshape(bsz, w, cfg.n_heads, cfg.hd)
    k = k.reshape(bsz, w, cfg.kv_heads, cfg.hd)
    v = v.reshape(bsz, w, cfg.kv_heads, cfg.hd)
    q, k = _rope_qk(cfg, q, k, pos)
    K, V = seg_cache["k"], seg_cache["v"]           # (B, C, kvh, hd)
    rows = jnp.arange(bsz)[:, None]
    K = K.at[rows, pos].set(k.astype(K.dtype))
    V = V.at[rows, pos].set(v.astype(V.dtype))
    n_rep = cfg.n_heads // cfg.kv_heads
    Kr = jnp.repeat(K.astype(dt), n_rep, axis=2) if n_rep > 1 \
        else K.astype(dt)
    Vr = jnp.repeat(V.astype(dt), n_rep, axis=2) if n_rep > 1 \
        else V.astype(dt)
    scores = jnp.einsum("bqhd,bchd->bhqc", q, Kr) \
        .astype(jnp.float32) / math.sqrt(cfg.hd)
    mask = jnp.arange(K.shape[1])[None, None, :] <= pos[:, :, None]
    scores = jnp.where(mask[:, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, -1).astype(dt)
    o = jnp.einsum("bhqc,bchd->bqhd", probs, Vr)
    out = o.reshape(bsz, w, cfg.q_dim) @ p["wo"].astype(dt)
    return out, {"k": K, "v": V}


def window_supported(cfg: ModelConfig) -> bool:
    """Configs `decode_window` handles: plain linear-cache attention."""
    return (cfg.family == "transformer" and not cfg.use_mla
            and not cfg.window)


def decode_window(cfg: ModelConfig, params: Params, tokens, cache):
    """Verify W speculated tokens in ONE cached forward.

    tokens: (B, W) int32 at positions index..index+W-1; their KV is
    written into the cache and logits for every window position come
    back as (B, W, vocab).  The caller rewinds over-written positions
    simply by resetting `cache["index"]` — slots past the index are
    masked out of every later attention, so stale KV is harmless.
    """
    if not window_supported(cfg):
        raise NotImplementedError(
            "decode_window: plain-attention transformer only "
            f"(family={cfg.family}, mla={cfg.use_mla}, "
            f"window={cfg.window})")
    raw_index = jnp.asarray(cache["index"])
    bsz, w = tokens.shape
    index = raw_index if raw_index.ndim == 1 \
        else jnp.full((bsz,), raw_index, jnp.int32)
    pos = index[:, None] + jnp.arange(w, dtype=jnp.int32)[None]
    x = embed_tokens(cfg, params, tokens)
    new_segs = []
    for seg, seg_cache in zip(params["segments"], cache["segments"]):
        kind = segment_kind(seg)
        sp = segment_params(seg)
        count = jax.tree_util.tree_leaves(sp)[0].shape[0]
        ncs = []
        for i in range(count):
            lp = jax.tree.map(lambda a: a[i], sp)
            lc = jax.tree.map(lambda a: a[i], seg_cache)
            a, nci = _window_attn(cfg, lp["attn"],
                                  apply_norm(cfg, lp["norm1"], x), lc, pos)
            x, h = apply_norm_residual(cfg, lp["norm2"], x, a)
            if kind == "moe":
                x = x + moe_block(cfg, lp["moe"], h)
            else:
                x = x + mlp_block(cfg, lp["mlp"], h)
            ncs.append(nci)
        new_segs.append(jax.tree.map(lambda *xs: jnp.stack(xs), *ncs))
    x = apply_norm(cfg, params["final_norm"], x)
    logits = unembed(cfg, params, x)
    return logits, {"segments": new_segs, "index": raw_index + w}


def prefill(cfg: ModelConfig, params: Params, tokens, max_len: int, *,
            embeds=None):
    """Run the prompt, fill the cache, return (last_logits, cache)."""
    logits, _, kvs = forward(cfg, params, tokens, embeds=embeds,
                             collect_kv=True)
    bsz = (tokens if tokens is not None else embeds).shape[0]
    s = logits.shape[1]
    cache = init_cache(cfg, bsz, max_len)
    clen = cache_len(cfg, max_len)

    def _place(src, seq_axis):
        """Write the last `take` positions into the (ring) cache so that
        position p lands in slot p % clen (ring invariant)."""
        take = min(s, clen)
        last = jax.lax.slice_in_dim(src, s - take, s, axis=seq_axis)
        if take < clen:          # prompt shorter than cache: slots 0..s-1
            pads = [(0, 0)] * src.ndim
            pads[seq_axis] = (0, clen - take)
            return jnp.pad(last, pads)
        if cfg.window:           # full ring: roll so slot j holds p%clen==j
            return jnp.roll(last, shift=s % clen, axis=seq_axis)
        return last

    new_segs = []
    for seg_kv, seg_cache in zip(kvs, cache["segments"]):
        if cfg.use_mla:
            lat = seg_kv[0]                      # (L, B, S, kvr+rd)
            new_segs.append(
                {"latent": _place(lat, 2).astype(
                    seg_cache["latent"].dtype)})
        else:
            k, v = seg_kv                        # (L, B, S, kvh, hd)
            new_segs.append({
                "k": _place(k, 2).astype(seg_cache["k"].dtype),
                "v": _place(v, 2).astype(seg_cache["v"].dtype)})
    return logits[:, -1:], {"segments": new_segs,
                            "index": jnp.asarray(s, jnp.int32)}
