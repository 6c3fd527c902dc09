"""Unified model facade: family dispatch for init/loss/prefill/decode.

Batch dict convention (matches launch.input_specs):
  train:   {"tokens": (B,S) i32, "labels": (B,S) i32[, "embeds": (B,P,d)]}
  prefill: {"tokens": (B,S)[, "embeds": ...]}
  decode:  {"tokens": (B,1), cache pytree}
Whisper uses {"embeds": frames, "tokens": decoder tokens, "labels": ...}.
"""
from __future__ import annotations

from typing import Any, Callable

import jax

from . import rglru, rwkv6, transformer, whisper
from .config import ModelConfig

Params = Any

_FAMS = {"transformer": transformer, "rglru": rglru, "rwkv6": rwkv6,
         "whisper": whisper}


def family_module(cfg: ModelConfig):
    return _FAMS[cfg.family]


def init_params(cfg: ModelConfig, key) -> Params:
    return family_module(cfg).init_params(cfg, key)


def loss_fn(cfg: ModelConfig, params: Params, batch) -> jax.Array:
    m = family_module(cfg)
    if cfg.family == "whisper":
        return m.loss_fn(cfg, params, batch)
    if cfg.family == "transformer":
        return m.loss_fn(cfg, params, batch)
    return m.loss_fn(cfg, params, batch)


def forward(cfg: ModelConfig, params: Params, batch):
    m = family_module(cfg)
    if cfg.family == "whisper":
        return m.forward(cfg, params, batch["embeds"], batch["tokens"])
    if cfg.family == "transformer":
        return m.forward(cfg, params, batch.get("tokens"),
                         embeds=batch.get("embeds"))
    return m.forward(cfg, params, batch["tokens"])


def prefill(cfg: ModelConfig, params: Params, batch, max_len: int):
    m = family_module(cfg)
    if cfg.family == "whisper":
        return m.prefill(cfg, params, batch["embeds"], batch["tokens"],
                         max_len)
    if cfg.family == "transformer":
        return m.prefill(cfg, params, batch.get("tokens"), max_len,
                         embeds=batch.get("embeds"))
    return m.prefill(cfg, params, batch["tokens"], max_len)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               enc_len: int | None = None):
    m = family_module(cfg)
    if cfg.family == "whisper":
        return m.init_cache(cfg, batch, max_len,
                            enc_len or max_len)
    return m.init_cache(cfg, batch, max_len)


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                     dtype=None, *, rows: bool = True):
    """Paged KV page pools (transformer-only — the serving engine falls
    back to the dense cache for every other family)."""
    if cfg.family != "transformer":
        raise NotImplementedError(
            f"paged KV cache is transformer-only, not {cfg.family}")
    return family_module(cfg).init_paged_cache(cfg, num_pages, page_size,
                                               dtype, rows=rows)


def decode_step(cfg: ModelConfig, params: Params, tokens, cache):
    return family_module(cfg).decode_step(cfg, params, tokens, cache)


def decode_window(cfg: ModelConfig, params: Params, tokens, cache):
    """Verify a (B, W) token window in one cached forward (spec-decode).
    Plain-attention transformers only; see `transformer.decode_window`."""
    if cfg.family != "transformer":
        raise NotImplementedError(
            f"decode_window is transformer-only, not {cfg.family}")
    return transformer.decode_window(cfg, params, tokens, cache)


def param_count(params: Params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))
