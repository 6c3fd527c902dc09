"""Operations and bytes a serving step needs, from the model's shapes.

Everything here is computed from the configuration file's `model` sizes
(a plain dict), never from the program: the counts are the yardstick the
per-layer metrics divide by.  "Needed" means what the algorithm requires,
not what the program happens to do: a decode step reads the weights once
and the live KV of each active request, not the whole gathered capacity;
a prefill computes one row of logits, not a row per bucket position.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The published peaks of one chip of `device_kind`.  A kind that is
    not in the table is an error, never a default."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def head_dim(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["n_heads"]


def layer_matmul_params(m: dict) -> int:
    """Weights of one dense GQA layer that enter a matmul."""
    d, hd = m["d_model"], head_dim(m)
    q, kv = m["n_heads"] * hd, m["kv_heads"] * hd
    attn = d * q + 2 * d * kv + q * d
    mlp = (3 if m.get("swiglu", True) else 2) * d * m["d_ff"]
    return attn + mlp


def param_count(m: dict) -> int:
    """All parameters: embeddings, layers (with their two norms), final
    norm, and the output head unless it is tied to the embeddings."""
    d = m["d_model"]
    embed = m["vocab"] * d
    per_layer = layer_matmul_params(m) + 2 * d
    head = 0 if m.get("tie_embeddings") else d * m["vocab"]
    return embed + m["n_layers"] * per_layer + d + head


def kv_bytes_per_token(m: dict) -> int:
    """Keys and values of one token over every layer."""
    return 2 * m["n_layers"] * m["kv_heads"] * head_dim(m) * DTYPE_BYTES[m.get("dtype", "bfloat16")]


def _weight_bytes(m: dict) -> int:
    """Weights one forward pass reads: every layer's matmul weights and
    norms, the final norm and the output head (the tied embedding matrix
    when tied).  Embedding rows are counted per token by the callers."""
    wb = DTYPE_BYTES[m.get("dtype", "bfloat16")]
    d = m["d_model"]
    layers = m["n_layers"] * (layer_matmul_params(m) + 2 * d)
    return wb * (layers + d + d * m["vocab"])


def _attn_flops(m: dict, q_tokens_times_ctx: int) -> int:
    """QK^T and PV over `sum(query positions x attended positions)`."""
    return 4 * m["n_layers"] * m["n_heads"] * head_dim(m) * q_tokens_times_ctx


def decode_cost(m: dict, active: int, ctx_total: int) -> tuple[float, float]:
    """(flops, bytes) one decode step needs for `active` requests whose
    caches hold `ctx_total` positions in all after this step's write:
    the weights once, each request's live KV, the new KV written."""
    d, wb = m["d_model"], DTYPE_BYTES[m.get("dtype", "bfloat16")]
    head = d * m["vocab"]
    flops = 2 * active * (m["n_layers"] * layer_matmul_params(m) + head) + _attn_flops(m, ctx_total)
    nbytes = _weight_bytes(m) + active * d * wb + kv_bytes_per_token(m) * ctx_total
    return float(flops), float(nbytes)


def prefill_cost(m: dict, plen: int) -> tuple[float, float]:
    """(flops, bytes) a prefill of `plen` real prompt tokens needs: every
    layer over the prompt, causal attention, one row of logits."""
    d, wb = m["d_model"], DTYPE_BYTES[m.get("dtype", "bfloat16")]
    flops = (2 * plen * m["n_layers"] * layer_matmul_params(m)
             + _attn_flops(m, plen * (plen + 1) // 2) + 2 * d * m["vocab"])
    nbytes = _weight_bytes(m) + plen * d * wb + kv_bytes_per_token(m) * plen
    return float(flops), float(nbytes)


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """Seconds the chip needs at least: the larger of the compute and the
    memory bound."""
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
