"""Work a step of a dense GQA decoder with QKV bias needs, for the tests'
second tiny configuration: `bench/flops.py`'s counts, plus each layer's
biases read once a step and added once a token.  The tests copy it into
their copy of the benchmark as a file of its own, which the
configuration names by its `"cost"` key."""

from bench import flops


def _bias(m: dict) -> int:
    return m["n_layers"] * (m["n_heads"] + 2 * m["kv_heads"]) * flops.head_dim(m)


def decode_cost(m: dict, active: int, ctx_total: int) -> tuple[float, float]:
    f, b = flops.decode_cost(m, active, ctx_total)
    return f + active * _bias(m), b + _bias(m) * flops.DTYPE_BYTES[m.get("dtype", "bfloat16")]


def prefill_cost(m: dict, plen: int) -> tuple[float, float]:
    f, b = flops.prefill_cost(m, plen)
    return f + plen * _bias(m), b + _bias(m) * flops.DTYPE_BYTES[m.get("dtype", "bfloat16")]
