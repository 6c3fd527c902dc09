"""The plain reference agrees with the engine's paged prefill and decode
logits at a tiny float32 size, and the fp8 control departs from it."""

import jax
import numpy as np
import pytest

from bench import reference, weights
from conftest import TINY_MODEL


def tiny(dtype="float32"):
    from repro import configs

    cfg = configs.get_config("smollm-135m").replace(**TINY_MODEL, dtype=dtype, param_dtype=dtype)
    model = dict(TINY_MODEL, tie_embeddings=True, rope_theta=10000.0, dtype=dtype)
    return cfg, model


@pytest.mark.parametrize("tie", [True, False])
def test_reference_matches_engine_prefill_and_decode(tie):
    from repro.serving.engine import Request, ServingEngine

    cfg, model = tiny()
    cfg, model["tie_embeddings"] = cfg.replace(tie_embeddings=tie), tie
    w = weights.make(cfg, 2**33 + 5)
    eng = ServingEngine(cfg, w, max_batch=2, max_len=64, page_size=8, paged=True, kv_quant=False)
    seen = []      # (request, position, logits row)
    inner_p, inner_d = eng.state.prefill, eng.state.decode

    def prefill(fn, params, b, seq, frames=None):
        last = inner_p(fn, params, b, seq, frames)
        seen.append((b, len(seq) - 1, np.asarray(last[0, -1])))
        return last

    def decode(fn, params, tok, active):
        pos = {b: int(eng.state.pool.index[b]) for b in active}
        logits, lane = inner_d(fn, params, tok, active)
        seen.extend((b, pos[b], np.asarray(logits[lane[b], -1])) for b in active)
        return logits, lane

    eng.state.prefill, eng.state.decode = prefill, decode
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, 512, n).astype(np.int32), max_new_tokens=5)
            for i, n in enumerate((7, 19))]
    slot = {}
    for r in reqs:
        eng.submit(r)
    while eng.queue or any(s is not None for s in eng.slots):
        for b, s in enumerate(eng.slots):
            if s is not None:
                slot[s.rid] = b
        eng.step()
        for b, s in enumerate(eng.slots):
            if s is not None:
                slot[s.rid] = b
    assert len(seen) == 2 * 5
    for r in reqs:
        seq = np.concatenate([r.prompt, np.asarray(r.out_tokens[:-1], np.int32)])
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(reference.forward(model, w, seq))
        rows = [(p, lg) for b, p, lg in seen if b == slot[r.rid] and p < len(seq)]
        rows = [(p, lg) for p, lg in rows if p >= len(r.prompt) - 1][: len(r.out_tokens)]
        assert len(rows) == len(r.out_tokens)
        for p, lg in rows:
            np.testing.assert_allclose(lg, ref[p], rtol=2e-4, atol=2e-5)
        gaps = reference.served_gaps(model, w, r.prompt, r.out_tokens, 64)
        assert gaps.max() == pytest.approx(0.0, abs=1e-6)


def test_control_departs_from_the_reference():
    cfg, model = tiny()
    w = weights.make(cfg, 11)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 512, 20).astype(np.int32)
    served = rng.integers(0, 512, 30).astype(np.int32)
    ctrl = reference.served_gaps(model, w, prompt, served, 64, control=True)
    with jax.default_matmul_precision("highest"):
        f32 = np.asarray(reference.forward(model, w, np.concatenate([prompt, served[:-1]])))
        fp8 = np.asarray(reference.forward(model, w, np.concatenate([prompt, served[:-1]]), "fp8"))
    assert np.abs(fp8 - f32).max() > 1e-3
    assert ctrl.max() > 0.0


def test_weights_repeat_from_a_wide_seed():
    cfg, _ = tiny("bfloat16")
    a = jax.tree.leaves(weights.make(cfg, 2**40 + 3))
    b = jax.tree.leaves(weights.make(cfg, 2**40 + 3))
    c = jax.tree.leaves(weights.make(cfg, 2**40 + 4))
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not all(np.array_equal(x, y) for x, y in zip(a, c))
    assert all(x.dtype == jax.numpy.bfloat16 for x in a)
