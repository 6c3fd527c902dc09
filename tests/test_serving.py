"""Serving engine: continuous batching correctness, sampling, spec-decode
equivalence properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import api, transformer as T
from repro.models.config import ModelConfig
from repro.serving.engine import Request, ServingEngine
from repro.serving.sampling import sample
from repro.serving.specdec import spec_decode_greedy, spec_decode_sampled

pytestmark = pytest.mark.slow   # multi-minute JAX compile/run; excluded from tier-1

CFG = ModelConfig(name="tiny", n_layers=2, d_model=64, n_heads=4,
                  kv_heads=2, head_dim=16, d_ff=128, vocab=97,
                  dtype="float32", param_dtype="float32",
                  scan_min_layers=2)


@pytest.fixture(scope="module")
def params():
    return api.init_params(CFG, jax.random.PRNGKey(0))


def _single_decode(params, prompt, n=8):
    toks = jnp.asarray(prompt[None], jnp.int32)
    last, cache = api.prefill(CFG, params, {"tokens": toks}, 64)
    out = [int(jnp.argmax(last[0, -1]))]
    for _ in range(n - 1):
        lg, cache = api.decode_step(
            CFG, params, jnp.asarray([[out[-1]]], jnp.int32), cache)
        out.append(int(jnp.argmax(lg[0, -1])))
    return out


def test_continuous_batching_matches_single(params):
    prompts = [np.arange(4 + i, dtype=np.int32) + i for i in range(5)]
    want = [_single_decode(params, p) for p in prompts]
    eng = ServingEngine(CFG, params, max_batch=3, max_len=64)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=8)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    for r, w in zip(reqs, want):
        assert r.out_tokens == w, r.rid
    assert eng.stats["prefills"] == 5
    st = eng.stats
    assert 0 < st["live_slot_steps"] / (st["decode_steps"] * eng.max_batch) <= 1.0


def test_sampling_modes():
    logits = jnp.asarray([[0.0, 5.0, 1.0, -2.0]])
    key = jax.random.PRNGKey(0)
    assert int(sample(logits, key)[0]) == 1                   # greedy
    s = sample(logits, key, temperature=1.0, top_k=1)
    assert int(s[0]) == 1                                     # top-1
    draws = [int(sample(logits, jax.random.PRNGKey(i),
                        temperature=1.0, top_p=0.5)[0])
             for i in range(20)]
    assert set(draws) == {1}                                  # p mass top-1


def test_specdec_greedy_equals_target(params):
    dcfg = CFG.replace(n_layers=1, d_model=32, n_heads=2, kv_heads=1,
                       d_ff=64)
    dparams = api.init_params(dcfg, jax.random.PRNGKey(1))
    # per-test closures over params: retracing is the point of the test
    tf = jax.jit(lambda t: T.forward(CFG, params, t))  # mzc: ignore[MZC013]
    df = jax.jit(lambda t: T.forward(dcfg, dparams, t))  # mzc: ignore[MZC013]
    prompt = np.arange(6, dtype=np.int32)
    out, stats = spec_decode_greedy(tf, df, prompt, k=4,
                                    max_new_tokens=12)
    ref = list(prompt)
    for _ in range(12):
        lg = tf(jnp.asarray([ref], jnp.int32))
        ref.append(int(jnp.argmax(lg[0, -1])))
    assert list(out) == ref[len(prompt):]
    assert stats.iterations >= 1
    assert stats.tokens_per_iteration >= 1.0


def test_specdec_self_draft_accepts_everything(params):
    """Draft == target => every proposal accepted, k+1 tokens/iter."""
    tf = jax.jit(lambda t: T.forward(CFG, params, t))  # mzc: ignore[MZC013]
    prompt = np.arange(5, dtype=np.int32)
    out, stats = spec_decode_greedy(tf, tf, prompt, k=4,
                                    max_new_tokens=10)
    assert stats.acceptance_rate == pytest.approx(1.0)
    assert stats.tokens_per_iteration == pytest.approx(5.0)


def test_specdec_sampled_runs(params):
    dcfg = CFG.replace(n_layers=1)
    dparams = api.init_params(dcfg, jax.random.PRNGKey(2))
    tf = jax.jit(lambda t: T.forward(CFG, params, t))  # mzc: ignore[MZC013]
    df = jax.jit(lambda t: T.forward(dcfg, dparams, t))  # mzc: ignore[MZC013]
    out, stats = spec_decode_sampled(tf, df, np.arange(4, dtype=np.int32),
                                     jax.random.PRNGKey(3), k=3,
                                     max_new_tokens=8)
    assert len(out) == 8
    assert 0.0 <= stats.acceptance_rate <= 1.0
