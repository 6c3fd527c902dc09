"""Family-agnostic serving: the ISSUE-10 acceptance surface.

Every model family in the zoo decodes through the SAME engine loop via
its `serving.state.DecodeState`; these tests pin the contract:

* engine-vs-model parity per family — the engine's slot-churned decode
  must emit exactly the tokens a model-level `api.prefill` +
  `api.decode_step` greedy loop emits for each request;
* the pre-refactor GOLDEN token trace — a fixed-seed transformer run
  whose tokens were captured before the DecodeState refactor; every
  engine path (paged/dense x compact/full x f32/int8) must still
  reproduce it byte-for-byte;
* RecurrentState gather/scatter roundtrips under slot churn (property
  test: padding lanes duplicate real slots, untouched slots stay
  bitwise identical);
* mixed-family ServingCluster — tagged requests route only to replicas
  serving their model and finish with the tokens a per-family
  single-engine run produces;
* live speculative decoding — greedy token-exact vs the target-only
  engine, acceptance at the `high_tar_pair` shared-prefix ceiling, and
  the sampled-temperature rejection.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.models import api
from repro.models.config import ModelConfig
from repro.serving import workload
from repro.serving.cluster import ServingCluster
from repro.serving.engine import Request, ServingEngine
from repro.serving.specdec import SpecDecodeEngine, high_tar_pair
from repro.serving.state import RecurrentState, _gather_layers, _scatter_layers

TINY = dict(n_layers=2, d_model=32, d_ff=64, vocab=61,
            dtype="float32", param_dtype="float32")
ENC_LEN = 8

FAMILY_CFGS = {
    "transformer": ModelConfig(name="fam-tf", n_heads=2, kv_heads=1,
                               head_dim=16, scan_layers=False, **TINY),
    "rglru": ModelConfig(name="fam-rg", family="rglru", n_heads=2,
                         kv_heads=1, head_dim=16, lru_width=48,
                         attn_every=2, window=8, **TINY),
    "rwkv6": ModelConfig(name="fam-rw", family="rwkv6", head_dim=16,
                         wkv_chunk=8, **TINY),
    "whisper": ModelConfig(name="fam-wh", family="whisper", n_enc_layers=1,
                           n_heads=2, kv_heads=2, norm="layernorm",
                           swiglu=False, **TINY),
}


@pytest.fixture(scope="module")
def family_params():
    return {fam: api.init_params(cfg, jax.random.PRNGKey(0))
            for fam, cfg in FAMILY_CFGS.items()}


def _family_requests(cfg, n=3, max_new=5, seed=5):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(3, 9))
        frames = None
        if cfg.family == "whisper":
            frames = workload.synthetic_frames(rng, ENC_LEN, cfg.d_model)
        reqs.append(Request(rid=i,
                            prompt=rng.integers(0, cfg.vocab, size=plen)
                            .astype(np.int32),
                            max_new_tokens=max_new, frames=frames))
    return reqs


def _model_greedy(cfg, params, req, max_len=32):
    """Model-level reference: api.prefill + api.decode_step, batch=1,
    greedy argmax — the oracle the engine must match token-for-token."""
    toks = jnp.asarray(req.prompt[None, :], jnp.int32)
    if cfg.family == "whisper":
        # same fixed-window padding CrossAttnState applies at admission
        frames = np.zeros((1, ENC_LEN, cfg.d_model), np.float32)
        f = np.asarray(req.frames, np.float32)
        frames[0, :min(len(f), ENC_LEN)] = f[:ENC_LEN]
        last, cache = api.prefill(cfg, params, {
            "embeds": jnp.asarray(frames), "tokens": toks}, max_len)
    else:
        last, cache = api.prefill(cfg, params, {"tokens": toks}, max_len)
    out = [int(jnp.argmax(last[0, -1]))]
    while len(out) < req.max_new_tokens:
        lg, cache = api.decode_step(
            cfg, params, jnp.asarray([[out[-1]]], jnp.int32), cache)
        out.append(int(jnp.argmax(lg[0, -1])))
    return out


@pytest.mark.parametrize("family", sorted(FAMILY_CFGS))
def test_engine_matches_model_decode(family, family_params):
    """Slot-churned engine decode (max_batch=2 over 3 requests, so one
    request admits mid-flight) == per-request model-level greedy."""
    cfg = FAMILY_CFGS[family]
    params = family_params[family]
    reqs = _family_requests(cfg)
    eng = ServingEngine(cfg, params, max_batch=2, max_len=32,
                        paged=False, enc_len=ENC_LEN)
    assert eng.state.kind == {"transformer": "dense", "rglru": "recurrent",
                              "rwkv6": "recurrent",
                              "whisper": "cross_attn"}[family]
    for r in reqs:
        eng.submit(r)
    eng.run()
    for r in reqs:
        assert r.finish_reason == "max_new_tokens"
        assert r.out_tokens == _model_greedy(cfg, params, r), \
            f"{family} engine diverged from model-level decode"


# -- golden trace: every engine variant against the model-level oracle -------

GOLDEN_CFG = ModelConfig(name="golden", n_layers=2, d_model=64, n_heads=4,
                         kv_heads=2, head_dim=16, d_ff=128, vocab=97,
                         dtype="float32", param_dtype="float32",
                         scan_layers=False)


def _golden_requests():
    rng = np.random.default_rng(7)
    reqs = []
    for i in range(6):
        plen = int(rng.integers(3, 9))
        reqs.append(Request(rid=i,
                            prompt=rng.integers(0, 97, size=plen)
                            .astype(np.int32),
                            max_new_tokens=6))
    return reqs


@pytest.mark.parametrize("paged,compact,kv_quant", [
    (True, True, "0"), (False, True, "0"), (False, False, "0"),
    (True, True, "1"), (False, True, "dense"),
])
def test_golden_trace_survives_refactor(paged, compact, kv_quant):
    """Paged/dense x compact/full x f32/int8 engines over 6 requests on 4
    slots emit exactly the model-level greedy tokens computed in the same
    run (token ids captured once go stale across JAX releases; the oracle
    does not)."""
    params = api.init_params(GOLDEN_CFG, jax.random.PRNGKey(0))
    eng = ServingEngine(GOLDEN_CFG, params, max_batch=4, max_len=32,
                        paged=paged, compact=compact, kv_quant=kv_quant)
    reqs = _golden_requests()
    for r in reqs:
        eng.submit(r)
    eng.run()
    want = [_model_greedy(GOLDEN_CFG, params, r) for r in _golden_requests()]
    assert [r.out_tokens for r in reqs] == want


# -- RecurrentState gather/scatter roundtrip (property) -----------------------

RS_CFG = FAMILY_CFGS["rwkv6"]


def _filled_state(max_batch=4, max_len=16):
    """RecurrentState whose every leaf row b is filled with value b+1,
    so slot provenance is readable off any element."""
    state = RecurrentState(RS_CFG, max_batch, max_len, decode_batch=2)
    state.cache["layers"] = jax.tree.map(
        lambda a: jnp.broadcast_to(
            jnp.arange(1, max_batch + 1, dtype=a.dtype)
            .reshape((max_batch,) + (1,) * (a.ndim - 1)), a.shape).copy()
        if a.ndim >= 1 and a.shape[0] == max_batch else a,
        state.cache["layers"])
    state.cache["index"] = jnp.arange(max_batch, dtype=jnp.int32) * 3
    return state


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000))
def test_recurrent_gather_scatter_roundtrip(seed):
    """Under arbitrary slot churn (random active sets, padding lanes
    duplicating active[0]) a gather->scatter with an unmodified sub-cache
    is the identity, and a modified sub-cache writes ONLY the selected
    slots — inactive recurrent state must stay bitwise untouched."""
    rng = np.random.default_rng(seed)
    max_batch = 4
    state = _filled_state(max_batch)
    before = jax.tree.map(lambda a: np.asarray(a), state.cache)
    n_active = int(rng.integers(1, max_batch + 1))
    active = sorted(rng.choice(max_batch, size=n_active, replace=False)
                    .tolist())
    sel = active + [active[0]] * (state.decode_batch - len(active)) \
        if n_active < state.decode_batch else active[:state.decode_batch]
    sel_arr = jnp.asarray(sel, jnp.int32)

    sub = _gather_layers(state.cache, sel_arr)
    for j, b in enumerate(sel):
        got = np.asarray(jax.tree.leaves(sub["layers"])[0])[j]
        assert np.all(got == b + 1)
    # identity roundtrip
    back = _scatter_layers(state.cache, sub, sel_arr)
    for a, c in zip(jax.tree.leaves(back), jax.tree.leaves(before)):
        np.testing.assert_array_equal(np.asarray(a), c)
    # modified sub touches exactly the selected slots
    bumped = {"layers": jax.tree.map(lambda a: a + 100, sub["layers"]),
              "index": sub["index"] + 1}
    after = _scatter_layers(state.cache, bumped, sel_arr)
    touched = set(sel)
    for leaf_a, leaf_b in zip(jax.tree.leaves(after["layers"]),
                              jax.tree.leaves(before["layers"])):
        for b in range(max_batch):
            if b in touched:
                assert np.all(np.asarray(leaf_a)[b] == leaf_b[b] + 100)
            else:
                np.testing.assert_array_equal(np.asarray(leaf_a)[b],
                                              leaf_b[b])


# -- mixed-family cluster -----------------------------------------------------

def test_mixed_family_cluster_token_parity(family_params):
    """A fleet with one transformer and one rwkv6 replica: tagged
    requests route only to their family's replica and finish with
    exactly the tokens the per-family single-engine runs produce."""
    tf_cfg, rw_cfg = FAMILY_CFGS["transformer"], FAMILY_CFGS["rwkv6"]
    tf_p, rw_p = family_params["transformer"], family_params["rwkv6"]

    def traces():
        tf_t = workload.zipf_mix_requests(
            np.random.default_rng(2), 4, tf_cfg.vocab,
            bands=((3, 8),), max_new_tokens=5, model=tf_cfg.name)
        rw_t = workload.zipf_mix_requests(
            np.random.default_rng(9), 4, rw_cfg.vocab,
            bands=((3, 8),), max_new_tokens=5, model=rw_cfg.name)
        return tf_t, rw_t

    tf_trace, rw_trace = traces()
    cluster = ServingCluster(
        tf_cfg, tf_p, replica_models=[(tf_cfg, tf_p), (rw_cfg, rw_p)],
        max_batch=2, max_len=32, paged=False)
    merged = workload.interleave_tagged([tf_trace, rw_trace])
    for r in merged:
        cluster.submit(r)
    cluster.run()
    # every tagged request landed on the one eligible replica
    for r in merged:
        assert r.finish_reason == "max_new_tokens"
        i = cluster.assignment[r.rid]
        assert cluster.replicas[i].mcfg.name == r.model

    ref_tf, ref_rw = traces()
    for cfg, p, trace in ((tf_cfg, tf_p, ref_tf), (rw_cfg, rw_p, ref_rw)):
        eng = ServingEngine(cfg, p, max_batch=2, max_len=32, paged=False)
        for r in trace:
            eng.submit(r)
        eng.run()
    assert [r.out_tokens for r in tf_trace] == [r.out_tokens for r in ref_tf]
    assert [r.out_tokens for r in rw_trace] == [r.out_tokens for r in ref_rw]


# -- live speculative decoding ------------------------------------------------

SPEC_CFG = ModelConfig(name="fam-spec", n_layers=4, d_model=32, n_heads=2,
                       kv_heads=1, head_dim=16, d_ff=64, vocab=61,
                       dtype="float32", param_dtype="float32",
                       scan_layers=False)


def _spec_requests(n=4, max_new=8):
    rng = np.random.default_rng(13)
    return [Request(rid=i,
                    prompt=rng.integers(0, SPEC_CFG.vocab,
                                        size=int(rng.integers(3, 8)))
                    .astype(np.int32),
                    max_new_tokens=max_new) for i in range(n)]


def test_specdec_token_exact_vs_target_only():
    """Greedy spec-decode emits EXACTLY the target-only stream even with
    a random (near-zero-acceptance) draft — acceptance only buys speed."""
    params = api.init_params(SPEC_CFG, jax.random.PRNGKey(0))
    dcfg = SPEC_CFG.replace(name="fam-spec-d", n_layers=1)
    dparams = api.init_params(dcfg, jax.random.PRNGKey(1))
    ref = ServingEngine(SPEC_CFG, params, max_batch=2, max_len=32,
                        paged=False)
    ref_reqs = _spec_requests()
    for r in ref_reqs:
        ref.submit(r)
    ref.run()
    eng = SpecDecodeEngine(SPEC_CFG, params, dcfg, dparams, k=3,
                           max_batch=2, max_len=32)
    reqs = _spec_requests()
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert [r.out_tokens for r in reqs] == \
        [r.out_tokens for r in ref_reqs]
    assert eng.spec_stats.iterations > 0


def test_specdec_high_tar_pair_full_acceptance():
    """high_tar_pair zeroes the target's residual writes past n_draft, so
    the draft IS the target's prefix: every proposal must be accepted."""
    params = api.init_params(SPEC_CFG, jax.random.PRNGKey(0))
    tparams, dcfg, dparams = high_tar_pair(SPEC_CFG, params, 2)
    eng = SpecDecodeEngine(SPEC_CFG, tparams, dcfg, dparams, k=3,
                           max_batch=2, max_len=32)
    reqs = _spec_requests()
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert eng.spec_stats.acceptance_rate == pytest.approx(1.0)
    assert eng.spec_stats.tokens_per_iteration == pytest.approx(3.0)


def test_specdec_rejects_sampled_requests():
    params = api.init_params(SPEC_CFG, jax.random.PRNGKey(0))
    dcfg = SPEC_CFG.replace(name="fam-spec-d2", n_layers=1)
    dparams = api.init_params(dcfg, jax.random.PRNGKey(1))
    eng = SpecDecodeEngine(SPEC_CFG, params, dcfg, dparams, k=2,
                           max_batch=2, max_len=32)
    with pytest.raises(ValueError, match="greedy"):
        eng.submit(Request(rid=0, prompt=np.asarray([1, 2, 3], np.int32),
                           temperature=0.7))
