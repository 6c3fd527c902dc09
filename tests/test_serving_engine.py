"""Tier-1 serving-engine scheduling tests: slot-stable rotation under
churn, sampled admission, compacted sub-batch gather/scatter, and
compacted-vs-full decode parity on a real (tiny) model.

The scheduling tests drive the engine with a STUB model (the jitted
decode/prefill attributes are replaced after construction), so they
exercise the host-side slot logic without any XLA compilation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import api
from repro.models.config import ModelConfig
from repro.serving import engine as eng_mod
from repro.serving.engine import Request, ServingEngine
from repro.serving.sampling import sample

TINY = ModelConfig(
    name="tiny",
    n_layers=1,
    d_model=32,
    n_heads=2,
    kv_heads=1,
    head_dim=16,
    d_ff=64,
    vocab=61,
    dtype="float32",
    param_dtype="float32",
    scan_layers=False,
)


def _stub_engine(max_batch=4, decode_batch=None, compact=True, vocab=61):
    """Engine whose decode/prefill are pure-Python fakes: decode emits
    logits peaked at (slot_index + step) % vocab and advances the cache
    index; prefill fills a length-1 cache."""
    eng = ServingEngine(
        TINY,
        params={},
        max_batch=max_batch,
        max_len=16,
        decode_batch=decode_batch,
        compact=compact,
        paged=False,  # the fakes replace the DENSE decode/prefill path
    )

    def fake_decode(params, tokens, cache):
        b = tokens.shape[0]
        step = int(np.asarray(cache["index"]).max())
        logits = np.full((b, 1, vocab), -1e9, np.float32)
        for j in range(b):
            logits[j, 0, (j + step) % vocab] = 0.0
        return jnp.asarray(logits), {
            "segments": cache["segments"],
            "index": cache["index"] + 1,
        }

    def fake_prefill(params, toks):
        cache = api.init_cache(TINY, 1, eng.max_len)
        logits = np.zeros((1, 1, vocab), np.float32)
        logits[0, 0, int(toks[0, -1]) % vocab] = 5.0
        return jnp.asarray(logits), cache

    eng._decode = fake_decode
    eng._prefill = fake_prefill
    return eng


def test_non_transformer_family_always_compacts():
    """Recurrent families advance state irreversibly — there is nothing
    to rewind after a full-width emulation step — so their DecodeState is
    ALWAYS the gathered sub-batch form, regardless of the compact knob."""
    rglru_cfg = ModelConfig(
        name="tiny-rglru",
        family="rglru",
        n_layers=2,
        d_model=32,
        n_heads=2,
        kv_heads=2,
        head_dim=16,
        d_ff=64,
        vocab=61,
        attn_every=2,
        lru_width=32,
        dtype="float32",
        param_dtype="float32",
        scan_layers=False,
    )
    eng = ServingEngine(
        rglru_cfg, params={}, max_batch=4, max_len=16, decode_batch=2
    )
    assert eng.compact is True
    assert eng.state.kind == "recurrent"
    eng_full = ServingEngine(
        rglru_cfg, params={}, max_batch=4, max_len=16, decode_batch=2,
        compact=False
    )
    assert eng_full.compact is True
    tf_eng = _stub_engine(max_batch=4, decode_batch=2)
    assert tf_eng.compact is True


def test_select_active_rotation_is_slot_stable():
    eng = _stub_engine(max_batch=4, decode_batch=2)
    assert eng._select_active([0, 1, 2, 3]) == [0, 1]
    assert eng._select_active([0, 1, 2, 3]) == [2, 3]
    # slot 1 finishes: remaining slots keep their cyclic order — the
    # cursor is a slot id, so the shrink cannot re-alias the rotation
    assert eng._select_active([0, 2, 3]) == [0, 2]
    assert eng._select_active([0, 2, 3]) == [3, 0]
    # slot 1 slot is re-admitted mid-cycle: it joins at its slot id
    assert eng._select_active([0, 1, 2, 3]) == [1, 2]
    assert eng._select_active([0, 1, 2, 3]) == [3, 0]
    # fewer active than the sub-batch width: everyone advances
    assert eng._select_active([2]) == [2]


def test_rotation_fairness_under_churn():
    """Under admission/finish churn every concurrently-active slot is
    served within one rotation of every other (the PR-4 cursor, taken
    modulo the shifting active COUNT, starved or double-served slots)."""
    eng = _stub_engine(max_batch=4, decode_batch=2)
    served: list[list[int]] = []
    orig = eng._select_active

    def spy(all_active):
        picked = orig(all_active)
        served.append((list(all_active), list(picked)))
        return picked

    eng._select_active = spy
    # staggered lengths force churn: slots finish and re-fill mid-run
    lengths = [3, 9, 5, 7, 4, 6, 8, 3]
    for i, n in enumerate(lengths):
        eng.submit(
            Request(rid=i, prompt=np.asarray([i + 1], np.int32), max_new_tokens=n)
        )
    eng.run()
    assert all(r is None for r in eng.slots) and not eng.queue
    # fairness: within every window where the active set is unchanged,
    # serve counts differ by at most one across the set's slots
    i = 0
    while i < len(served):
        j = i
        while j < len(served) and served[j][0] == served[i][0]:
            j += 1
        counts = {b: 0 for b in served[i][0]}
        for _, picked in served[i:j]:
            for b in picked:
                counts[b] += 1
        if len(counts) > 1:
            assert max(counts.values()) - min(counts.values()) <= 1, (
                served[i][0],
                counts,
            )
        i = j
    # every step serves min(width, active) distinct slots
    for all_active, picked in served:
        assert len(set(picked)) == len(picked)
        assert len(picked) == min(2, len(all_active))


def test_admit_samples_with_request_temperature():
    """The first (prefill) token goes through sampling.sample with the
    request's temperature/key and is counted in tokens_out."""
    eng = _stub_engine(max_batch=1)
    req = Request(
        rid=0, prompt=np.asarray([7], np.int32), max_new_tokens=1, temperature=3.0
    )
    eng.submit(req)
    # replicate the engine's key stream for the admission sample
    key0 = jax.random.PRNGKey(0)
    _, k = jax.random.split(key0)
    logits = np.zeros((1, 61), np.float32)
    logits[0, 7] = 5.0
    want = int(sample(jnp.asarray(logits), k, temperature=3.0)[0])
    eng.step()
    assert req.out_tokens[0] == want
    assert eng.stats["tokens_out"] == 1
    assert eng.stats["prefills"] == 1


def test_gather_scatter_roundtrip():
    cache = api.init_cache(TINY, 4, 8)
    cache["index"] = jnp.asarray([3, 1, 4, 2], jnp.int32)
    cache["segments"] = jax.tree.map(
        lambda a: jax.random.normal(jax.random.PRNGKey(a.ndim), a.shape),
        cache["segments"],
    )
    sel = jnp.asarray([2, 0], jnp.int32)
    sub = eng_mod._gather_slots(cache, sel)
    assert int(sub["index"][0]) == 4 and int(sub["index"][1]) == 3
    for full, part in zip(
        jax.tree_util.tree_leaves(cache["segments"]),
        jax.tree_util.tree_leaves(sub["segments"]),
    ):
        np.testing.assert_array_equal(np.asarray(part), np.asarray(full[:, [2, 0]]))
    back = eng_mod._scatter_slots(cache, sub, sel)
    for a, b in zip(jax.tree_util.tree_leaves(cache), jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_steady_state_serving_does_not_recompile():
    """tracecheck (the runtime half of MZC01): after one warm-up run,
    compacted decode steps and prefills of an already-seen prompt length
    build 0 new XLA executables — the static shapes really are static."""
    from tools.mozart_check.tracecheck import CompileMonitor

    params = api.init_params(TINY, jax.random.PRNGKey(0))

    def drive(n_reqs):
        eng = ServingEngine(
            TINY, params, max_batch=4, max_len=32, decode_batch=2, compact=True
        )
        reqs = [
            Request(
                rid=i,
                prompt=(np.arange(6) % TINY.vocab).astype(np.int32),
                max_new_tokens=4,
            )
            for i in range(n_reqs)
        ]
        for r in reqs:
            eng.submit(r)
        eng.run()
        assert eng.stats["decode_steps"] > 0
        assert all(r.done for r in reqs)
        return eng

    # warm-up compiles the length-6 prefill, the compacted decode, and
    # the gather/scatter pair (the jitted builders are lru-cached per
    # config, so a fresh engine below reuses every executable)
    drive(4)
    with CompileMonitor() as mon:
        # more requests than slots: steady-state decode plus repeated
        # same-length prefills through admission churn
        drive(6)
    assert mon.count == 0, mon.events


@pytest.mark.parametrize("temperature", [0.0])
def test_compacted_decode_matches_full_batch(temperature):
    """Fixed-seed bit-parity: compacted sub-batch decode, the legacy
    full-width emulation, and plain full-batch decode emit identical
    tokens; compaction trades steps for narrow width."""
    params = api.init_params(TINY, jax.random.PRNGKey(0))
    prompts = [np.arange(5, dtype=np.int32) + i for i in range(4)]

    def run(decode_batch, compact):
        eng = ServingEngine(
            TINY,
            params,
            max_batch=4,
            max_len=32,
            decode_batch=decode_batch,
            compact=compact,
        )
        reqs = [
            Request(rid=i, prompt=p, max_new_tokens=5, temperature=temperature)
            for i, p in enumerate(prompts)
        ]
        for r in reqs:
            eng.submit(r)
        eng.run()
        return [r.out_tokens for r in reqs], eng.stats["decode_steps"]

    full, steps_full = run(4, True)
    comp, steps_comp = run(2, True)
    emul, steps_emul = run(2, False)
    assert comp == full
    assert emul == full
    assert steps_comp == steps_emul > steps_full


# -- batched sampling ----------------------------------------------------------


def _loop_sample(logits, key, lanes, temps):
    """The per-slot loop the batched sampler replaces: split the key once
    per active slot, in order, and sample that slot's row."""
    toks = []
    for lane, t in zip(lanes, temps):
        key, k = jax.random.split(key)
        toks.append(int(sample(logits[lane, -1:], k, temperature=t)[0]))
    return toks, key


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "width, lanes, temps",
    [
        pytest.param(4, [0, 1, 2, 3], [0.0] * 4, id="greedy"),
        pytest.param(4, [2, 0, 3, 1], [0.0, 0.7, 0.0, 3.0], id="mixed"),
        pytest.param(4, [0, 1, 2, 3], [0.5, 1.0, 2.0, 3.0], id="sampled"),
        pytest.param(4, [1, 0], [0.7, 1.3], id="padding"),
        # the dense state's full-width lane map {3: 3, 1: 1}: not contiguous
        pytest.param(4, [3, 1], [1.5, 0.0], id="dense-full-width"),
        pytest.param(1, [0], [3.0], id="admission"),
    ],
)
def test_batched_sampler_matches_the_per_slot_loop(width, lanes, temps, dtype):
    """One program, one read: the same tokens, and the same key left
    behind, as splitting the key and sampling slot by slot."""
    eng = _stub_engine(max_batch=4)
    eng.key = jax.random.PRNGKey(2**31 - 5)
    rng = np.random.default_rng(width * 10 + len(lanes))
    logits = jnp.asarray(rng.normal(size=(width, 1, TINY.vocab)) * 2, dtype)
    want, want_key = _loop_sample(logits, eng.key, lanes, temps)
    toks, finite, key = eng._sample(logits, lanes, temps)
    assert toks.tolist() == want and finite
    np.testing.assert_array_equal(np.asarray(key), np.asarray(want_key))
    assert eng.stats["host_syncs"] == 1


def test_active_count_never_recompiles_the_sampler():
    """CompileMonitor-verified: after a warm-up at full width, a paged
    engine whose active count runs through every value from 1 to its
    width, with greedy and sampled requests, compiles nothing."""
    from tools.mozart_check.tracecheck import CompileMonitor

    params = api.init_params(TINY, jax.random.PRNGKey(0))
    eng = ServingEngine(TINY, params, max_batch=4, max_len=32, paged=True)
    prompt = (np.arange(6) % TINY.vocab).astype(np.int32)
    for i in range(4):
        eng.submit(Request(rid=i, prompt=prompt, max_new_tokens=3))
    eng.run()
    served = []
    with CompileMonitor() as mon:
        for i in range(4):
            eng.submit(Request(rid=10 + i, prompt=prompt, max_new_tokens=6 - i,
                               temperature=0.8 * (i % 2)))
            served.append(eng.step())
        while eng.queue or any(s is not None for s in eng.slots):
            served.append(eng.step())
    assert set(served) >= {1, 2, 3, 4}
    assert mon.count == 0, mon.events


@pytest.mark.parametrize("guard", [True, False])
def test_non_finite_logits_emit_nothing_and_keep_the_key(guard):
    """With the guard on, a step over non-finite logits emits no token,
    flags the engine and leaves its key where it was; with the guard off
    the flag is not read and the step samples as usual."""
    eng = _stub_engine(max_batch=2)
    eng.guard_nan = guard
    reqs = [Request(rid=i, prompt=np.asarray([3 + i], np.int32), max_new_tokens=8)
            for i in range(2)]
    for r in reqs:
        eng.submit(r)
    assert eng.step() == 2
    real = eng._decode

    def poisoned(params, tokens, cache):
        logits, cache = real(params, tokens, cache)
        return logits.at[0, 0, 0].set(jnp.nan), cache

    eng._decode = poisoned
    key, emitted = np.asarray(eng.key), [len(r.out_tokens) for r in reqs]
    syncs = eng.stats["host_syncs"]
    served = eng.step()
    assert eng.stats["host_syncs"] == syncs + 1
    if guard:
        assert served == 0
        assert [len(r.out_tokens) for r in reqs] == emitted
        assert eng.health["nan_detected"] and eng.stats["nan_steps"] == 1
        np.testing.assert_array_equal(np.asarray(eng.key), key)
    else:
        assert served == 2
        assert [len(r.out_tokens) for r in reqs] == [n + 1 for n in emitted]
        assert not eng.health["nan_detected"] and eng.stats["nan_steps"] == 0
        assert not np.array_equal(np.asarray(eng.key), key)
