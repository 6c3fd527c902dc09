"""Tier-1 tests for the block-paged KV cache + bucketed prefill.

Covers the ISSUE-7 acceptance surface: page lifecycle (alloc/free under
churn, preemption, the reserved null page), bucket-boundary prefill
parity (prompt lengths at bucket, bucket-1, bucket+1), paged-vs-dense
decode bit-parity on fixed seeds, the CompileMonitor-verified prefill
executable budget over a mixed prompt-length run, the cache-boundary
admission/decode bugfixes, and the paged-attention kernel triplet.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import api
from repro.models.config import ModelConfig
from repro.kernels.flash_attention.ops import to_pool_rows
from repro.serving import paged as paged_mod
from repro.serving.engine import Request, ServingEngine
from repro.serving.paged import PagePool, bucket_for, prefill_buckets

TINY = ModelConfig(
    name="tiny-paged",
    n_layers=2,
    d_model=32,
    n_heads=4,
    kv_heads=2,
    head_dim=8,
    d_ff=64,
    vocab=61,
    dtype="float32",
    param_dtype="float32",
    scan_layers=False,
)


@pytest.fixture(scope="module")
def tiny_params():
    return api.init_params(TINY, jax.random.PRNGKey(0))


def _prompt(rng, n):
    return rng.integers(1, TINY.vocab - 1, size=n).astype(np.int32)


def _run_engine(params, prompts, *, max_new=8, **kw):
    eng = ServingEngine(TINY, params, **kw)
    reqs = [
        Request(rid=i, prompt=p, max_new_tokens=max_new) for i, p in enumerate(prompts)
    ]
    for r in reqs:
        eng.submit(r)
    eng.run()
    return eng, reqs


# -- bucket math --------------------------------------------------------------


def test_prefill_buckets_cover_admissible_lengths():
    buckets = prefill_buckets(512, 16)
    assert buckets == (16, 32, 64, 128, 256, 512)
    assert prefill_buckets(64, 16) == (16, 32, 64)
    # a non-power-of-two max_len is covered by the next bucket up
    assert prefill_buckets(100, 16)[-1] >= 99
    for plen in (1, 16, 17, 99):
        assert bucket_for(plen, prefill_buckets(100, 16)) >= plen
    with pytest.raises(ValueError):
        bucket_for(1000, prefill_buckets(64, 16))


# -- page pool lifecycle ------------------------------------------------------


def test_page_pool_alloc_free_churn():
    pool = PagePool(TINY, max_batch=4, max_len=64, page_size=16)
    total = pool.num_pages - 1  # page 0 is the reserved null page
    assert pool.free_pages == total
    assert pool.ensure(0, 20)  # 2 pages
    assert pool.ensure(1, 16)  # 1 page
    assert pool.owned(0) != pool.owned(1)
    assert 0 not in pool.owned(0) and 0 not in pool.owned(1)
    assert pool.free_pages == total - 3
    # growth is incremental and idempotent
    assert pool.ensure(0, 21)
    assert pool.ensure(0, 33)
    assert len(pool.owned(0)) == 3
    # table rows mirror ownership, null-padded to the requested width
    row = pool.table_row(0, 4)
    assert tuple(row[:3]) == pool.owned(0) and row[3] == 0
    pool.release(0)
    assert pool.free_pages == total - 1
    assert pool.owned(0) == () and not pool.tables[0].any()
    # churn: repeated alloc/release cycles conserve the pool exactly
    for i in range(25):
        b = i % 4
        assert pool.ensure(b, 1 + (i * 7) % 60)
        pool.release(b)
    pool.release(1)
    assert pool.free_pages == total
    assert pool.stats["page_allocs"] == pool.stats["page_frees"]
    assert pool.stats["peak_pages_in_use"] <= total


def test_page_pool_exhaustion_is_atomic():
    pool = PagePool(TINY, max_batch=2, max_len=64, page_size=16, num_pages=4)
    assert pool.ensure(0, 32)  # 2 of 3 usable pages
    free_before = pool.free_pages
    assert not pool.ensure(1, 32)  # needs 2, only 1 left: no partial alloc
    assert pool.free_pages == free_before and pool.owned(1) == ()
    assert pool.ensure(1, 16)
    with pytest.raises(ValueError):
        PagePool(TINY, max_batch=1, max_len=64, page_size=24)


def test_eviction_under_churn_frees_every_page(tiny_params):
    """A pool far too small for the offered load forces preemptions; all
    requests still finish and every page returns to the free list."""
    rng = np.random.default_rng(3)
    prompts = [_prompt(rng, n) for n in (20, 30, 25, 18, 22, 27)]
    eng, reqs = _run_engine(
        tiny_params,
        prompts,
        max_new=16,
        max_batch=4,
        max_len=64,
        paged=True,
        page_size=16,
        num_pages=9,
    )
    assert all(r.done for r in reqs)
    assert all(len(r.out_tokens) == 16 for r in reqs)
    assert eng.stats["preemptions"] > 0
    assert eng.pool.free_pages == eng.pool.num_pages - 1
    assert eng.pool.stats["page_allocs"] == eng.pool.stats["page_frees"]


def test_lone_request_exhausting_pool_finishes_with_capacity(tiny_params):
    eng = ServingEngine(
        TINY, tiny_params, max_batch=1, max_len=512, paged=True, page_size=16, num_pages=3
    )
    req = Request(rid=0, prompt=np.arange(1, 11, dtype=np.int32), max_new_tokens=400)
    eng.submit(req)
    eng.run()
    assert req.done and req.finish_reason == "capacity"
    # 2 usable pages = 32 positions; prompt used 10
    assert len(req.out_tokens) == 32 - 10 + 1


# -- parity against the dense cache -------------------------------------------


@pytest.mark.parametrize("delta", [-1, 0, 1])
def test_bucket_boundary_prefill_parity(tiny_params, delta):
    """Prompt lengths straddling a bucket edge (bucket-1, bucket, and
    bucket+1, which spills into the next bucket) emit exactly the dense
    engine's tokens."""
    bucket = 16
    rng = np.random.default_rng(40 + delta)
    prompts = [_prompt(rng, bucket + delta)]
    kw = dict(max_new=8, max_batch=2, max_len=64)
    _, dense = _run_engine(tiny_params, prompts, paged=False, **kw)
    _, paged = _run_engine(tiny_params, prompts, paged=True, **kw)
    assert [r.out_tokens for r in paged] == [r.out_tokens for r in dense]


@pytest.mark.parametrize("compact", [True, False])
def test_paged_matches_dense_on_fixed_seed_mix(tiny_params, compact):
    """Fixed-seed bit-parity over a mixed-length workload with admission
    churn, in both the compacted and full-width-emulation schedules."""
    rng = np.random.default_rng(7)
    prompts = [_prompt(rng, n) for n in (5, 17, 33, 9, 21, 40, 2, 13)]
    kw = dict(max_new=10, max_batch=4, max_len=64, decode_batch=2, compact=compact)
    _, dense = _run_engine(tiny_params, prompts, paged=False, **kw)
    _, paged = _run_engine(tiny_params, prompts, paged=True, **kw)
    assert [r.out_tokens for r in paged] == [r.out_tokens for r in dense]
    assert [r.finish_reason for r in paged] == [r.finish_reason for r in dense]


def test_paged_decode_gather_is_bit_identical(tiny_params):
    """The paged decode (pool read in place by the kernel, then the new
    token's K/V written into its page) matches the dense cache within
    float32 rounding: copy one dense cache into pool pages by hand and
    compare the decode logits and the written K/V."""
    from repro.serving.engine import _decode_fn
    from repro.serving.paged import paged_decode_fn

    rng = np.random.default_rng(11)
    max_len, bsz = 64, 2
    toks = jnp.asarray(np.stack([_prompt(rng, 33), _prompt(rng, 33)]))
    _, cache = api.prefill(TINY, tiny_params, {"tokens": toks}, max_len)
    index = np.asarray([33, 33], np.int32)
    cache = {"segments": cache["segments"], "index": jnp.asarray(index)}
    pool = PagePool(TINY, max_batch=bsz, max_len=max_len, page_size=16)
    ps = pool.page_size
    for b in range(bsz):
        assert pool.ensure(b, 34)
        pool.index[b] = 33
    new_segs = []
    for seg_d, seg_p in zip(cache["segments"], pool.segments):

        def place(pages, dense):
            out = np.asarray(pages).copy()
            rows = np.asarray(to_pool_rows(dense, pages.shape[-1]))
            for b in range(bsz):
                for j, pg in enumerate(pool.owned(b)):
                    out[:, pg] = rows[:, b, j * ps : (j + 1) * ps]
            return jnp.asarray(out)

        new_segs.append(jax.tree.map(place, seg_p, seg_d))
    pool.segments = new_segs
    tok = jnp.asarray([[7], [9]], jnp.int32)
    sel = np.asarray([0, 1])
    logits_d, new_d = _decode_fn(TINY)(tiny_params, tok, cache)
    logits_p, segs_p = paged_decode_fn(TINY)(
        tiny_params, tok, pool.segments, pool.tables[sel], pool.index[sel]
    )
    np.testing.assert_allclose(
        np.asarray(logits_p), np.asarray(logits_d), rtol=1e-5, atol=1e-5
    )
    for seg_d, seg_p in zip(new_d["segments"], segs_p):
        for name in ("k", "v"):
            rows = np.asarray(to_pool_rows(seg_d[name], seg_p[name].shape[-1]))
            for b in range(bsz):
                page = pool.owned(b)[33 // ps]
                np.testing.assert_allclose(
                    np.asarray(seg_p[name])[:, page, 33 % ps],
                    rows[:, b, 33],
                    rtol=1e-6,
                    atol=1e-6,
                )


def _gqa_config(n_heads, kv_heads, head_dim, dtype="float32"):
    return ModelConfig(
        name=f"paged-{n_heads}-{kv_heads}-{head_dim}-{dtype}",
        n_layers=2,
        d_model=64,
        n_heads=n_heads,
        kv_heads=kv_heads,
        head_dim=head_dim,
        d_ff=96,
        vocab=61,
        qkv_bias=n_heads == 10,
        dtype=dtype,
        param_dtype=dtype,
        scan_layers=False,
    )


# (query heads, kv heads, head_dim, dtype): groups 1, 2, 3 and 5 at head
# widths 64 and 128, one in bf16
GQA_CASES = [
    (2, 2, 64, "float32"),
    (4, 2, 128, "float32"),
    (9, 3, 64, "float32"),
    (10, 2, 128, "float32"),
    (4, 2, 128, "bfloat16"),
]


@pytest.mark.parametrize("case", GQA_CASES, ids=lambda c: "-".join(map(str, c)))
def test_paged_decode_step_matches_dense(case):
    """The in-place paged decode against the dense `decode_step` over the
    same KV: lanes of different lengths (one on a page boundary, one
    whose new token starts a page), stale data in a freed page, past the
    lengths and in the null page, padding lanes; and the new token's K/V
    land in the right page and offset and nowhere else."""
    from repro.models import transformer
    from repro.serving.state import PagedKVState

    n_heads, kv_heads, hd, dtype = case
    cfg = _gqa_config(n_heads, kv_heads, hd, dtype)
    params = api.init_params(cfg, jax.random.PRNGKey(3))
    rng = np.random.default_rng(sum(case[:3]))
    st = PagedKVState(cfg, 4, 64, decode_batch=4, compact=True, page_size=8,
                      num_pages=None, bucket_min=16)
    assert st.in_place
    pool = st.pool
    lengths = {0: 13, 1: 16, 2: 40}  # 16: a page boundary; 40: fills 5 pages
    assert pool.ensure(3, 30)
    pool.release(3)  # slot 3's pages are freed: stale data, never read
    for b, n in lengths.items():
        assert pool.ensure(b, n + 1)
        pool.index[b] = n
    # random KV everywhere, loud garbage in the null page
    pool.segments = [
        {
            name: jnp.asarray(
                rng.normal(size=a.shape) * np.where(np.arange(a.shape[1]) == 0, 1e3, 1.0)[
                    None, :, None, None
                ],
                a.dtype,
            )
            for name, a in seg.items()
        }
        for seg in pool.segments
    ]
    before = [jax.tree.map(np.asarray, seg) for seg in pool.segments]
    active = [2, 0, 1]  # one padding lane at width 4
    nxt = rng.integers(1, cfg.vocab, size=(4, 1)).astype(np.int32)
    logits, lane = st.decode(st.decode_fn(), params, nxt, active)

    # dense reference over the very same pages
    act = np.asarray(active)

    def heads(a):  # (L, P, ps, W) rows -> (L, n, C, kvh, hd) of the lanes
        g = a[:, pool.tables[act], :, : kv_heads * hd]
        return jnp.asarray(g.reshape(a.shape[0], len(active), -1, kv_heads, hd))

    dense = [jax.tree.map(heads, seg) for seg in before]
    idx = np.asarray([lengths[b] for b in active], np.int32)
    want, new = transformer.decode_step(
        cfg, params, jnp.asarray(nxt[act]), {"segments": dense, "index": jnp.asarray(idx)}
    )
    tol = 5e-2 if dtype == "bfloat16" else 1e-4
    got = np.asarray(logits, np.float32)[[lane[b] for b in active]]
    np.testing.assert_allclose(got, np.asarray(want, np.float32), rtol=tol, atol=tol)
    for seg_b, seg_a, seg_n in zip(before, pool.segments, new["segments"]):
        for name in ("k", "v"):
            after = np.asarray(seg_a[name]).copy()
            rows = np.asarray(to_pool_rows(seg_n[name], after.shape[-1]), np.float32)
            for j, b in enumerate(active):
                page = pool.tables[b, lengths[b] // 8]
                off = lengths[b] % 8
                np.testing.assert_allclose(
                    after[:, page, off].astype(np.float32), rows[:, j, lengths[b]],
                    rtol=tol, atol=tol,
                )
                after[:, page, off] = seg_b[name][:, page, off]
            # nothing else moved, apart from the padding lane's null-page write
            after[:, 0, 0] = seg_b[name][:, 0, 0]
            np.testing.assert_array_equal(after, seg_b[name])


MLA = ModelConfig(
    name="tiny-paged-mla", n_layers=2, d_model=32, n_heads=4, kv_heads=4,
    head_dim=8, d_ff=64, vocab=61, mla_q_rank=16, mla_kv_rank=16,
    mla_rope_dim=8, dtype="float32", param_dtype="float32", scan_layers=False,
)


@pytest.mark.parametrize("cfg", [MLA, TINY], ids=["mla-latents", "kv-heads-axis"])
def test_gathered_decode_is_bit_identical(cfg):
    """Where the gather path remains (an MLA latent pool, and a K/V pool
    with the kv heads on their own axis, as a mesh shards it), paged
    decode is BIT-exact against the dense cache: copy one dense cache into
    pool pages by hand, gather -> decode_step -> scatter, compare
    bitwise."""
    from repro.serving.engine import _decode_fn
    from repro.serving.paged import gathered_decode_fn

    params = api.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    max_len, bsz, n = 64, 2, 21
    toks = jnp.asarray(rng.integers(1, cfg.vocab - 1, size=(bsz, n)), jnp.int32)
    _, cache = api.prefill(cfg, params, {"tokens": toks}, max_len)
    cache = {"segments": cache["segments"], "index": jnp.full((bsz,), n, jnp.int32)}
    pool = PagePool(cfg, max_batch=bsz, max_len=max_len, page_size=16, rows=False)
    ps = pool.page_size
    for b in range(bsz):
        assert pool.ensure(b, n + 1)
        pool.index[b] = n

    def place(pages, dense):
        out = np.asarray(pages).copy()
        for b in range(bsz):
            for j, pg in enumerate(pool.owned(b)):
                out[:, pg] = np.asarray(dense)[:, b, j * ps : (j + 1) * ps]
        return jnp.asarray(out)

    segs = [jax.tree.map(place, seg_p, seg_d)
            for seg_d, seg_p in zip(cache["segments"], pool.segments)]
    tok = jnp.asarray([[7], [9]], jnp.int32)
    sel = np.asarray([0, 1])
    want, _ = _decode_fn(cfg)(params, tok, cache)
    got, _ = gathered_decode_fn(cfg)(params, tok, segs, pool.tables[sel], pool.index[sel])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize(
    "cfg, quantized, devices, in_place",
    [(TINY, False, None, True), (TINY, False, 1, True), (TINY, False, 4, False),
     (TINY, True, None, False), (MLA, False, None, False)],
    ids=["one-device", "one-device-mesh", "four-device-mesh", "int8", "mla-latents"],
)
def test_decode_path_follows_what_the_state_sees(cfg, quantized, devices, in_place):
    """One decision picks the decode and the pool layout it reads: the
    kernel reads a bf16/f32 K/V pool in rows on one device; the int8 pool,
    MLA latents and a pool over a mesh of several devices keep their
    layout and gather."""
    from types import SimpleNamespace

    from repro.serving.state import PagedKVState

    # the state reads only the mesh's size; a real mesh of several devices
    # is exercised in tests/parallel_prog.py
    mesh = None if devices is None else SimpleNamespace(size=devices)
    st = PagedKVState(cfg, 2, 32, decode_batch=2, compact=True, page_size=8,
                      num_pages=None, bucket_min=16, quantized=quantized, mesh=mesh)
    assert st.in_place is in_place
    ndims = {a.ndim for a in jax.tree.leaves(st.pool.segments)}
    if in_place:
        assert st.decode_fn() is paged_mod.paged_decode_fn(cfg, False)
        assert ndims == {4}  # (L, P, ps, W) rows
    elif quantized:
        assert st.decode_fn() is paged_mod.paged_decode_fn(cfg, True)
        assert ndims == {5}
    else:
        assert st.decode_fn() is paged_mod.gathered_decode_fn(cfg)
        assert ndims == ({4} if cfg.use_mla else {5})  # latents, or kv heads on axis 3


@pytest.mark.parametrize("page_size", [4, 16])
def test_in_place_decode_matches_dense_tokens(page_size):
    """Engine-level greedy token equality, paged (read in place) vs dense,
    for grouped heads: lengths cross page boundaries, slots churn, and the
    compacted width leaves padding lanes."""
    cfg = _gqa_config(6, 2, 16)
    params = api.init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(43)
    prompts = [rng.integers(1, cfg.vocab - 1, size=n).astype(np.int32) for n in (3, 15, 16, 29, 7)]
    outs = {}
    for paged in (True, False):
        eng = ServingEngine(cfg, params, max_batch=4, max_len=64, decode_batch=3,
                            paged=paged, page_size=page_size)
        assert not paged or eng.state.in_place
        reqs = [Request(rid=i, prompt=p, max_new_tokens=12) for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        eng.run()
        outs[paged] = [r.out_tokens for r in reqs]
    assert outs[True] == outs[False]
    assert all(len(t) == 12 for t in outs[True])


@pytest.mark.parametrize("quant", [False, True])
def test_decode_pages_read_counter(tiny_params, quant):
    """kv_pages_read / kv_pages_capacity is the share of the lanes' pages
    the decodes read: each live slot's pages up to its length in place,
    the whole capacity (1.0) on the int8 gather path."""
    ps, max_len, width = 8, 64, 2
    eng = ServingEngine(TINY, tiny_params, max_batch=2, max_len=max_len, page_size=ps,
                        kv_quant=quant)
    req = Request(rid=0, prompt=np.arange(1, 12, dtype=np.int32), max_new_tokens=9)
    eng.submit(req)
    eng.run()
    steps = eng.stats["decode_steps"]
    assert steps == 8
    stats = eng.pool.stats
    assert stats["kv_pages_capacity"] == steps * width * (max_len // ps)
    if quant:
        assert stats["kv_pages_read"] == stats["kv_pages_capacity"]
    else:
        # the decode at length n reads the ceil(n / ps) pages holding it
        assert stats["kv_pages_read"] == sum(-(-n // ps) for n in range(11, 11 + steps))
        assert stats["kv_pages_read"] / stats["kv_pages_capacity"] == pytest.approx(18 / 128)


# -- compile budget -----------------------------------------------------------


def test_prefill_executable_budget_over_mixed_lengths(tiny_params):
    """CompileMonitor-verified: once each bucket has been seen once, a
    mixed run over MANY distinct prompt lengths compiles NOTHING — i.e.
    the whole admissible length space needs at most len(buckets) prefill
    executables (plus one decode executable)."""
    from tools.mozart_check.tracecheck import CompileMonitor

    rng = np.random.default_rng(13)
    eng = ServingEngine(
        TINY, tiny_params, max_batch=4, max_len=64, decode_batch=2, paged=True
    )
    assert eng.buckets == (16, 32, 64)
    # warm exactly one prompt per bucket
    for i, n in enumerate((5, 20, 40)):
        eng.submit(Request(rid=i, prompt=_prompt(rng, n), max_new_tokens=4))
    eng.run()
    with CompileMonitor() as mon:
        for i, n in enumerate((3, 7, 11, 19, 23, 37, 50, 61, 13, 29)):
            eng.submit(Request(rid=100 + i, prompt=_prompt(rng, n), max_new_tokens=4))
        eng.run()
    assert mon.count == 0, mon.events


# -- cache-boundary bugfix regressions ----------------------------------------


@pytest.mark.parametrize("paged", [True, False])
def test_admit_rejects_prompts_at_or_past_capacity(tiny_params, paged):
    """Regression (ISSUE 7): prompts with len(prompt) >= max_len used to
    prefill anyway and decode past the end of the slot."""
    rng = np.random.default_rng(17)
    too_long = Request(rid=0, prompt=_prompt(rng, 32), max_new_tokens=4)
    way_too_long = Request(rid=1, prompt=_prompt(rng, 50), max_new_tokens=4)
    fits = Request(rid=2, prompt=_prompt(rng, 8), max_new_tokens=4)
    eng = ServingEngine(TINY, tiny_params, max_batch=2, max_len=32, paged=paged)
    for r in (too_long, way_too_long, fits):
        eng.submit(r)
    eng.run()
    assert too_long.done and too_long.finish_reason == "rejected"
    assert way_too_long.finish_reason == "rejected"
    assert too_long.out_tokens == [] and way_too_long.out_tokens == []
    assert fits.finish_reason == "max_new_tokens" and len(fits.out_tokens) == 4
    assert eng.stats["rejected"] == 2


@pytest.mark.parametrize("paged", [True, False])
def test_decode_finishes_at_cache_boundary(tiny_params, paged):
    """Regression (ISSUE 7): a generous max_new_tokens used to decode
    past max_len, silently overwriting the slot's last cache position."""
    rng = np.random.default_rng(19)
    req = Request(rid=0, prompt=_prompt(rng, 28), max_new_tokens=100)
    eng = ServingEngine(TINY, tiny_params, max_batch=2, max_len=32, paged=paged)
    eng.submit(req)
    eng.run()
    assert req.done and req.finish_reason == "length"
    # positions 28..31 hold decoded KV; the +1 token's KV was never written
    assert len(req.out_tokens) == 32 - 28 + 1


def test_timing_marks_are_monotone(tiny_params):
    rng = np.random.default_rng(23)
    _, reqs = _run_engine(
        tiny_params, [_prompt(rng, 9)], max_new=4, max_batch=2, max_len=32, paged=True
    )
    (req,) = reqs
    assert req.t_submit is not None and req.t_first is not None
    assert req.t_submit <= req.t_first <= req.t_done


# -- paged-attention kernel triplet -------------------------------------------


def _pool_case(rng, bsz, hkv, hd, layers, pages, ps, npp, lens):
    w = -(-hkv * hd // 128) * 128
    kp = rng.normal(size=(layers, pages, ps, w)).astype(np.float32)
    vp = rng.normal(size=(layers, pages, ps, w)).astype(np.float32)
    tables = np.zeros((bsz, npp), np.int32)
    perm = rng.permutation(np.arange(1, pages))
    off = 0
    for b in range(bsz):
        n = -(-(int(lens[b]) + 1) // ps)
        tables[b, :n] = perm[off : off + n]
        off += n
    return kp, vp, tables


@pytest.mark.parametrize("group", [1, 4])
def test_paged_decode_attention_matches_ref(group):
    from repro.kernels.flash_attention.ops import paged_decode_attention
    from repro.kernels.flash_attention.ref import paged_decode_attention_ref

    rng = np.random.default_rng(29)
    bsz, hkv, hd, pages, ps, npp = 5, 2, 16, 24, 8, 4
    h = hkv * group
    # an empty lane, a page boundary, a full slot less its new token
    lens = np.asarray([5, 0, 8, 17, 31], np.int32)
    kp, vp, tables = _pool_case(rng, bsz, hkv, hd, 2, pages, ps, npp, lens)
    q = jnp.asarray(rng.normal(size=(bsz, h, hd)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(bsz, hkv, hd)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(bsz, hkv, hd)), jnp.float32)
    args = (q, kn, vn, jnp.asarray(kp), jnp.asarray(vp), jnp.int32(1),
            jnp.asarray(tables), jnp.asarray(lens))
    want = paged_decode_attention_ref(*args)
    got = paged_decode_attention(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_paged_decode_attention_ignores_null_and_stale_pages():
    """Garbage in the null page, in positions past `lengths` and in other
    layers must not leak into the output: poisoning them leaves the result
    unchanged."""
    from repro.kernels.flash_attention.ops import paged_decode_attention

    rng = np.random.default_rng(31)
    bsz, h, hd, pages, ps, npp = 2, 2, 8, 6, 4, 3
    q = jnp.asarray(rng.normal(size=(bsz, h, hd)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(bsz, h, hd)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(bsz, h, hd)), jnp.float32)
    w = 128
    kp = np.asarray(rng.normal(size=(2, pages, ps, w)), np.float32)
    vp = np.asarray(rng.normal(size=(2, pages, ps, w)), np.float32)
    tables = jnp.asarray([[1, 2, 0], [3, 0, 0]], jnp.int32)
    lens = jnp.asarray([6, 3], jnp.int32)

    def run(k, v):
        return paged_decode_attention(q, kn, vn, jnp.asarray(k), jnp.asarray(v),
                                      jnp.int32(1), tables, lens)

    base = run(kp, vp)
    kp2, vp2 = kp.copy(), vp.copy()
    kp2[1, 0], vp2[1, 0] = 1e6, 1e6  # null page
    kp2[1, 2, 2:], vp2[1, 2, 2:] = -1e6, -1e6  # positions 6,7 of slot 0 (past length)
    kp2[1, 3, 3:], vp2[1, 3, 3:] = 1e6, -1e6  # position 3 of slot 1 (past length)
    kp2[0], vp2[0] = 1e6, -1e6  # the other layer
    kp2[1, 1, :, 2 * hd:], vp2[1, 1, :, 2 * hd:] = 1e6, 1e6  # row padding
    np.testing.assert_array_equal(np.asarray(base), np.asarray(run(kp2, vp2)))


def test_models_api_paged_cache_is_transformer_only():
    from repro.kernels.flash_attention.kernel import pool_row_width

    pool = api.init_paged_cache(TINY, num_pages=4, page_size=8)
    for seg in pool:
        assert seg["k"].shape == (TINY.n_layers, 4, 8, pool_row_width(TINY.kv_heads, TINY.hd))
    assert pool_row_width(TINY.kv_heads, TINY.hd) == 128  # 2 kv heads of 8, padded to the lanes
    for seg in api.init_paged_cache(TINY, num_pages=4, page_size=8, rows=False):
        assert seg["k"].shape == (TINY.n_layers, 4, 8, TINY.kv_heads, TINY.hd)
    rnn = ModelConfig(
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
    )
    with pytest.raises(NotImplementedError):
        api.init_paged_cache(rnn, num_pages=4, page_size=8)
    eng = ServingEngine(rnn, params={}, max_batch=2, max_len=16, paged=True)
    assert eng.paged is False  # silent fallback to the dense cache


def test_full_width_rewind_is_vectorized(tiny_params, monkeypatch):
    """Regression (ISSUE 7): the full-width emulation used one
    `.at[b].add(-1)` dispatch PER inactive slot; it must issue exactly
    one batched rewind covering all inactive slots per decode step."""
    from repro.serving import engine as eng_mod

    rng = np.random.default_rng(37)
    eng = ServingEngine(
        TINY, tiny_params, max_batch=4, max_len=32, decode_batch=1, compact=False, paged=False
    )
    for i in range(4):
        eng.submit(Request(rid=i, prompt=_prompt(rng, 4), max_new_tokens=3))
    calls = []
    orig = eng_mod._rewind_inactive

    def spy(index, inactive):
        calls.append(list(inactive))
        return orig(index, inactive)

    monkeypatch.setattr(eng_mod, "_rewind_inactive", spy)
    steps = 0
    while any(s is not None for s in eng.slots) or eng.queue:
        before = len(calls)
        eng.step()
        steps += 1
        assert len(calls) - before <= 1  # one batched rewind per step, max
        if steps > 50:
            raise AssertionError("engine did not drain")
    # with decode_batch=1 the first full step rewinds THREE slots at once
    assert any(len(c) == 3 for c in calls)
    # and every request still decoded correctly
    assert all(s is None for s in eng.slots)
