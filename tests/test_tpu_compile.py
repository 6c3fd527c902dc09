"""Compile the serving main path's Pallas kernels for a v5e chip at
SmolLM-135M widths (the paged decode also at InternLM2-1.8B's and
Qwen2.5-32B's, and its whole program at the chip benchmark cell's), with
no chip attached.

The TPU compiler refuses what interpret mode accepts: blocks off the
(8, 128) tiling, unsupported primitives, more VMEM than a kernel may use.
Each test lowers one kernel with `interpret=False` onto a described v5e
device and asserts that the compiled program holds the Mosaic kernel
(`tpu_custom_call`).  Nothing runs, so these say nothing about results or
times; the interpret-mode parity tests cover results.

The topology is described inside a module-scoped fixture (never at
import): only one process at a time may load the TPU library, and every
test worker imports every test file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.kernel import (flash_attention_bhsd,
                                                  paged_decode_attention_hp)
from repro.kernels.fused_mlp.kernel import fused_mlp_pallas
from repro.kernels.fused_norm.kernel import (fused_rmsnorm_pallas,
                                             fused_rmsnorm_residual_pallas)

# SmolLM-135M: 9 query heads over 3 kv heads of 64, d_model 576, d_ff 1536
H, HKV, HD, D, F = 9, 3, 64, 576, 1536
DECODE_BATCH = 4
PAGE_SIZE = 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
            jax.config.update("jax_enable_compilation_cache", was)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    # one lower + compile per test is the point: nothing is called twice
    return jax.jit(fn).lower(*args).compile().as_text()  # mzc: ignore[MZC013]


def _bf16(*shape):
    return (shape, jnp.bfloat16)


@pytest.mark.parametrize("bucket", [16, 32, 64, 128, 256])
def test_flash_prefill_compiles(one_chip, bucket):
    text = _compiled_text(
        one_chip,
        lambda q, k, v: flash_attention_bhsd(q, k, v, causal=True,
                                             interpret=False),
        _bf16(H, bucket, HD), _bf16(HKV, bucket, HD), _bf16(HKV, bucket, HD))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("tokens", [DECODE_BATCH, 128])
def test_fused_mlp_compiles(one_chip, tokens):
    text = _compiled_text(
        one_chip,
        lambda x, wg, wi, wo: fused_mlp_pallas(x, wg, wi, wo, swiglu=True,
                                               interpret=False),
        _bf16(tokens, D), _bf16(D, F), _bf16(D, F), _bf16(F, D))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("tokens", [DECODE_BATCH, 128])
def test_fused_rmsnorm_compiles(one_chip, tokens):
    text = _compiled_text(
        one_chip,
        lambda x, g: fused_rmsnorm_pallas(x, g, interpret=False),
        _bf16(tokens, D), _bf16(D))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("tokens", [DECODE_BATCH, 128])
def test_fused_rmsnorm_residual_compiles(one_chip, tokens):
    text = _compiled_text(
        one_chip,
        lambda x, r, g: fused_rmsnorm_residual_pallas(x, r, g,
                                                      interpret=False),
        _bf16(tokens, D), _bf16(tokens, D), _bf16(D))
    assert "tpu_custom_call" in text


def test_paged_decode_compiles(one_chip):
    _compile_paged_decode(one_chip, H, HKV, HD)


@pytest.mark.parametrize("heads", [(16, 8, 128), (40, 8, 128)],
                         ids=["internlm2", "qwen2.5-32b"])
def test_paged_decode_compiles_at_other_widths(one_chip, heads):
    _compile_paged_decode(one_chip, *heads)


def _compile_paged_decode(one_chip, h, hkv, hd):
    max_len, layers = 256, 2
    pages_per_slot = max_len // PAGE_SIZE
    num_pages = 1 + DECODE_BATCH * pages_per_slot
    w = -(-hkv * hd // 128) * 128
    text = _compiled_text(
        one_chip,
        lambda q, kn, vn, kp, vp, l, t, n: paged_decode_attention_hp(
            q, kn, vn, kp, vp, l, t, n, scale=hd ** -0.5,
            pages_per_block=128 // PAGE_SIZE, interpret=False),
        _bf16(DECODE_BATCH, h, w),
        _bf16(DECODE_BATCH, 1, w),
        _bf16(DECODE_BATCH, 1, w),
        _bf16(layers, num_pages, PAGE_SIZE, w),
        _bf16(layers, num_pages, PAGE_SIZE, w),
        ((), jnp.int32),
        ((DECODE_BATCH, pages_per_slot), jnp.int32),
        ((DECODE_BATCH,), jnp.int32))
    assert "tpu_custom_call" in text


def test_paged_decode_program_at_cell_widths(one_chip, monkeypatch):
    """The whole paged decode program of the chip benchmark's cell
    (InternLM2-1.8B: 24 layers, 16/8 heads of 128; 16 lanes of 2048
    positions, page 16) runs the kernel, and its temporaries stay far
    below the 3.2 GB pool: no dense sub-cache and no copy of the pool."""
    from repro.kernels.flash_attention import ops
    from repro.models import api
    from repro.models.config import ModelConfig
    from repro.serving import paged

    monkeypatch.setattr(ops, "interpret_mode", lambda interpret=None: False)
    cfg = ModelConfig(name="internlm2-1.8b-cell", n_layers=24, d_model=2048,
                      n_heads=16, kv_heads=8, head_dim=128, d_ff=8192,
                      vocab=92544, tie_embeddings=False, rope_theta=1e6,
                      norm_eps=1e-5, dtype="bfloat16", param_dtype="bfloat16")
    lanes, max_len = 16, 2048
    npp = max_len // PAGE_SIZE

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(sds, jax.eval_shape(
        lambda: api.init_params(cfg, jax.random.PRNGKey(0))))
    pool = jax.tree.map(sds, jax.eval_shape(
        lambda: api.init_paged_cache(cfg, 1 + lanes * npp, PAGE_SIZE)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)  # noqa: E731
    compiled = paged.paged_decode_fn(cfg).lower(
        params, i32(lanes, 1), pool, i32(lanes, npp), i32(lanes)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
