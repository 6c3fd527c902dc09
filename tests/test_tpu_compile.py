"""Compile the serving main path's Pallas kernels for a v5e chip at
SmolLM-135M widths, with no chip attached.

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
    max_len = 256
    pages_per_slot = max_len // PAGE_SIZE
    num_pages = 1 + DECODE_BATCH * pages_per_slot
    text = _compiled_text(
        one_chip,
        lambda q, k, v, t, n: paged_decode_attention_hp(q, k, v, t, n,
                                                        interpret=False),
        _bf16(DECODE_BATCH, H, HD),
        _bf16(HKV, num_pages, PAGE_SIZE, HD),
        _bf16(HKV, num_pages, PAGE_SIZE, HD),
        ((DECODE_BATCH, pages_per_slot), jnp.int32),
        ((DECODE_BATCH,), jnp.int32))
    assert "tpu_custom_call" in text
