"""Tier-1 parity tests for the serving fused kernels (interpret mode on
CPU): fused dense gated-MLP and fused RMSNorm(+residual) vs their
pure-jnp oracles, plus the ModelConfig mlp_impl/norm_impl dispatch
through the transformer forward/decode paths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fused_mlp.ops import fused_mlp
from repro.kernels.fused_mlp.ref import fused_mlp_ref
from repro.kernels.fused_norm.ops import fused_rmsnorm, fused_rmsnorm_residual
from repro.kernels.fused_norm.ref import fused_rmsnorm_ref, fused_rmsnorm_residual_ref
from repro.models import api
from repro.models.config import ModelConfig


@pytest.mark.parametrize(
    "n,d,f,swiglu,bt,bf,dt",
    [
        (8, 16, 32, True, 4, 8, jnp.float32),
        (10, 16, 48, False, 128, 512, jnp.float32),  # ragged + no gate
        (3, 8, 8, True, 2, 4, jnp.float32),  # padding on both axes
        (6, 16, 32, True, 4, 16, jnp.bfloat16),
    ],
)
def test_fused_mlp_matches_ref(n, d, f, swiglu, bt, bf, dt):
    ks = jax.random.split(jax.random.PRNGKey(n * 31 + f), 4)
    x = jax.random.normal(ks[0], (n, d), dt)
    wg = jax.random.normal(ks[1], (d, f), dt)
    wi = jax.random.normal(ks[2], (d, f), dt)
    wo = jax.random.normal(ks[3], (f, d), dt)
    # the gate operand is skipped entirely for plain-GELU MLPs
    out = fused_mlp(x, wg if swiglu else None, wi, wo, swiglu=swiglu, bt=bt, bf=bf)
    ref = fused_mlp_ref(x, wg, wi, wo, swiglu=swiglu)
    tol = 2.5e-2 if dt == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=tol, atol=tol
    )


def test_fused_mlp_batched_layout():
    """(B, S, d) inputs flatten through the wrapper unchanged."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (2, 5, 16), jnp.float32)
    wg = jax.random.normal(ks[1], (16, 32), jnp.float32)
    wi = jax.random.normal(ks[2], (16, 32), jnp.float32)
    wo = jax.random.normal(ks[3], (32, 16), jnp.float32)
    out = fused_mlp(x, wg, wi, wo, bt=4, bf=16)
    ref = fused_mlp_ref(x.reshape(-1, 16), wg, wi, wo).reshape(2, 5, 16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize(
    "n,d,dt,tol",
    [
        (8, 16, jnp.float32, 1e-6),
        (5, 32, jnp.float32, 1e-6),  # padding (bt=4 over 5 rows)
        (6, 16, jnp.bfloat16, 2.5e-2),
    ],
)
def test_fused_rmsnorm_matches_ref(n, d, dt, tol):
    ks = jax.random.split(jax.random.PRNGKey(n * 7 + d), 3)
    x = jax.random.normal(ks[0], (2, n, d), dt)
    res = jax.random.normal(ks[1], (2, n, d), dt)
    scale = jax.random.normal(ks[2], (d,), dt)
    out = fused_rmsnorm(x, scale, bt=4)
    ref = fused_rmsnorm_ref(x, scale)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=tol, atol=tol
    )
    s, y = fused_rmsnorm_residual(x, res, scale, bt=4)
    s_ref, y_ref = fused_rmsnorm_residual_ref(x, res, scale)
    np.testing.assert_allclose(
        np.asarray(s, np.float32), np.asarray(s_ref, np.float32), rtol=tol, atol=tol
    )
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(y_ref, np.float32), rtol=tol, atol=tol
    )


BASE = ModelConfig(
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


@pytest.mark.parametrize(
    "kw",
    [
        {"mlp_impl": "fused"},
        {"norm_impl": "fused"},
        {"mlp_impl": "fused", "norm_impl": "fused"},
    ],
)
def test_model_fused_impls_match_dense(kw):
    """forward + decode_step with the fused Pallas impls agree with the
    dense/ref paths on the same params."""
    from repro.models import transformer as T

    params = api.init_params(BASE, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0, BASE.vocab)
    want = np.asarray(T.forward(BASE, params, toks))
    cfg = BASE.replace(**kw)
    got = np.asarray(T.forward(cfg, params, toks))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    last, cache = api.prefill(BASE, params, {"tokens": toks}, 16)
    step = jnp.argmax(last, -1).astype(jnp.int32)
    lg_want, _ = api.decode_step(BASE, params, step, cache)
    lg_got, _ = api.decode_step(cfg, params, step, cache)
    np.testing.assert_allclose(
        np.asarray(lg_got), np.asarray(lg_want), rtol=2e-5, atol=2e-5
    )


def test_config_validates_impls():
    BASE.replace(mlp_impl="fused", norm_impl="fused").validate()
    with pytest.raises(AssertionError):
        BASE.replace(mlp_impl="bogus").validate()
    with pytest.raises(AssertionError):
        BASE.replace(norm_impl="bogus").validate()


# -- backend selection: interpret only on the CPU -----------------------------


def _wrapper_calls():
    """One call per Pallas ops wrapper, at shapes no other test traces (the
    jit cache would otherwise skip the backend check)."""
    from repro.kernels.flash_attention.ops import flash_attention, paged_decode_attention
    from repro.kernels.moe_mlp.ops import moe_mlp
    from repro.kernels.rglru_scan.ops import rglru_scan
    from repro.kernels.wkv6.ops import wkv6

    z = jnp.zeros
    i32 = jnp.int32
    return {
        "flash_attention": lambda: flash_attention(
            z((1, 5, 3, 8)), z((1, 5, 3, 8)), z((1, 5, 3, 8))
        ),
        "paged_decode_attention": lambda: paged_decode_attention(
            z((1, 3, 8)), z((1, 3, 8)), z((1, 3, 8)), z((2, 3, 2, 128)), z((2, 3, 2, 128)),
            z((), i32), z((1, 2), i32), z((1,), i32)
        ),
        "fused_mlp": lambda: fused_mlp(z((5, 24)), z((24, 40)), z((24, 40)), z((40, 24))),
        "fused_rmsnorm": lambda: fused_rmsnorm(z((5, 24)), z((24,))),
        "fused_rmsnorm_residual": lambda: fused_rmsnorm_residual(z((5, 24)), z((5, 24)), z((24,))),
        "moe_mlp": lambda: moe_mlp(z((3, 5, 24)), z((3, 24, 40)), z((3, 24, 40)), z((3, 40, 24))),
        "rglru_scan": lambda: rglru_scan(z((1, 5, 24)), z((1, 5, 24)), z((1, 24))),
        "wkv6": lambda: wkv6(
            z((3, 5, 8)), z((3, 5, 8)), z((3, 5, 8)), z((3, 5, 8)), z((3, 1, 8)), z((3, 8, 8))
        ),
    }


@pytest.mark.parametrize("name", sorted(_wrapper_calls()))
def test_ops_wrapper_refuses_non_tpu_backend(monkeypatch, name):
    """On a backend that is neither the CPU nor the TPU a kernel must not
    quietly run in interpret mode: the wrapper raises instead."""
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="compile only for the TPU"):
        _wrapper_calls()[name]()


def test_interpret_mode_by_backend(monkeypatch):
    from repro.kernels._backend import interpret_mode

    assert interpret_mode() is True  # tests run under JAX_PLATFORMS=cpu
    assert interpret_mode(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert interpret_mode() is False
    assert interpret_mode(True) is True


def test_ff_block_fits_vmem_at_large_widths():
    """The MLP kernels keep SmolLM's ff block and halve it where the
    double-buffered weight tiles would overflow the VMEM limit."""
    from repro.kernels.fused_mlp.kernel import VMEM_LIMIT_BYTES, fit_ff_block

    assert fit_ff_block(512, 128, 576, 2) == 512  # SmolLM-135M
    assert fit_ff_block(512, 128, 4096, 2) == 512  # Mixtral experts
    assert fit_ff_block(512, 128, 7168, 2) == 256  # DeepSeek-V3 experts
    assert fit_ff_block(512, 128, 7168, 4) == 128
    for d in (576, 4096, 7168):
        bf = fit_ff_block(512, 128, d, 2)
        assert 2 * 2 * (2 * 128 * d + 3 * d * bf) + 4 * 128 * d <= VMEM_LIMIT_BYTES
