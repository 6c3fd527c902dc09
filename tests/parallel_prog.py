"""Multi-device correctness program — run in a SUBPROCESS with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main pytest
process keeps its single CPU device.  Exits nonzero on any failure."""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402
import numpy as np                                           # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa

from repro.models import api, transformer as T               # noqa: E402
from repro.models.config import ModelConfig                  # noqa: E402
from repro.parallel.pipeline import pipeline_apply, split_stages  # noqa
from repro.parallel.sharding import (cache_shardings, data_shardings,
                                     optimizer_shardings,
                                     params_shardings)       # noqa: E402
from repro.training.optimizer import OptimizerConfig, init_opt  # noqa

CFG = ModelConfig(name="tp", n_layers=2, d_model=64, n_heads=4,
                  kv_heads=4, head_dim=16, d_ff=128, vocab=128,
                  dtype="float32", param_dtype="float32",
                  scan_min_layers=2)


def make_mesh(shape, names):
    """jax.make_mesh across JAX versions: axis_types only where it exists."""
    axis_type = getattr(jax.sharding, "AxisType", None)
    if axis_type is not None:
        return jax.make_mesh(shape, names,
                             axis_types=(axis_type.Auto,) * len(names))
    return jax.make_mesh(shape, names)


def check_tp_dp_forward_matches_single():
    assert len(jax.devices()) == 8
    mesh = make_mesh((2, 4), ("data", "model"))
    params = api.init_params(CFG, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                              CFG.vocab)
    want = np.asarray(T.forward(CFG, params, toks))
    pshard = params_shardings(mesh, params)
    dshard = data_shardings(mesh, {"tokens": toks})
    with mesh:
        p = jax.device_put(params, pshard)
        t = jax.device_put(toks, dshard["tokens"])
        # one-shot parity check: traced once, then discarded
        got = jax.jit(lambda pp, tt: T.forward(CFG, pp, tt))(p, t)  # mzc: ignore[MZC013]
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4,
                               atol=2e-4)
    print("tp_dp_forward ok")


def check_sharded_decode_matches_single():
    mesh = make_mesh((2, 4), ("data", "model"))
    params = api.init_params(CFG, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 12), 0,
                              CFG.vocab)
    last, cache = api.prefill(CFG, params, {"tokens": toks}, 32)
    lg_want, _ = api.decode_step(
        CFG, params, jnp.argmax(last, -1).astype(jnp.int32), cache)
    pshard = params_shardings(mesh, params)
    cshard = cache_shardings(mesh, cache, CFG.kv_heads, 4)
    with mesh:
        p = jax.device_put(params, pshard)
        c = jax.device_put(cache, cshard)
        # one-shot parity check: traced once, then discarded
        lg, _ = jax.jit(lambda pp, tt, cc: api.decode_step(  # mzc: ignore[MZC013]
            CFG, pp, tt, cc))(p, jnp.argmax(last, -1).astype(jnp.int32),
                              c)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lg_want),
                               rtol=2e-4, atol=2e-4)
    print("sharded_decode ok")


def check_serving_engine_tp_matches_single():
    """ServingEngine with a TP mesh (policy tp consumed) must emit the
    same tokens as the unsharded single-device engine."""
    from repro.serving.engine import Request, ServingEngine
    mesh = make_mesh((2, 4), ("data", "model"))
    params = api.init_params(CFG, jax.random.PRNGKey(0))
    prompts = [np.arange(4 + i, dtype=np.int32) + i for i in range(4)]

    def run(mesh_arg, decode_batch=None):
        eng = ServingEngine(CFG, params, max_batch=4, max_len=32,
                            decode_batch=decode_batch, mesh=mesh_arg)
        if mesh_arg is not None:
            # the paged kernel reads one device's pool: over the mesh the
            # decode gathers, and the pool's kv heads shard over "model"
            assert eng.paged and not eng.state.in_place
            for leaf in jax.tree.leaves(eng.pool.segments):
                assert leaf.ndim == 5 and leaf.sharding.spec[3] == "model", \
                    leaf.sharding
        reqs = [Request(rid=i, prompt=p, max_new_tokens=5)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        eng.run()
        return [r.out_tokens for r in reqs]

    want = run(None)
    got = run(mesh)
    assert got == want, (got, want)
    got_sub = run(mesh, decode_batch=2)   # compacted decode, sharded
    assert got_sub == want, (got_sub, want)
    print("serving_tp ok")


def check_pipeline_parallel():
    mesh = make_mesh((8,), ("pp",))
    n_stages, n_micro, mb, d = 8, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), n_stages)
    ws = jnp.stack([jax.random.normal(k, (d, d)) * 0.3 for k in ks])
    x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, d))

    def layer(w, h):
        return jnp.tanh(h @ w["w"])

    stage_params = {"w": ws}
    got = pipeline_apply(layer, stage_params, x, mesh=mesh, axis="pp")
    want = x
    for i in range(n_stages):
        want = jnp.tanh(want @ ws[i])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    print("pipeline_parallel ok")


def check_optimizer_shardings_cover_tree():
    mesh = make_mesh((2, 4), ("data", "model"))
    params = jax.eval_shape(
        lambda: api.init_params(CFG, jax.random.PRNGKey(0)))
    for name in ("adamw", "adafactor"):
        ocfg = OptimizerConfig(name=name)
        opt = jax.eval_shape(lambda: init_opt(ocfg, params))
        sh = optimizer_shardings(mesh, params, {"inner": opt})
        n = len(jax.tree_util.tree_leaves(sh))
        assert n == len(jax.tree_util.tree_leaves(opt)), (name, n)
    print("optimizer_shardings ok")


def check_elastic_reshard_roundtrip(tmpdir):
    """Save on mesh A (2x4), restore onto mesh B (4x2)."""
    from repro.checkpoint.manager import CheckpointManager
    params = api.init_params(CFG, jax.random.PRNGKey(0))
    mesh_a = make_mesh((2, 4), ("data", "model"))
    mesh_b = make_mesh((4, 2), ("data", "model"))
    pa = jax.device_put(params, params_shardings(mesh_a, params))
    m = CheckpointManager(tmpdir)
    m.save(1, pa)
    shard_b = params_shardings(mesh_b, params)
    out, _ = m.restore(params, shardings=shard_b)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(out)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    leaf = jax.tree_util.tree_leaves(out)[0]
    assert leaf.sharding.mesh.shape == mesh_b.shape
    print("elastic_reshard ok")


if __name__ == "__main__":
    import tempfile
    check_tp_dp_forward_matches_single()
    check_sharded_decode_matches_single()
    check_serving_engine_tp_matches_single()
    check_pipeline_parallel()
    check_optimizer_shardings_cover_tree()
    with tempfile.TemporaryDirectory() as td:
        check_elastic_reshard_roundtrip(td)
    print("ALL_PARALLEL_OK")
