"""Tier-1 tests for the multi-replica serving cluster + int8 KV quant.

Covers the ISSUE-8 acceptance surface: router policies (round-robin
rotation over healthy replicas, least-loaded-by-free-pages,
join-shortest-queue), the seeded open-loop workload/LoadGenerator
determinism (and that the hoisted Zipf mix replays bench_serving's
pre-hoist trace), churn fairness under a cluster, the replica-failure
injection contract (a killed replica's in-flight requests finish on the
survivors with the token streams an uninterrupted run produces — zero
lost or duplicated tokens), cluster metrics, the `replica_meshes` data-
axis split, and the int8-quantized paged KV path (token-level parity
tolerance vs f32, >= 2x pages per HBM byte, per-page scale shapes, and
stale-data hygiene on page reuse).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import api
from repro.models.config import ModelConfig
from repro.serving import quant as kvq
from repro.serving import workload
from repro.serving.cluster import (
    ClusterMetrics,
    LoadGenerator,
    Router,
    ServingCluster,
)
from repro.serving.engine import Request, ServingEngine

TINY = ModelConfig(
    name="tiny-cluster",
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


def _mk_requests(n, seed=3, max_new=6, bands=((4, 9), (10, 14))):
    rng = np.random.default_rng(seed)
    return workload.zipf_mix_requests(
        rng, n, TINY.vocab, bands=bands, max_new_tokens=max_new
    )


def _mk_cluster(params, **kw):
    kw.setdefault("n_replicas", 2)
    kw.setdefault("router", "round_robin")
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("num_pages", 33)
    return ServingCluster(TINY, params, **kw)


# -- workload / load generator ------------------------------------------------


def test_zipf_mix_matches_pre_hoist_trace():
    """The hoisted generator must replay the exact draw order of the old
    inline bench_serving mix, or every fixed-seed baseline shifts."""
    bands = workload.DEFAULT_BANDS
    weights = np.asarray([1.0, 1 / 2.0, 1 / 3.0])
    weights = weights / weights.sum()
    rng_old = np.random.default_rng(7)
    old = []
    for _ in range(12):
        lo, hi = bands[int(rng_old.choice(len(bands), p=weights))]
        old.append(
            rng_old.integers(0, 512, size=int(rng_old.integers(lo, hi + 1))).astype(
                np.int32
            )
        )
    new = workload.zipf_mix_requests(np.random.default_rng(7), 12, 512)
    assert [len(p) for p in old] == [len(r.prompt) for r in new]
    assert all(np.array_equal(p, r.prompt) for p, r in zip(old, new))


def test_poisson_arrivals_seeded_and_monotone():
    a = workload.poisson_arrivals(np.random.default_rng(5), 20, rate=100.0)
    b = workload.poisson_arrivals(np.random.default_rng(5), 20, rate=100.0)
    assert np.array_equal(a, b)
    assert np.all(np.diff(a) > 0)
    assert np.all(workload.poisson_arrivals(np.random.default_rng(5), 4, 0.0) == 0.0)


def test_load_generator_schedule_deterministic():
    mk = lambda: LoadGenerator(n_requests=6, rate=50.0, vocab=61, seed=11).schedule()
    s1, s2 = mk(), mk()
    assert [t for t, _ in s1] == [t for t, _ in s2]
    assert all(np.array_equal(a.prompt, b.prompt) for (_, a), (_, b) in zip(s1, s2))
    assert [t for t, _ in s1] == sorted(t for t, _ in s1)


# -- router policies ----------------------------------------------------------


def test_router_rejects_unknown_policy():
    with pytest.raises(ValueError):
        Router("bogus")


def test_round_robin_cycles_and_skips_dead(tiny_params):
    cl = _mk_cluster(tiny_params, n_replicas=3)
    picks = [cl.router.pick(cl.replicas, [0, 1, 2]) for _ in range(6)]
    assert picks == [0, 1, 2, 0, 1, 2]
    # a dead replica's turn passes to the next healthy one
    picks = [cl.router.pick(cl.replicas, [0, 2]) for _ in range(4)]
    assert 1 not in picks and set(picks) == {0, 2}


def test_least_loaded_routes_to_free_pages(tiny_params):
    cl = _mk_cluster(tiny_params, router="least_loaded")
    # drain pages from replica 0: the router must prefer replica 1
    assert cl.replicas[0].pool.ensure(0, 64)
    assert cl.router.pick(cl.replicas, [0, 1]) == 1
    cl.replicas[0].pool.release(0)
    # tie -> lowest id
    assert cl.router.pick(cl.replicas, [0, 1]) == 0


def test_shortest_queue_balances_queued_plus_live(tiny_params):
    cl = _mk_cluster(tiny_params, router="shortest_queue")
    reqs = _mk_requests(3)
    cl.replicas[0].queue.extend(reqs[:2])
    assert cl.router.pick(cl.replicas, [0, 1]) == 1
    cl.replicas[1].queue.extend(reqs)
    assert cl.router.pick(cl.replicas, [0, 1]) == 0


# -- cluster serving ----------------------------------------------------------


def test_cluster_completes_all_requests_and_attributes_metrics(tiny_params):
    cl = _mk_cluster(tiny_params)
    reqs = _mk_requests(6)
    for r in reqs:
        cl.submit(r)
    cl.run()
    assert all(r.done for r in reqs)
    s = cl.metrics.summary(cl)
    assert s["aggregate"]["n_finished"] == 6
    assert s["aggregate"]["tokens_out"] == sum(len(r.out_tokens) for r in reqs)
    assert sum(r["n_finished"] for r in s["per_replica"]) == 6
    assert len(cl.metrics.series["free_pages"]) == cl.stats["steps"]


def test_cluster_matches_single_engine_tokens(tiny_params):
    """Routing must not change what any request decodes (greedy)."""
    single = _mk_requests(6, seed=9)
    eng = ServingEngine(
        TINY, tiny_params, max_batch=2, max_len=64, page_size=8, num_pages=33
    )
    for r in single:
        eng.submit(r)
    eng.run()
    clustered = _mk_requests(6, seed=9)
    cl = _mk_cluster(tiny_params)
    for r in clustered:
        cl.submit(r)
    cl.run()
    assert [r.out_tokens for r in clustered] == [r.out_tokens for r in single]


def test_churn_fairness_under_cluster_preemption(tiny_params):
    """Page-pool churn inside a replica (preempt/resume) must not starve
    or corrupt any request routed to it: every request finishes with
    exactly max_new tokens and no preempted request is lost."""
    cl = _mk_cluster(tiny_params, num_pages=6, max_batch=3)
    reqs = _mk_requests(8, seed=2, max_new=12, bands=((4, 8),))
    for r in reqs:
        cl.submit(r)
    cl.run()
    assert all(r.done for r in reqs)
    assert all(r.finish_reason == "max_new_tokens" for r in reqs)
    assert all(len(r.out_tokens) == 12 for r in reqs)
    agg = cl.metrics.summary(cl)["aggregate"]
    assert agg["preemptions"] > 0, "geometry no longer exercises churn"
    assert agg["rejected"] == 0


def test_kill_replica_finishes_elsewhere_with_exact_tokens(tiny_params):
    """ISSUE-8 acceptance: kill a replica mid-decode; its queued AND
    in-flight requests finish on the survivors with the token streams an
    uninterrupted run produces — zero lost, zero duplicated."""
    base = _mk_requests(8, seed=5)
    eng = ServingEngine(
        TINY, tiny_params, max_batch=2, max_len=64, page_size=8, num_pages=33
    )
    for r in base:
        eng.submit(r)
    eng.run()
    want = [list(r.out_tokens) for r in base]

    reqs = _mk_requests(8, seed=5)
    cl = _mk_cluster(tiny_params)
    for r in reqs:
        cl.submit(r)
    for _ in range(3):  # let replica 0 admit and decode a few tokens
        cl.step()
    assert any(s is not None for s in cl.replicas[0].slots)
    moved = cl.kill_replica(0)
    assert moved > 0
    cl.run()
    assert all(r.done for r in reqs)
    assert [list(r.out_tokens) for r in reqs] == want
    assert cl.stats["replica_failures"] == 1
    assert cl.stats["requeued"] == moved
    # the dead replica took no further work
    assert 0 not in cl.healthy
    assert all(s is None for s in cl.replicas[0].slots)
    assert not cl.replicas[0].queue


def test_kill_replica_guards(tiny_params):
    """ISSUE-9 semantics: killing the LAST healthy replica no longer
    raises — its work parks on the cluster for a later restart."""
    cl = _mk_cluster(tiny_params)
    reqs = _mk_requests(2, seed=6)
    for r in reqs:
        cl.submit(r)
    cl.step()
    cl.kill_replica(0)
    cl.kill_replica(1)  # total outage: parks, does not raise
    assert not cl.healthy
    assert len(cl.parked) == sum(1 for r in reqs if not r.done)
    assert cl.metrics.summary(cl)["aggregate"]["n_unrouted"] == len(cl.parked)
    assert cl.kill_replica(0) == 0  # already dead: no-op


def test_submits_after_failure_avoid_dead_replica(tiny_params):
    cl = _mk_cluster(tiny_params, n_replicas=3)
    cl.kill_replica(1)
    reqs = _mk_requests(6, seed=8)
    picks = {cl.submit(r) for r in reqs}
    assert 1 not in picks
    cl.run()
    assert all(r.done for r in reqs)


def test_open_loop_drive_completes_and_reports(tiny_params):
    cl = _mk_cluster(tiny_params, router="least_loaded")
    lg = LoadGenerator(n_requests=5, rate=200.0, vocab=TINY.vocab, seed=4)
    summary = cl.drive(lg.schedule())
    assert summary["aggregate"]["n_finished"] == 5
    assert summary["aggregate"]["ttft_p99_ms"] >= summary["aggregate"]["ttft_p50_ms"]


def test_cluster_metrics_empty_summary(tiny_params):
    cl = _mk_cluster(tiny_params)
    s = cl.metrics.summary(cl)
    assert s["aggregate"]["n_finished"] == 0
    assert s["aggregate"]["tokens_out"] == 0


# -- replica meshes -----------------------------------------------------------


def test_replica_meshes_split_data_axis():
    from repro.parallel.sharding import replica_meshes

    assert replica_meshes(None, 3) == [None, None, None]
    devs = np.array(jax.devices()[:1]).reshape(1, 1, 1)
    from jax.sharding import Mesh

    mesh = Mesh(devs, ("pod", "data", "model"))
    assert replica_meshes(mesh, 1) == [mesh]
    with pytest.raises(ValueError):
        replica_meshes(mesh, 2)  # data axis of 1 cannot split into 2
    mesh2 = Mesh(np.array(jax.devices()[:1] * 2).reshape(1, 2, 1),
                 ("pod", "data", "model"))
    subs = replica_meshes(mesh2, 2)
    assert len(subs) == 2
    assert all(m.devices.shape == (1, 1, 1) for m in subs)
    assert all(m.axis_names == ("pod", "data", "model") for m in subs)


REPLICA_MESH_PROG = r"""
import jax
import numpy as np
from repro.launch.serve import replica_mesh
from repro.models import api
from repro.models.config import ModelConfig
from repro.parallel.sharding import replica_meshes
from repro.serving.cluster import ServingCluster
from repro.serving.engine import Request

devs = jax.devices()
assert len(devs) == 4, devs
assert replica_mesh(1) is None and replica_mesh(5) is None
for n in (2, 3, 4):
    subs = replica_meshes(replica_mesh(n), n)
    assert [m.devices.size for m in subs] == [1] * n
    assert [m.devices.flat[0] for m in subs] == devs[:n]

cfg = ModelConfig(name="t", n_layers=1, d_model=32, n_heads=2, kv_heads=1,
                  head_dim=16, d_ff=64, vocab=61, dtype="float32",
                  param_dtype="float32")
params = api.init_params(cfg, jax.random.PRNGKey(0))
streams = []
for mesh in (replica_mesh(4), None):
    cl = ServingCluster(cfg, params, n_replicas=4, router="round_robin",
                        mesh=mesh, max_batch=2, max_len=32)
    for i, eng in enumerate(cl.replicas):
        want = devs[i] if mesh is not None else devs[0]
        for leaf in jax.tree.leaves((eng.params, eng.pool.segments)):
            assert leaf.devices() == {want}, (i, leaf.devices())
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, prompt=rng.integers(0, 61, size=int(rng.integers(3, 9)))
                    .astype(np.int32), max_new_tokens=4) for i in range(8)]
    for r in reqs:
        cl.submit(r)
    cl.run()
    assert all(r.finish_reason == "max_new_tokens" for r in reqs)
    streams.append([r.out_tokens for r in reqs])
assert streams[0] == streams[1]
print("REPLICA_MESH_OK")
"""


def test_serve_replica_mesh_one_device_per_replica():
    """`serve --replicas N` without a policy mesh puts replica i's params
    and page pool on device i (4 virtual CPU devices, in a subprocess:
    the device count is fixed when JAX starts), and the token streams
    equal those of the same cluster with every replica on device 0."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(
        os.environ,
        PYTHONPATH=os.path.join(root, "src"),
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
    )
    out = subprocess.run(
        [sys.executable, "-c", REPLICA_MESH_PROG],
        capture_output=True,
        text=True,
        timeout=600,
        env=env,
        cwd=root,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "REPLICA_MESH_OK" in out.stdout


# -- int8 KV quant ------------------------------------------------------------


def test_quant_roundtrip_tolerance():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 8, 2, 8)) * 3.0
    q, s = kvq.quantize_block(x, ps_axis=2)
    assert q.dtype == jnp.int8
    assert s.shape == (2, 4, 1, 2, 1)
    back = kvq.dequantize_block(q, s)
    err = jnp.abs(back - x).max() / jnp.abs(x).max()
    assert float(err) < 1.0 / 127.0


def test_quant_scales_are_per_page_and_head():
    x = jnp.zeros((1, 3, 4, 2, 8))
    # one hot page/head combination: only its scale moves off the floor
    x = x.at[0, 1, :, 1, :].set(100.0)
    _, s = kvq.quantize_block(x, ps_axis=2)
    assert float(s[0, 1, 0, 1, 0]) == pytest.approx(100.0 / 127.0)
    assert float(s[0, 1, 0, 0, 0]) == pytest.approx(kvq.SCALE_FLOOR)
    assert float(s[0, 0, 0, 1, 0]) == pytest.approx(kvq.SCALE_FLOOR)


def test_quant_pool_capacity_at_least_2x():
    budget = kvq.kv_page_nbytes(TINY, 8, quant=False) * 64
    f32 = kvq.pages_for_byte_budget(TINY, budget, 8, quant=False)
    int8 = kvq.pages_for_byte_budget(TINY, budget, 8, quant=True)
    assert int8 >= 2 * f32


def test_quant_engine_token_parity_tolerance(tiny_params):
    """ISSUE-8 acceptance: int8-KV serving is token-parity within
    tolerance vs f32 on fixed seeds (prefix-match fraction)."""
    outs = {}
    for q in (False, True):
        reqs = _mk_requests(6, seed=9, max_new=6)
        eng = ServingEngine(
            TINY, tiny_params, max_batch=2, max_len=64, page_size=8,
            num_pages=33, kv_quant=q,
        )
        for r in reqs:
            eng.submit(r)
        eng.run()
        assert all(r.done for r in reqs)
        outs[q] = [r.out_tokens for r in reqs]
    total = sum(len(t) for t in outs[False])
    matched = 0
    for a, b in zip(outs[False], outs[True]):
        for x, y in zip(a, b):
            if x != y:
                break
            matched += 1
    assert matched / total >= 0.7, (matched, total, outs)


def test_quant_pool_storage_is_int8_with_scales(tiny_params):
    eng = ServingEngine(
        TINY, tiny_params, max_batch=2, max_len=64, page_size=8,
        num_pages=17, kv_quant=True,
    )
    assert eng.kv_quant
    for leaf in jax.tree.leaves(eng.pool.segments):
        assert leaf.dtype == jnp.int8
    for k, g in zip(jax.tree.leaves(eng.pool.segments),
                    jax.tree.leaves(eng.pool.scales)):
        assert g.shape == k.shape[:2] + (1,) + k.shape[3:4] + (1,)
    f32 = ServingEngine(
        TINY, tiny_params, max_batch=2, max_len=64, page_size=8,
        num_pages=17, kv_quant=False,
    )
    assert f32.pool.page_nbytes >= 2 * eng.pool.page_nbytes


def test_quant_dense_engines_ignore_kv_quant(tiny_params):
    eng = ServingEngine(TINY, tiny_params, max_batch=2, max_len=32,
                        paged=False, kv_quant=True)
    assert not eng.kv_quant  # int8 rides the paged gather/scatter only


def test_quant_page_reuse_does_not_poison_scales(tiny_params):
    """A freed page re-allocated to a new request must not let stale
    int8 garbage inflate the fresh scatter's absmax scales: serve two
    churny waves through a small pool and require decode to stay exact
    per-request (all requests same length => same token count)."""
    reqs = _mk_requests(6, seed=1, max_new=5, bands=((6, 10),))
    eng = ServingEngine(
        TINY, tiny_params, max_batch=2, max_len=64, page_size=8,
        num_pages=9, kv_quant=True,
    )
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert all(r.done and r.finish_reason == "max_new_tokens" for r in reqs)
    assert all(len(r.out_tokens) == 5 for r in reqs)
    assert eng.pool.free_pages == 8  # everything released after churn
