"""Serving launcher: continuous-batching engine (optionally with
speculative decoding) on synthetic requests, optionally driven by a
Mozart deployment artifact.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
        --smoke --requests 8 --max-new 16
    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
        --smoke --specdec
    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
        --smoke --policy deployment.json

`--policy` accepts either a `mozart.compile(...).save()` deployment
artifact or a bare `ExecutionPolicy.to_json` file and *applies* it:
fusion flags select the fused Pallas kernels (flash_attention ->
attn_impl="flash", fused_mlp -> mlp_impl="fused", fused_norm ->
norm_impl="fused"), the policy's batch split sets the engine's
max/decode batch (decode runs COMPACTED at decode_batch width), and the
TP degree builds the mesh the engine shards its params/cache/compute
over.

`--replicas N` (with `--router round_robin|least_loaded|shortest_queue`)
scales the SAME policy out as a serving cluster: the policy's mesh keeps
its "model" (TP) extent inside every replica while the replicas are laid
out along the mesh "data" axis (`parallel.sharding.replica_meshes`), so
`--policy X --replicas N` is the paper's fleet story — N copies of one
composed BASIC behind a router, each with its own paged KV pool.
Without a policy mesh, `--replicas N` with N <= the device count puts
one replica on each of the first N devices (`replica_mesh`); with one
device, or more replicas than devices, the replicas share the default
device.  `--rate R` drives the cluster open-loop at R req/s (Poisson,
seeded) instead of the closed-loop burst.

Resilience flags: `--deadline-ms D` stamps a D-millisecond SLO deadline
on every generated request (default: the `MOZART_DEADLINE_DEFAULT_MS`
knob; 0 = none) — the engines shed requests that cannot meet it
(`finish_reason="shed"`).  `--chaos` replays a seeded fault script
(`MOZART_CHAOS_SEED`; kill/restart/stall/nan events from
`serving.resilience.ChaosSchedule.generate`) against the cluster while
it serves, and the summary reports the shed / poisoned / quarantined /
unrouted counts next to goodput (deadline-met tokens).

`--profile DIR` turns on the engine's spans (`serving.spans`) and
records a JAX profiler trace of the serving loop under DIR: the
`serve.*` spans of every engine step sit on the same timeline as the
device's operations and the named programs (`jit_paged_decode`,
`jit_paged_prefill`, ...).  Open it in TensorBoard's profile plugin or
Perfetto.
"""
from __future__ import annotations

import argparse
import contextlib
import time

import jax
import numpy as np
from jax.sharding import Mesh

from repro import configs
from repro.core.policy import ExecutionPolicy
from repro.launch import knobs
from repro.launch.compile_cache import use_compile_cache
from repro.models import api, transformer
from repro.models.config import ModelConfig
from repro.serving import spans
from repro.serving.engine import Request, ServingEngine
from repro.serving.specdec import spec_decode_greedy


def apply_policy(pol: ExecutionPolicy, mcfg: ModelConfig,
                 max_batch: int, n_devices: int | None = None
                 ) -> tuple[ModelConfig, dict, list[str]]:
    """Lower an ExecutionPolicy onto the serving substrate.

    Returns (model config, ServingEngine kwargs, log lines).  Pure —
    no engine or mesh is constructed here — so the mapping is unit-
    testable without JAX compilation.
    """
    if n_devices is None:
        n_devices = len(jax.devices())
    lines: list[str] = []
    flags = pol.fusion_flags()

    applied = []
    # the fused kernel hooks live in the transformer family's
    # attention/mlp_block/apply_norm dispatch; every other combination
    # logs the ACTUAL unsupported reason instead of claiming application
    # (the engine serves all families now, so "engine is transformer-
    # only" is no longer the gate — the kernel dispatch is)
    if flags["flash_attention"]:
        if mcfg.family == "transformer":
            mcfg = mcfg.replace(attn_impl="flash")
            applied.append("flash_attention->attn_impl=flash")
        elif mcfg.family == "rglru":
            applied.append("flash_attention(no hook: rglru's interleaved "
                           "attention decodes through its ring-buffer "
                           "window path)")
        elif mcfg.family == "whisper":
            applied.append("flash_attention(no hook: whisper decoder "
                           "blocks interleave cross-attention over the "
                           "encoder window)")
        else:
            applied.append(f"flash_attention(no hook: {mcfg.family} has "
                           f"no softmax-attention operator)")
    if flags["fused_mlp"]:
        if mcfg.family == "transformer":
            mcfg = mcfg.replace(mlp_impl="fused")
            applied.append("fused_mlp->mlp_impl=fused")
        elif mcfg.family == "whisper":
            applied.append("fused_mlp(no hook: whisper cross-attn blocks "
                           "interleave the MLP with encoder reads)")
        else:
            applied.append(f"fused_mlp(no hook: {mcfg.family} uses gated "
                           f"recurrent channel mixing, not the plain MLP "
                           f"the fused kernel covers)")
    if flags["fused_norm"]:
        if mcfg.family == "transformer" and mcfg.norm == "rmsnorm":
            mcfg = mcfg.replace(norm_impl="fused")
            applied.append("fused_norm->norm_impl=fused")
        elif mcfg.family == "transformer":
            applied.append(f"fused_norm(no hook: norm={mcfg.norm}; the "
                           f"fused kernel implements rmsnorm only)")
        else:
            applied.append(f"fused_norm(no hook: {mcfg.family}'s norm "
                           f"dispatch has no fused path, norm="
                           f"{mcfg.norm})")
    lines.append(f"[serve] policy network={pol.network} "
                 f"fusion flags: flash_attention={flags['flash_attention']} "
                 f"fused_mlp={flags['fused_mlp']} "
                 f"fused_norm={flags['fused_norm']} "
                 f"applied=[{', '.join(applied) or 'none'}]")

    # Insight 2's batch split: batch-sensitive stages (projections) set
    # the engine-wide slot count, batch-agnostic stages (attention/scan)
    # bound the lock-step decode batch.  The CLI --max-batch stays a cap
    # (cache memory), the policy drives within it.
    sens, agn = pol.batch_sensitive_batch, pol.batch_agnostic_batch
    eng_batch = max(1, min(max_batch, sens))
    dec_batch = max(1, min(eng_batch, agn))
    lines.append(f"[serve] policy microbatch: max_batch {max_batch}->"
                 f"{eng_batch} (batch_sensitive_batch={sens}), "
                 f"decode_batch={dec_batch} (batch_agnostic_batch={agn})")
    if mcfg.family != "transformer":
        # recurrent / encoder-decoder families decode through the
        # ALWAYS-gathered DecodeState sub-batch, so the policy's
        # batch-agnostic split maps to the gathered lane width directly
        lines.append(f"[serve] policy microbatch: {mcfg.family} decodes "
                     f"gathered at width {dec_batch} (recurrent state is "
                     f"irreversible; no full-width emulation)")

    tp = pol.tp_degree
    if tp > 1 and n_devices % tp == 0 and n_devices >= tp:
        lines.append(f"[serve] policy tp={tp}: building mesh with model "
                     f"axis {tp} over {n_devices} device(s); engine "
                     f"params/cache/compute shard over it")
        mesh_tp = tp
    else:
        if tp > 1:
            lines.append(f"[serve] policy tp={tp}: only {n_devices} "
                         f"device(s), running unsharded (tp=1)")
        mesh_tp = 1
    kwargs = {"max_batch": eng_batch, "decode_batch": dec_batch}
    return mcfg, {**kwargs, "mesh_tp": mesh_tp}, lines


def replica_mesh(n_replicas: int):
    """One device per replica: a ("data", "model") = (N, 1) mesh over the
    first N devices in `jax.devices()` order, which
    `parallel.sharding.replica_meshes` splits so that replica i runs on
    device i.  (`jax.make_mesh` would reorder the devices along the
    chips' physical ring; replicas never talk to each other, so the order
    is kept plain.)  None (replicas share the default device) when the
    process sees one device or fewer devices than replicas."""
    devices = jax.devices()
    if n_replicas < 2 or len(devices) < 2 or n_replicas > len(devices):
        return None
    return Mesh(np.asarray(devices[:n_replicas]).reshape(n_replicas, 1),
                ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", required=True, choices=configs.ARCH_IDS)
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--max-batch", type=int, default=4)
    p.add_argument("--max-len", type=int, default=128)
    p.add_argument("--specdec", action="store_true",
                   help="speculative decoding demo (draft = thinner config; "
                        "uncached reference loop — see --scenario specdec "
                        "for the live in-engine path)")
    p.add_argument("--k", type=int, default=None,
                   help="spec-decode draft window (default: the "
                        "MOZART_SPEC_K knob)")
    p.add_argument("--scenario", default=None, choices=("", "specdec"),
                   help="serving scenario (default: the MOZART_SCENARIO "
                        "knob): `specdec` serves through the live "
                        "SpecDecodeEngine (SpecDecodeScenario; draft = "
                        "shared-trunk layer truncation)")
    p.add_argument("--policy", default=None, metavar="DEPLOYMENT_JSON",
                   help="mozart deployment artifact (or bare policy JSON) "
                        "to apply: fusion flags, microbatches, TP")
    p.add_argument("--policy-network", default=None,
                   help="which network's policy to take from a "
                        "multi-network artifact")
    p.add_argument("--replicas", type=int, default=None,
                   help="serving-cluster replica count (default: the "
                        "MOZART_REPLICAS knob); >1 maps replicas onto "
                        "the mesh 'data' axis")
    p.add_argument("--router", default=None,
                   choices=("round_robin", "least_loaded",
                            "shortest_queue"),
                   help="cluster routing policy (default: the "
                        "MOZART_ROUTER knob)")
    p.add_argument("--rate", type=float, default=0.0,
                   help="open-loop Poisson arrival rate in req/s for "
                        "the cluster path (0 = closed-loop burst)")
    p.add_argument("--deadline-ms", type=float, default=None,
                   help="per-request SLO deadline in ms (default: the "
                        "MOZART_DEADLINE_DEFAULT_MS knob; 0 = none); "
                        "infeasible requests are shed at admission")
    p.add_argument("--chaos", action="store_true",
                   help="replay a seeded fault script (MOZART_CHAOS_SEED: "
                        "kill/restart/stall/nan) against the cluster "
                        "while it serves")
    p.add_argument("--profile", default=None, metavar="DIR",
                   help="turn the engine's serve.* spans on and write a "
                        "JAX profiler trace of the serving loop under DIR")
    args = p.parse_args()
    use_compile_cache()

    def serving_loop():
        return spans.profile(args.profile) if args.profile \
            else contextlib.nullcontext()

    mcfg = configs.get_smoke_config(args.arch) if args.smoke \
        else configs.get_config(args.arch)

    eng_kwargs = {"max_batch": args.max_batch}
    if args.policy:
        from repro.mozart import load_policy
        pol = load_policy(args.policy, args.policy_network)
        mcfg, kw, lines = apply_policy(pol, mcfg, args.max_batch)
        for ln in lines:
            print(ln)
        mesh_tp = kw.pop("mesh_tp")
        if mesh_tp > 1:
            from repro.launch.mesh import make_host_mesh
            mesh = make_host_mesh(model_axis=mesh_tp)
            axes = dict(zip(mesh.axis_names, mesh.devices.shape))
            print(f"[serve] mesh built: {axes}; engine params/cache "
                  f"placed with parallel.sharding rules")
            kw["mesh"] = mesh
        eng_kwargs = kw

    params = api.init_params(mcfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    spec_k = args.k if args.k is not None else knobs.get_int("MOZART_SPEC_K")

    if args.specdec:
        if mcfg.family != "transformer":
            raise SystemExit("specdec demo targets transformer archs")
        dcfg = mcfg.replace(n_layers=max(1, mcfg.n_layers // 4))
        dparams = api.init_params(dcfg, jax.random.PRNGKey(1))
        # one-shot CLI demo: the jitted pair lives for exactly one
        # spec-decode run, so per-call construction cannot re-trace
        tf = jax.jit(lambda t: transformer.forward(mcfg, params, t))  # mzc: ignore[MZC013]
        df = jax.jit(lambda t: transformer.forward(dcfg, dparams, t))  # mzc: ignore[MZC013]
        prompt = rng.integers(0, mcfg.vocab, size=12).astype(np.int32)
        t0 = time.time()
        out, stats = spec_decode_greedy(tf, df, prompt, k=spec_k,
                                        max_new_tokens=args.max_new)
        dt = time.time() - t0
        print(f"[serve] specdec: {len(out)} tokens in {dt:.2f}s; "
              f"accept={stats.acceptance_rate:.2f} "
              f"tokens/iter={stats.tokens_per_iteration:.2f}")
        return

    scenario = args.scenario if args.scenario is not None \
        else knobs.get_str("MOZART_SCENARIO")
    if scenario == "specdec":
        from repro.core.scenarios import get_scenario
        from repro.serving.specdec import (SpecDecodeEngine,
                                           shared_trunk_draft)
        if mcfg.family != "transformer":
            raise SystemExit("--scenario specdec needs a transformer arch")
        sc = get_scenario("spec_decode")
        try:
            dcfg, dparams = shared_trunk_draft(
                mcfg, params, max(1, mcfg.n_layers // 4))
            draft_src = "shared-trunk"
        except ValueError:
            # scanned/multi-segment archs: fall back to a fresh-init
            # thin draft (acceptance will be whatever it is)
            dcfg = mcfg.replace(n_layers=max(1, mcfg.n_layers // 4))
            dparams = api.init_params(dcfg, jax.random.PRNGKey(1))
            draft_src = "fresh-init"
        eng = SpecDecodeEngine(mcfg, params, dcfg, dparams, k=spec_k,
                               max_len=args.max_len, **eng_kwargs)
        print(f"[serve] scenario={sc.name} (roles={sc.roles}): live "
              f"spec-decode, k={spec_k}, draft={draft_src} "
              f"{dcfg.n_layers}/{mcfg.n_layers} layers")
        for i in range(args.requests):
            plen = int(rng.integers(4, 12))
            eng.submit(Request(
                rid=i, prompt=rng.integers(0, mcfg.vocab,
                                           size=plen).astype(np.int32),
                max_new_tokens=args.max_new))
        t0 = time.time()
        with serving_loop():
            eng.run()
        dt = time.time() - t0
        st = eng.spec_stats
        print(f"[serve] specdec-live: {eng.stats['tokens_out']} tokens in "
              f"{dt:.2f}s ({eng.stats['tokens_out'] / max(dt, 1e-9):.1f} "
              f"tok/s); accept={st.acceptance_rate:.2f} "
              f"tokens/iter={st.tokens_per_iteration:.2f} "
              f"({eng.stats['decode_steps']} verify steps)")
        return

    n_replicas = args.replicas or knobs.get_int("MOZART_REPLICAS")
    if n_replicas > 1:
        from repro.serving.cluster import LoadGenerator, ServingCluster
        from repro.serving.resilience import ChaosSchedule
        mesh = eng_kwargs.pop("mesh", None) or replica_mesh(n_replicas)
        deadline_ms = args.deadline_ms if args.deadline_ms is not None \
            else float(knobs.get_int("MOZART_DEADLINE_DEFAULT_MS"))
        deadline_bands = (((deadline_ms / 1e3, deadline_ms / 1e3),)
                          if deadline_ms > 0 else None)
        cl = ServingCluster(mcfg, params, n_replicas=n_replicas,
                            router=args.router, mesh=mesh,
                            max_len=args.max_len, **eng_kwargs)
        lg = LoadGenerator(n_requests=args.requests, rate=args.rate,
                           vocab=mcfg.vocab, seed=0,
                           max_new_tokens=args.max_new,
                           deadline_bands=deadline_bands)
        chaos = None
        if args.chaos:
            chaos = ChaosSchedule.generate(
                n_replicas=n_replicas,
                horizon=max(args.requests * args.max_new, 64))
            print(f"[serve] chaos script: "
                  f"{[(e.step, e.kind, e.replica) for e in chaos.events]}")
        t0 = time.time()
        with serving_loop():
            summary = cl.drive(lg.schedule(), chaos=chaos)
        dt = time.time() - t0
        agg = summary["aggregate"]
        print(f"[serve] cluster x{n_replicas} router={cl.router.policy} "
              f"rate={args.rate:g}: {agg['tokens_out']} tokens in "
              f"{dt:.2f}s ({agg['tokens_out'] / max(dt, 1e-9):.1f} tok/s "
              f"aggregate), ttft p50/p99 "
              f"{agg['ttft_p50_ms']:.1f}/{agg['ttft_p99_ms']:.1f}ms, "
              f"tpot p50/p99 "
              f"{agg['tpot_p50_ms']:.2f}/{agg['tpot_p99_ms']:.2f}ms")
        print(f"[serve]   goodput {agg['goodput_tokens']} tokens "
              f"({agg['goodput_tokens'] / max(dt, 1e-9):.1f} tok/s), "
              f"deadlines met/missed "
              f"{agg['deadline_met']}/{agg['deadline_missed']}, "
              f"shed={agg['shed']} poisoned={agg['poisoned']} "
              f"quarantined={agg['quarantined']} "
              f"restarts={agg['restarts']} unrouted={agg['n_unrouted']}")
        for row in summary["per_replica"]:
            print(f"[serve]   replica {row['replica']}: "
                  f"{row['tokens_out']} tokens, {row['prefills']} "
                  f"prefills, {row['preemptions']} preemptions")
        return

    eng = ServingEngine(mcfg, params, max_len=args.max_len, **eng_kwargs)
    for i in range(args.requests):
        plen = int(rng.integers(4, 12))
        eng.submit(Request(
            rid=i, prompt=rng.integers(0, mcfg.vocab,
                                       size=plen).astype(np.int32),
            max_new_tokens=args.max_new))
    t0 = time.time()
    with serving_loop():
        eng.run()
    dt = time.time() - t0
    occ = eng.stats["live_slot_steps"] / max(
        eng.stats["decode_steps"] * eng.max_batch, 1)
    print(f"[serve] {eng.stats['tokens_out']} tokens, "
          f"{eng.stats['decode_steps']} steps, "
          f"{eng.stats['prefills']} prefills in {dt:.2f}s "
          f"({eng.stats['tokens_out'] / max(dt, 1e-9):.1f} tok/s, "
          f"occupancy {occ:.2f})")


if __name__ == "__main__":
    main()
