"""Speculative decoding as a first-class Mozart scenario (paper §6.2.1)
plus the serving substrate it deploys onto.

Stage 1 codesigns the draft/target pair declaratively: the
`spec_decode` scenario hands the latency-critical draft and the
batched verifier each their own requirement split from the chatbot
TPOT budget (Insight 3), and `mozart.compile` returns one artifact
with both policies.  Stage 2 runs the actual JAX substrate: the
continuous-batching engine and draft/target speculative decoding.

    PYTHONPATH=src python examples/serve_spec_decode.py
"""
import time

import jax
import numpy as np

from repro import configs, mozart
from repro.core import operators
from repro.core.fusion import GAConfig
from repro.core.operators import OPT_1_3B
from repro.core.pool import SAConfig
from repro.models import api, transformer
from repro.serving.engine import Request, ServingEngine
from repro.serving.specdec import spec_decode_greedy


def codesign() -> None:
    scen = mozart.get_scenario("spec_decode")
    d_req = scen.requirement_for("draft")
    t_req = scen.requirement_for("target")
    print(f"scenario: {scen.name} ({scen.description})")
    print(f"  draft  per-token deadline: {d_req.max_e2e * 1e3:.1f} ms")
    print(f"  target verify-pass deadline: {t_req.max_e2e * 1e3:.1f} ms")

    spec = mozart.MozartSpec(
        networks={
            "draft": mozart.NetworkSpec(
                workload=operators.lm_operator_graph(
                    OPT_1_3B, 2048, "decode", cache_len=2048),
                role="draft"),
            "target_verify": mozart.NetworkSpec(
                workload=operators.lm_operator_graph(
                    operators.OPT_66B, seq=scen.k + 1, phase="prefill"),
                role="target"),
        },
        scenario="spec_decode",
        pool_size=4,
        sa=SAConfig(iterations=2,
                    inner_ga=GAConfig(population=4, generations=1)),
        ga=GAConfig(population=6, generations=3),
        baselines=(),
    )
    dep = mozart.compile(spec)
    for name in dep.networks:
        sol = dep.designs[name].fusion.solution
        pol = dep.policy(name)
        print(f"  {name}: lat={sol.delay_e2e * 1e3:.1f} ms "
              f"batch(agnostic/sensitive)="
              f"{pol.batch_agnostic_batch}/{pol.batch_sensitive_batch}")


def substrate() -> None:
    mcfg = configs.get_smoke_config("smollm-135m")
    params = api.init_params(mcfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    # --- continuous batching
    eng = ServingEngine(mcfg, params, max_batch=4, max_len=96)
    for i in range(8):
        eng.submit(Request(
            rid=i,
            prompt=rng.integers(0, mcfg.vocab, size=int(
                rng.integers(4, 12))).astype(np.int32),
            max_new_tokens=12))
    t0 = time.time()
    eng.run()
    occ = eng.stats["live_slot_steps"] / (
        eng.stats["decode_steps"] * eng.max_batch)
    print(f"continuous batching: {eng.stats['tokens_out']} tokens in "
          f"{time.time() - t0:.1f}s, occupancy {occ:.2f}")

    # --- speculative decoding (draft = 1/4-depth model)
    dcfg = mcfg.replace(n_layers=max(1, mcfg.n_layers // 4))
    dparams = api.init_params(dcfg, jax.random.PRNGKey(1))
    # one-shot demo pair — constructed once per example run
    tf = jax.jit(lambda t: transformer.forward(mcfg, params, t))  # mzc: ignore[MZC013]
    df = jax.jit(lambda t: transformer.forward(dcfg, dparams, t))  # mzc: ignore[MZC013]
    prompt = rng.integers(0, mcfg.vocab, size=10).astype(np.int32)
    out, stats = spec_decode_greedy(tf, df, prompt, k=5,
                                    max_new_tokens=20)
    print(f"specdec: {len(out)} tokens, accept={stats.acceptance_rate:.2f},"
          f" tokens/iter={stats.tokens_per_iteration:.2f}"
          f" (draft latency-critical, verifier batched — Insight 3)")


def main() -> None:
    codesign()
    substrate()


if __name__ == "__main__":
    main()
