"""Chip smoke test: serve SmolLM-135M at its published widths on a TPU.

    python chip_smoke.py              # one chip: plain and fused-kernel serving
    python chip_smoke.py --chips 4    # four chips: one replica per chip
                                      # against four replicas on chip 0

It drives the serving stack through the entry points a user calls
(`configs.get_config`, `api.init_params`, `ServingEngine`, `apply_policy`,
`ServingCluster`) with random weights made from `--seed`, and checks what
comes out.  Every line but the last is labelled `[smoke]`; its timings are
smoke output, not benchmark results.  The last line of standard output is
one JSON object, `{"ok": true, "device": {...}}`, printed only when every
check passed.  The script exits non-zero on any failed check, and at once
when JAX finds no TPU: it never falls back to the CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "smollm-135m"
MAX_BATCH = 4
MAX_LEN = 256
N_REQUESTS = 8
MAX_NEW = 32
# fused-kernel vs plain first-step logits, both bf16 end to end: max
# |fused - plain| over max |plain|, per request.  The kernels keep f32
# inside and round once; the plain path rounds to bf16 after every op, and
# the gap grows with depth (on the CPU at full width: 0.014 at 2 layers,
# 0.027 at 8, 0.036 at 16).  A kernel that reads the wrong head, page or
# mask is off by a large fraction of the logits' range.
LOGIT_RTOL = 0.1


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def make_requests(vocab: int, seed: int, n: int, max_new: int):
    """Seeded prompts whose lengths span several prefill buckets."""
    from repro.serving.engine import Request

    rng = np.random.default_rng(seed)
    lens = rng.integers(4, 120, size=n)
    lens[:2] = (12, 100)  # buckets 16 and 128 at least
    return [
        Request(rid=i, prompt=rng.integers(0, vocab, size=int(s)).astype(np.int32),
                max_new_tokens=max_new)
        for i, s in enumerate(lens)
    ]


def check_finished(reqs, vocab: int, what: str) -> None:
    for r in reqs:
        check(r.finish_reason == "max_new_tokens",
              f"{what}: request {r.rid} finished {r.finish_reason!r}")
        check(len(r.out_tokens) == r.max_new_tokens,
              f"{what}: request {r.rid} has {len(r.out_tokens)} tokens")
        check(all(0 <= t < vocab for t in r.out_tokens),
              f"{what}: request {r.rid} has a token outside the vocab")


def check_healthy(eng, what: str) -> None:
    check(not eng.health["nan_detected"], f"{what}: non-finite logits")
    check(eng.stats["rejected"] == 0 and eng.stats["shed"] == 0,
          f"{what}: rejected {eng.stats['rejected']}, shed {eng.stats['shed']}")


def timed_pass(eng, reqs) -> float:
    """Serve `reqs` to completion; seconds on the host clock.  Every
    sampled token is a host int, so the run has synced with the device
    by the time it returns."""
    t0 = time.perf_counter()
    for r in reqs:
        check(eng.submit(r), f"request {r.rid} was shed at submission")
    eng.run()
    return time.perf_counter() - t0


def record_first_logits(eng) -> tuple[dict, object]:
    """Wrap the engine's prefill to keep each prompt's first-step
    (last prompt token) logits; returns the record and the original."""
    seen: dict = {}
    inner = eng.state.prefill

    def prefill(fn, params, b, seq, frames=None):
        last = inner(fn, params, b, seq, frames)
        seen.setdefault(tuple(int(t) for t in seq),
                        np.asarray(last[0, -1], np.float32))
        return last

    eng.state.prefill = prefill
    return seen, inner


def serve_twice(eng, vocab: int, seed: int, n: int, max_new: int, what: str):
    """First pass (compiles), then an identical second pass under a
    compile counter.  Returns (first-pass requests, first-step logits)."""
    from tools.mozart_check.tracecheck import CompileMonitor

    first, inner = record_first_logits(eng)
    reqs = make_requests(vocab, seed, n, max_new)
    t_first = timed_pass(eng, reqs)
    eng.state.prefill = inner
    check_finished(reqs, vocab, what)
    check_healthy(eng, what)

    again = make_requests(vocab, seed, n, max_new)
    with CompileMonitor() as mon:
        t_second = timed_pass(eng, again)
    check_finished(again, vocab, what)
    check_healthy(eng, what)
    toks = sum(len(r.out_tokens) for r in again)
    log(f"{what}: first pass {t_first:.3f} s (compiles included); second pass "
        f"{toks} tokens in {t_second:.3f} s = {toks / t_second:.1f} tokens/s, "
        f"{mon.count} recompiles")
    check(mon.count == 0, f"{what}: {mon.count} recompiles in the second pass")
    check([r.out_tokens for r in again] == [r.out_tokens for r in reqs],
          f"{what}: the second pass emitted other tokens than the first")
    return reqs, first


def fused_policy():
    """A deployment policy whose fusion flags select all three kernels."""
    from repro.core.policy import ExecutionPolicy, OperatorPolicy

    ops = [OperatorPolicy(group=g, batch=MAX_BATCH, tp=1, memory="hbm",
                          chiplet="tpu", fused=True)
           for g in ("norm1+qkv_proj+attention", "norm2+mlp")]
    return ExecutionPolicy(network=ARCH, interval_s=0.0, operators=ops)


def prefill_program_text(eng, bucket: int) -> str:
    """The compiled paged prefill program the engine runs at `bucket`."""
    from repro.serving import paged

    ps = eng.pool.page_size
    fn = paged.paged_prefill_fn(eng.mcfg, bucket, ps, eng.kv_quant)
    toks = np.zeros((1, bucket), np.int32)
    row = eng.pool.table_row(0, bucket // ps)
    args = (eng.params, toks, 1, eng.pool.segments)
    if eng.kv_quant:
        args += (eng.pool.scales,)
    return fn.lower(*args, row).compile().as_text()


def one_chip(mcfg, params, seed: int) -> None:
    from repro.launch.serve import apply_policy
    from repro.serving.engine import ServingEngine

    eng = ServingEngine(mcfg, params, max_batch=MAX_BATCH, max_len=MAX_LEN)
    check(eng.paged, "plain: the engine did not take the paged KV path")
    log(f"plain: paged KV, max_batch {MAX_BATCH}, max_len {MAX_LEN}, "
        f"buckets {eng.buckets}, {N_REQUESTS} requests x {MAX_NEW} new tokens")
    plain_reqs, plain_first = serve_twice(eng, mcfg.vocab, seed, N_REQUESTS,
                                          MAX_NEW, "plain")
    del eng

    fcfg, kw, lines = apply_policy(fused_policy(), mcfg, MAX_BATCH, n_devices=1)
    for line in lines:
        log(line)
    check((fcfg.attn_impl, fcfg.mlp_impl, fcfg.norm_impl) == ("flash", "fused", "fused"),
          "fused: the policy did not select all three kernels")
    kw.pop("mesh_tp")
    feng = ServingEngine(fcfg, params, max_len=MAX_LEN, **kw)
    fused_reqs, fused_first = serve_twice(feng, mcfg.vocab, seed, N_REQUESTS,
                                          MAX_NEW, "fused")
    bucket = feng.buckets[0]
    text = prefill_program_text(feng, bucket)
    check("tpu_custom_call" in text,
          f"fused: the bucket-{bucket} prefill program has no tpu_custom_call")
    log(f"fused: the compiled bucket-{bucket} prefill program holds "
        f"{text.count('tpu_custom_call')} tpu_custom_call mentions")

    check(plain_first.keys() == fused_first.keys(),
          "fused: other prompts were prefilled than on the plain path")
    worst = 0.0
    for key, want in plain_first.items():
        got = fused_first[key]
        check(bool(np.all(np.isfinite(got))), "fused: non-finite first-step logits")
        worst = max(worst, float(np.max(np.abs(got - want)) / np.max(np.abs(want))))
    log(f"fused vs plain first-step logits: max |diff| / max |logit| = {worst:.6f} "
        f"(limit {LOGIT_RTOL})")
    check(worst <= LOGIT_RTOL, f"fused: first-step logits differ by {worst:.6f}")
    agree = sum(a == b for p, f in zip(plain_reqs, fused_reqs)
                for a, b in zip(p.out_tokens, f.out_tokens))
    log(f"fused vs plain greedy tokens agreeing position by position: "
        f"{agree}/{N_REQUESTS * MAX_NEW} (random weights: ties expected)")


def placed_on(tree, device) -> bool:
    return all(leaf.devices() == {device} for leaf in jax.tree.leaves(tree))


def four_chips(mcfg, params, seed: int) -> None:
    from repro.launch.serve import replica_mesh
    from repro.serving.cluster import ServingCluster

    devices = jax.devices()
    n = 4
    check(len(devices) >= n, f"--chips 4: JAX sees {len(devices)} devices")
    mesh = replica_mesh(n)
    check(mesh is not None, "--chips 4: serve built no replica mesh")
    kw = dict(n_replicas=n, router="round_robin", max_batch=MAX_BATCH,
              max_len=MAX_LEN)
    spread = ServingCluster(mcfg, params, mesh=mesh, **kw)
    for i, eng in enumerate(spread.replicas):
        check(placed_on(eng.params, devices[i]) and placed_on(eng.pool.segments, devices[i]),
              f"--chips 4: replica {i} is not on {devices[i]}")
    log(f"one replica per chip: replica i's params and page pool on "
        f"{[str(devices[i]) for i in range(n)]}")
    shared = ServingCluster(mcfg, params, **kw)
    for i, eng in enumerate(shared.replicas):
        check(placed_on(eng.params, devices[0]) and placed_on(eng.pool.segments, devices[0]),
              f"--chips 4: shared replica {i} is not on {devices[0]}")

    streams = {}
    for name, cl in (("one per chip", spread), ("all on chip 0", shared)):
        reqs = make_requests(mcfg.vocab, seed, 2 * N_REQUESTS, MAX_NEW // 2)
        t0 = time.perf_counter()
        for r in reqs:
            cl.submit(r)
        cl.run()
        dt = time.perf_counter() - t0
        check_finished(reqs, mcfg.vocab, name)
        for eng in cl.replicas:
            check_healthy(eng, name)
        served = [sum(1 for r in reqs if cl.assignment[r.rid] == i) for i in range(n)]
        log(f"{name}: {len(reqs)} requests routed {served}, served in {dt:.3f} s "
            f"(compiles included)")
        streams[name] = [r.out_tokens for r in reqs]
    check(streams["one per chip"] == streams["all on chip 0"],
          "--chips 4: token streams differ between the two placements")
    log("--chips 4: greedy token streams identical across placements")


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4 runs only the one-replica-per-chip cluster and "
                        "its all-on-chip-0 comparison")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"[smoke] FAIL: JAX finds no TPU (device 0 is {dev.platform}); "
              f"this smoke test runs only on the chip", file=sys.stderr)
        return 1
    from repro import configs
    from repro.launch.compile_cache import use_compile_cache
    from repro.models import api

    log(f"device {dev.device_kind}, {len(devices)} device(s); compile cache "
        f"{use_compile_cache()}")
    mcfg = configs.get_config(ARCH)
    log(f"{ARCH}: {mcfg.n_layers} layers, d_model {mcfg.d_model}, heads "
        f"{mcfg.n_heads}/{mcfg.kv_heads}, d_ff {mcfg.d_ff}, vocab {mcfg.vocab}, "
        f"{mcfg.dtype}, random weights from seed {args.seed}")
    params = api.init_params(mcfg, jax.random.PRNGKey(args.seed))
    try:
        if args.chips == 4:
            four_chips(mcfg, params, args.seed)
        else:
            one_chip(mcfg, params, args.seed)
    except SmokeFailure as e:
        print(f"[smoke] FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
