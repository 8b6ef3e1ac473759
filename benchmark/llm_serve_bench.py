#!/usr/bin/env python
"""Continuous-batching LLM serving benchmark.

The ISSUE 7 acceptance harness, extended by ISSUE 11: a mixed-length
request workload (short and long prompts, varied max_new_tokens) served
several ways —

- **sequential baseline**: one warm ``generate()`` call per request,
  batch 1, exactly how the repo decoded before ``serving.llm`` (a
  single long sequence holds the device while every other request
  waits);
- **continuous batching**: the same requests through
  :class:`~mxnet_tpu.serving.llm.LLMEngine` — paged KV block pool,
  pow2-bucketed prefill spliced into the running decode batch, in-flight
  admission into free lanes every step;
- **speculative + prefix-cached** (``--spec --prefix``): a
  shared-system-prompt workload served twice — by the plain PR-7 engine
  and by the engine with a weight-sharing draft model proposing
  ``--draft-k`` tokens per verify round AND the shared-prefix block
  cache skipping the resident prefix's prefill. The ISSUE 11 acceptance
  gate: >=2x aggregate tok/s over the plain engine on that workload,
  ``prefix_hit_rate > 0``, ``draft_acceptance_rate`` recorded, zero
  compiles in the timed window.

Reported: aggregate tok/s each way, speedups, p50/p99 per-token latency,
lane occupancy, an int8-KV engine row, greedy token-parity checks
(engine vs offline; spec+prefix engine vs plain engine), and the
no-retrace gates. ``--quick`` is the seconds-scale smoke wired into
tier-1 (``tests/test_perf_harnesses.py::test_llm_serve_bench_quick``);
the full run banks ``benchmark/results_llm_serving_cpu.json``.

CLI:
    python benchmark/llm_serve_bench.py [--quick] [--output out.json]
        [--units 384] [--layers 2] [--requests 48] [--lanes 16]
        [--spec] [--prefix] [--draft-k 4] [--draft-layers 1]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as onp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import code_rev  # noqa: E402


def log(*a):
    print("[llm_serve_bench]", *a, file=sys.stderr, flush=True)


def build_workload(rng, vocab, configs, n_requests):
    """(prompt, max_new) pairs cycling the mixed-length configs."""
    reqs = []
    for i in range(n_requests):
        p, n = configs[i % len(configs)]
        reqs.append((rng.randint(0, vocab, (p,)).astype(onp.int32), n))
    return reqs


def build_prefix_workload(rng, vocab, prefix_len, configs, n_requests):
    """Shared-system-prompt workload: every request = one shared
    ``prefix_len``-token preamble + a unique tail, cycling the (tail,
    max_new) configs — the millions-of-users shape prefix caching
    exists for."""
    shared = rng.randint(0, vocab, (prefix_len,)).astype(onp.int32)
    reqs = []
    for i in range(n_requests):
        t, n = configs[i % len(configs)]
        tail = rng.randint(0, vocab, (t,)).astype(onp.int32)
        reqs.append((onp.concatenate([shared, tail]), n))
    return reqs


def make_draft(net, *, vocab, units, heads, max_length, draft_layers):
    """A draft model sharing the target's embeddings + leading layers:
    the cheap truncated-stack draft (same residual stream early exit),
    whose proposals correlate with the target far better than an
    independent random model — acceptance is measured, not assumed."""
    from mxnet_tpu.gluon.model_zoo.bert import gpt_like

    draft = gpt_like(vocab_size=vocab, units=units,
                     hidden_size=4 * units, num_layers=draft_layers,
                     num_heads=heads, max_length=max_length, dropout=0.0)
    draft.initialize()
    tgt = net.collect_params()
    for name, p in draft.collect_params().items():
        src = tgt.get(name)
        if src is not None and tuple(src.shape) == tuple(p.shape):
            p.set_data(src.data())
    return draft


def damp_upper_layers(net, num_layers, alpha):
    """Scale the residual branches of layers >= 1 by ``alpha``.

    Random-init draft/target pairs are adversarially uncorrelated — a
    truncated-stack draft of a random target accepts at ~chance, which
    measures nothing (production drafts are DISTILLED to match their
    target). Damping the upper layers' residual contributions puts the
    synthetic pair in the distilled regime so the harness exercises
    realistic acceptance; alpha is reported in the banked row and the
    acceptance rate is measured, never assumed."""
    for i in range(1, num_layers):
        ly = getattr(net.encoder, f"layer{i}")
        for p in (ly.attn.out_proj.weight, ly.attn.out_proj.bias,
                  ly.ffn.ffn_2.weight, ly.ffn.ffn_2.bias):
            p.set_data(p.data() * alpha)


def run_sequential(net, reqs, configs, rng, vocab):
    """Warm one generate() program per config, then serve the workload
    one request at a time (the pre-engine decode path)."""
    from mxnet_tpu.gluon.model_zoo.generation import generate

    for p, n in configs:                    # warm (compiles excluded)
        prompt = rng.randint(0, vocab, (1, p)).astype(onp.int32)
        generate(net, prompt, max_new_tokens=n).asnumpy()
    outs = []
    t0 = time.perf_counter()
    for prompt, n in reqs:
        outs.append(generate(net, prompt[None],
                             max_new_tokens=n).asnumpy()[0])
    return time.perf_counter() - t0, outs


def token_latency_ms(t0, t1):
    """Per-token latency over ``[t0, t1]`` (``time.perf_counter``), read
    from the engine's spans: each decode step's ``llm.decode.launch`` +
    ``llm.decode.fetch`` over the tokens its ``llm.emit`` handed out."""
    from mxnet_tpu.telemetry import tracing
    from mxnet_tpu.telemetry.registry import Histogram

    hist, step_s = Histogram(), 0.0
    for name, start, end, args in sorted(tracing.rows(t0, t1),
                                         key=lambda r: r[1]):
        if name in ("llm.decode.launch", "llm.decode.fetch"):
            step_s += end - start
        elif name == "llm.emit":
            if args.get("tokens"):
                hist.observe(step_s * 1e3 / args["tokens"])
            step_s = 0.0
    return hist.summary()


def run_engine(net, reqs, *, lanes, block_size, max_context, kv_dtype,
               wait_s, draft=None, draft_k=4, prefix=False,
               prime_reqs=None, num_blocks=None, donate=None):
    from mxnet_tpu.serving.llm import LLMEngine

    eng = LLMEngine(net, max_running=lanes, block_size=block_size,
                    max_context=max_context, kv_cache_dtype=kv_dtype,
                    num_blocks=num_blocks, draft_model=draft,
                    draft_k=draft_k, prefix_cache=prefix, donate=donate)
    eng.warmup(prompt_lengths=sorted({int(p.shape[0]) for p, _ in reqs}))
    if prime_reqs:
        # untimed steady-state priming: compiles every suffix bucket /
        # spec program and fills the prefix cache — the timed window
        # below measures the serving steady state, not cold starts
        for h in [eng.submit(p, n) for p, n in prime_reqs]:
            h.wait(timeout=wait_s)
    c0 = dict(eng.stats()["counters"])
    t0 = time.perf_counter()
    handles = [eng.submit(p, n) for p, n in reqs]
    outs = [h.wait(timeout=wait_s) for h in handles]
    wall = time.perf_counter() - t0
    stats = eng.stats()
    eng.close()
    token_latency = token_latency_ms(t0, time.perf_counter())
    total = sum(n for _, n in reqs)
    c = {k: stats["counters"][k] - c0.get(k, 0)
         for k in stats["counters"]}
    occupancy = (c["decode_steps"] and
                 (total - c["prefills"]) / c["decode_steps"])
    row = {
        "wall_s": round(wall, 3),
        "tok_s": round(total / wall, 1),
        "kv_cache_dtype": kv_dtype,
        "lane_occupancy": round(float(occupancy), 2),
        "lanes": lanes,
        "decode_steps": c["decode_steps"],
        "prefills": c["prefills"],
        "decode_step_ms": stats["decode_step_ms"],
        "prefill_ms": stats["prefill_ms"],
        "token_latency_ms": token_latency,
        "token_latency_p50_ms": token_latency["p50"],
        "token_latency_p99_ms": token_latency["p99"],
        # zero compiles in the timed window = every shape was warmed =
        # sequence growth / admission / retirement never retraced
        "compiles_during_serving": c["compiles"],
        "pool_blocks_total": stats["pool_blocks_total"],
    }
    if draft is not None:
        row["speculative"] = stats["speculative"]
        row["draft_acceptance_rate"] = \
            stats["speculative"]["draft_acceptance_rate"]
        row["spec_steps"] = c["spec_steps"]
    if prefix:
        row["prefix_cache"] = stats["prefix_cache"]
        row["prefix_hit_rate"] = stats["prefix_cache"]["prefix_hit_rate"]
    return row, outs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="seconds-scale smoke (tier-1)")
    ap.add_argument("--units", type=int, default=0)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--requests", type=int, default=0)
    ap.add_argument("--lanes", type=int, default=0)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--spec", action="store_true",
                    help="add the speculative-decoding rows")
    ap.add_argument("--prefix", action="store_true",
                    help="add the shared-prefix caching rows")
    ap.add_argument("--draft-k", type=int, default=3)
    ap.add_argument("--draft-layers", type=int, default=1)
    ap.add_argument("--output", default=None)
    args = ap.parse_args()

    import jax

    import mxnet_tpu as mx  # noqa: F401
    from mxnet_tpu.gluon.model_zoo.bert import gpt_like

    platform = jax.devices()[0].platform
    quick = bool(args.quick)
    units = args.units or (128 if quick else 384)
    n_requests = args.requests or (12 if quick else 48)
    lanes = args.lanes or (8 if quick else 16)
    # mixed lengths: short/long prompts x short/long generations
    configs = ([(8, 12), (24, 16), (12, 8)] if quick
               else [(8, 32), (24, 40), (48, 48), (12, 24)])
    max_context = 64 if quick else 96
    onp.random.seed(0)
    net = gpt_like(vocab_size=args.vocab, units=units,
                   hidden_size=4 * units, num_layers=args.layers,
                   num_heads=args.heads, max_length=512, dropout=0.0)
    net.initialize()
    rng = onp.random.RandomState(1)
    reqs = build_workload(rng, args.vocab, configs, n_requests)
    total = sum(n for _, n in reqs)
    wait_s = 600 if quick else 1200

    log(f"workload: {n_requests} requests, {total} new tokens, "
        f"configs {configs}, units {units}, lanes {lanes}")
    seq_dt, seq_outs = run_sequential(net, reqs, configs, rng, args.vocab)
    log(f"sequential: {total / seq_dt:.1f} tok/s ({seq_dt:.2f}s)")

    # headline: the engine at its DEFAULT configuration (int8 KV — the
    # bandwidth-bound decode path reads half the bytes, and on CPU the
    # narrower gather wins too)
    eng_row, _ = run_engine(
        net, reqs, lanes=lanes, block_size=args.block_size,
        max_context=max_context, kv_dtype="int8", wait_s=wait_s)
    log(f"engine int8-kv: {eng_row['tok_s']} tok/s "
        f"(occupancy {eng_row['lane_occupancy']})")

    # fp32-KV row: bit-exact math vs the dense cache, so greedy tokens
    # must be IDENTICAL to the offline baseline per sequence (the
    # acceptance gate: paged continuous batching must not change tokens)
    fp_row, eng_outs = run_engine(
        net, reqs, lanes=lanes, block_size=args.block_size,
        max_context=max_context, kv_dtype="float32", wait_s=wait_s)
    log(f"engine fp32-kv: {fp_row['tok_s']} tok/s")
    mismatches = sum(
        1 for a, b in zip(seq_outs, eng_outs)
        if list(a) != list(onp.asarray(b)))
    parity = {"token_identical": mismatches == 0,
              "n_checked": len(reqs), "n_mismatched": mismatches}
    log(f"parity: {parity}")

    rec = {
        "metric": "llm_continuous_batching",
        "value": eng_row["tok_s"],
        "unit": "tok/s",
        "quick": quick,
        "device": platform,
        "device_kind": getattr(jax.devices()[0], "device_kind", ""),
        "workload": {
            "n_requests": n_requests,
            "configs": [list(c) for c in configs],
            "total_new_tokens": total,
            "units": units, "layers": args.layers,
            "vocab": args.vocab,
        },
        "sequential": {"wall_s": round(seq_dt, 3),
                       "tok_s": round(total / seq_dt, 1)},
        "engine": eng_row,
        "engine_fp32": fp_row,
        "speedup": round(seq_dt / eng_row["wall_s"], 2),
        "speedup_fp32": round(seq_dt / fp_row["wall_s"], 2),
        "int8_vs_fp32": round(eng_row["tok_s"] / fp_row["tok_s"], 3),
        "parity": parity,
        "zero_retraces":
            eng_row["compiles_during_serving"] == 0
            and fp_row["compiles_during_serving"] == 0,
        "code_rev": code_rev(),
    }

    if args.spec or args.prefix:
        # the ISSUE 11 decode-at-the-roofline rows: a shared-prefix
        # workload served by the plain PR-7 engine vs spec+prefix. The
        # shape is the system-prompt fleet shape prefix caching exists
        # for: a LONG shared preamble (most of every request's compute
        # under the plain engine is re-prefilling it — production
        # system prompts run hundreds to thousands of tokens), short
        # unique tails, moderate generations. Its own target model:
        # deeper than the front rows (a 1-layer draft must be
        # proportionally cheap) with draft-friendly upper-layer damping
        # (see damp_upper_layers). Both engines run donate=True (the
        # in-place pool update; without it every launch copies the
        # full pools, which flattens every ratio on CPU).
        bs = args.block_size
        sp_layers = args.layers if quick else max(args.layers, 4)
        sp_alpha = 0.05
        prefix_len = 3 * bs if quick else 28 * bs
        sp_configs = ([(4, 8), (bs - 2, 12), (6, 8)] if quick
                      else [(4, 16), (12, 24), (bs + 4, 12), (8, 16)])
        sp_requests = n_requests
        sp_max_context = (prefix_len + 2 * bs
                          + max(n for _, n in sp_configs) + args.draft_k)
        onp.random.seed(10)
        sp_net = gpt_like(vocab_size=args.vocab, units=units,
                          hidden_size=4 * units, num_layers=sp_layers,
                          num_heads=args.heads, max_length=512,
                          dropout=0.0)
        sp_net.initialize()
        damp_upper_layers(sp_net, sp_layers, sp_alpha)
        sp_rng = onp.random.RandomState(2)
        sp_reqs = build_prefix_workload(sp_rng, args.vocab, prefix_len,
                                        sp_configs, sp_requests)
        prime = build_prefix_workload(
            onp.random.RandomState(3), args.vocab, prefix_len,
            sp_configs, min(len(sp_configs) * 2, sp_requests))
        # same shared prefix for priming (fills the cache the timed
        # window hits) — build_prefix_workload reseeds, so splice it
        prime = [(onp.concatenate([sp_reqs[0][0][:prefix_len],
                                   p[prefix_len:]]), n)
                 for p, n in prime]
        sp_total = sum(n for _, n in sp_reqs)
        draft = make_draft(
            sp_net, vocab=args.vocab, units=units, heads=args.heads,
            max_length=512,
            draft_layers=args.draft_layers) if args.spec else None

        plain_row, plain_outs = run_engine(
            sp_net, sp_reqs, lanes=lanes, block_size=bs,
            max_context=sp_max_context, kv_dtype="int8", wait_s=wait_s,
            donate=True, prime_reqs=prime[:len(sp_configs)])
        log(f"shared-prefix workload, plain engine: "
            f"{plain_row['tok_s']} tok/s")
        sp_row, sp_outs = run_engine(
            sp_net, sp_reqs, lanes=lanes, block_size=bs,
            max_context=sp_max_context, kv_dtype="int8", wait_s=wait_s,
            donate=True, draft=draft, draft_k=args.draft_k,
            prefix=args.prefix, prime_reqs=prime)
        log(f"shared-prefix workload, spec+prefix engine: "
            f"{sp_row['tok_s']} tok/s "
            f"(acceptance {sp_row.get('draft_acceptance_rate')}, "
            f"hit rate {sp_row.get('prefix_hit_rate')})")
        sp_mism = sum(1 for a, b in zip(plain_outs, sp_outs)
                      if list(onp.asarray(a)) != list(onp.asarray(b)))
        rec["spec_prefix"] = {
            "prefix_len": prefix_len,
            "configs": [list(c) for c in sp_configs],
            "n_requests": sp_requests,
            "total_new_tokens": sp_total,
            "target_layers": sp_layers,
            "draft_friendly_alpha": sp_alpha,
            "draft_k": args.draft_k,
            "draft_layers": args.draft_layers,
            "spec": bool(args.spec),
            "prefix": bool(args.prefix),
            "engine_plain": plain_row,
            "engine_spec_prefix": sp_row,
            "speedup_vs_plain": round(
                plain_row["wall_s"] / sp_row["wall_s"], 2),
            "parity_vs_plain": {"token_identical": sp_mism == 0,
                                "n_checked": len(sp_reqs),
                                "n_mismatched": sp_mism},
            "zero_retraces":
                plain_row["compiles_during_serving"] == 0
                and sp_row["compiles_during_serving"] == 0,
        }
        log(f"spec+prefix speedup vs plain engine: "
            f"{rec['spec_prefix']['speedup_vs_plain']}x "
            f"(parity {rec['spec_prefix']['parity_vs_plain']})")

    text = json.dumps(rec)
    print(text, flush=True)
    if args.output:
        with open(args.output, "w") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()
