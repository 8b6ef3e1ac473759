#!/usr/bin/env python
"""Attention-kernel benchmark: Pallas flash attention vs naive XLA
attention across sequence lengths.

Long context is first-class in this framework (SURVEY §5: the reference
materialized O(L²) attention single-device); this measures the fused
blockwise kernel's throughput and memory headroom on the current device.
Reports tokens/s for causal self-attention fwd (inference shape) and
fwd+bwd (training), per sequence length.

CLI:
    python benchmark/attention_bench.py [--seqs 1024,2048,4096,8192]
        [--heads 16] [--head-dim 64] [--batch 8] [--output out.json] [--cpu]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as onp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def measure(fn, x0, tag, log, min_s=3.0):
    """``fn(x) -> (result, next_x)`` — SERIAL-CHAINED: each iteration's
    input derives from the previous result, so no dispatch/caching layer
    can elide or overlap identical calls, and the final scalar fetch is
    an honest completion barrier for the whole chain (the bench.py
    protocol)."""
    import jax
    import jax.numpy as jnp

    jfn = jax.jit(fn)
    t0 = time.time()
    out, x = jfn(x0)
    float(jnp.sum(jax.tree_util.tree_leaves(out)[0].astype(jnp.float32)))
    float(jnp.sum(x.astype(jnp.float32)))
    log(f"{tag}: compiled in {time.time() - t0:.1f}s")
    t0 = time.perf_counter()
    out, x = jfn(x)
    float(jnp.sum(x.astype(jnp.float32)))
    per = max(time.perf_counter() - t0, 1e-4)
    iters = max(3, min(200, int(min_s / per)))
    total, dt = 0, 0.0
    while dt < min_s and total < 2000:
        t0 = time.perf_counter()
        for _ in range(iters):
            out, x = jfn(x)
        float(jnp.sum(x.astype(jnp.float32)))  # chain barrier
        dt += time.perf_counter() - t0
        total += iters
    return total / dt  # steps/s


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", default="1024,2048,4096,8192")
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--output", default=None)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()

    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import nn as opsnn

    def log(*a):
        print("[attention_bench]", *a, file=sys.stderr, flush=True)

    log("devices:", jax.devices())
    dt = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    B, H, D = args.batch, args.heads, args.head_dim
    results = []
    for L in [int(s) for s in args.seqs.split(",")]:
        rng = onp.random.RandomState(0)
        qkv = jnp.asarray(
            rng.randn(B, L, H * D).astype(onp.float32), dt)

        def chain(x, scalar):
            pert = (jnp.tanh(scalar) * 1e-6).astype(x.dtype)
            return x * (1 + pert)

        def fwd(x):
            out = opsnn.attend(x, x, x, H, causal=True)
            return out, chain(x, jnp.sum(out.astype(jnp.float32)) * 1e-6)

        def train(x):
            def loss(x_):
                out = opsnn.attend(x_, x_, x_, H, causal=True)
                return jnp.sum(out.astype(jnp.float32) ** 2)

            g = jax.grad(loss)(x)
            return g, chain(x, jnp.sum(g.astype(jnp.float32)) * 1e-6)

        # analytic attention FLOPs (causal ~halves the K range):
        # QK^T + PV, 2 MACs each: 2 * 2 * B*H*L^2*D / 2
        fwd_flops = 2.0 * B * H * L * L * D
        try:
            f_sps = measure(fwd, qkv, f"L={L} fwd", log)
            t_sps = measure(train, qkv, f"L={L} fwd+bwd", log)
            # FLOPs convention (stated in-record, ADVICE r4): achieved
            # numbers use ALGORITHMIC FA2 accounting — fwd 2 matmul
            # units, bwd 5 (s recomputed once) = 3.5x fwd — the standard
            # flash-attention reporting basis, comparable across
            # implementations. The two-kernel Pallas backward EXECUTES
            # more: dq and dkv each recompute s and dO-derived terms
            # (~9 units incl fwd = 4.5x); executed_est reports that on
            # the TPU, where the Pallas backward is what runs (fixed
            # rule in ops/pallas/flash_attention._flash_bwd).
            pallas_bwd_ran = jax.default_backend() == "tpu"
            exec_factor = 4.5 if pallas_bwd_ran else 3.5
            rec = {"seq_len": L, "batch": B, "heads": H, "head_dim": D,
                   "dtype": args.dtype,
                   "fwd_tok_s": round(f_sps * B * L, 1),
                   "train_tok_s": round(t_sps * B * L, 1),
                   "fwd_achieved_tflops": round(f_sps * fwd_flops / 1e12, 2),
                   "train_achieved_tflops": round(
                       t_sps * 3.5 * fwd_flops / 1e12, 2),
                   "flops_accounting": "algorithmic FA2 (fwd 2 units, "
                                       "bwd 5, recompute counted once = "
                                       "3.5x fwd)",
                   "train_bwd_kernel": ("pallas dq+dkv"
                                        if pallas_bwd_ran else "xla-scan"),
                   "train_executed_tflops_est": round(
                       t_sps * exec_factor * fwd_flops / 1e12, 2)}
            if jax.devices()[0].platform == "tpu":
                # same-window effective-peak control — the VERDICT's
                # ">=30% of peak" bar is only meaningful against what
                # THIS window's chip delivers on a pure chained matmul.
                # In a multi-length run the memoized value would be tens
                # of minutes stale by L=8192 (the timescale of 5-10x
                # rate swings), so re-measure per length; the daemon
                # path (one length per child) pays once either way.
                from bench import window_control_tflops
                ctl = window_control_tflops(
                    refresh=len(args.seqs.split(",")) > 1)
                if ctl:
                    rec["window_control_tflops"] = ctl
                    rec["fwd_vs_window_control"] = round(
                        rec["fwd_achieved_tflops"] / ctl, 4)
                    rec["train_vs_window_control"] = round(
                        rec["train_achieved_tflops"] / ctl, 4)
            log(rec)
            results.append(rec)
        except Exception as e:  # noqa: BLE001 — one OOM length shouldn't kill the run
            log(f"L={L} failed: {e!r}")
            results.append({"seq_len": L, "error": str(e)[:200]})
    from bench import code_rev
    out = {"device": jax.devices()[0].platform,
           "code_rev": code_rev(),
           "device_kind": jax.devices()[0].device_kind,
           "results": results}
    text = json.dumps(out, indent=2)
    print(text)
    if args.output:
        with open(args.output, "w") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()
