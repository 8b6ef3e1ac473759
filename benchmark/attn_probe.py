#!/usr/bin/env python
"""Flash-attention diagnosis probe (round 5).

The ablation profile showed the pure attention op (B32 H12 L1024 D64,
causal, fwd+bwd) at 42 ms/layer — ~2% of peak, 78.5% of the GPT step.
This probe decomposes that: forward alone vs fwd+bwd, Pallas backward vs
the XLA-scan fallback, naive O(L^2) XLA attention as the control, and a
block-size sweep — each timed with K serially-chained calls inside ONE
jitted executable (launch effects amortized).

Usage: python benchmark/attn_probe.py [--out PATH] [--quick]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as onp

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def log(*a):
    print("[attn_probe]", *a, file=sys.stderr, flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()

    from bench import code_rev

    import jax
    import jax.numpy as jnp
    from jax import lax

    # the pallas package re-exports the flash_attention FUNCTION under
    # the same name as its defining module, so plain imports resolve to
    # the function; go through sys.modules for the module itself
    import importlib
    fa = importlib.import_module("mxnet_tpu.ops.pallas.flash_attention")

    dev = jax.devices()[0]
    log("devices:", jax.devices())

    B, H, L, D = 32, 12, 1024, 64
    rng = onp.random.RandomState(0)
    q0 = jnp.asarray(rng.standard_normal((B, H, L, D)), jnp.bfloat16)

    # algorithmic FA2 FLOPs, causal: 2 matmuls fwd (QK^T, PV), 5 bwd
    # units, x0.5 causal skip
    fwd_flops = 2 * 2 * B * H * L * L * D * 0.5
    fb_flops = fwd_flops * 3.5

    def timed(fn, k_steps, flops_per_step):
        """K chained calls in one executable; min-of-3 fetch-barrier."""
        def chain(q):
            def body(carry, _):
                out_val = fn(carry)
                # perturb so the next step depends on this one
                s = jnp.sum(out_val.astype(jnp.float32)) if hasattr(
                    out_val, "astype") else out_val
                nxt = carry * (1 + jnp.tanh(s) * 1e-7).astype(carry.dtype)
                return nxt, s
            fin, sums = lax.scan(body, q, None, length=k_steps)
            return jnp.sum(sums)

        jfn = jax.jit(chain)
        s = jfn(q0)
        float(s)
        best = None
        for _ in range(2 if args.quick else 3):
            t0 = time.perf_counter()
            float(jfn(q0))
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        ms = best / k_steps * 1e3
        return round(ms, 3), round(flops_per_step / (best / k_steps) / 1e12, 2)

    K = 4 if args.quick else 8
    out = {"device_kind": dev.device_kind, "platform": dev.platform,
           "code_rev": code_rev(),
           "captured_unix": time.time(),
           "shape": {"b": B, "h": H, "l": L, "d": D, "causal": True},
           "flops_accounting": "FA2 algorithmic, causal x0.5; fwd 2 units, "
                               "fwd+bwd 3.5x", "rows": []}

    def naive(qkv):
        qf = qkv.astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, qf,
                       preferred_element_type=jnp.float32) * (D ** -0.5)
        mask = jnp.tril(jnp.ones((L, L), bool))
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(jnp.bfloat16)
        return jnp.einsum("bhqk,bhkd->bhqd", p, qkv,
                          preferred_element_type=jnp.float32)

    # same-run control: a big square matmul, so that attention TFLOPs
    # can be read beside what the same chip delivers on plain matmul
    nctl = 4096
    actl = jnp.asarray(rng.standard_normal((nctl, nctl)), jnp.bfloat16)

    def control(q):
        # the carry feeds the lhs so the scan can't hoist the matmul
        s0 = (jnp.sum(q[0, 0, 0]) * 1e-30).astype(jnp.bfloat16)
        o = lax.dot_general(actl + s0, actl, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
        return o
    try:
        ms, tf = timed(lambda q: control(q), K, 2.0 * nctl ** 3)
        out["control_mm_4096_tflops"] = tf
        out["rows"].append({"case": "control_mm_4096", "ms": ms,
                            "tflops": tf})
        log(f"control_mm_4096: {ms} ms ({tf} TFLOPs)")
    except Exception as e:  # noqa: BLE001
        out["rows"].append({"case": "control_mm_4096",
                            "error": repr(e)[:160]})

    cases = []
    # forward-only, default blocks and sweep
    for bq in (None, (256, 512), (128, 128), (512, 512), (256, 256),
               (512, 1024), (1024, 1024)):
        label = f"pallas_fwd_{bq[0]}x{bq[1]}" if bq else "pallas_fwd_default"
        kw = {} if bq is None else {"block_q": bq[0], "block_k": bq[1]}
        cases.append((label, lambda q, kw=kw: fa.flash_attention(
            q, q, q, causal=True, **kw)))
    cases.append(("naive_xla_fwd", naive))

    for label, fn in cases:
        try:
            ms, tf = timed(fn, K, fwd_flops)
            out["rows"].append({"case": label, "ms": ms, "tflops": tf})
            log(f"{label}: {ms} ms ({tf} TFLOPs)")
        except Exception as e:  # noqa: BLE001 — sweep entry may reject
            out["rows"].append({"case": label, "error": repr(e)[:160]})
            log(f"{label} failed: {repr(e)[:160]}")

    # fwd+bwd: default, pallas-bwd engaged vs scan fallback, naive
    def fb(attn_fn):
        def run(q):
            def f(q, k, v):
                return jnp.sum(attn_fn(q, k, v).astype(jnp.float32))
            l, gs = jax.value_and_grad(f, argnums=(0, 1, 2))(q, q, q)
            return l + 1e-30 * sum(jnp.sum(g.astype(jnp.float32))
                                   for g in gs)
        return run

    fb_cases = [
        ("pallas_fb_default", fb(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True))),
        ("pallas_fb_128x128", fb(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128))),
        ("pallas_fb_256x256", fb(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, block_q=256, block_k=256))),
        ("naive_xla_fb", fb(lambda q, k, v: naive(q))),
    ]
    for label, fn in fb_cases:
        try:
            ms, tf = timed(fn, K, fb_flops)
            out["rows"].append({"case": label, "ms": ms, "tflops": tf})
            log(f"{label}: {ms} ms ({tf} TFLOPs)")
        except Exception as e:  # noqa: BLE001
            out["rows"].append({"case": label, "error": repr(e)[:160]})
            log(f"{label} failed: {repr(e)[:160]}")

    line = json.dumps(out)
    print(line, flush=True)
    # a run on another backend must never overwrite the TPU artifact:
    # block-ladder evidence from the wrong backend is worse than a stale
    # capture
    if args.out and dev.platform != "tpu" and "_tpu" in args.out:
        log(f"platform is {dev.platform}; refusing to write {args.out}")
    elif args.out:
        tmp = args.out + ".tmp"
        with open(tmp, "w") as f:
            f.write(line + "\n")
        os.replace(tmp, args.out)


if __name__ == "__main__":
    main()
