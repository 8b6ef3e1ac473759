#!/usr/bin/env python
"""Effective-peak probe: what bf16/int8 matmul rate can THIS chip
actually sustain when launch overhead is fully amortized?

Every banked MFU row divides by the v5e nominal peak (197 bf16 TFLOPs).
This probe gives the other denominator: K matmuls chained inside ONE
executable via lax.scan (zero per-step dispatch), swept over K and size.
If TFLOPs converge to ~nominal as K grows, dispatch was the tax; if they
plateau below, the plateau is what this chip delivers and rows can
report `mfu_effective` against it.

Usage: python benchmark/peak_probe.py [--out PATH]
Prints one JSON line.
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
    print("[peak_probe]", *a, file=sys.stderr, flush=True)


def chained_matmul_rate(n, k_steps, dtype=None, acc_dtype=None, runs=3):
    """K serially-chained n^3 matmuls in ONE jitted executable.

    The carry feeds each step's lhs (bench.py serial-chain rule), and
    timing ends with a one-element fetch of a value the whole chain
    feeds into. Module-level so bench children can reuse it as the
    same-run control (bench.window_control_tflops).

    Returns (tflops, best_launch_seconds)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    dtype = dtype or jnp.bfloat16
    acc_dtype = acc_dtype or jnp.float32
    rng = onp.random.RandomState(0)
    if dtype == jnp.int8:
        a = jnp.asarray(rng.randint(-127, 127, (n, n)), dtype)
        b = jnp.asarray(rng.randint(-127, 127, (n, n)), dtype)
    else:
        a = jnp.asarray(rng.standard_normal((n, n)), dtype)
        b = jnp.asarray(rng.standard_normal((n, n)), dtype)

    def body(carry, _):
        out = lax.dot_general(carry, b, (((1,), (0,)), ((), ())),
                              preferred_element_type=acc_dtype)
        # renormalise so the chain neither overflows nor denorms,
        # and the next lhs depends on this step's output
        nxt = (out - jnp.mean(out)).astype(dtype) if dtype != jnp.int8 \
            else (out & 127).astype(dtype)
        return nxt, jnp.sum(out.astype(jnp.float32))

    def chain(a):
        final, sums = lax.scan(body, a, None, length=k_steps)
        return jnp.sum(sums)

    jfn = jax.jit(chain)
    s = jfn(a)
    float(s)  # compile + warm
    best = None
    for _ in range(runs):
        t0 = time.perf_counter()
        s = jfn(a)
        float(s)  # fetch barrier through the full chain
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    flops = 2.0 * n ** 3 * k_steps
    return flops / best / 1e12, best


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from bench import code_rev  # shared provenance

    import jax
    import jax.numpy as jnp
    from jax import lax

    dev = jax.devices()[0]
    log("devices:", jax.devices())

    out = {"device_kind": dev.device_kind, "platform": dev.platform,
           "code_rev": code_rev(), "captured_unix": time.time(),
           "protocol": "K n^3 matmuls serially chained in one lax.scan "
                       "executable; min of 3 timed launches; fetch-barrier",
           "bf16": [], "int8": []}

    for n in (4096, 8192):
        for k in (1, 8, 32):
            try:
                tf, dt = chained_matmul_rate(n, k, jnp.bfloat16, jnp.float32)
                row = {"n": n, "k": k, "tflops": round(tf, 1),
                       "launch_s": round(dt, 4)}
                out["bf16"].append(row)
                log(f"bf16 n={n} k={k}: {tf:.1f} TFLOPs ({dt*1e3:.1f} ms)")
            except Exception as e:  # noqa: BLE001 — partial evidence still banks
                out["bf16"].append({"n": n, "k": k, "error": repr(e)[:200]})
                log(f"bf16 n={n} k={k} failed: {e!r}")
    for n in (4096,):
        for k in (1, 8, 32):
            try:
                tf, dt = chained_matmul_rate(n, k, jnp.int8, jnp.int32)
                row = {"n": n, "k": k, "tops": round(tf, 1),
                       "launch_s": round(dt, 4)}
                out["int8"].append(row)
                log(f"int8 n={n} k={k}: {tf:.1f} TOPs ({dt*1e3:.1f} ms)")
            except Exception as e:  # noqa: BLE001
                out["int8"].append({"n": n, "k": k, "error": repr(e)[:200]})
                log(f"int8 n={n} k={k} failed: {e!r}")

    bf_ok = [r for r in out["bf16"] if "tflops" in r]
    if bf_ok:
        eff = max(r["tflops"] for r in bf_ok)
        out["effective_peak_bf16_tflops"] = eff
        out["nominal_peak_bf16_tflops"] = 197.0
        out["effective_over_nominal"] = round(eff / 197.0, 3)
    i8_ok = [r for r in out["int8"] if "tops" in r]
    if i8_ok:
        out["effective_peak_int8_tops"] = max(r["tops"] for r in i8_ok)

    line = json.dumps(out)
    print(line, flush=True)
    if args.out:
        tmp = args.out + ".tmp"
        with open(tmp, "w") as f:
            f.write(line + "\n")
        os.replace(tmp, args.out)


if __name__ == "__main__":
    main()
