#!/usr/bin/env python
"""Fleet autoscaler benchmark (ISSUE 16 acceptance harness).

Three phases over :mod:`mxnet_tpu.serving.autoscale`:

1. **warm vs cold scale-up** — a saturated 1-replica fleet trips the
   free-capacity gauge; banks the gauge-trip → first-served-token
   latency with the warm pool parked (scale-up = ``activate()`` on the
   pre-warmed SPARE, a state flip) vs with no spare (scale-up =
   ``add_replica()``, engine build + warmup ON the critical path). The
   warm-pool policy exists to collapse this gap.
2. **overload ramp, autoscaler on vs off** — the same client flood
   against the same 1-replica fleet, once with the autoscaler loop
   running (gauge trip admits the spare mid-ramp) and once without;
   banks both p99s and the lost-request count (acceptance gate:
   **exactly 0** across every phase — scaling never loses a request).
3. **consolidation** — N model factories on ONE shared pool
   (:class:`~mxnet_tpu.serving.ModelSpec`, one engine per model per
   replica => hard per-model KV budgets) vs N dedicated single-model
   pools serving the same per-model workload; banks both p99s and the
   replica-count consolidation ratio at comparable p99.

``--quick`` is the seconds-scale smoke wired into tier-1
(``tests/test_autoscale.py::test_autoscale_bench_quick``); the full
run banks ``benchmark/results_autoscale_cpu.json``; no chip row exists.

CLI:
    python benchmark/autoscale_bench.py [--quick] [--output out.json]
        [--units 96] [--layers 2]
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
from benchmark.fleet_bench import LoadGen, pctl  # noqa: E402


def log(*a):
    print("[autoscale_bench]", *a, file=sys.stderr, flush=True)


def _net(vocab, units, layers):
    from mxnet_tpu.gluon.model_zoo.bert import gpt_like

    onp.random.seed(0)
    net = gpt_like(vocab_size=vocab, units=units, hidden_size=4 * units,
                   num_layers=layers, num_heads=4, max_length=128,
                   dropout=0.0)
    net.initialize()
    return net


def _factory(net, lanes):
    from mxnet_tpu.serving import LLMEngine

    def build():
        eng = LLMEngine(net, max_running=lanes, block_size=4,
                        max_context=48, kv_cache_dtype="int8")
        eng.warmup(prompt_lengths=[5])
        return eng

    return build


# ---------------------------------------------------------------------------
# phase 1: gauge-trip -> first-served-token, warm spare vs cold compile
# ---------------------------------------------------------------------------
def scale_up_phase(net, vocab, lanes, quick, warmed):
    from mxnet_tpu.serving import (AutoscalePolicy, Autoscaler,
                                   ReplicaPool, Router)

    pool = ReplicaPool(_factory(net, lanes), n_replicas=1,
                       heartbeat_s=0.1)
    router = Router(pool, hedge_ms=0)
    asc = Autoscaler(pool, policy=AutoscalePolicy(
        min_replicas=1, max_replicas=2, warm_spares=1 if warmed else 0,
        up_cooldown_s=0.0, free_frac_up=0.95, free_frac_down=0.96))
    lost = 0
    try:
        if warmed:
            asc.ensure_warm()            # park the spare OFF the path
        # saturate the lone replica so the free-capacity gauge trips
        gens = [LoadGen(router, "default", vocab, 8 if quick else 16,
                        0.0, 40 + i).start() for i in range(3)]
        deadline = time.monotonic() + 10
        while (pool.free_units() / pool.capacity_units() >= 0.95
               and time.monotonic() < deadline):
            time.sleep(0.005)
        # trip -> decide -> actuate -> the first token served on the
        # grown fleet: ONE timed span
        t0 = time.perf_counter()
        decision = asc.step()
        rng = onp.random.RandomState(99)
        router.submit(rng.randint(0, vocab, (5,)).astype(onp.int32),
                      1).wait(timeout=300)
        first_tok_ms = (time.perf_counter() - t0) * 1e3
        for g in gens:
            g.stop()
        lost = sum(len(g.other) for g in gens)
        mode = asc.events[-1].mode if asc.events else None
        row = {
            "warmed": warmed,
            "decision": decision,
            "mode": mode,
            "first_token_ms": round(first_tok_ms, 3),
            "healthy_after": len(pool.healthy()),
            "lost": lost,
        }
        log(f"scale-up ({'warm' if warmed else 'cold'}): mode={mode} "
            f"first-token {row['first_token_ms']} ms")
        return row
    finally:
        asc.stop()
        router.close()


# ---------------------------------------------------------------------------
# phase 2: overload ramp p99, autoscaler on vs off
# ---------------------------------------------------------------------------
def ramp_phase(net, vocab, lanes, quick, autoscale_on):
    from mxnet_tpu.serving import (AutoscalePolicy, Autoscaler,
                                   ReplicaPool, Router)

    # few lanes + paced clients: the lone replica is QUEUE-bound with
    # compute headroom, so an activated second replica genuinely
    # relieves the ramp (on one shared host, extra replicas add lanes,
    # not FLOPs)
    ramp_lanes = 2
    pool = ReplicaPool(_factory(net, ramp_lanes), n_replicas=1,
                       heartbeat_s=0.1)
    router = Router(pool, hedge_ms=0)
    asc = None
    ramp_s = 3.0 if quick else 10.0
    tok_new = 8 if quick else 16
    try:
        if autoscale_on:
            asc = Autoscaler(pool, policy=AutoscalePolicy(
                min_replicas=1, max_replicas=2, warm_spares=1,
                up_cooldown_s=0.0, down_cooldown_s=60.0, idle_s=60.0,
                free_frac_up=0.95, free_frac_down=0.96, poll_s=0.05))
            asc.ensure_warm()
            asc.start()
        gens = [LoadGen(router, "default", vocab, tok_new, 0.005,
                        50 + i).start() for i in range(6 if quick else 10)]
        time.sleep(ramp_s)
        for g in gens:
            g.stop()
        lats = [l * 1e3 for g in gens for _, l in g.lat]
        row = {
            "autoscaler": autoscale_on,
            "p50_ms": pctl(lats, 50),
            "p99_ms": pctl(lats, 99),
            "ok": sum(g.ok for g in gens),
            "shed_at_admission": sum(g.shed for g in gens),
            "lost": sum(len(g.other) for g in gens),
            "healthy_end": len(pool.healthy()),
            "scale_events": ([e.to_dict() for e in asc.events]
                             if asc else []),
        }
        log(f"ramp (autoscaler={'on' if autoscale_on else 'off'}): "
            f"p99 {row['p99_ms']} ms, ok {row['ok']}, "
            f"healthy {row['healthy_end']}")
        return row
    finally:
        if asc is not None:
            asc.stop()
        router.close()


# ---------------------------------------------------------------------------
# phase 3: N models on one shared pool vs N dedicated pools
# ---------------------------------------------------------------------------
def consolidation_phase(net, vocab, lanes, quick):
    from mxnet_tpu.serving import (ModelSpec, ReplicaPool, Router,
                                   TenantConfig)

    models = ["chat", "code"]
    serve_s = 2.0 if quick else 8.0
    tok_new = 8 if quick else 16

    def drive(gens):
        t0 = time.monotonic()
        time.sleep(serve_s)
        for g in gens:
            g.stop()
        # drop the warm-in quarter: the steady tail is the comparison
        cut = t0 + serve_s * 0.25
        lats = [l * 1e3 for g in gens for t, l in g.lat if t >= cut]
        return {"p99_ms": pctl(lats, 99), "p50_ms": pctl(lats, 50),
                "ok": sum(g.ok for g in gens),
                "lost": sum(len(g.other) for g in gens)}

    # shared: both model factories on ONE pool (per-model engines =>
    # hard per-model KV budgets), tenants pinned to their model
    shared_pool = ReplicaPool(
        models=[ModelSpec(m, _factory(net, lanes)) for m in models],
        n_replicas=2, heartbeat_s=0.1)
    shared_router = Router(shared_pool, tenants=[
        TenantConfig(m, model=m) for m in models], hedge_ms=0)
    try:
        shared = drive([LoadGen(shared_router, m, vocab, tok_new, 0.01,
                                60 + i).start()
                        for i, m in enumerate(models)])
        shared["replicas"] = 2
    finally:
        shared_router.close()

    # dedicated: one single-model pool per model, same replica count
    # EACH, serving CONCURRENTLY (same total workload, same wall — the
    # layout the shared pool consolidates away)
    routers = []
    try:
        for m in models:
            pool = ReplicaPool(_factory(net, lanes), n_replicas=2,
                               heartbeat_s=0.1)
            routers.append(Router(pool, tenants=[TenantConfig(m)],
                                  hedge_ms=0))
        dedicated = drive([LoadGen(r, m, vocab, tok_new, 0.01,
                                   70 + i).start()
                           for i, (r, m) in enumerate(zip(routers,
                                                          models))])
        dedicated["replicas"] = 2 * len(models)
    finally:
        for r in routers:
            r.close()
    ded_p99 = dedicated["p99_ms"]
    ratio = round(dedicated["replicas"] / shared["replicas"], 3)
    row = {
        "models": models,
        "shared": shared,
        "dedicated": {"p99_ms": ded_p99, "ok": dedicated["ok"],
                      "lost": dedicated["lost"],
                      "replicas": dedicated["replicas"]},
        "consolidation_ratio": ratio,
        "p99_shared_over_dedicated": (
            round(shared["p99_ms"] / ded_p99, 3)
            if shared["p99_ms"] and ded_p99 else None),
    }
    log(f"consolidation: {dedicated['replicas']} dedicated -> "
        f"{shared['replicas']} shared replicas (ratio {ratio}), "
        f"p99 {ded_p99} -> {shared['p99_ms']} ms")
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="seconds-scale smoke (tier-1)")
    ap.add_argument("--units", type=int, default=0)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--output", default=None)
    args = ap.parse_args()

    import jax

    import mxnet_tpu as mx  # noqa: F401

    quick = bool(args.quick)
    platform = jax.devices()[0].platform
    vocab = 64
    units = args.units or (96 if quick else 192)
    lanes = 4 if quick else 8
    net = _net(vocab, units, args.layers)

    warm = scale_up_phase(net, vocab, lanes, quick, warmed=True)
    cold = scale_up_phase(net, vocab, lanes, quick, warmed=False)
    ramp_on = ramp_phase(net, vocab, lanes, quick, autoscale_on=True)
    ramp_off = ramp_phase(net, vocab, lanes, quick, autoscale_on=False)
    consolidation = consolidation_phase(net, vocab, lanes, quick)

    lost = (warm["lost"] + cold["lost"] + ramp_on["lost"]
            + ramp_off["lost"] + consolidation["shared"]["lost"]
            + consolidation["dedicated"]["lost"])
    metrics = [
        {"metric": "scale_up_first_token_warm_ms",
         "value": warm["first_token_ms"], "unit": "ms"},
        {"metric": "scale_up_first_token_cold_ms",
         "value": cold["first_token_ms"], "unit": "ms"},
        {"metric": "ramp_p99_autoscaler_on_ms",
         "value": ramp_on["p99_ms"], "unit": "ms"},
        {"metric": "ramp_p99_autoscaler_off_ms",
         "value": ramp_off["p99_ms"], "unit": "ms"},
        {"metric": "consolidation_ratio",
         "value": consolidation["consolidation_ratio"], "unit": "x"},
    ]
    rec = {
        "metric": "autoscale",
        "value": warm["first_token_ms"],
        "unit": "ms",
        "quick": quick,
        "device": platform,
        "device_kind": getattr(jax.devices()[0], "device_kind", ""),
        "metrics": metrics,
        "scale_up": {"warm": warm, "cold": cold},
        "ramp": {"on": ramp_on, "off": ramp_off},
        "consolidation": consolidation,
        "lost_requests": lost,
        "code_rev": code_rev(),
    }
    text = json.dumps(rec)
    print(text, flush=True)
    if args.output:
        with open(args.output, "w") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()
