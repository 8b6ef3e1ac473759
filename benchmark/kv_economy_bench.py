#!/usr/bin/env python
"""Cluster-wide KV economy benchmark (ISSUE 19 acceptance harness).

Phases over :mod:`mxnet_tpu.serving` (kv_hash / kv_spill / the
affinity Router):

1. **fleet prefix hit rate, affinity on vs off** — an 8-replica
   (quick: 4) in-process fleet serves a shared-system-prompt workload
   (a handful of prefixes, thousands of users' unique suffixes); banks
   the fleet-wide ``cluster_prefix_hit_rate`` both ways. Affinity-on
   concentrates each prefix on its rendezvous owner, so the fleet pays
   ~1 prefill per prefix instead of ~1 per (prefix, replica) pair.
2. **resumed-session TTFT, spill re-attach vs re-prefill** — a
   multi-turn session returns after its KV blocks were LRU-evicted:
   with the spill tier armed the blocks re-attach from host RAM (a
   memcpy), without it the prompt re-prefills (matmuls); banks both
   median TTFTs.
3. **effective context capacity with spill armed** — HBM pool blocks
   vs HBM + host-tier capacity at the engine's exact per-block byte
   cost, plus a measured second-pass hit rate over a working set ~2x
   the HBM pool.
4. **drills** (the ``lost_requests == 0`` gate): kill the affinity
   owner mid-flood (every request re-admits exactly once), and a
   garbled remote spill fetch (CRC reject → typed retry → local
   re-prefill fallback — correct output, bounded, no hang).

``--quick`` is the seconds-scale smoke wired into tier-1
(``tests/test_kv_economy.py::test_kv_economy_bench_quick``); the full
run banks ``benchmark/results_kv_economy_cpu.json``; no chip row exists.

CLI:
    python benchmark/kv_economy_bench.py [--quick] [--output out.json]
        [--units 192] [--layers 2]
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import threading
import time

import numpy as onp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import code_rev  # noqa: E402

BS = 4          # KV block size everywhere in this bench


def log(*a):
    print("[kv_economy_bench]", *a, file=sys.stderr, flush=True)


def _net(vocab, units, layers):
    from mxnet_tpu.gluon.model_zoo.bert import gpt_like

    onp.random.seed(0)
    net = gpt_like(vocab_size=vocab, units=units, hidden_size=4 * units,
                   num_layers=layers, num_heads=4, max_length=128,
                   dropout=0.0)
    net.initialize()
    return net


def _prefix_tokens():
    """Fleet-wide (hit, miss) prompt-token totals — the exact sums
    ``telemetry.cluster.derive`` folds into cluster_prefix_hit_rate."""
    from mxnet_tpu.telemetry.registry import get_registry

    fam = get_registry().snapshot()["metrics"].get(
        "llm_prefix_tokens_total")
    hit = miss = 0.0
    for sr in (fam or {}).get("series", ()):
        if sr["labels"].get("result") == "hit":
            hit += sr["value"]
        elif sr["labels"].get("result") == "miss":
            miss += sr["value"]
    return hit, miss


# ---------------------------------------------------------------------------
# phase 1: fleet prefix hit rate, affinity on vs off
# ---------------------------------------------------------------------------
def affinity_phase(net, vocab, quick, affinity_on):
    from mxnet_tpu.serving import LLMEngine, ReplicaPool, Router

    replicas = 4 if quick else 8
    n_req = 32 if quick else 96
    n_prefixes = 12
    clients = 4

    def build():
        # 24 blocks: enough for the 4 decode lanes, NOT enough to keep
        # all 12 shared prefixes (36 blocks) resident — the phase
        # measures cache *economy* under competition, so affinity-off
        # must be able to thrash
        eng = LLMEngine(net, max_running=4, block_size=BS,
                        max_context=48, kv_cache_dtype="float32",
                        prefix_cache=True, num_blocks=24)
        eng.warmup(prompt_lengths=[5])
        return eng

    pool = ReplicaPool(build, n_replicas=replicas, heartbeat_s=0.1)
    router = Router(pool, affinity=affinity_on, affinity_block_size=BS,
                    affinity_blocks=2, hedge_ms=0)
    shed = [0]
    rng = onp.random.RandomState(23)
    # the shared system prompts: 3 full blocks each (the affinity key
    # hashes the leading 2) + a unique 4-token user suffix per request;
    # each client draws its prefix per request so the routing policy,
    # not the client->prefix aliasing, decides which replica warms what
    prefixes = [rng.randint(1, vocab, (3 * BS,)).astype(onp.int32)
                for _ in range(n_prefixes)]
    hit0, miss0 = _prefix_tokens()
    lost, errs = [], []
    lock = threading.Lock()

    def client(cid):
        from mxnet_tpu.serving import ServerOverload

        r = onp.random.RandomState(100 + cid)
        for _k in range(cid, n_req, clients):
            prompt = onp.concatenate(
                [prefixes[int(r.randint(0, n_prefixes))],
                 r.randint(1, vocab, (BS,)).astype(onp.int32)])
            for attempt in range(40):
                try:
                    router.generate(prompt, 2)
                    break
                except ServerOverload:
                    # typed shed is control flow ("retry with
                    # backoff"), not a lost request — honor it like a
                    # real client and count it separately
                    with lock:
                        shed[0] += 1
                    time.sleep(0.05 * (attempt + 1))
                except Exception as e:  # noqa: BLE001 — the gate
                    with lock:
                        lost.append(repr(e))
                        errs.append(e)
                    break
            else:
                with lock:
                    lost.append("shed retries exhausted")

    try:
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(600)
        hit1, miss1 = _prefix_tokens()
        dh, dm = hit1 - hit0, miss1 - miss0
        rate = round(dh / (dh + dm), 5) if (dh + dm) > 0 else 0.0
        c = router.stats()["counters"]
        row = {
            "affinity": affinity_on,
            "replicas": replicas,
            "requests": n_req,
            "prefixes": n_prefixes,
            "cluster_prefix_hit_rate": rate,
            "hit_tokens": dh, "miss_tokens": dm,
            "affinity_hit": c["affinity_hit"],
            "affinity_fallback": c["affinity_fallback"],
            "shed_retries": shed[0],
            "lost": len(lost),
            "errors": lost[:4],
        }
        log(f"affinity={'on' if affinity_on else 'off'}: "
            f"hit rate {rate} over {replicas} replicas "
            f"({int(dh)}/{int(dh + dm)} tokens)")
        return row
    finally:
        router.close()


# ---------------------------------------------------------------------------
# phase 2: resumed-session TTFT, spill re-attach vs re-prefill
# ---------------------------------------------------------------------------
def resumed_ttft_phase(net, vocab, quick, spill):
    from mxnet_tpu.serving import LLMEngine

    # The resumed session carries a LONG context (120 tokens) so the
    # avoided work is real prefill compute, not dispatch overhead: the
    # re-attach path restores 29 blocks by memcpy and prefills only the
    # 8-token suffix, the cold path re-prefills all 120 tokens.
    iters = 3 if quick else 7
    plen = 120
    eng = LLMEngine(net, max_running=4, block_size=BS, max_context=128,
                    kv_cache_dtype="float32", prefix_cache=True,
                    kv_spill=spill, kv_spill_bytes=64 << 20)
    rng = onp.random.RandomState(31)
    prompt = rng.randint(1, vocab, (plen,)).astype(onp.int32)
    lost = 0

    def flood():
        # distinct long prompts roll the whole LRU pool: the session's
        # resident blocks are evicted (spilled when armed, freed else)
        for _ in range(5):
            eng.submit(rng.randint(1, vocab, (plen,)).astype(onp.int32),
                       1).wait(timeout=300)

    def resume_ttft():
        first = []
        t0 = time.perf_counter()
        eng.submit(prompt, 2, on_token=lambda tok: first.append(
            time.perf_counter() - t0) if not first else None
        ).wait(timeout=300)
        return first[0] * 1e3

    try:
        eng.submit(prompt, 2).wait(timeout=300)   # the first turn
        flood()
        resume_ttft()        # unmeasured: compiles the re-attach path
        samples = []
        for _ in range(iters):
            flood()
            samples.append(resume_ttft())
        med = round(statistics.median(samples), 3)
        reattached = 0
        if spill:
            from mxnet_tpu.telemetry.registry import get_registry

            fam = get_registry().snapshot()["metrics"].get(
                "llm_kv_reattach_total") or {}
            reattached = sum(sr["value"] for sr in fam.get("series", ()))
        row = {"spill": spill, "ttft_ms": med,
               "samples_ms": [round(s, 3) for s in samples],
               "reattached_blocks_total": reattached, "lost": lost}
        log(f"resumed TTFT ({'re-attach' if spill else 're-prefill'}): "
            f"{med} ms over {iters} resumes")
        return row
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# phase 3: effective context capacity with spill armed
# ---------------------------------------------------------------------------
def capacity_phase(net, vocab, quick):
    from mxnet_tpu.serving import LLMEngine

    spill_bytes = 32 << 20
    eng = LLMEngine(net, max_running=4, block_size=BS, max_context=48,
                    kv_cache_dtype="float32", prefix_cache=True,
                    kv_spill=True, kv_spill_bytes=spill_bytes)
    try:
        # the engine's exact per-block byte cost (k + v pool rows)
        per_block = 2 * int(
            onp.asarray(eng._kv.pools[0][0][:, 0]).nbytes)
        hbm_blocks = eng.num_blocks
        spill_cap = spill_bytes // per_block
        # measured: a working set ~2x the HBM pool, streamed twice —
        # the second pass's prefix hits can only come from re-attach
        n_sessions = max(4, (2 * hbm_blocks) // 7)
        if quick:
            n_sessions = min(n_sessions, 8)
        rng = onp.random.RandomState(41)
        sessions = [rng.randint(1, vocab, (28,)).astype(onp.int32)
                    for _ in range(n_sessions)]
        lost = 0
        for p in sessions:
            eng.submit(p, 1).wait(timeout=300)
        hit0, miss0 = _prefix_tokens()
        for p in sessions:
            eng.submit(p, 1).wait(timeout=300)
        hit1, miss1 = _prefix_tokens()
        dh, dm = hit1 - hit0, miss1 - miss0
        second_pass_rate = (round(dh / (dh + dm), 5)
                            if (dh + dm) > 0 else 0.0)
        spilled_now, spilled_bytes = eng._kv.spill.level()
        row = {
            "per_block_bytes": per_block,
            "hbm_blocks": hbm_blocks,
            "spill_capacity_blocks": int(spill_cap),
            "effective_blocks": int(hbm_blocks + spill_cap),
            "working_set_sessions": n_sessions,
            "second_pass_hit_rate": second_pass_rate,
            "spilled_blocks_now": spilled_now,
            "spilled_bytes_now": spilled_bytes,
            "lost": lost,
        }
        log(f"capacity: {hbm_blocks} HBM blocks + {int(spill_cap)} "
            f"spill blocks ({per_block} B/block); second-pass hit "
            f"rate {second_pass_rate} over {n_sessions} sessions")
        return row
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# phase 4: the drills
# ---------------------------------------------------------------------------
def kill_drill(net, vocab, quick):
    from mxnet_tpu.serving import LLMEngine, ReplicaPool, Router, kv_hash

    def build():
        eng = LLMEngine(net, max_running=4, block_size=BS,
                        max_context=48, kv_cache_dtype="float32",
                        prefix_cache=True)
        eng.warmup(prompt_lengths=[5])
        return eng

    pool = ReplicaPool(build, n_replicas=3, heartbeat_s=0.1)
    router = Router(pool, affinity_block_size=BS, affinity_blocks=2,
                    hedge_ms=0, readmit_limit=2)
    rng = onp.random.RandomState(53)
    prefix = rng.randint(1, vocab, (3 * BS,)).astype(onp.int32)
    akey = kv_hash.prefix_key(prefix, BS, depth=2)
    lost, results = [], []
    lock = threading.Lock()
    n_req = 8 if quick else 16

    def one(i):
        from mxnet_tpu.serving import ServerOverload

        r = onp.random.RandomState(200 + i)
        prompt = onp.concatenate(
            [prefix, r.randint(1, vocab, (BS,)).astype(onp.int32)])
        for attempt in range(40):
            try:
                out = list(router.generate(prompt, 2))
                with lock:
                    results.append(out)
                break
            except ServerOverload:
                time.sleep(0.05 * (attempt + 1))
            except Exception as e:  # noqa: BLE001 — the gate
                with lock:
                    lost.append(repr(e))
                break
        else:
            with lock:
                lost.append("shed retries exhausted")

    try:
        target = router._affinity_target(akey)
        router.generate(onp.concatenate(
            [prefix, rng.randint(1, vocab, (BS,)).astype(onp.int32)]), 2)
        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(n_req)]
        for t in threads:
            t.start()
        pool.kill(target)
        for t in threads:
            t.join(300)
        c = router.stats()["counters"]
        row = {
            "killed": target,
            "requests": n_req,
            "completed": len(results),
            "readmitted": c["readmitted"],
            "affinity_rebuilds": c["affinity_rebuilds"],
            "map_dropped_dead": target not in router._affinity_members,
            "lost": len(lost),
            "errors": lost,
        }
        log(f"kill drill: killed {target}, {len(results)}/{n_req} "
            f"completed, {int(c['readmitted'])} readmitted, "
            f"lost {len(lost)}")
        return row
    finally:
        router.close()


def garble_drill(net, vocab, quick):
    from mxnet_tpu.resilience import chaos
    from mxnet_tpu.serving import LLMEngine

    rng = onp.random.RandomState(61)
    prompt = rng.randint(1, vocab, (28,)).astype(onp.int32)
    lost = []
    a = LLMEngine(net, max_running=4, block_size=BS, max_context=48,
                  kv_cache_dtype="float32", prefix_cache=True,
                  kv_spill=True, num_blocks=10, kv_spill_serve=True)
    try:
        first = list(a.submit(prompt, 2).wait(timeout=300))
        for _ in range(8):
            a.submit(rng.randint(1, vocab, (28,)).astype(onp.int32),
                     1).wait(timeout=300)
        b = LLMEngine(net, max_running=4, block_size=BS, max_context=48,
                      kv_cache_dtype="float32", prefix_cache=True,
                      kv_spill=True,
                      kv_spill_peers=[a.kv_spill_endpoint])
        try:
            with chaos.scope("io.net.frame", fail="garble"):
                t0 = time.monotonic()
                got = list(b.submit(prompt, 2).wait(timeout=300))
                wall = time.monotonic() - t0
            if got != first:
                lost.append("garble fallback output diverged")
            remote_errors = b._kv.spill.stats()["remote_errors"]
            row = {
                "fallback_correct": got == first,
                "wall_s": round(wall, 3),
                "remote_errors": remote_errors,
                "lost": len(lost),
            }
            log(f"garble drill: fallback correct={got == first} in "
                f"{wall:.2f}s ({remote_errors} contained remote errors)")
            return row
        finally:
            b.close()
    finally:
        a.close()


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
    units = args.units or (96 if quick else 256)
    net = _net(vocab, units, args.layers)

    aff_on = affinity_phase(net, vocab, quick, affinity_on=True)
    aff_off = affinity_phase(net, vocab, quick, affinity_on=False)
    ttft_spill = resumed_ttft_phase(net, vocab, quick, spill=True)
    ttft_cold = resumed_ttft_phase(net, vocab, quick, spill=False)
    capacity = capacity_phase(net, vocab, quick)
    kill = kill_drill(net, vocab, quick)
    garble = garble_drill(net, vocab, quick)

    lost = (aff_on["lost"] + aff_off["lost"] + ttft_spill["lost"]
            + ttft_cold["lost"] + capacity["lost"] + kill["lost"]
            + garble["lost"])
    metrics = [
        {"metric": "cluster_prefix_hit_rate_affinity_on",
         "value": aff_on["cluster_prefix_hit_rate"], "unit": "frac"},
        {"metric": "cluster_prefix_hit_rate_affinity_off",
         "value": aff_off["cluster_prefix_hit_rate"], "unit": "frac"},
        {"metric": "resumed_ttft_reattach_ms",
         "value": ttft_spill["ttft_ms"], "unit": "ms"},
        {"metric": "resumed_ttft_reprefill_ms",
         "value": ttft_cold["ttft_ms"], "unit": "ms"},
        {"metric": "effective_context_blocks_spill",
         "value": capacity["effective_blocks"], "unit": "blocks"},
        {"metric": "effective_context_blocks_hbm",
         "value": capacity["hbm_blocks"], "unit": "blocks"},
    ]
    rec = {
        "metric": "kv_economy",
        "value": aff_on["cluster_prefix_hit_rate"],
        "unit": "frac",
        "quick": quick,
        "device": platform,
        "device_kind": getattr(jax.devices()[0], "device_kind", ""),
        "metrics": metrics,
        "affinity": {"on": aff_on, "off": aff_off},
        "resumed_ttft": {"reattach": ttft_spill, "reprefill": ttft_cold},
        "capacity": capacity,
        "drills": {"kill_affinity_owner": kill, "remote_garble": garble},
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
