#!/usr/bin/env python
"""Pod-scale disaggregated serving benchmark (ISSUE 20 acceptance
harness).

Phases over :mod:`mxnet_tpu.serving` (the GSPMD-sharded
:class:`~mxnet_tpu.serving.llm.LLMEngine` + the
:class:`~mxnet_tpu.serving.disagg.DisaggRouter`):

1. **sharded token identity + largest-servable-model** — the same
   prompt decodes on a single chip and on a ``tp``-way mesh (virtual
   CPU devices when real ones are scarce); the token streams must be
   identical, and the banked per-device KV pool bytes shrink by the
   mesh width — the headroom that decides the largest servable model
   per chip.
2. **mixed-load decode p99, disaggregated vs colocated** — long
   prefill-heavy prompts flood alongside short interactive requests.
   Colocated: one 3-replica fleet time-slices both. Disaggregated: a
   1-replica prefill fleet + 2-replica decode fleet behind one
   :class:`DisaggRouter` — the long prompts stage on the prefill fleet
   and re-attach on decode by DMA, so the interactive p99 stops paying
   for strangers' prefills.
3. **drills** (the ``lost_requests == 0`` gate): kill the ONLY
   prefill replica mid-flood (every in-flight and subsequent request
   falls back to a local re-prefill — degraded, never lost), and a
   garbled handoff frame (CRC reject → counted remote error → local
   re-prefill, token-identical output).

``--quick`` is the seconds-scale smoke wired into tier-1
(``tests/test_disagg.py::test_disagg_bench_quick``); the full run
banks ``benchmark/results_disagg_cpu.json``; no chip row exists.

CLI:
    python benchmark/disagg_bench.py [--quick] [--output out.json]
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

# the sharded phase needs a mesh: force virtual CPU devices BEFORE jax
# imports (harmless when real accelerators provide >= 4 devices)
if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

import numpy as onp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import code_rev  # noqa: E402

BS = 4          # KV block size everywhere in this bench


def log(*a):
    print("[disagg_bench]", *a, file=sys.stderr, flush=True)


def _net(vocab, units, layers):
    from mxnet_tpu.gluon.model_zoo.bert import gpt_like

    onp.random.seed(0)
    net = gpt_like(vocab_size=vocab, units=units, hidden_size=4 * units,
                   num_layers=layers, num_heads=4, max_length=128,
                   dropout=0.0)
    net.initialize()
    return net


def _engine(net, **kw):
    from mxnet_tpu.serving import LLMEngine

    kw.setdefault("max_running", 4)
    kw.setdefault("block_size", BS)
    kw.setdefault("max_context", 64)
    kw.setdefault("kv_cache_dtype", "float32")
    kw.setdefault("prefix_cache", True)
    return LLMEngine(net, **kw)


def _p99(samples):
    if not samples:
        return None
    s = sorted(samples)
    return round(s[min(len(s) - 1, int(round(0.99 * (len(s) - 1))))], 3)


# ---------------------------------------------------------------------------
# phase 1: sharded token identity + largest-servable-model headroom
# ---------------------------------------------------------------------------
def sharded_phase(net, vocab, quick):
    import jax

    from mxnet_tpu.parallel.mesh import make_mesh

    devs = jax.devices()
    tp = 4 if len(devs) >= 4 else max(1, len(devs))
    rng = onp.random.RandomState(17)
    prompt = rng.randint(1, vocab, (24,)).astype(onp.int32)

    base = _engine(net)
    try:
        toks0 = list(base.submit(prompt, 6).wait(timeout=300))
        bytes_tp1 = base._kv.bytes_per_device()
    finally:
        base.close()

    mesh = make_mesh({"tp": tp}, devices=devs[:tp])
    eng = _engine(net, mesh=mesh)
    try:
        toks1 = list(eng.submit(prompt, 6).wait(timeout=300))
        shard = eng.stats()["sharding"]
    finally:
        eng.close()

    identical = toks0 == toks1
    shrink = (round(bytes_tp1 / shard["pool_bytes_per_device"], 3)
              if shard["pool_bytes_per_device"] else None)
    row = {
        "tp": tp,
        "token_identical": identical,
        "tokens": len(toks0),
        "pool_bytes_per_device_tp1": int(bytes_tp1),
        f"pool_bytes_per_device_tp{tp}": shard["pool_bytes_per_device"],
        "per_device_shrink_factor": shrink,
        "topology": shard["topology"],
        "lost": 0 if identical else 1,
    }
    log(f"sharded: tp={tp} token_identical={identical}, per-device "
        f"pool {bytes_tp1} -> {shard['pool_bytes_per_device']} B "
        f"(x{shrink} headroom for the largest servable model)")
    return row


# ---------------------------------------------------------------------------
# phase 2: mixed-load decode p99, disaggregated vs colocated
# ---------------------------------------------------------------------------
def _mixed_load(submit_long, submit_short, n_long, n_short, clients=2):
    """Run the mixed workload: ``clients`` long-flood threads +
    ``clients`` interactive threads. Returns (short_latencies_ms,
    lost_list)."""
    from mxnet_tpu.serving import ServerOverload

    lats, lost = [], []
    lock = threading.Lock()

    def run(fn, n, cid, measure):
        for _k in range(cid, n, clients):
            t0 = time.perf_counter()
            for attempt in range(40):
                try:
                    fn(_k)
                    if measure:
                        with lock:
                            lats.append((time.perf_counter() - t0) * 1e3)
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

    threads = ([threading.Thread(target=run,
                                 args=(submit_long, n_long, i, False))
                for i in range(clients)]
               + [threading.Thread(target=run,
                                   args=(submit_short, n_short, i, True))
                  for i in range(clients)])
    for t in threads:
        t.start()
    for t in threads:
        t.join(600)
    return lats, lost


def mixed_phase(net, vocab, quick, disagg):
    from mxnet_tpu.serving import DisaggRouter, ReplicaPool, Router

    n_long = 4 if quick else 24
    n_short = 8 if quick else 24
    rng = onp.random.RandomState(29)
    plen = 40 if quick else 56
    longs = [rng.randint(1, vocab, (plen,)).astype(onp.int32)
             for _ in range(n_long)]
    shorts = [rng.randint(1, vocab, (6,)).astype(onp.int32)
              for _ in range(n_short)]

    def build(role=None):
        def f():
            # warm BOTH the interactive and the long-prompt buckets on
            # every replica: the measured window must show steady-state
            # prefill/decode collision, not cold-compile collision
            eng = _engine(net, role=role)
            eng.warmup(prompt_lengths=[5, plen])
            return eng
        return f

    if disagg:
        pp = ReplicaPool(build("prefill"), n_replicas=1,
                         heartbeat_s=0.1, role="prefill")
        dp = ReplicaPool(build("decode"), n_replicas=2,
                         heartbeat_s=0.1, role="decode")
        front = DisaggRouter(pp, dp, min_prefill_blocks=2,
                             prefill_router_kw={"hedge_ms": 0},
                             decode_router_kw={"hedge_ms": 0})
    else:
        pool = ReplicaPool(build(), n_replicas=3, heartbeat_s=0.1)
        front = Router(pool, hedge_ms=0)

    try:
        front.generate(longs[0], 1)      # compile/warm outside the clock
        lats, lost = _mixed_load(
            lambda k: front.generate(longs[k], 2),
            lambda k: front.generate(shorts[k], 8),
            n_long, n_short)
        row = {
            "disaggregated": disagg,
            "long_requests": n_long,
            "short_requests": n_short,
            "short_p50_ms": (round(statistics.median(lats), 3)
                             if lats else None),
            "short_p99_ms": _p99(lats),
            "lost": len(lost),
            "errors": lost[:4],
        }
        if disagg:
            row["handoff"] = front.handoff_counts()
        log(f"mixed load ({'disagg' if disagg else 'colocated'}): "
            f"short p99 {row['short_p99_ms']} ms over "
            f"{len(lats)} interactive requests, lost {len(lost)}")
        return row
    finally:
        front.close()


# ---------------------------------------------------------------------------
# phase 3: the drills
# ---------------------------------------------------------------------------
def kill_prefill_drill(net, vocab, quick):
    from mxnet_tpu.serving import DisaggRouter, ReplicaPool

    n_req = 8 if quick else 16
    rng = onp.random.RandomState(43)
    prompts = [rng.randint(1, vocab, (24,)).astype(onp.int32)
               for _ in range(n_req)]

    def build(role):
        def f():
            eng = _engine(net, role=role)
            eng.warmup(prompt_lengths=[5])
            return eng
        return f

    pp = ReplicaPool(build("prefill"), n_replicas=1, heartbeat_s=0.1,
                     role="prefill")
    dp = ReplicaPool(build("decode"), n_replicas=2, heartbeat_s=0.1,
                     role="decode")
    router = DisaggRouter(pp, dp, min_prefill_blocks=2,
                          prefill_router_kw={"hedge_ms": 0},
                          decode_router_kw={"hedge_ms": 0,
                                            "readmit_limit": 2})
    results, lost = [], []
    lock = threading.Lock()

    def one(i):
        from mxnet_tpu.serving import ServerOverload

        for attempt in range(40):
            try:
                out = list(router.generate(prompts[i], 2))
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
        router.generate(prompts[0], 1)   # warm the handoff path
        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(n_req)]
        for t in threads:
            t.start()
        victim = pp.replicas[0].name
        pp.kill(victim)
        for t in threads:
            t.join(300)
        hc = router.handoff_counts()
        row = {
            "killed": victim,
            "requests": n_req,
            "completed": len(results),
            "handoff": hc,
            "export_endpoints_after": len(
                pp.kv_export_endpoints()),
            "lost": len(lost),
            "errors": lost,
        }
        log(f"kill-prefill drill: killed {victim}, "
            f"{len(results)}/{n_req} completed, handoff {hc}, "
            f"lost {len(lost)}")
        return row
    finally:
        router.close()


def garble_drill(net, vocab, quick):
    from mxnet_tpu.resilience import chaos
    from mxnet_tpu.serving import DisaggRouter, ReplicaPool

    rng = onp.random.RandomState(59)
    prompt = rng.randint(1, vocab, (24,)).astype(onp.int32)
    lost = []

    ref = _engine(net)
    try:
        expect = list(ref.submit(prompt, 2).wait(timeout=300))
    finally:
        ref.close()

    def build(role):
        def f():
            eng = _engine(net, role=role)
            eng.warmup(prompt_lengths=[5])
            return eng
        return f

    pp = ReplicaPool(build("prefill"), n_replicas=1, heartbeat_s=0.1,
                     role="prefill")
    dp = ReplicaPool(build("decode"), n_replicas=1, heartbeat_s=0.1,
                     role="decode")
    router = DisaggRouter(pp, dp, min_prefill_blocks=2,
                          prefill_router_kw={"hedge_ms": 0},
                          decode_router_kw={"hedge_ms": 0})
    try:
        # EVERY handoff frame corrupts: the transport CRC rejects, the
        # spill tier counts a contained remote error, the decode engine
        # re-prefills locally — same tokens, bounded wall time
        with chaos.scope("io.net.frame", fail="garble"):
            t0 = time.monotonic()
            got = list(router.generate(prompt, 2))
            wall = time.monotonic() - t0
        if got != expect:
            lost.append("garble fallback output diverged")
        remote_errors = [0]
        dp.each_engine(lambda e: remote_errors.__setitem__(
            0, remote_errors[0]
            + int(e._kv.spill.stats()["remote_errors"])))
        row = {
            "fallback_correct": got == expect,
            "wall_s": round(wall, 3),
            "remote_errors": remote_errors[0],
            "handoff": router.handoff_counts(),
            "lost": len(lost),
        }
        log(f"garble drill: fallback correct={got == expect} in "
            f"{wall:.2f}s ({remote_errors[0]} contained remote errors)")
        return row
    finally:
        router.close()


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
    units = args.units or (96 if quick else 128)
    net = _net(vocab, units, args.layers)

    sharded = sharded_phase(net, vocab, quick)
    colo = mixed_phase(net, vocab, quick, disagg=False)
    dis = mixed_phase(net, vocab, quick, disagg=True)
    kill = kill_prefill_drill(net, vocab, quick)
    garble = garble_drill(net, vocab, quick)

    lost = (sharded["lost"] + colo["lost"] + dis["lost"]
            + kill["lost"] + garble["lost"])
    metrics = [
        {"metric": "decode_p99_colocated_ms",
         "value": colo["short_p99_ms"], "unit": "ms"},
        {"metric": "decode_p99_disagg_ms",
         "value": dis["short_p99_ms"], "unit": "ms"},
        {"metric": "sharded_token_identical",
         "value": int(sharded["token_identical"]), "unit": "bool"},
        {"metric": "shard_pool_shrink_factor",
         "value": sharded["per_device_shrink_factor"], "unit": "x"},
        {"metric": "handoff_exported",
         "value": dis.get("handoff", {}).get("exported", 0),
         "unit": "requests"},
    ]
    rec = {
        "metric": "disagg",
        "value": dis["short_p99_ms"],
        "unit": "ms",
        "quick": quick,
        "device": platform,
        "device_kind": getattr(jax.devices()[0], "device_kind", ""),
        "metrics": metrics,
        "sharded": sharded,
        "mixed_load": {"colocated": colo, "disaggregated": dis},
        "drills": {"kill_prefill": kill, "handoff_garble": garble},
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
