#!/usr/bin/env python
"""Serving-fleet fault-domain benchmark (ISSUE 12 acceptance harness).

Four phases over :mod:`mxnet_tpu.serving.fleet`:

1. **steady** — an N-replica LLM fleet (in-process replicas sharing one
   model => one compile per program shape) serves a mixed-tenant
   workload; banks aggregate tok/s + request p50/p99.
2. **chaos-kill drill** — sustained load, chaos-kill 1 replica
   mid-flight (``serving.fleet.replica`` fatal): banks the lost-request
   count (acceptance gate: **exactly 0** — every request completes or
   fails typed-transient), the re-admission count, and p99 during the
   kill/recovery window vs steady state.
3. **noisy neighbor** — a bronze tenant floods the fleet while gold
   serves its paced load; banks gold's p99 alone vs under the flood
   (``isolation_ratio``) and the bronze shed counts (weighted-fair
   quota + deadline-class pressure doing their job). The **SLO
   sentinel** (ISSUE 15) runs through this overload ramp: a p99
   ceiling declared off the measured steady phase must stay silent
   before the flood and fire a typed ``SloViolation`` during it
   (banked as the ``slo`` row).
4. **infer fleet** — a 2-replica fixed-shape (InferenceEngine) fleet
   under concurrent clients; banks aggregate img/s (the fleet hosts
   both engine kinds).

``--quick`` (2 replicas, small workload) is the seconds-scale smoke
wired into tier-1 (``tests/test_fleet.py::test_fleet_bench_quick``);
the full run banks ``benchmark/results_fleet_cpu.json``; no chip row
exists.

CLI:
    python benchmark/fleet_bench.py [--quick] [--output out.json]
        [--replicas 3] [--units 128] [--layers 2] [--requests 60]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as onp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import code_rev  # noqa: E402


def log(*a):
    print("[fleet_bench]", *a, file=sys.stderr, flush=True)


def pctl(vals, q):
    return round(float(onp.percentile(vals, q)), 4) if vals else None


class LoadGen:
    """Paced closed-ish loop clients against a Router; every outcome is
    classified (ok / typed-transient / shed-at-admission / other). The
    acceptance gate is ``other == 0`` and ``ok + transient ==
    submitted`` — nothing lost, nothing double-counted."""

    def __init__(self, router, tenant, vocab, max_new, period_s, seed):
        self.router = router
        self.tenant = tenant
        self.vocab = vocab
        self.max_new = max_new
        self.period = period_s
        self.rng = onp.random.RandomState(seed)
        self.lock = threading.Lock()
        self.lat = []                     # (t_done, latency_s)
        self.ok = self.transient = self.shed = 0
        self.other = []
        self.submitted = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        from mxnet_tpu.base import TransientError

        while not self._stop.is_set():
            prompt = self.rng.randint(0, self.vocab, (5,)).astype(onp.int32)
            t0 = time.monotonic()
            try:
                h = self.router.submit(prompt, self.max_new,
                                       tenant=self.tenant, timeout_ms=None)
            except TransientError:
                with self.lock:
                    self.shed += 1
                # a shed client backs off (the retry-loop contract) —
                # also keeps a zero-paced flood from pure-spinning
                time.sleep(max(self.period, 0.005))
                continue
            except Exception as e:  # noqa: BLE001 — the gate
                with self.lock:
                    self.other.append(repr(e))
                continue
            with self.lock:
                self.submitted += 1
            try:
                h.wait(timeout=300)
                with self.lock:
                    self.ok += 1
                    self.lat.append((time.monotonic(),
                                     time.monotonic() - t0))
            except TransientError:
                with self.lock:
                    self.transient += 1
            except Exception as e:  # noqa: BLE001
                with self.lock:
                    self.other.append(repr(e))
            time.sleep(self.period)

    def start(self):
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        self._thread.join(120)

    def row(self):
        with self.lock:
            lats = [l for _, l in self.lat]
            return {
                "tenant": self.tenant,
                "submitted": self.submitted,
                "ok": self.ok,
                "transient": self.transient,
                "shed_at_admission": self.shed,
                "lost": len(self.other),
                "p50_ms": pctl([l * 1e3 for l in lats], 50),
                "p99_ms": pctl([l * 1e3 for l in lats], 99),
            }


def build_fleet(net, replicas, lanes, tenants):
    from mxnet_tpu.serving import LLMEngine, ReplicaPool, Router

    def factory():
        eng = LLMEngine(net, max_running=lanes, block_size=4,
                        max_context=48, kv_cache_dtype="int8")
        eng.warmup(prompt_lengths=[5])
        return eng

    pool = ReplicaPool(factory, n_replicas=replicas, heartbeat_s=0.1)
    return Router(pool, tenants=tenants, hedge_ms=0), pool


def llm_phases(args, quick):
    from mxnet_tpu.gluon.model_zoo.bert import gpt_like
    from mxnet_tpu.resilience import chaos
    from mxnet_tpu.serving import TenantConfig
    from mxnet_tpu.serving.fleet import DEAD, HEALTHY

    vocab = 64
    units = args.units or (96 if quick else 192)
    onp.random.seed(0)
    net = gpt_like(vocab_size=vocab, units=units, hidden_size=4 * units,
                   num_layers=args.layers, num_heads=4, max_length=128,
                   dropout=0.0)
    net.initialize()
    replicas = args.replicas or (2 if quick else 3)
    lanes = 4 if quick else 8
    tenants = [TenantConfig("gold", weight=3.0, deadline_class=2),
               TenantConfig("bronze", weight=1.0, deadline_class=0)]
    tok_new = 8 if quick else 16

    # ---- phase 1+2: steady, then chaos-kill under sustained load ----
    router, pool = build_fleet(net, replicas, lanes, tenants)
    gens = [LoadGen(router, "gold", vocab, tok_new, 0.005, 10).start(),
            LoadGen(router, "gold", vocab, tok_new, 0.005, 11).start(),
            LoadGen(router, "bronze", vocab, tok_new, 0.01, 12).start()]
    steady_s = 1.5 if quick else 6.0
    recover_s = 2.0 if quick else 8.0
    time.sleep(steady_s)
    kill_t = time.monotonic()
    victim = max(pool.replicas, key=lambda r: r.host.inflight())
    deadline = time.monotonic() + 30
    while victim.host.inflight() == 0 and time.monotonic() < deadline:
        time.sleep(0.005)
    with chaos.scope(f"serving.fleet.replica.{victim.name}",
                     fail="fatal", times=1):
        deadline = time.monotonic() + 30
        while victim.state != DEAD and time.monotonic() < deadline:
            time.sleep(0.01)
    killed = victim.state == DEAD
    time.sleep(recover_s)
    for g in gens:
        g.stop()
    c = router.stats()["counters"]
    all_lat = sorted(t_l for g in gens for t_l in g.lat)
    steady_lat = [l * 1e3 for t, l in all_lat if t < kill_t]
    recovery_lat = [l * 1e3 for t, l in all_lat if t >= kill_t]
    total_ok = sum(g.ok for g in gens)
    total_tok = total_ok * tok_new       # completed requests' tokens
    wall = steady_s + recover_s
    survivors = sum(1 for r in pool.replicas if r.state == HEALTHY)
    drill = {
        "replicas": replicas,
        "lanes_per_replica": lanes,
        "killed_replica": victim.name if killed else None,
        "lost_request_count": sum(len(g.other) for g in gens),
        "accounting_exact": all(
            g.ok + g.transient == g.submitted for g in gens),
        "readmitted": c["readmitted"],
        "replica_dead": c["replica_dead"],
        "completed": c["completed"],
        "aggregate_tok_s": round(total_tok / wall, 1),
        "p99_steady_ms": pctl(steady_lat, 99),
        "p99_recovery_ms": pctl(recovery_lat, 99),
        "p50_steady_ms": pctl(steady_lat, 50),
        "p50_recovery_ms": pctl(recovery_lat, 50),
        "survivors_healthy": survivors,
        "clients": [g.row() for g in gens],
    }
    router.close()
    log(f"drill: killed={drill['killed_replica']} "
        f"lost={drill['lost_request_count']} "
        f"readmitted={drill['readmitted']} "
        f"tok/s={drill['aggregate_tok_s']} "
        f"p99 {drill['p99_steady_ms']} -> {drill['p99_recovery_ms']} ms")

    # ---- phase 3: noisy neighbor isolation + the SLO sentinel -------
    from mxnet_tpu.telemetry import SloRule, SloSentinel

    router, pool = build_fleet(net, replicas, lanes, tenants)
    solo = LoadGen(router, "gold", vocab, tok_new, 0.01, 20).start()
    time.sleep(steady_s)
    solo.stop()
    # declare the p99 ceiling off the measured steady phase, scoped to
    # THIS fleet's gold series (the sentinel evaluates the local
    # in-process registry as a single-process cluster); the overload
    # ramp below must breach it, the steady phase must not
    steady_p99 = solo.row()["p99_ms"] or 100.0
    slo_ceiling = round(max(1.5 * steady_p99, steady_p99 + 10.0), 3)
    sentinel = SloSentinel(
        [SloRule("gold_p99", "p99_ms_max", slo_ceiling,
                 metric="fleet_request_ms",
                 labels={"fleet": pool.name, "tenant": "gold"})],
        bundle=False)
    steady_fired = sentinel.evaluate()       # the steady-phase verdict
    gold = LoadGen(router, "gold", vocab, tok_new, 0.01, 21).start()
    # the flood is genuinely concurrent: enough bronze clients that the
    # tenant's weighted-fair quota BINDS (shed_at_admission > 0 is the
    # isolation mechanism working, not a failure)
    flood = [LoadGen(router, "bronze", vocab, tok_new, 0.0, 22 + i).start()
             for i in range(8 if quick else 16)]
    flood_fired = []
    flood_deadline = time.monotonic() + steady_s
    while time.monotonic() < flood_deadline:
        flood_fired.extend(sentinel.evaluate())
        time.sleep(0.1)
    gold.stop()
    for g in flood:
        g.stop()
    solo_row, gold_row = solo.row(), gold.row()
    noisy_rows = [g.row() for g in flood]
    noisy_shed = sum(r["shed_at_admission"] for r in noisy_rows)
    iso = (round(gold_row["p99_ms"] / solo_row["p99_ms"], 3)
           if solo_row["p99_ms"] and gold_row["p99_ms"] else None)
    isolation = {
        "gold_alone": solo_row,
        "gold_with_noisy_neighbor": gold_row,
        "noisy_neighbor_clients": len(flood),
        "noisy_neighbor_ok": sum(r["ok"] for r in noisy_rows),
        "noisy_neighbor_lost": sum(r["lost"] for r in noisy_rows),
        "isolation_ratio_p99": iso,
        "neighbor_shed_total": noisy_shed,
    }
    slo = {
        "rule": "gold_p99",
        "p99_ceiling_ms": slo_ceiling,
        "steady_violations": len(steady_fired),
        "flood_violations": len(flood_fired),
        "first_violation": (flood_fired[0].to_dict()
                            if flood_fired else None),
    }
    router.close()
    log(f"isolation: gold p99 {solo_row['p99_ms']} -> "
        f"{gold_row['p99_ms']} ms (ratio {iso}), neighbor shed "
        f"{noisy_shed}")
    log(f"slo: ceiling {slo_ceiling} ms, steady violations "
        f"{slo['steady_violations']}, flood violations "
        f"{slo['flood_violations']}")
    return drill, isolation, slo


def infer_phase(args, quick):
    """Fixed-shape fleet: aggregate img/s over 2 InferenceEngine
    replicas under concurrent clients."""
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.serving import InferenceEngine, ReplicaPool, Router

    onp.random.seed(1)
    net = nn.HybridSequential()
    net.add(nn.Dense(64, activation="relu"), nn.Dense(8))
    net.initialize()

    def factory():
        eng = InferenceEngine(
            net, example_input=onp.zeros((1, 32), "float32"),
            max_batch_size=8, max_delay_ms=1.0)
        eng.warmup((32,))
        return eng

    pool = ReplicaPool(factory, n_replicas=2, heartbeat_s=0.1)
    router = Router(pool, hedge_ms=0)
    n_clients = 4
    per_client = 30 if quick else 120
    done = [0] * n_clients

    def client(i):
        rng = onp.random.RandomState(30 + i)
        for _ in range(per_client):
            x = rng.randn(2, 32).astype(onp.float32)
            router.submit(x, 0).wait(timeout=300)
            done[i] += 1

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(i,))
               for i in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(600)
    wall = time.perf_counter() - t0
    imgs = sum(done) * 2                  # 2 rows per request
    router.close()
    row = {
        "replicas": 2,
        "clients": n_clients,
        "requests": sum(done),
        "img_s": round(imgs / wall, 1),
        "wall_s": round(wall, 3),
    }
    log(f"infer fleet: {row['img_s']} img/s over {row['requests']} reqs")
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="seconds-scale smoke (tier-1)")
    ap.add_argument("--replicas", type=int, default=0)
    ap.add_argument("--units", type=int, default=0)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--output", default=None)
    args = ap.parse_args()

    import jax

    import mxnet_tpu as mx  # noqa: F401

    quick = bool(args.quick)
    platform = jax.devices()[0].platform
    drill, isolation, slo = llm_phases(args, quick)
    infer = infer_phase(args, quick)

    rec = {
        "metric": "fleet_serving",
        "value": drill["aggregate_tok_s"],
        "unit": "tok/s",
        "quick": quick,
        "device": platform,
        "device_kind": getattr(jax.devices()[0], "device_kind", ""),
        "drill": drill,
        "isolation": isolation,
        "slo": slo,
        "infer_fleet": infer,
        "img_s": infer["img_s"],
        "code_rev": code_rev(),
    }
    text = json.dumps(rec)
    print(text, flush=True)
    if args.output:
        with open(args.output, "w") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()
