"""Cluster-wide KV economy (ISSUE 19): prefix-affinity routing +
tiered KV block storage.

Correctness pins:

- ONE chain-hash discipline: the engine prefix cache and the public
  ``serving.kv_hash`` helper produce identical digests (drift test);
- spill re-attach is token-identical to a cold re-prefill (the byte
  copy of pool rows IS the identity oracle);
- the spill tier is bytes-bounded with exact accounting, and the
  engine pool identity (free + in-use == total) holds while spilling;
- a remote spill fetch survives the garble drill: CRC reject → typed
  retry → local re-prefill fallback, bounded, never a hang;
- the affinity-replica-kill drill loses zero requests with
  exactly-once re-admission and an affinity-map rebuild;
- the autoscaler's capacity/quota semantics are unchanged by spill
  (host-RAM copies are not HBM headroom).
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as onp
import pytest

from mxnet_tpu.gluon.model_zoo import bert
from mxnet_tpu.serving import kv_hash
from mxnet_tpu.serving.kv_spill import KVSpillTier
from mxnet_tpu.serving.llm import LLMEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_NET = None


def _shared_net():
    global _NET
    if _NET is None:
        onp.random.seed(0)
        net = bert.gpt_like(vocab_size=37, units=16, hidden_size=32,
                            num_layers=2, num_heads=4, max_length=64,
                            dropout=0.0)
        net.initialize()
        _NET = net
    return _NET


def _engine(**kw):
    kw.setdefault("max_running", 4)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_context", 32)
    kw.setdefault("kv_cache_dtype", "float32")
    return LLMEngine(_shared_net(), **kw)


def _counter(name, labels=None):
    from mxnet_tpu.telemetry.registry import get_registry

    fam = get_registry().snapshot()["metrics"].get(name)
    if not fam:
        return 0.0
    total = 0.0
    for sr in fam["series"]:
        if not labels or all(sr["labels"].get(k) == v
                             for k, v in labels.items()):
            total += sr["value"]
    return total


def _payload(rng, nbytes=1024):
    n = max(1, nbytes // 8)
    return {"k": rng.randn(n).astype(onp.float64)}


# ---------------------------------------------------------------------------
# the shared hash discipline
# ---------------------------------------------------------------------------

def test_kv_hash_drift_engine_vs_helper():
    """The engine's prefix-cache hashes and the public helper must be
    THE SAME function — a router hashing even slightly differently
    would route every request to the wrong replica's cache."""
    eng = _engine(prefix_cache=True)
    try:
        rng = onp.random.RandomState(3)
        for n in (4, 9, 16, 23):
            prompt = rng.randint(0, 37, (n,)).astype(onp.int32)
            with eng._state_lock:
                res = eng._kv.reserve(prompt, n + 1)
                eng._kv.release(res.blocks)
            assert res.hashes == kv_hash.chain_hashes(prompt,
                                                      eng.block_size)
        prompt = rng.randint(0, 37, (20,)).astype(onp.int32)
        hs = kv_hash.chain_hashes(prompt, 4)
        assert kv_hash.prefix_key(prompt, 4, depth=2) == hs[1]
        # depth caps at the available full blocks
        assert kv_hash.prefix_key(prompt, 4, depth=99) == hs[-1]
        assert kv_hash.prefix_key(prompt[:3], 4) is None
        # dtype-independent: int64 tokens hash identically
        assert kv_hash.chain_hashes(prompt.astype(onp.int64), 4) == hs
        # chain property: hash j commits to the WHOLE prefix
        other = prompt.copy()
        other[0] += 1
        assert kv_hash.chain_hashes(other, 4)[-1] != hs[-1]
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# spill tier unit: bounded bytes, exact accounting
# ---------------------------------------------------------------------------

def test_spill_tier_bytes_bound_and_disk_demotion(tmp_path):
    rng = onp.random.RandomState(0)
    tier = KVSpillTier(bytes_limit=4096, root=str(tmp_path / "spill"))
    try:
        payloads = {}
        for i in range(8):
            h = bytes([i]) * 16
            payloads[h] = _payload(rng, 1024)
            tier.put(h, payloads[h])
        blocks, nbytes = tier.level()
        assert nbytes <= 4096, f"host tier over budget: {nbytes}"
        assert blocks == 4
        st = tier.stats()
        assert st["puts"] == 8
        # overflow demoted to disk, nothing dropped (a root is armed)
        assert st["demoted_to_disk"] == 4 and st["dropped"] == 0
        # a demoted entry comes back from disk byte-identical and is
        # promoted into the host tier
        h0 = bytes([0]) * 16
        got, from_tier = tier.get(h0)
        assert from_tier == "disk"
        onp.testing.assert_array_equal(got["k"], payloads[h0]["k"])
        assert tier.get(h0)[1] == "host"          # promoted
        # host tier still bounded after the promotion
        assert tier.level()[1] <= 4096
        assert tier.get(b"\xff" * 16) == (None, None)
    finally:
        tier.close()


def test_spill_tier_without_disk_drops_overflow():
    rng = onp.random.RandomState(1)
    tier = KVSpillTier(bytes_limit=2048)
    try:
        for i in range(6):
            tier.put(bytes([i]) * 16, _payload(rng, 1024))
        st = tier.stats()
        assert st["dropped"] == 4 and st["demoted_to_disk"] == 0
        assert tier.level()[1] <= 2048
        assert tier.get(bytes([0]) * 16) == (None, None)
        assert tier.get(bytes([5]) * 16)[1] == "host"
    finally:
        tier.close()


# ---------------------------------------------------------------------------
# engine integration: evict → spill → re-attach, token-identical
# ---------------------------------------------------------------------------

def test_spill_reattach_token_identical_and_pool_identity():
    """THE resumed-session oracle: a prompt whose blocks were evicted
    to the spill tier must decode token-identically to a cold
    re-prefill — re-attach is a byte copy, not an approximation."""
    eng = _engine(prefix_cache=True, kv_spill=True,
                  kv_spill_bytes=1 << 20, num_blocks=10)
    try:
        prompt = (onp.arange(1, 17, dtype=onp.int32) % 30) + 1
        first = list(eng.submit(prompt, 5).wait())
        ev0 = eng.metrics.prefix_evictions.value
        rng = onp.random.RandomState(7)
        # flood with distinct prompts until the resident prefix blocks
        # for `prompt` are evicted into the spill tier
        for _ in range(10):
            eng.submit(rng.randint(1, 30, (16,)).astype(onp.int32),
                       1).wait()
        assert eng.metrics.prefix_evictions.value > ev0
        spilled_blocks, spilled_bytes = eng._kv.spill.level()
        assert spilled_blocks > 0 and spilled_bytes > 0
        # gauges mirror the tier's own accounting
        assert int(eng.metrics.kv_spill_blocks.get()) == spilled_blocks
        assert int(eng.metrics.kv_spill_bytes.get()) == spilled_bytes
        # pool identity holds while spilling: spill copies live in host
        # RAM, they never consume (or free) HBM pool blocks
        in_use = eng.num_blocks - eng._kv.free_blocks
        assert in_use == sum(1 for v in eng._kv.ref.values() if v > 0)
        r0 = _counter("llm_kv_reattach_total", {"tier": "host"})
        resumed = list(eng.submit(prompt, 5).wait())
        assert _counter("llm_kv_reattach_total", {"tier": "host"}) > r0
        assert resumed == first, (
            f"re-attach not token-identical: {resumed} vs {first}")
        # cold oracle: a fresh engine with no cache at all
        with _engine(prefix_cache=True) as cold:
            assert list(cold.submit(prompt, 5).wait()) == first
    finally:
        eng.close()
    # closed engine zeroes its spill gauges (no ghost host-RAM claims)
    assert int(eng.metrics.kv_spill_blocks.get()) == 0


def test_spill_survives_engine_fault_reset():
    """A pool rebuild clears block IDS; the spill tier is
    content-addressed so its entries stay valid — post-fault
    admissions re-attach instead of paying a cold re-prefill."""
    from mxnet_tpu.base import TransientError

    eng = _engine(prefix_cache=True, kv_spill=True, num_blocks=10)
    try:
        prompt = (onp.arange(2, 18, dtype=onp.int32) % 30) + 1
        first = list(eng.submit(prompt, 4).wait())
        rng = onp.random.RandomState(11)
        for _ in range(10):
            eng.submit(rng.randint(1, 30, (16,)).astype(onp.int32),
                       1).wait()
        assert eng._kv.spill.level()[0] > 0
        with eng._state_lock:
            assert eng._fault_locked(TransientError("drill"))
        assert len(eng._kv.prefix) == 0          # HBM cache reset
        assert eng._kv.spill.level()[0] > 0      # spill tier survived
        r0 = _counter("llm_kv_reattach_total", {"tier": "host"})
        assert list(eng.submit(prompt, 4).wait()) == first
        assert _counter("llm_kv_reattach_total", {"tier": "host"}) > r0
    finally:
        eng.close()


def test_kv_spill_requires_prefix_cache():
    with pytest.raises(ValueError, match="prefix_cache"):
        _engine(prefix_cache=False, kv_spill=True)


# ---------------------------------------------------------------------------
# remote tier: fetch over the block-transfer plane + the garble drill
# ---------------------------------------------------------------------------

def test_remote_spill_fetch_reattaches_and_garble_falls_back():
    """Replica B, which NEVER saw the prompt, re-attaches blocks
    spilled by replica A over the PR-17 transport (tier=remote),
    token-identically. Under persistent frame garbling the CRC
    verify-on-receive rejects every fetch and B falls back to a local
    re-prefill — correct output, bounded wall time, no hang."""
    from mxnet_tpu.resilience import chaos

    a = _engine(prefix_cache=True, kv_spill=True, num_blocks=10,
                kv_spill_serve=True)
    try:
        prompt = (onp.arange(3, 19, dtype=onp.int32) % 30) + 1
        first = list(a.submit(prompt, 4).wait())
        rng = onp.random.RandomState(13)
        for _ in range(10):
            a.submit(rng.randint(1, 30, (16,)).astype(onp.int32),
                     1).wait()
        assert a._kv.spill.level()[0] > 0
        assert a.kv_spill_endpoint is not None
        b = _engine(prefix_cache=True, kv_spill=True,
                    kv_spill_peers=[a.kv_spill_endpoint])
        try:
            r0 = _counter("llm_kv_reattach_total", {"tier": "remote"})
            got = list(b.submit(prompt, 4).wait())
            assert got == first
            assert _counter("llm_kv_reattach_total",
                            {"tier": "remote"}) > r0
        finally:
            b.close()
        # the garble drill: EVERY remote frame corrupts → typed retry
        # exhaustion inside the tier → miss → local re-prefill
        c = _engine(prefix_cache=True, kv_spill=True,
                    kv_spill_peers=[a.kv_spill_endpoint])
        try:
            with chaos.scope("io.net.frame", fail="garble"):
                t0 = time.monotonic()
                got = list(c.submit(prompt, 4).wait())
                wall = time.monotonic() - t0
            assert got == first
            assert wall < 30.0, f"garble fallback took {wall:.1f}s"
            assert c._kv.spill.stats()["remote_errors"] > 0
        finally:
            c.close()
    finally:
        a.close()


def test_spill_resolver_rejects_garbage_names():
    tier = KVSpillTier(bytes_limit=4096, serve=True)
    try:
        assert tier._resolve("not-kv/abc") is None
        assert tier._resolve("kv/not-hex!") is None
        assert tier._resolve("kv/" + "00" * 16) is None
    finally:
        tier.close()


# ---------------------------------------------------------------------------
# prefix-affinity routing
# ---------------------------------------------------------------------------

def _fleet(n=3, **kw):
    from mxnet_tpu.serving.fleet import ReplicaPool

    net = _shared_net()

    def build():
        eng = LLMEngine(net, max_running=4, block_size=4,
                        max_context=32, kv_cache_dtype="float32")
        eng.warmup(prompt_lengths=[5])
        return eng

    kw.setdefault("heartbeat_s", 0.1)
    return ReplicaPool(build, n_replicas=n, **kw)


def test_affinity_routing_concentrates_on_rendezvous_owner():
    from mxnet_tpu.serving.fleet import Router

    pool = _fleet(3)
    router = Router(pool, affinity_block_size=4, affinity_blocks=2,
                    hedge_ms=0)
    try:
        prompt = (onp.arange(1, 13, dtype=onp.int32) % 30) + 1
        akey = kv_hash.prefix_key(prompt, 4, depth=2)
        target = router._affinity_target(akey)
        assert target in router._affinity_members
        h0 = router.stats()["counters"]["affinity_hit"]
        for _ in range(6):
            router.generate(prompt, 2)
        c = router.stats()["counters"]
        assert c["affinity_hit"] - h0 >= 5
        # a different prefix maps independently (usually elsewhere) —
        # and deterministically
        assert router._affinity_target(akey) == target
    finally:
        router.close()


def test_affinity_disabled_and_fixed_shape_fleets_have_no_akey():
    from mxnet_tpu.serving.fleet import Router

    pool = _fleet(2)
    router = Router(pool, affinity=False, hedge_ms=0)
    try:
        prompt = (onp.arange(1, 13, dtype=onp.int32) % 30) + 1
        router.generate(prompt, 2)
        c = router.stats()["counters"]
        assert c["affinity_hit"] == 0 and c["affinity_fallback"] == 0
    finally:
        router.close()


def test_affinity_kill_drill_zero_lost_exactly_once():
    """Kill the affinity owner with requests in flight: every request
    completes exactly once (re-admitted elsewhere), the affinity map
    rebuilds without the dead member, zero lost."""
    from mxnet_tpu.serving.fleet import Router

    pool = _fleet(3)
    router = Router(pool, affinity_block_size=4, affinity_blocks=2,
                    hedge_ms=0, readmit_limit=2)
    try:
        prompt = (onp.arange(5, 17, dtype=onp.int32) % 30) + 1
        akey = kv_hash.prefix_key(prompt, 4, depth=2)
        target = router._affinity_target(akey)
        router.generate(prompt, 2)               # warm the owner

        results, errors = [], []

        def one():
            try:
                results.append(list(router.generate(prompt, 2)))
            except Exception as e:  # noqa: BLE001 — counted as lost
                errors.append(e)

        threads = [threading.Thread(target=one) for _ in range(8)]
        for t in threads:
            t.start()
        pool.kill(target)
        for t in threads:
            t.join(120)
        assert not errors, f"lost requests: {errors!r}"
        assert len(results) == 8
        # exactly-once: all results identical (greedy decode) — a
        # double delivery would have tripped the one-shot FleetRequest
        assert all(r == results[0] for r in results)
        # the membership edge fired: the dead owner left the map
        assert target not in router._affinity_members
        new_target = router._affinity_target(akey)
        assert new_target is not None and new_target != target
        c = router.stats()["counters"]
        assert c["affinity_rebuilds"] >= 2
        assert c["failed"] == 0
    finally:
        router.close()


# ---------------------------------------------------------------------------
# cluster derivation + autoscaler semantics
# ---------------------------------------------------------------------------

def test_cluster_scraper_derives_prefix_hit_rate_and_spill():
    from mxnet_tpu.telemetry.cluster import ClusterScraper

    eng = _engine(prefix_cache=True, kv_spill=True, num_blocks=10)
    try:
        prompt = (onp.arange(4, 20, dtype=onp.int32) % 30) + 1
        eng.submit(prompt, 2).wait()
        eng.submit(prompt, 2).wait()             # second pass hits
        rng = onp.random.RandomState(17)
        for _ in range(10):
            eng.submit(rng.randint(1, 30, (16,)).astype(onp.int32),
                       1).wait()
        snap = ClusterScraper(root=None).scrape()
        c = snap["cluster"]
        assert 0.0 < c["prefix_hit_rate"] <= 1.0
        assert c["llm_kv_spill_blocks_total"] > 0
        from mxnet_tpu.telemetry import prometheus_text

        txt = prometheus_text()
        assert "cluster_prefix_hit_rate" in txt
        assert "cluster_kv_spill_blocks" in txt
    finally:
        eng.close()


def test_autoscale_capacity_and_quota_unchanged_by_spill():
    """Spill parks copies in host RAM: fleet capacity, free units and
    tenant quotas MUST be identical with and without it — spilled
    blocks are not HBM headroom and must never feed a scale decision."""
    from mxnet_tpu.serving.autoscale import AutoscalePolicy, Autoscaler
    from mxnet_tpu.serving.fleet import Router

    from mxnet_tpu.serving.fleet import ReplicaPool

    caps = {}
    net = _shared_net()
    for spill in (False, True):
        def build(spill=spill):
            eng = LLMEngine(net, max_running=4, block_size=4,
                            max_context=32, kv_cache_dtype="float32",
                            prefix_cache=True, kv_spill=spill)
            eng.warmup(prompt_lengths=[5])
            return eng

        pool = ReplicaPool(build, n_replicas=2, heartbeat_s=0.1)
        router = Router(pool, hedge_ms=0)
        try:
            prompt = (onp.arange(6, 22, dtype=onp.int32) % 30) + 1
            router.generate(prompt, 2)
            st = router.stats()
            caps[spill] = (st["capacity_units"], st["free_units"],
                           {t: v["quota_units"]
                            for t, v in st["tenants"].items()})
        finally:
            router.close()
    assert caps[False] == caps[True], (
        f"spill changed capacity semantics: {caps}")
    # the autoscaler surfaces the hit rate as observability only
    from mxnet_tpu.telemetry.cluster import ClusterScraper

    pool = _fleet(2)
    router = Router(pool, hedge_ms=0)
    scaler = Autoscaler(pool, scraper=ClusterScraper(root=None),
                        policy=AutoscalePolicy(min_replicas=1,
                                               max_replicas=3))
    try:
        obs = scaler.observe()
        assert "prefix_hit_rate" in obs
    finally:
        scaler.stop()
        router.close()


# ---------------------------------------------------------------------------
# bench quick gate
# ---------------------------------------------------------------------------

def test_kv_economy_bench_quick():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    for k in list(env):
        if k.startswith(("MXNET_TPU_CHAOS", "MXNET_TPU_AOT",
                         "MXNET_TPU_FLEET", "MXNET_TPU_AUTOSCALE",
                         "MXNET_TPU_LLM")):
            env.pop(k)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark",
                                      "kv_economy_bench.py"), "--quick"],
        capture_output=True, text=True, timeout=560, env=env)
    assert out.returncode == 0, out.stdout + out.stderr
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["quick"] is True
    names = {m["metric"] for m in rec["metrics"]}
    assert {"cluster_prefix_hit_rate_affinity_on",
            "cluster_prefix_hit_rate_affinity_off",
            "resumed_ttft_reattach_ms",
            "resumed_ttft_reprefill_ms",
            "effective_context_blocks_spill",
            "effective_context_blocks_hbm"} <= names
    assert rec["lost_requests"] == 0
