"""mxnet_tpu.serving — dynamic-batching inference engine.

Contract under test (ISSUE 1 / docs/serving.md):
- concurrent clients get exactly their rows back after pad-and-slice;
- coalescing actually happens (mean batch occupancy > 1 under
  concurrency);
- overload and expired-deadline requests fail with the TYPED errors
  (ServerOverload / DeadlineExceeded), without crashing the engine or
  leaking queue slots;
- close() drains cleanly;
- the bench harness (the thing tools/serve_bench.py drives) produces a
  well-formed row — the tier-1 smoke keeping the subsystem from rotting.

All CPU, all tier-1-fast.
"""
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon import nn
from mxnet_tpu.serving import (AdmissionQueue, DeadlineExceeded, Histogram,
                               InferenceEngine, Request, ServerOverload,
                               ServingMetrics)
from mxnet_tpu.serving.engine import _pow2_bucket


def _mlp(classes=4, in_dim=16):
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"), nn.Dense(classes))
    net.initialize()
    return net


def _slow_engine(delay_s=0.05, **kw):
    """Engine over a host-side callable that sleeps — deterministic
    queue pressure without big models."""

    def slow(x):
        time.sleep(delay_s)
        return x * 2.0

    kw.setdefault("max_batch_size", 1)
    kw.setdefault("max_delay_ms", 1)
    return InferenceEngine(slow, jit=False, **kw)


# ---------------------------------------------------------------------------
# correctness: pad-and-slice under concurrency
# ---------------------------------------------------------------------------
def test_concurrent_clients_get_their_own_rows():
    net = _mlp()
    eng = InferenceEngine(net, example_input=onp.zeros((1, 16), "float32"),
                          max_batch_size=16, max_delay_ms=50,
                          max_queue_size=64)
    try:
        n_clients = 12
        xs = [onp.random.RandomState(i).uniform(size=(1, 16))
              .astype("float32") for i in range(n_clients)]
        refs = [net(mx.np.array(x)).asnumpy() for x in xs]
        outs = [None] * n_clients
        barrier = threading.Barrier(n_clients)

        def client(i):
            barrier.wait()  # submit together so coalescing must happen
            outs[i] = eng.infer(xs[i]).asnumpy()

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        for i in range(n_clients):
            onp.testing.assert_allclose(outs[i], refs[i],
                                        rtol=1e-5, atol=1e-5)
        snap = eng.stats()
        # 12 simultaneous single-row requests into a 16-wide bucket: the
        # batcher must have coalesced (sequential would record mean 1.0)
        assert snap["batch_occupancy"]["mean"] > 1.0
        assert snap["counters"]["completed"] == n_clients
        assert snap["counters"]["failed"] == 0
    finally:
        eng.close()


def test_multi_row_requests_sliced_correctly():
    net = _mlp()
    eng = InferenceEngine(net, example_input=onp.zeros((1, 16), "float32"),
                          max_batch_size=8, max_delay_ms=30)
    try:
        sizes = [1, 3, 2]
        xs = [onp.random.RandomState(7 + n).uniform(size=(n, 16))
              .astype("float32") for n in sizes]
        refs = [net(mx.np.array(x)).asnumpy() for x in xs]
        outs = [None] * len(sizes)
        barrier = threading.Barrier(len(sizes))

        def client(i):
            barrier.wait()
            outs[i] = eng.infer(xs[i]).asnumpy()

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(sizes))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        for i, n in enumerate(sizes):
            assert outs[i].shape[0] == n
            onp.testing.assert_allclose(outs[i], refs[i],
                                        rtol=1e-5, atol=1e-5)
    finally:
        eng.close()


def test_infer_one_strips_batch_axis():
    net = _mlp()
    eng = InferenceEngine(net, example_input=onp.zeros((1, 16), "float32"),
                          max_batch_size=4, max_delay_ms=1)
    try:
        x = onp.random.uniform(size=(16,)).astype("float32")
        out = eng.infer_one(x)
        assert out.shape == (4,)
        ref = net(mx.np.array(x[None])).asnumpy()[0]
        onp.testing.assert_allclose(out.asnumpy(), ref, rtol=1e-5, atol=1e-5)
    finally:
        eng.close()


def test_bucketing_policy_and_warm_executables():
    assert _pow2_bucket(1, 32) == 1
    assert _pow2_bucket(3, 32) == 4
    assert _pow2_bucket(9, 32) == 16
    assert _pow2_bucket(33, 32) == 32     # capped
    assert _pow2_bucket(5, 6) == 6        # non-pow2 cap is a valid bucket
    net = _mlp()
    eng = InferenceEngine(net, example_input=onp.zeros((1, 16), "float32"),
                          max_batch_size=8, max_delay_ms=1)
    try:
        warmed = eng.warmup((16,))
        assert warmed == [1, 2, 4, 8]
        # arbitrary request sizes land on the warm pow2 buckets only
        for n in (1, 3, 5):
            eng.infer(onp.zeros((n, 16), "float32"))
        buckets = {b for (b, _s, _d) in eng._warm_buckets}
        assert buckets == {1, 2, 4, 8}
        assert eng.stats()["counters"]["compiles"] == 4  # no novel shapes
    finally:
        eng.close()


def test_request_size_validation():
    eng = _slow_engine(delay_s=0.0, max_batch_size=4)
    try:
        with pytest.raises(ValueError):
            eng.infer(onp.zeros((5, 4), "float32"))   # > max_batch_size
        with pytest.raises(ValueError):
            eng.infer(onp.zeros((0, 4), "float32"))   # empty batch
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# load shedding: typed errors, no leaked slots, no wedge
# ---------------------------------------------------------------------------
def test_overload_and_deadline_shed_typed_and_recoverable():
    eng = _slow_engine(delay_s=0.05, max_queue_size=3)
    try:
        handles, overloads = [], 0
        for _ in range(10):
            try:
                handles.append(eng.infer_async(
                    onp.ones((1, 4), "float32"), timeout_ms=15))
            except ServerOverload:
                overloads += 1
        assert overloads > 0, "queue bound never triggered"
        ok = deadline = 0
        for h in handles:
            try:
                h.wait()
                ok += 1
            except DeadlineExceeded:
                deadline += 1
        assert deadline > 0, "queued requests should have expired"
        assert ok + deadline == len(handles)  # every handle resolved
        # no leaked queue slots: the queue drains and fresh traffic flows
        out = eng.infer(onp.ones((1, 4), "float32"))
        onp.testing.assert_allclose(out.asnumpy(), 2.0)
        snap = eng.stats()
        assert snap["queue_len"] == 0
        assert snap["counters"]["shed_overload"] == overloads
        assert snap["counters"]["shed_deadline"] == deadline
        assert snap["shed_rate"] > 0
        assert eng._batcher.alive
    finally:
        eng.close()


def test_poison_batch_fails_only_its_requests():
    def poison(x):
        raise RuntimeError("kaboom")

    eng = InferenceEngine(poison, jit=False, max_batch_size=4,
                          max_delay_ms=1)
    try:
        with pytest.raises(RuntimeError, match="kaboom"):
            eng.infer(onp.ones((1, 4), "float32"))
        assert eng._batcher.alive  # the loop survived the poison batch
        assert eng.stats()["counters"]["failed"] == 1
    finally:
        eng.close()


def test_close_drains_pending_requests():
    eng = _slow_engine(delay_s=0.02, max_queue_size=32)
    handles = [eng.infer_async(onp.full((1, 4), float(i), "float32"))
               for i in range(5)]
    eng.close(drain=True)
    for i, h in enumerate(handles):
        onp.testing.assert_allclose(h.wait().asnumpy(), 2.0 * i)
    with pytest.raises(ServerOverload):
        eng.infer(onp.ones((1, 4), "float32"))  # closed = typed reject


def test_close_without_drain_fails_pending_typed():
    eng = _slow_engine(delay_s=0.05, max_queue_size=32)
    handles = [eng.infer_async(onp.ones((1, 4), "float32"))
               for i in range(6)]
    eng.close(drain=False)
    outcomes = {"ok": 0, "overload": 0}
    for h in handles:
        try:
            h.wait(timeout=10)
            outcomes["ok"] += 1
        except ServerOverload:
            outcomes["overload"] += 1
    assert outcomes["overload"] > 0
    assert outcomes["ok"] + outcomes["overload"] == 6


# ---------------------------------------------------------------------------
# admission queue unit behavior
# ---------------------------------------------------------------------------
def test_admission_queue_signature_grouping():
    q = AdmissionQueue(max_size=16)
    sig_a = ((4,), "float32")
    sig_b = ((8,), "float32")
    for sig in (sig_a, sig_a, sig_b, sig_a):
        q.submit(Request(onp.zeros((1,) + sig[0], sig[1]), 1, sig, None))
    first = q.take(16, max_wait_s=0.01)
    assert [r.signature for r in first] == [sig_a, sig_a]  # stops at b
    second = q.take(16, max_wait_s=0.01)
    assert [r.signature for r in second] == [sig_b]
    third = q.take(16, max_wait_s=0.01)
    assert [r.signature for r in third] == [sig_a]


def test_histogram_quantiles_and_snapshot():
    h = Histogram(cap=100)
    for v in range(1, 101):
        h.observe(float(v))
    s = h.summary()
    assert s["count"] == 100 and s["min"] == 1.0 and s["max"] == 100.0
    assert 45 <= s["p50"] <= 55 and s["p99"] >= 95
    m = ServingMetrics()
    m.count("submitted", 10)
    m.observe_batch(n_real=6, bucket=8, exec_s=0.01)
    snap = m.snapshot()
    assert snap["counters"]["batches"] == 1
    assert snap["batch_occupancy"]["mean"] == 6.0
    assert snap["pad_waste"]["mean"] == 0.25


# ---------------------------------------------------------------------------
# bench harness smoke — the tier-1 wiring that keeps serving from rotting
# ---------------------------------------------------------------------------
def test_serving_bench_smoke_row():
    from mxnet_tpu.serving.bench import run_serving_bench

    row = run_serving_bench(model="synthetic-tiny", image_size=16,
                            classes=4, clients=4, max_batch=4,
                            max_delay_ms=5.0, duration_s=0.5,
                            seq_requests=2, queue_size=16,
                            shed_deadline_ms=5.0, log=lambda m: None)
    # benchmark/-format row: metric/value/unit + serving fields
    assert row["metric"].startswith("serving_dynbatch_")
    assert row["unit"] == "req/s" and row["value"] > 0
    assert row["mean_batch_occupancy"] > 1.0  # coalescing observed
    assert row["sequential_req_s"] > 0
    assert row["shed"]["burst"] == 16 + 2 * 4
    assert (row["shed"]["served"] + row["shed"]["deadline"]
            + row["shed"]["overload"] + 0) <= row["shed"]["burst"]
    assert row["counters"]["failed"] == 0
    assert row["client_errors"] == []


def test_serve_bench_cli_smoke():
    """tools/serve_bench.py --smoke end to end in a subprocess (argparse,
    JSON-line protocol, exit code)."""
    import json
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "serve_bench.py"),
         "--smoke", "--duration", "0.5", "--clients", "4",
         "--max-batch", "4"],
        capture_output=True, text=True, timeout=300, env=env, cwd=root)
    assert proc.returncode == 0, proc.stderr[-2000:]
    row = json.loads(proc.stdout.strip().splitlines()[-1])
    assert row["model"] == "synthetic-tiny"
    assert row["value"] > 0 and row["mean_batch_occupancy"] > 1.0


def test_serving_symbolblock_from_export(tmp_path):
    """The engine also serves a SymbolBlock loaded from a durable
    StableHLO export (the 'Symbol executor' case). Exports are
    fixed-shape, so bucket_sizes pins the ladder to the export batch:
    EVERY request — including 1-row ones — pads up to it."""
    from mxnet_tpu.gluon.block import SymbolBlock

    net = _mlp(classes=3)
    x = mx.np.array(onp.random.RandomState(0).uniform(size=(4, 16))
                    .astype("float32"))
    ref = net(x).asnumpy()
    net.hybridize()
    net(x)
    jf, pf = net.export(str(tmp_path / "m"))
    sym = SymbolBlock.imports(jf, param_file=pf)
    eng = InferenceEngine(sym, example_input=onp.zeros((4, 16), "float32"),
                          bucket_sizes=[4], max_delay_ms=1)
    try:
        assert eng.max_batch_size == 4
        out = eng.infer(onp.asarray(x.asnumpy()))
        onp.testing.assert_allclose(out.asnumpy(), ref,
                                    rtol=1e-5, atol=1e-5)
        # the case a pow2 ladder would break: 1 row -> padded to 4, the
        # export's only legal shape, then sliced back to 1
        one = eng.infer(onp.asarray(x.asnumpy()[:1]))
        assert one.shape == (1, 3)
        onp.testing.assert_allclose(one.asnumpy(), ref[:1],
                                    rtol=1e-5, atol=1e-5)
    finally:
        eng.close()


def test_engine_retraces_on_stem_knob_flip(monkeypatch):
    """The engine's executable cache is keyed by the conv-lowering trace
    environment (stem_s2d_cache_key): flipping MXNET_TPU_STEM_S2D in a
    long-lived serving process must compile a fresh executable, not
    serve the stale lowering — same contract as the hybridize cache."""
    net = nn.HybridSequential()
    net.add(nn.Conv2D(4, kernel_size=7, strides=2, padding=3,
                      in_channels=3))
    net.initialize()
    eng = InferenceEngine(net, example_input=onp.zeros((1, 3, 32, 32),
                                                       "float32"),
                          max_batch_size=4, max_delay_ms=1)
    try:
        x = onp.random.RandomState(3).uniform(size=(1, 3, 32, 32)) \
            .astype("float32")
        monkeypatch.setenv("MXNET_TPU_STEM_S2D", "0")
        y0 = eng.infer(x).asnumpy()
        assert len(eng._execs) == 1
        monkeypatch.setenv("MXNET_TPU_STEM_S2D", "force")
        y1 = eng.infer(x).asnumpy()
        assert len(eng._execs) == 2  # new env -> new executable
        onp.testing.assert_allclose(y1, y0, rtol=1e-5, atol=1e-5)
    finally:
        eng.close()


def test_explicit_bucket_ladder():
    from mxnet_tpu.serving.engine import _ladder_bucket

    assert _ladder_bucket(1, (4,)) == 4
    assert _ladder_bucket(3, (2, 4, 6)) == 4
    assert _ladder_bucket(5, (2, 4, 6)) == 6
    with pytest.raises(ValueError):
        InferenceEngine(lambda x: x, jit=False, bucket_sizes=[])
    with pytest.raises(ValueError):
        InferenceEngine(lambda x: x, jit=False, bucket_sizes=[4],
                        max_batch_size=8)  # cap must equal largest bucket
