"""Continuous-batching LLM serving (serving.llm + the paged KV path).

Correctness pins (ISSUE 7): paged decode must be token-identical to the
dense cache on greedy decode; in-flight admission must produce exactly
the tokens offline ``generate()`` produces per sequence; block churn
must recycle the free list; sequence-length growth must never retrace;
faults are typed through the resilience classifier; a chaos kill
mid-decode leaves a flight dump carrying lane/pool state.
"""
import json
import os
import subprocess
import sys
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon.model_zoo import bert
from mxnet_tpu.gluon.model_zoo.generation import generate
from mxnet_tpu.serving.llm import LLMEngine
from mxnet_tpu.serving.admission import ServerOverload

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny_lm(seed=0, vocab=37, units=16, heads=4, layers=2, max_length=64):
    onp.random.seed(seed)
    net = bert.gpt_like(vocab_size=vocab, units=units, hidden_size=2 * units,
                        num_layers=layers, num_heads=heads,
                        max_length=max_length, dropout=0.0)
    net.initialize()
    return net


def _engine(net, **kw):
    kw.setdefault("max_running", 4)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_context", 32)
    kw.setdefault("kv_cache_dtype", "float32")
    return LLMEngine(net, **kw)


def _tiny_draft(seed=99, vocab=37, units=16, heads=4, max_length=64):
    """A 1-layer draft for the 2-layer target — small enough that a
    verify step is cheaper than K plain decode steps, uncorrelated
    enough (random init) that rejections actually happen."""
    onp.random.seed(seed)
    net = bert.gpt_like(vocab_size=vocab, units=units, hidden_size=2 * units,
                        num_layers=1, num_heads=heads,
                        max_length=max_length, dropout=0.0)
    net.initialize()
    return net


# ---------------------------------------------------------------------------
# op level
# ---------------------------------------------------------------------------
def _pools(rng, layers, nb, bs, h, d, dtype, garbage_block=None):
    """Random K and V pools in the one pool layout,
    ``(L, NB, bs, H*D')``, and the per-head float values they hold;
    ``garbage_block``: a block of every layer that holds 1e30s."""
    import jax.numpy as jnp

    from mxnet_tpu.ops.nn import (kv_cache_dequantize, kv_cache_quantize,
                                  kv_pool_rows)

    out = []
    for _ in range(2):
        t = jnp.asarray(rng.randn(layers, nb, bs, h, d), jnp.float32)
        if garbage_block is not None:
            t = t.at[:, garbage_block].set(1e30)
        if dtype == "int8":
            c = kv_cache_quantize(t)
            assert c.dtype == jnp.int8 and c.shape[-1] == d + 4
            vals = kv_cache_dequantize(c, jnp.float32)
        else:
            c = t.astype(dtype)
            vals = c.astype(jnp.float32)
        pool = kv_pool_rows(c)
        assert pool.shape == (layers, nb, bs, h * c.shape[-1])
        out += [pool, onp.asarray(vals)]
    return out


def test_paged_attention_matches_manual():
    """The jnp gather path against a dense numpy oracle, on a layer
    other than 0 of the ``(L, NB, bs, H*D)`` pools."""
    import jax.numpy as jnp

    from mxnet_tpu.ops.nn import kv_pool_heads, paged_attention

    rng = onp.random.RandomState(0)
    r, h, d, bs, nb, mb, layer = 3, 2, 8, 4, 7, 3, 1
    q = rng.randn(r, h, d).astype(onp.float32)
    kp, kv, vp, vv = _pools(rng, 2, nb, bs, h, d, "float32")
    onp.testing.assert_array_equal(                # rows <-> heads round trip
        onp.asarray(kv_pool_heads(kp, h)), kv)
    bt = rng.randint(0, nb, (r, mb)).astype(onp.int32)
    lens = onp.array([3, 7, 12], onp.int32)
    out = onp.asarray(paged_attention(
        jnp.asarray(q), kp, vp, jnp.asarray(bt), jnp.asarray(lens),
        layer=layer, use_kernel=False))
    for i in range(r):
        keys = kv[layer][bt[i]].reshape(mb * bs, h, d)
        vals = vv[layer][bt[i]].reshape(mb * bs, h, d)
        for hh in range(h):
            s = keys[:lens[i], hh] @ q[i, hh] / onp.sqrt(d)
            p = onp.exp(s - s.max())
            p /= p.sum()
            want = p @ vals[:lens[i], hh]
            onp.testing.assert_allclose(out[i, hh], want, rtol=2e-5,
                                        atol=2e-5)


# (bs, mb, lengths) of the kernel test's geometries. "toy": lengths that
# end inside a block. "cell": the benchmark's serving cell (blocks of 16,
# 64 to a lane) with lengths at the edges of a group of blocks (a grid
# step handles G of them: 128 or 256 positions there), a lane of one
# position and a full one. "mb5" / "mb13": tables that no G divides.
_KERNEL_GEOMETRIES = {
    "toy": (8, 4, [5, 17, 32]),
    "cell": (16, 64, [1, 16, 127, 128, 129, 1000, 1024]),
    "mb5": (16, 5, [1, 33, 80]),
    "mb13": (16, 13, [16, 129, 208]),
}
_KERNEL_CASES = [
    (heads, d, dtype, t, "toy", False)
    for heads, d in [(12, 64), (20, 64), (4, 16)]
    for dtype in ["float32", "bfloat16", "bfloat16-f32q", "int8"]
    for t in [1, 3]
] + [
    (20, 64, "bfloat16-f32q", 1, "cell", False),    # what the cell serves
    (20, 64, "bfloat16-f32q", 3, "cell", False),
    (12, 64, "float32", 1, "cell", False),
    (12, 64, "int8", 1, "cell", False),
    (12, 64, "bfloat16", 3, "cell", False),
    (20, 64, "bfloat16-f32q", 1, "cell", True),     # garbage past a length
    (12, 64, "float32", 1, "cell", True),
    (12, 64, "int8", 1, "cell", True),
    (4, 16, "float32", 1, "mb5", False),
    (20, 64, "bfloat16-f32q", 1, "mb5", True),
    (4, 16, "int8", 3, "mb5", False),
    (4, 16, "float32", 1, "mb13", True),
    (12, 64, "bfloat16-f32q", 1, "mb13", False),
    (12, 64, "int8", 3, "mb13", False),
    # grouped K/V heads, (query heads, K/V heads): Qwen3-Next's 16 over
    # 2 of 256 (a pool row of 512), beside GPT-2-large's 20 of 64 above
    ((16, 2), 256, "bfloat16-f32q", 1, "toy", False),
    ((16, 2), 256, "float32", 1, "toy", False),
    ((16, 2), 256, "bfloat16", 1, "mb5", False),
    ((16, 2), 256, "bfloat16-f32q", 1, "cell", True),
    ((4, 2), 128, "float32", 1, "mb13", True),
]


@pytest.mark.parametrize(
    "heads,d,dtype,t,geometry,garbage", _KERNEL_CASES,
    ids=["-".join(map(str, c[:5])) + ("-garbage" if c[5] else "")
         for c in _KERNEL_CASES])
def test_paged_kernel_matches_jnp(heads, d, dtype, t, geometry, garbage):
    """The Pallas kernel (interpret mode on CPU — the compiled Mosaic
    path on TPU) against the jnp gather oracle on the one pool layout:
    GPT-2's heads x head size (rows of 768 and 1,280 lanes) and a toy,
    float and int8 pools (the engine DEFAULT: the bitcast-scale rows
    dequantize inside the kernel), bf16 pools under a float32 query (a
    bf16 model's norms hand float32 on: what the chip serves), T = 1
    (decode) and T > 1 (suffix prefill, speculative verify: the same
    kernel on R*T virtual lanes), a layer other than 0, grouped K/V
    heads (``heads`` a pair: the pool rows hold the second number), and
    the geometries above. ``garbage``: every table entry past a lane's
    length points at a block of 1e30s, as the engine's point at its
    trash block — what is there may be anything finite and the result
    may not feel it."""
    import jax.numpy as jnp

    from mxnet_tpu.ops.nn import paged_attention, paged_attention_multi

    heads, kv_heads = heads if isinstance(heads, tuple) else (heads, heads)
    rng = onp.random.RandomState(1 + heads + t)
    bs, mb, lens = _KERNEL_GEOMETRIES[geometry]
    lens = onp.array(lens, onp.int32)
    r, nb, layer = len(lens), mb + 6, 2
    dtype, _, f32q = dtype.partition("-")
    qdt = "float32" if dtype == "int8" or f32q else dtype
    kp, _, vp, _ = _pools(rng, 3, nb, bs, kv_heads, d, dtype,
                          garbage_block=nb - 1 if garbage else None)
    bt = rng.randint(0, nb - 1, (r, mb)).astype(onp.int32)
    if garbage:
        past = onp.arange(mb)[None, :] * bs >= lens[:, None]
        bt = onp.where(past, nb - 1, bt).astype(onp.int32)
    bt = jnp.asarray(bt)
    if t == 1:
        q = jnp.asarray(rng.randn(r, heads, d), qdt)
        ref, got = (paged_attention(q, kp, vp, bt, jnp.asarray(lens),
                                    layer=layer, use_kernel=uk)
                    for uk in (False, True))
    else:
        q = jnp.asarray(rng.randn(r, t, heads, d), qdt)
        # the last of a lane's T queries sees ``lens`` positions
        pos = jnp.asarray(onp.maximum(lens - t, 0))
        ref, got = (paged_attention_multi(q, kp, vp, bt, pos, layer=layer,
                                          use_kernel=uk)
                    for uk in (False, True))
    assert got.shape == q.shape and got.dtype == ref.dtype
    tol = 3e-2 if dtype == "bfloat16" else 2e-5
    onp.testing.assert_allclose(onp.asarray(got, dtype=onp.float32),
                                onp.asarray(ref, dtype=onp.float32),
                                rtol=tol, atol=tol)


def test_paged_kernel_grid_takes_blocks_in_groups():
    """The mechanism of PR 30, held without a chip: at the serving
    cell's geometry (32 lanes x 64 blocks of 16, rows of 1,280, bf16
    pools under a float32 query) one grid step handles at least four of
    a lane's blocks, so the ``pallas_call`` has at most 32 * 64 / 4 grid
    steps. One block a step (73,728 steps a decode step at 0.57 us each,
    whatever the lanes held) was 88% of the cell's device time."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.pallas.paged_attention import paged_attention_kernel

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))

    r, h, d, bs, mb = 32, 20, 64, 16, 64
    pool = s((36, 1701, bs, h * d), "bfloat16")
    jaxpr = jax.make_jaxpr(
        lambda *a: paged_attention_kernel(*a, interpret=True))(
        s((r, h, d), "float32"), pool, pool, s((r, mb), "int32"),
        s((r,), "int32"), s((), "int32"))
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1, [e.primitive.name for e in jaxpr.jaxpr.eqns]
    grid = calls[0].params["grid_mapping"].grid
    assert int(onp.prod(grid)) <= r * mb // 4, grid


# ---------------------------------------------------------------------------
# paged vs dense decode
# ---------------------------------------------------------------------------
@pytest.mark.seed(31)
def test_paged_decode_token_identical_to_dense():
    """Greedy decode through the engine == offline generate() — prompt
    lengths chosen to hit partial blocks and block-boundary crossings."""
    net = _tiny_lm()
    with _engine(net) as eng:
        for p_len, n_new in ((4, 6), (5, 7), (3, 9), (8, 4)):
            prompt = onp.arange(1, p_len + 1, dtype=onp.int32) % 37
            ref = generate(net, prompt[None], max_new_tokens=n_new,
                           greedy=True).asnumpy()[0]
            got = eng.generate(prompt, n_new)
            onp.testing.assert_array_equal(got, ref)


@pytest.mark.seed(32)
def test_inflight_admission_token_parity():
    """Sequences admitted INTO a running decode batch still produce
    exactly the offline tokens (the in-flight batching acceptance)."""
    net = _tiny_lm(seed=1)
    rng = onp.random.RandomState(2)
    reqs = [(rng.randint(0, 37, (p,)).astype(onp.int32), n)
            for p, n in ((4, 12), (7, 10), (3, 14), (9, 8), (5, 12),
                         (6, 9))]
    refs = [generate(net, p[None], max_new_tokens=n, greedy=True)
            .asnumpy()[0] for p, n in reqs]
    with _engine(net, max_running=2) as eng:  # 2 lanes, 6 requests:
        # admissions necessarily land mid-decode of earlier sequences
        handles = []
        for i, (p, n) in enumerate(reqs):
            handles.append(eng.submit(p, n))
            if i == 1:
                time.sleep(0.02)  # let the first pair start decoding
        outs = [h.wait(timeout=120) for h in handles]
    for got, ref in zip(outs, refs):
        onp.testing.assert_array_equal(onp.asarray(got), ref)


@pytest.mark.seed(33)
def test_int8_kv_parity_bound():
    """int8-KV engine (the default config) tokens mostly agree with the
    fp32 path on a random tiny model (quantization may flip near-tie
    argmaxes — same bound as the dense int8 test)."""
    net = _tiny_lm(seed=3)
    prompt = onp.array([1, 5, 9, 2], onp.int32)
    ref = generate(net, prompt[None], max_new_tokens=8,
                   greedy=True).asnumpy()[0]
    with _engine(net, kv_cache_dtype="int8") as eng:
        got = onp.asarray(eng.generate(prompt, 8))
    assert got.shape == ref.shape
    assert (got == ref).mean() >= 0.6, (got, ref)


# ---------------------------------------------------------------------------
# pool / scheduler behavior
# ---------------------------------------------------------------------------
@pytest.mark.seed(34)
def test_block_freelist_reuse_under_churn():
    """Waves of requests through a small pool: blocks recycle, the free
    list returns to full, and every sequence is correct."""
    net = _tiny_lm(seed=4)
    with _engine(net, max_running=2, num_blocks=8) as eng:
        for wave in range(4):
            prompts = [onp.array([wave + 1, 2, 3], onp.int32),
                       onp.array([5, wave + 1], onp.int32)]
            handles = [eng.submit(p, 6) for p in prompts]
            outs = [h.wait(timeout=120) for h in handles]
            for p, o in zip(prompts, outs):
                ref = generate(net, p[None], max_new_tokens=6,
                               greedy=True).asnumpy()[0]
                onp.testing.assert_array_equal(onp.asarray(o), ref)
            assert eng.stats()["pool_blocks_free"] == 8
        c = eng.stats()["counters"]
        assert c["completed"] == 8 and c["failed"] == 0


@pytest.mark.seed(35)
def test_pool_exhaustion_sheds_typed():
    """A pool that can hold one sequence: concurrent requests beyond it
    shed with ServerOverload (a TransientError — the client retry loop
    contract), never deadlock, and the pool recovers."""
    from mxnet_tpu.base import TransientError

    net = _tiny_lm(seed=5)
    # 3 blocks of 4 = one (p=4 + n=8) sequence exactly
    with _engine(net, max_running=4, num_blocks=3) as eng:
        handles = [eng.submit(onp.array([1, 2, 3, 4], onp.int32), 8)
                   for _ in range(3)]
        done = shed = 0
        for h in handles:
            try:
                h.wait(timeout=120)
                done += 1
            except ServerOverload as e:
                assert isinstance(e, TransientError)
                shed += 1
        assert done >= 1 and done + shed == 3
        assert eng.stats()["pool_blocks_free"] == 3


@pytest.mark.seed(36)
def test_no_retrace_across_sequence_lengths():
    """The sentinel: ONE decode trace serves every mix of prompt
    lengths, generation lengths, admissions and retirements (jit cache
    size pinned), and the engine reports zero compiles during serving."""
    net = _tiny_lm(seed=6)
    with _engine(net) as eng:
        eng.warmup(prompt_lengths=[3, 5, 9])
        decode_jit = eng._decode.run._plain
        assert decode_jit is not None and decode_jit._cache_size() == 1
        compiles0 = eng.stats()["counters"]["compiles"]
        rng = onp.random.RandomState(7)
        handles = [eng.submit(rng.randint(0, 37, (p,)).astype(onp.int32), n)
                   for p, n in ((3, 5), (5, 9), (9, 12), (4, 7), (8, 3))]
        for h in handles:
            h.wait(timeout=120)
        assert decode_jit._cache_size() == 1  # no retrace, ever
        assert eng.stats()["counters"]["compiles"] == compiles0


@pytest.mark.seed(37)
def test_streaming_and_eos_retirement():
    net = _tiny_lm(seed=7)
    prompt = onp.array([1, 2], onp.int32)
    first = int(generate(net, prompt[None], max_new_tokens=1,
                         greedy=True).asnumpy()[0, 0])
    seen = []
    with _engine(net) as eng:
        out = onp.asarray(eng.submit(prompt, 6, on_token=seen.append)
                          .wait(timeout=120))
        # eos == the first greedy token -> retire after ONE token and
        # free the blocks immediately
        out_eos = onp.asarray(eng.submit(prompt, 6, eos_token=first)
                              .wait(timeout=120))
        assert eng.stats()["pool_blocks_free"] == \
            eng.stats()["pool_blocks_total"]
    assert seen == list(out)            # streamed == final, in order
    assert list(out_eos) == [first]


@pytest.mark.seed(41)
def test_raising_stream_callback_contained_to_its_request():
    """A client callback bug fails ITS request (typed FATAL) without
    touching other lanes or the engine."""
    from mxnet_tpu.base import FatalError

    net = _tiny_lm(seed=12)
    prompt = onp.array([1, 2, 3], onp.int32)
    ref = generate(net, prompt[None], max_new_tokens=6,
                   greedy=True).asnumpy()[0]

    def bad_cb(tok):
        raise RuntimeError("client bug")

    with _engine(net) as eng:
        h_bad = eng.submit(prompt, 6, on_token=bad_cb)
        h_ok = eng.submit(prompt, 6)
        with pytest.raises(FatalError):
            h_bad.wait(timeout=120)
        onp.testing.assert_array_equal(
            onp.asarray(h_ok.wait(timeout=120)), ref)
        st = eng.stats()
        assert st["pool_blocks_free"] == st["pool_blocks_total"]
        # the engine is NOT broken: serve again
        onp.testing.assert_array_equal(
            onp.asarray(eng.generate(prompt, 6)), ref)


def test_deadline_shed_typed():
    from mxnet_tpu.serving.admission import DeadlineExceeded

    net = _tiny_lm(seed=8)
    with _engine(net) as eng:
        # expired before the scheduler can prefill: shed, typed, no
        # compute spent
        h = eng.submit(onp.array([1, 2, 3], onp.int32), 4,
                       timeout_ms=0.0001)
        with pytest.raises(DeadlineExceeded):
            h.wait(timeout=60)


# ---------------------------------------------------------------------------
# faults: chaos site, classifier typing, flight dump
# ---------------------------------------------------------------------------
@pytest.mark.seed(38)
def test_chaos_prefill_fault_typed_and_contained():
    """A chaos fault on the prefill-splice path fails THAT request with
    the typed error; the engine keeps serving afterwards."""
    from mxnet_tpu.resilience import chaos

    net = _tiny_lm(seed=9)
    prompt = onp.array([1, 2, 3], onp.int32)
    with _engine(net) as eng:
        with chaos.scope("serving.llm", fail="transient", times=1):
            h = eng.submit(prompt, 4)
            with pytest.raises(chaos.ChaosTransient):
                h.wait(timeout=120)
        # engine recovered: full pool, next request serves
        ref = generate(net, prompt[None], max_new_tokens=4,
                       greedy=True).asnumpy()[0]
        onp.testing.assert_array_equal(
            onp.asarray(eng.generate(prompt, 4)), ref)
        st = eng.stats()
        assert st["pool_blocks_free"] == st["pool_blocks_total"]
        assert st["counters"]["resets"] == 1


@pytest.mark.seed(39)
def test_scheduler_fatal_typed_and_engine_stops():
    """A non-chaos scheduler bug classifies FATAL: in-flight requests
    fail with FatalError, later submits shed typed."""
    from mxnet_tpu.base import FatalError

    net = _tiny_lm(seed=10)
    eng = _engine(net)
    try:
        def boom(*a, **k):
            raise ValueError("scheduler bug")  # classifier: FATAL

        eng._decode.run = boom
        h = eng.submit(onp.array([1, 2, 3], onp.int32), 6)
        with pytest.raises(FatalError):
            h.wait(timeout=120)
        with pytest.raises(ServerOverload):
            eng.submit(onp.array([1], onp.int32), 2)
    finally:
        eng.close(drain=False)


_KILL_DRILL = """
import os
import numpy as onp
import mxnet_tpu as mx
from mxnet_tpu.gluon.model_zoo import bert
from mxnet_tpu.serving.llm import LLMEngine

onp.random.seed(0)
net = bert.gpt_like(vocab_size=37, units=16, hidden_size=32, num_layers=2,
                    num_heads=4, max_length=64, dropout=0.0)
net.initialize()
eng = LLMEngine(net, max_running=2, block_size=4, max_context=32,
                kv_cache_dtype="float32")
# 1st prefill survives and starts decoding; the 2nd admission fires the
# chaos kill MID-DECODE of lane 0
h1 = eng.submit(onp.array([1, 2, 3, 4], onp.int32), 24)
h2 = eng.submit(onp.array([5, 6], onp.int32), 24)
h1.wait(timeout=120)
h2.wait(timeout=120)
print("UNREACHABLE")
"""


def test_chaos_kill_mid_decode_leaves_flight_dump(tmp_path):
    """The ISSUE 7 drill: a chaos kill mid-decode must leave a
    parseable post-mortem whose metrics carry the lane/pool state."""
    flight = tmp_path / "flight"
    script = tmp_path / "drill.py"
    script.write_text(_KILL_DRILL)
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu",
               MXNET_TPU_FLIGHT_DIR=str(flight),
               MXNET_TPU_CHAOS="serving.llm=kill:2")
    r = subprocess.run([sys.executable, str(script)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 137, (r.returncode, r.stderr[-2000:])
    assert "UNREACHABLE" not in r.stdout
    latest = flight / "flight_latest.json"
    assert latest.exists(), "chaos kill must leave a post-mortem"
    payload = json.loads(latest.read_text())
    assert payload["reason"] == "chaos_kill:serving.llm"
    # lane/pool state rode along in the registry snapshot
    metrics = payload["metrics"]["metrics"]
    assert "llm_lanes_active" in metrics
    assert "llm_pool_blocks_free" in metrics
    assert "llm_events_total" in metrics
    free = metrics["llm_pool_blocks_free"]["series"][0]["value"]
    total = metrics["llm_pool_blocks_total"]["series"][0]["value"]
    assert 0 <= free < total    # lane 0 held blocks when the kill hit
    # decode spans made it into the ring tail
    span_names = {s.get("name") for s in payload["spans"]}
    assert any(n and n.startswith("step[llm_") for n in span_names)


# ---------------------------------------------------------------------------
# telemetry + AOT
# ---------------------------------------------------------------------------
@pytest.mark.seed(40)
def test_telemetry_gauges_and_step_spans():
    from mxnet_tpu import telemetry

    net = _tiny_lm(seed=11)
    with _engine(net) as eng:
        eng.generate(onp.array([1, 2, 3], onp.int32), 5)
        eid = eng.metrics.engine_id
        snap = telemetry.snapshot()
        by_name = snap["metrics"]
        assert "llm_lanes_active" in by_name
        assert "llm_pool_blocks_free" in by_name
        series = {tuple(sorted(s["labels"].items())): s
                  for s in by_name["llm_tokens_total"]["series"]}
        dec = series[(("engine", eid), ("phase", "decode"))]["value"]
        pre = series[(("engine", eid), ("phase", "prefill"))]["value"]
        assert pre == 1 and dec == 4       # 5 tokens = 1 prefill + 4 decode
        prom = telemetry.prometheus_text()
        assert "llm_tok_s" in prom and "llm_step_ms" in prom
        # decode/prefill steps are step-timeline spans with attribution
        # (what tools/trace_view.py consumes)
        events = telemetry.tracing.buffer().snapshot()
        steps = [e for e in events
                 if e.get("name") in ("step[llm_decode]", "step[llm_prefill]")
                 and e.get("cat") == "step"]
        assert steps, "llm steps must land in the shared trace ring"
        att = steps[-1]["args"]
        assert "device" in att and "wall_ms" in att
        assert att["device"] > 0


_AOT_DRILL = """
import os, sys, json
import numpy as onp
import mxnet_tpu as mx
from mxnet_tpu import aot
from mxnet_tpu.gluon.model_zoo import bert
from mxnet_tpu.serving.llm import LLMEngine

phase, store, manifest = sys.argv[1], sys.argv[2], sys.argv[3]
onp.random.seed(0)
net = bert.gpt_like(vocab_size=37, units=16, hidden_size=32, num_layers=2,
                    num_heads=4, max_length=64, dropout=0.0)
net.initialize()
eng = LLMEngine(net, max_running=2, block_size=4, max_context=32,
                kv_cache_dtype="float32")
if phase == "cold":
    eng.warmup(prompt_lengths=[3])
    eng.save_warmup_manifest(manifest)
else:
    eng.warmup(manifest=manifest)
out = eng.generate(onp.array([1, 2, 3], onp.int32), 4)
eng.close()
print(json.dumps({"aot": aot.stats(), "tokens": [int(t) for t in out]}))
"""


# ---------------------------------------------------------------------------
# ISSUE 11: speculative decoding
# ---------------------------------------------------------------------------
@pytest.mark.seed(50)
def test_spec_greedy_token_identical():
    """The spec-decode oracle: greedy decode through the draft-verify
    engine emits EXACTLY the plain paged engine's tokens (which are
    themselves pinned to offline generate()) — draft quality affects
    only the acceptance rate, never the output."""
    net = _tiny_lm(seed=20)
    draft = _tiny_draft(seed=21)
    with _engine(net, draft_model=draft, draft_k=3) as eng:
        for p_len, n_new in ((4, 6), (5, 7), (3, 9), (8, 4), (1, 11)):
            prompt = onp.arange(1, p_len + 1, dtype=onp.int32) % 37
            ref = generate(net, prompt[None], max_new_tokens=n_new,
                           greedy=True).asnumpy()[0]
            got = eng.generate(prompt, n_new)
            onp.testing.assert_array_equal(onp.asarray(got), ref)
        st = eng.stats()
        spec = st["speculative"]
        assert spec["proposed"] > 0
        assert 0.0 <= spec["draft_acceptance_rate"] <= 1.0
        assert st["counters"]["spec_steps"] > 0
        # all blocks home after retirement (spec slack included)
        assert st["pool_blocks_free"] == st["pool_blocks_total"]


@pytest.mark.seed(51)
def test_spec_inflight_admission_token_parity():
    """Spec decode + continuous batching: sequences admitted INTO a
    running draft-verify batch still emit exactly the offline tokens."""
    net = _tiny_lm(seed=22)
    draft = _tiny_draft(seed=23)
    rng = onp.random.RandomState(24)
    reqs = [(rng.randint(0, 37, (p,)).astype(onp.int32), n)
            for p, n in ((4, 10), (7, 8), (3, 12), (5, 9))]
    refs = [generate(net, p[None], max_new_tokens=n, greedy=True)
            .asnumpy()[0] for p, n in reqs]
    with _engine(net, max_running=2, draft_model=draft, draft_k=4) as eng:
        handles = []
        for i, (p, n) in enumerate(reqs):
            handles.append(eng.submit(p, n))
            if i == 1:
                time.sleep(0.02)
        outs = [h.wait(timeout=120) for h in handles]
    for got, ref in zip(outs, refs):
        onp.testing.assert_array_equal(onp.asarray(got), ref)


def test_spec_rejection_sampling_distribution():
    """Exact rejection sampling: over many seeds at fixed logits, the
    marginal of the FIRST emitted token from _spec_accept must match
    the target policy's distribution (the Leviathan guarantee), even
    though the draft proposes from a very different distribution."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.gluon.model_zoo.generation import (_policy_probs,
                                                      _spec_accept)

    rng = onp.random.RandomState(3)
    v, k = 8, 2
    t_logits = jnp.asarray(rng.randn(1, k + 1, v) * 1.5, jnp.float32)
    d_logits = jnp.asarray(rng.randn(1, k, v) * 1.5, jnp.float32)
    p_target = onp.asarray(
        _policy_probs(t_logits, False, 1.0, 0))[0, 0]      # (V,)
    q_draft = _policy_probs(d_logits, False, 1.0, 0)

    n = 4000
    counts = onp.zeros(v)

    @jax.jit
    def one(key):
        kd, kv_ = jax.random.split(key)
        # the draft proposes from ITS policy (as the draft program does)
        d0 = jax.random.categorical(kd, jnp.log(q_draft[0, 0]))
        d1 = jax.random.categorical(kd, jnp.log(q_draft[0, 1]))
        toks = jnp.stack([d0, d1]).astype(jnp.int32)[None]
        out, n_acc = _spec_accept(t_logits, d_logits, toks, kv_,
                                  False, 1.0, 0)
        return out[0, 0], n_acc[0]

    for i in range(n):
        tok, _ = one(jax.random.PRNGKey(i))
        counts[int(tok)] += 1
    emp = counts / n
    # 4k samples: the empirical marginal tracks the target within a few
    # standard errors per bucket (~3.5 sigma; sigma <= 0.5/sqrt(n))
    assert onp.abs(emp - p_target).max() < 0.03, (emp, p_target)


@pytest.mark.seed(52)
def test_spec_sampled_engine_serves_and_records_acceptance():
    """A temperature-sampling spec engine must serve correctly-shaped
    output (distribution-exactness is pinned by the unit test above)
    and record its acceptance telemetry."""
    net = _tiny_lm(seed=25)
    draft = _tiny_draft(seed=26)
    with _engine(net, draft_model=draft, draft_k=3, greedy=False,
                 temperature=1.0, seed=7) as eng:
        out = onp.asarray(eng.generate(onp.array([1, 2, 3], onp.int32), 8))
        assert out.shape[0] <= 8 and out.dtype == onp.int32
        assert (0 <= out).all() and (out < 37).all()
        assert eng.stats()["speculative"]["proposed"] > 0


@pytest.mark.seed(53)
def test_chaos_draft_verify_fault_typed_and_contained():
    """ISSUE 11 satellite: a chaos fault on the draft-verify splice
    fails the in-flight request typed-transient; the engine keeps
    serving (pool rebuilt, next request exact)."""
    from mxnet_tpu.base import TransientError
    from mxnet_tpu.resilience import chaos

    net = _tiny_lm(seed=27)
    draft = _tiny_draft(seed=28)
    prompt = onp.array([1, 2, 3], onp.int32)
    with _engine(net, draft_model=draft, draft_k=3) as eng:
        with chaos.scope("serving.llm.verify", fail="transient", times=1):
            h = eng.submit(prompt, 6)
            with pytest.raises(chaos.ChaosTransient) as ei:
                h.wait(timeout=120)
            assert isinstance(ei.value, TransientError)
        ref = generate(net, prompt[None], max_new_tokens=6,
                       greedy=True).asnumpy()[0]
        onp.testing.assert_array_equal(
            onp.asarray(eng.generate(prompt, 6)), ref)
        st = eng.stats()
        assert st["pool_blocks_free"] == st["pool_blocks_total"]
        assert st["counters"]["resets"] == 1


# ---------------------------------------------------------------------------
# ISSUE 11: shared-prefix block caching (COW block tables, refcounts)
# ---------------------------------------------------------------------------
@pytest.mark.seed(54)
def test_prefix_cache_hits_and_token_parity():
    """Shared-system-prompt requests reuse resident prefix blocks (hit
    rate > 0, fewer blocks recomputed) and stay token-identical to
    offline generate() for every divergent suffix."""
    net = _tiny_lm(seed=30)
    shared = (onp.arange(1, 13, dtype=onp.int32) * 3) % 37  # 3 full blocks
    tails = ([5, 1], [9, 2, 4], [7], [2, 8, 6, 3])
    with _engine(net, prefix_cache=True, num_blocks=24) as eng:
        for tail in tails:
            prompt = onp.concatenate([shared,
                                      onp.array(tail, onp.int32)])
            ref = generate(net, prompt[None], max_new_tokens=6,
                           greedy=True).asnumpy()[0]
            got = eng.generate(prompt, 6)
            onp.testing.assert_array_equal(onp.asarray(got), ref)
        st = eng.stats()["prefix_cache"]
        assert st["cached_blocks"] >= 3
        assert st["hit_requests"] == len(tails) - 1   # all but the first
        assert st["prefix_hit_rate"] > 0.4


@pytest.mark.seed(55)
def test_prefix_cow_refcounts_under_churn():
    """The COW acceptance: two lanes share prefix blocks concurrently;
    one finishing must NOT free blocks the other still reads (refcount
    > 0), divergent suffixes never alias (outputs exact), and after
    everything retires only cache-resident blocks stay off the free
    list."""
    net = _tiny_lm(seed=31)
    shared = (onp.arange(1, 9, dtype=onp.int32) * 5) % 37   # 2 full blocks
    with _engine(net, max_running=2, prefix_cache=True,
                 num_blocks=20) as eng:
        pa = onp.concatenate([shared, onp.array([3, 1], onp.int32)])
        pb = onp.concatenate([shared, onp.array([9, 4, 2], onp.int32)])
        # a finishes several tokens before b: its shared blocks are
        # decref'd while b's lane still attends through them
        ref_a = generate(net, pa[None], max_new_tokens=2,
                         greedy=True).asnumpy()[0]
        ref_b = generate(net, pb[None], max_new_tokens=14,
                         greedy=True).asnumpy()[0]
        # prime the cache so BOTH requests share resident blocks
        eng.generate(onp.concatenate([shared,
                                      onp.array([6], onp.int32)]), 2)
        ha = eng.submit(pa, 2)
        hb = eng.submit(pb, 14)
        onp.testing.assert_array_equal(onp.asarray(ha.wait(timeout=120)),
                                       ref_a)
        onp.testing.assert_array_equal(onp.asarray(hb.wait(timeout=120)),
                                       ref_b)
        st = eng.stats()
        pc = st["prefix_cache"]
        assert pc["hit_requests"] >= 2
        # free + cache-resident accounts for the whole pool: nothing
        # leaked, nothing double-freed
        assert st["pool_blocks_free"] + pc["cached_blocks"] == \
            st["pool_blocks_total"]
        # waves of churn: recycled blocks keep every sequence exact
        for wave in range(3):
            tail = onp.array([wave + 1, 11 - wave], onp.int32)
            prompt = onp.concatenate([shared, tail])
            ref = generate(net, prompt[None], max_new_tokens=5,
                           greedy=True).asnumpy()[0]
            onp.testing.assert_array_equal(
                onp.asarray(eng.generate(prompt, 5)), ref)


@pytest.mark.seed(56)
def test_prefix_cache_eviction_under_pool_pressure():
    """Cache-only residents are evicted LRU when an admission needs
    their blocks; live (lane-referenced) blocks never are."""
    net = _tiny_lm(seed=32)
    # pool of 4 blocks of 4: a (p=8 + n=4 -> 3 blocks) sequence leaves
    # 2 cached + 2 free, so the next 3-block reservation MUST evict
    with _engine(net, max_running=1, prefix_cache=True,
                 num_blocks=4) as eng:
        a = (onp.arange(1, 9, dtype=onp.int32) * 7) % 37
        eng.generate(a, 4)                       # caches 2 blocks of a
        st = eng.stats()
        assert st["prefix_cache"]["cached_blocks"] == 2
        assert st["pool_blocks_free"] == 2
        b = (onp.arange(1, 9, dtype=onp.int32) * 11) % 37
        ref = generate(net, b[None], max_new_tokens=4,
                       greedy=True).asnumpy()[0]
        got = eng.generate(b, 4)                 # evicts a's LRU block
        onp.testing.assert_array_equal(onp.asarray(got), ref)
        st = eng.stats()
        # 1 surviving block of a + 2 of b cached; accounting exact
        assert st["prefix_cache"]["cached_blocks"] == 3
        assert st["pool_blocks_free"] + \
            st["prefix_cache"]["cached_blocks"] == st["pool_blocks_total"]


@pytest.mark.seed(61)
def test_prefix_readmission_under_pressure_pins_hits():
    """Regression: re-admitting a prompt whose OWN hit blocks are the
    LRU eviction candidates must pin them first — eviction re-issuing a
    block this admission is about to share aliased live data and killed
    the scheduler (orphaning the request). The tightest pool that can
    serve the request at all must keep serving it forever."""
    net = _tiny_lm(seed=40)
    with _engine(net, max_running=1, prefix_cache=True,
                 num_blocks=4) as eng:
        prompt = (onp.arange(1, 13, dtype=onp.int32) * 7) % 37  # 3 blocks
        ref = generate(net, prompt[None], max_new_tokens=4,
                       greedy=True).asnumpy()[0]
        for _ in range(3):      # hit path + eviction pressure each time
            got = eng.generate(prompt, 4)
            onp.testing.assert_array_equal(onp.asarray(got), ref)
        st = eng.stats()
        assert st["counters"]["failed"] == 0
        assert st["pool_blocks_free"] + \
            st["prefix_cache"]["cached_blocks"] == st["pool_blocks_total"]


@pytest.mark.seed(57)
def test_spec_plus_prefix_combined_token_identity():
    """Both tentpole features at once: shared-prefix admission feeding
    the draft-verify decode loop stays token-identical."""
    net = _tiny_lm(seed=33)
    draft = _tiny_draft(seed=34)
    shared = (onp.arange(1, 13, dtype=onp.int32) * 2) % 37
    with _engine(net, draft_model=draft, draft_k=3, prefix_cache=True,
                 num_blocks=32) as eng:
        for tail in ([5, 1], [9, 2, 4], [7]):
            prompt = onp.concatenate([shared,
                                      onp.array(tail, onp.int32)])
            ref = generate(net, prompt[None], max_new_tokens=6,
                           greedy=True).asnumpy()[0]
            onp.testing.assert_array_equal(
                onp.asarray(eng.generate(prompt, 6)), ref)
        st = eng.stats()
        assert st["prefix_cache"]["prefix_hit_rate"] > 0
        assert st["speculative"]["proposed"] > 0


def test_kv_quantizer_layout_is_values_then_the_scale_bytes():
    """``kv_cache_quantize`` builds the scale's four bytes with integer
    ops (what Mosaic accepts inside the kernels): they must be the f32's
    own little-endian bytes, zero rows and wide scales included, and
    ``kv_cache_dequantize`` must read them back."""
    import jax.numpy as jnp

    from mxnet_tpu.ops.nn import kv_cache_dequantize, kv_cache_quantize

    rng = onp.random.RandomState(3)
    t = (rng.randn(37, 64) * rng.lognormal(0, 3, (37, 1))).astype("float32")
    t[3] = 0.0
    scale = onp.maximum(onp.abs(t).max(-1, keepdims=True),
                        onp.float32(1e-6)) / onp.float32(127.0)
    want = onp.concatenate(
        [onp.clip(onp.round(t / scale), -127, 127).astype(onp.int8),
         scale.astype("<f4").view(onp.int8)], axis=-1)
    got = onp.asarray(kv_cache_quantize(jnp.asarray(t)))
    onp.testing.assert_array_equal(got, want)
    back = onp.asarray(kv_cache_dequantize(jnp.asarray(got), jnp.float32))
    onp.testing.assert_array_equal(back, want[:, :64] * scale)


@pytest.mark.seed(60)
def test_spec_prefix_telemetry_gauges():
    """ISSUE 11 satellite: llm_draft_acceptance_rate and
    llm_prefix_hit_rate ride the registry — visible in snapshots and
    Prometheus text (the flight recorder dumps the same snapshot)."""
    from mxnet_tpu import telemetry

    net = _tiny_lm(seed=37)
    draft = _tiny_draft(seed=38)
    shared = (onp.arange(1, 9, dtype=onp.int32) * 3) % 37
    with _engine(net, draft_model=draft, draft_k=3, prefix_cache=True,
                 num_blocks=32) as eng:
        for tail in ([1, 2], [4, 5]):
            eng.generate(onp.concatenate([shared,
                                          onp.array(tail, onp.int32)]), 5)
        eid = eng.metrics.engine_id
        snap = telemetry.snapshot()["metrics"]
        for name in ("llm_draft_acceptance_rate", "llm_prefix_hit_rate",
                     "llm_spec_tokens_total", "llm_prefix_tokens_total"):
            assert name in snap, name
        series = {tuple(sorted(s["labels"].items())): s["value"]
                  for s in snap["llm_prefix_tokens_total"]["series"]}
        assert series[(("engine", eid), ("result", "hit"))] > 0
        prom = telemetry.prometheus_text()
        assert "llm_draft_acceptance_rate" in prom
        assert "llm_prefix_hit_rate" in prom


def test_aot_warm_start_zero_miss(tmp_path):
    """The replica scale-up drill: a fresh process warming from the
    manifest against the persistent store records ZERO cold compiles
    for the decode-frontier programs — and generates the same tokens."""
    store = tmp_path / "store"
    manifest = tmp_path / "llm_manifest.json"
    script = tmp_path / "drill.py"
    script.write_text(_AOT_DRILL)
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu",
               MXNET_TPU_AOT_CACHE=str(store))
    env.pop("MXNET_TPU_CHAOS", None)

    def run(phase):
        r = subprocess.run(
            [sys.executable, str(script), phase, str(store), str(manifest)],
            env=env, capture_output=True, text=True, timeout=600)
        assert r.returncode == 0, r.stderr[-3000:]
        return json.loads(r.stdout.strip().splitlines()[-1])

    cold = run("cold")
    assert cold["aot"]["aot_puts"] > 0, cold
    warm = run("warm")
    assert warm["aot"]["aot_misses"] == 0, warm
    assert warm["aot"]["aot_hits"] > 0, warm
    assert warm["tokens"] == cold["tokens"]
    # the manifest carries store keys for model-free replay
    entries = json.loads(manifest.read_text())["entries"]
    labels = {e["label"] for e in entries}
    assert {"llm.prefill", "llm.decode"} <= labels
    assert all(e.get("key") for e in entries)


# ---------------------------------------------------------------------------
# ISSUE 12 satellites: end-to-end deadlines, cancellation, close-with-queued
# ---------------------------------------------------------------------------
def test_deadline_retires_expired_lane_mid_decode():
    """A request whose deadline passes *inside* the running decode
    window is retired (blocks freed, lane reused) instead of streamed
    to a client that already gave up — and the typed error carries
    elapsed vs budget."""
    from mxnet_tpu.resilience import chaos
    from mxnet_tpu.serving.admission import DeadlineExceeded

    net = _tiny_lm()
    eng = _engine(net, step_hook=lambda: chaos.site("test.llm.tick"))
    try:
        eng.warmup(prompt_lengths=[4])
        # ~60 ms per scheduler tick: 25 tokens needs ~1.5 s, far past
        # the 400 ms budget — but admission + prefill fit inside it
        with chaos.scope("test.llm.tick", delay=0.06):
            h = eng.submit([1, 2, 3, 4], 25, timeout_ms=400)
            with pytest.raises(DeadlineExceeded) as ei:
                h.wait(timeout=120)
        e = ei.value
        assert e.budget_s is not None and abs(e.budget_s - 0.4) < 0.01
        assert e.elapsed_s is not None and e.elapsed_s >= e.budget_s
        assert "mid-decode" in str(e)
        assert 0 < len(h.tokens) < 25          # partial work, retired
        assert eng.metrics.counters()["retired_deadline"] == 1
        # the lane and its blocks came back: the engine keeps serving
        assert len(eng.generate([5, 6], 3, timeout_ms=None)) == 3
        assert eng._kv.free_blocks == eng.num_blocks
    finally:
        eng.close()


def test_cancel_retires_lane_and_frees_blocks():
    from mxnet_tpu.resilience import chaos
    from mxnet_tpu.serving.admission import RequestCancelled

    net = _tiny_lm()
    eng = _engine(net, step_hook=lambda: chaos.site("test.llm.tick2"))
    try:
        eng.warmup(prompt_lengths=[4])
        with chaos.scope("test.llm.tick2", delay=0.05):
            h = eng.submit([1, 2, 3, 4], 25, timeout_ms=None)
            time.sleep(0.3)                    # provably mid-decode
            h.cancel()
            with pytest.raises(RequestCancelled):
                h.wait(timeout=120)
        assert eng.metrics.counters()["cancelled"] == 1
        assert eng._kv.free_blocks == eng.num_blocks
        assert len(eng.generate([5, 6], 3, timeout_ms=None)) == 3
    finally:
        eng.close()


def test_close_with_queued_requests_fails_typed_not_hangs():
    """ISSUE 12 satellite: ``close()`` with requests still sitting in
    the admission queue must fail them typed — a queued ``wait()``
    must never hang, whether the close drains, the scheduler is
    wedged past the close timeout, or drain is refused."""
    from mxnet_tpu.resilience import chaos

    # (1) drain=False: queued requests fail typed immediately
    net = _tiny_lm()
    eng = _engine(net, step_hook=lambda: chaos.site("test.llm.wedge"))
    eng.warmup(prompt_lengths=[4])
    with chaos.scope("test.llm.wedge", delay=2.0, times=1):
        time.sleep(0.1)                  # the scheduler enters the wedge
        hs = [eng.submit([1, 2, 3], 4) for _ in range(3)]
        eng.close(drain=False, timeout_s=0.2)
    for h in hs:
        with pytest.raises(ServerOverload):
            h.wait(timeout=10)

    # (2) drain=True with the scheduler wedged past the close budget:
    # whatever is still queued fails typed instead of hanging
    eng2 = _engine(net, step_hook=lambda: chaos.site("test.llm.wedge2"))
    eng2.warmup(prompt_lengths=[4])
    with chaos.scope("test.llm.wedge2", delay=3.0, times=1):
        time.sleep(0.1)
        hs2 = [eng2.submit([1, 2, 3], 4) for _ in range(3)]
        t0 = time.monotonic()
        eng2.close(drain=True, timeout_s=0.3)
        assert time.monotonic() - t0 < 2.0
        for h in hs2:
            with pytest.raises(ServerOverload):
                h.wait(timeout=10)
