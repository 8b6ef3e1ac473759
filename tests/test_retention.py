"""Power retention on the serving path, at toy sizes on the CPU: the three
forms of the layer agree, the two kernels agree with their ``jax.numpy``
twins (interpret mode), and ``serving.LLMEngine`` serves a model whose
cache is a state through the one allocator — a slot a request, zero when
it starts, prefill in chunks — and refuses what cannot carry a state yet.
(The comparison with the plain reference, and the mutants it must catch,
are in ``tests/chipbench_tests/test_brumby.py``.)
"""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu.ops import retention as R

HQ, HK, D = 4, 2, 8


def _inputs(rng, t, hq=HQ, hk=HK, d=D):
    f = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)   # noqa: E731
    lg = jax.nn.log_sigmoid(f(t, hk) * 1.4 + 3.0)
    return f(t, hq, d), f(t, hk, d), f(t, hk, d), lg


def _pools(rng, layers, slots, hk=HK, d=D):
    dp = R.phi_size(d)
    return (jnp.asarray(rng.randn(layers, slots, hk, d, dp), jnp.float32),
            jnp.asarray(rng.randn(layers, slots, hk, dp), jnp.float32))


def _quadratic(q, k, v, lg):
    """The layer's definition, every token against every earlier one."""
    t, hq = q.shape[:2]
    big_g = jnp.cumsum(lg, 0)
    out = []
    for i in range(hq):
        j = i // (hq // k.shape[1])
        a = (q[:, i] @ k[:, j].T) ** 2 * jnp.exp(
            big_g[:, j][:, None] - big_g[:, j][None, :])
        a = jnp.where(jnp.tril(jnp.ones((t, t), bool)), a, 0.0)
        out.append(a @ v[:, j] / (a.sum(-1, keepdims=True) + R.EPS))
    return jnp.stack(out, 1)


def _in_chunks(q, k, v, lg, pools, c, slot=1, layer=0, fill=7.0):
    """The chunked form over a prompt, its last chunk padded with
    ``fill`` (which must change nothing)."""
    t, out = q.shape[0], []
    for start in range(0, t, c):
        n = min(c, t - start)

        def pad(x):
            return jnp.concatenate([
                x[start:start + n],
                jnp.full((c - n,) + x.shape[1:], fill, x.dtype)])

        o, *pools = R.retention_chunk_jnp(
            pad(q), pad(k), pad(v), pad(lg), *pools, slot, layer,
            start == 0, n)
        out.append(o[:n])
    return jnp.concatenate(out), pools


def test_phi_is_the_symmetric_square():
    rng = onp.random.RandomState(0)
    for d in (8, 128):
        u, w = (jnp.asarray(rng.randn(d), jnp.float32) for _ in range(2))
        a, b, wt = R.phi_layout(d)
        assert len(a) == R.phi_size(d) == d * (d // 2 + 1)
        pairs = {(min(x, y), max(x, y)) for x, y, z in zip(a, b, wt) if z}
        assert len(pairs) == (wt != 0).sum() == d * (d + 1) // 2
        assert float(R.phi(u) @ R.phi(w)) == pytest.approx(
            float(u @ w) ** 2, rel=1e-5)


@pytest.mark.parametrize("chunk", [5, 8, 23, 32])
def test_recurrent_chunked_and_quadratic_forms_agree(chunk):
    """23 tokens: chunks of 5 and 8 do not divide them, 23 does, 32 is one
    padded chunk. The slot starts with garbage in it and a fresh request
    must not see it."""
    rng = onp.random.RandomState(1)
    q, k, v, lg = _inputs(rng, 23)
    want = _quadratic(q, k, v, lg)
    pools = _pools(rng, 1, 3)
    ps, pz = pools[0].at[0, 1].set(0.0), pools[1].at[0, 1].set(0.0)
    steps = []
    for t in range(23):
        o, ps, pz = R.retention_step_jnp(
            q[t][None], k[t][None], v[t][None], lg[t][None], ps, pz,
            jnp.array([1]), 0)
        steps.append(o[0])
    # float32 sums in another order; a near-empty normaliser divides them
    assert jnp.abs(jnp.stack(steps) - want).max() < 5e-4
    got, (cs, cz) = _in_chunks(q, k, v, lg, pools, chunk)
    assert jnp.abs(got - want).max() < 5e-4
    # the chunks leave the state the recurrence leaves, in their own slot
    assert jnp.abs(cs[0, 1] - ps[0, 1]).max() < 1e-5 * jnp.abs(ps[0, 1]).max()
    assert jnp.abs(cz[0, 1] - pz[0, 1]).max() < 1e-5 * jnp.abs(pz[0, 1]).max()
    assert (cs[0, 0] == pools[0][0, 0]).all() and \
        (cs[0, 2] == pools[0][0, 2]).all()


def test_padding_rows_change_nothing():
    rng = onp.random.RandomState(2)
    q, k, v, lg = _inputs(rng, 11)
    pools = _pools(rng, 1, 2)
    a, (sa, za) = _in_chunks(q, k, v, lg, pools, 16, fill=7.0)
    b, (sb, zb) = _in_chunks(q, k, v, lg, pools, 16, fill=-3.0)
    exact, _ = _in_chunks(q, k, v, lg, pools, 11)
    assert (a == b).all() and (sa == sb).all() and (za == zb).all()
    assert jnp.abs(a - exact).max() < 1e-5


# --- the kernels against their twins (interpret mode, heads of 128) --------
def test_step_kernel_matches_its_twin():
    from mxnet_tpu.ops.pallas.power_retention import power_retention_step

    rng = onp.random.RandomState(3)
    q, k, v, lg = _inputs(rng, 3, 4, 2, 128)
    ps, pz = _pools(rng, 2, 3, 2, 128)
    pz = jnp.abs(pz) * 50.0
    slots = jnp.array([2, 0, 1], jnp.int32)
    want = R.retention_step_jnp(q, k, v, lg, ps, pz, slots, 1)
    got = power_retention_step(q, k, v, lg, ps, pz, slots, jnp.int32(1),
                               interpret=True)
    for w, g in zip(want, got):
        assert jnp.abs(w - g).max() < 1e-3 * max(1.0, float(jnp.abs(w).max()))
    assert (got[1][0] == ps[0]).all()        # the other layer is untouched


@pytest.mark.parametrize("fresh, n_real", [(True, 11), (False, 16)])
def test_chunk_kernel_matches_its_twin(fresh, n_real):
    from mxnet_tpu.ops.pallas.power_retention import power_retention_chunk

    rng = onp.random.RandomState(4)
    q, k, v, lg = _inputs(rng, 16, 4, 2, 128)
    ps, pz = _pools(rng, 1, 2, 2, 128)
    if not fresh:       # a state a request could have left: from a chunk
        _, ps, pz = R.retention_chunk_jnp(
            *_inputs(rng, 16, 4, 2, 128), ps, pz, 1, 0, True, 16)
    want = R.retention_chunk_jnp(q, k, v, lg, ps, pz, 1, 0, fresh, n_real)
    got = power_retention_chunk(q, k, v, lg, ps, pz, 1, 0, fresh, n_real,
                                tq=8, mxu_dtype=jnp.float32, interpret=True)
    assert jnp.abs(want[0] - got[0])[:n_real].max() < 1e-4
    for w, g in zip(want[1:], got[1:]):
        assert jnp.abs(w - g).max() < 1e-5 * float(jnp.abs(w).max())


# --- the engine ------------------------------------------------------------
SIZES = dict(vocab_size=61, units=64, hidden_size=96, num_layers=2,
             num_heads=HQ, num_kv_heads=HK, head_dim=D, max_length=256,
             prefill_chunk=16)


@pytest.fixture(scope="module")
def net():
    from mxnet_tpu.gluon.model_zoo import brumby

    model = brumby.brumby_like(**SIZES)
    model.initialize()
    return model


def _engine(model, **kw):
    from mxnet_tpu.serving import LLMEngine

    kw.setdefault("max_running", 3)
    kw.setdefault("max_context", 128)
    kw.setdefault("kv_cache_dtype", "float32")
    return LLMEngine(model, **kw)


def _prompts():
    rng = onp.random.RandomState(5)
    return [rng.randint(0, 61, (n,)).astype(onp.int32) for n in (37, 16, 5)]


def test_engine_serves_through_the_one_allocator(net):
    """A request of any length reserves one slot; the same tokens as the
    model's own forward over prompt + answer; the new span and counters."""
    import mxnet_tpu.numpy as mxnp
    from mxnet_tpu.telemetry import tracing

    eng = _engine(net)
    assert eng.max_blocks_per_seq == 1 and eng.num_blocks == 3
    try:
        eng.warmup()
        handles = [eng.submit(p, n) for p, n in zip(_prompts(), (9, 12, 7))]
        outs = [h.wait() for h in handles]
        stats = eng.stats()
        pool_bytes = int(eng.metrics.shard_pool_bytes.get())
    finally:
        eng.close()
    for p, o in zip(_prompts(), outs):
        seq = onp.concatenate([p, o]).astype(onp.int32)
        logits = onp.asarray(net(mxnp.array(seq[None]))._data)[0]
        assert (logits.argmax(-1)[len(p) - 1:len(seq) - 1] == o).all()
    c = stats["counters"]
    assert c["prefill_chunks"] == 3 + 1 + 1   # prompts of 37, 16 and 5
    assert c["compiles"] == 2
    # a slot is a block of the one allocator: the gauges every engine has
    assert stats["pool_blocks_total"] == stats["pool_blocks_free"] == 3
    dp = R.phi_size(D)
    assert pool_bytes == 2 * 4 * HK * (D * dp + dp) * 4
    chunks = tracing.rows(0.0, float("inf"), "llm.prefill.chunk")
    mine = [r[3] for r in chunks][-5:]
    assert [(a["start"], a["tokens"], a["pad"]) for a in mine] == [
        (0, 16, 0), (16, 16, 0), (32, 5, 11), (0, 16, 0), (0, 5, 11)]
    assert "parent" in mine[0]                         # under llm.prefill


def test_stats_reads_no_live_pool(net):
    """``stats()`` is called from a caller's thread while the scheduler's
    thread has the pools donated to the program that runs: the pools'
    bytes are the gauge set at construction, never the arrays (a deleted
    array raised on the chip, in one run of ten of the cell)."""
    eng = _engine(net)
    try:
        want = eng._kv.bytes_per_device()
        with eng._state_lock:
            pools = eng._kv.pools
            eng._kv.pools = None                # as good as deleted
            try:
                assert eng.stats()["pool_blocks_total"] == 3
                assert int(eng.metrics.shard_pool_bytes.get()) == want
            finally:
                eng._kv.pools = pools
    finally:
        eng.close()


def _snapshot_mid_flight(eng, prompt, new):
    """Submit, then take the request's cache from ``step_hook``'s place
    in a tick once it has decoded a few steps."""
    got = []

    def hook():
        if not got and len(req.tokens) >= 4:
            got.append(eng.snapshot_cache(req))

    req = eng.submit(prompt, new)
    eng._step_hook = hook
    out = req.wait()
    return got[0], out


def test_snapshot_cache_is_the_state_of_the_tokens_fed(net):
    """``snapshot_cache`` of a request in flight: the positions absorbed,
    the tokens so far and its one slot — the state that the recurrence
    over exactly those tokens leaves (``state_readings`` of both agree)."""
    import mxnet_tpu.numpy as mxnp

    prompt = _prompts()[0]
    eng = _engine(net)
    try:
        (pos, emitted, s, z), out = _snapshot_mid_flight(eng, prompt, 12)
    finally:
        eng.close()
    assert pos == len(prompt) + len(emitted) - 1
    assert (out[:len(emitted)] == emitted).all()
    assert s.shape[:2] == (2, 1) and z.shape[:2] == (2, 1)
    fed = onp.concatenate([prompt, emitted])[:pos].astype(onp.int32)
    ps, pz = net.init_block_pool(2, 0)
    i32 = lambda x: mxnp.array(onp.asarray(x, onp.int32))   # noqa: E731
    for t, tok in enumerate(fed):       # one token at a time: no chunks
        _, ps, pz = net.decode_step_paged(
            i32([[tok]]), ps, pz, i32([[1]]), i32([t]))
    u = jnp.asarray(onp.random.RandomState(3).randn(5, D), jnp.float32)
    want = R.state_readings(ps._data[:, 1], pz._data[:, 1], u)
    got = R.state_readings(s[:, 0], z[:, 0], u)
    for w, g in zip(want, got):
        assert jnp.abs(w - g).max() < 1e-4 * float(jnp.abs(w).max())


def test_snapshot_cache_of_the_block_path():
    """The same call on a model that keeps keys and values: the lane's
    blocks of rows, ``ceil(positions / block_size)`` of them at least."""
    from mxnet_tpu.gluon.model_zoo import bert
    from mxnet_tpu.serving import LLMEngine

    lm = bert.gpt_like(vocab_size=37, units=16, hidden_size=32, num_layers=1,
                       num_heads=4, max_length=64)
    lm.initialize()
    eng = LLMEngine(lm, max_running=2, block_size=4, max_context=48)
    try:
        (pos, emitted, k, v), _ = _snapshot_mid_flight(
            eng, onp.arange(9, dtype=onp.int32), 10)
        done = eng.submit(onp.arange(3, dtype=onp.int32), 2)
        done.wait()
        assert eng.snapshot_cache(done) is None     # no lane carries it
    finally:
        eng.close()
    assert pos == 9 + len(emitted) - 1
    assert k.shape == v.shape and k.shape[0] == 1
    assert k.shape[1] >= -(-pos // 4)


def test_state_readings_are_the_quadratic_sums():
    """``S phi(u)`` and ``z . phi(u)`` of a state built by the recurrence
    are the decayed sums of ``(u . k)^2 v`` and ``(u . k)^2``: the numbers
    a check compares with a form that has no phi."""
    rng = onp.random.RandomState(2)
    q, k, v, lg = _inputs(rng, 23)
    pools = (jnp.zeros((1, 2, HK, D, R.phi_size(D))),
             jnp.zeros((1, 2, HK, R.phi_size(D))))
    for t in range(23):
        _, *pools = R.retention_step_jnp(
            q[t][None], k[t][None], v[t][None], lg[t][None], *pools,
            jnp.asarray([1]), 0)
    u = jnp.asarray(rng.randn(3, D), jnp.float32)
    num, den = R.state_readings(pools[0][0, 1], pools[1][0, 1], u)
    big_g = jnp.cumsum(lg, 0)
    a = jnp.einsum("rd,sjd->jrs", u, k) ** 2 \
        * jnp.exp(big_g[-1][None] - big_g).T[:, None, :]
    assert jnp.abs(num - jnp.einsum("jrs,sjv->jrv", a, v)).max() < 1e-3
    assert jnp.abs(den - a.sum(-1)).max() < 1e-3


def test_a_reused_slot_starts_from_zero(net):
    """One slot, three requests one after another: each is answered as a
    fresh engine answers it, whatever the slot held before."""
    prompts = _prompts()
    eng = _engine(net, max_running=1)
    try:
        one = [eng.generate(p, 6) for p in prompts + prompts[:1]]
    finally:
        eng.close()
    for p, got in zip(prompts, one):
        fresh = _engine(net, max_running=1)
        try:
            assert (fresh.generate(p, 6) == got).all()
        finally:
            fresh.close()
    assert (one[0] == one[3]).all()


def test_lanes_of_different_lengths_match_their_solo_runs(net):
    prompts = _prompts()
    eng = _engine(net, max_running=3)
    try:
        handles = [eng.submit(p, n) for p, n in zip(prompts, (14, 5, 9))]
        together = [h.wait() for h in handles]
    finally:
        eng.close()
    for p, n, got in zip(prompts, (14, 5, 9), together):
        solo = _engine(net, max_running=1)
        try:
            assert (solo.generate(p, n) == got).all()
        finally:
            solo.close()


def test_max_context_bounds_positions_not_slots(net):
    eng = _engine(net, max_running=2, max_context=200)
    try:
        assert eng.num_blocks == 2
        out = eng.generate(onp.arange(150, dtype=onp.int32) % 61, 40)
        assert len(out) == 40
        with pytest.raises(ValueError, match="max_context"):
            eng.submit(onp.zeros((190,), onp.int32), 20)
    finally:
        eng.close()


@pytest.mark.parametrize("kw, word", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(kv_cache_dtype="int8"), "kv_cache_dtype"),
    (dict(draft_model="net"), "draft_model"),
    (dict(prefix_cache=True, kv_spill=True), "kv_spill"),
    (dict(role="prefill"), "role"),
    (dict(mesh="mesh"), "mesh"),
])
def test_what_cannot_carry_a_state_refuses_at_construction(net, kw, word):
    from mxnet_tpu.parallel.mesh import make_mesh

    if kw.get("draft_model"):
        kw = dict(kw, draft_model=net)
    if kw.get("mesh"):
        kw = dict(kw, mesh=make_mesh({"tp": 2}, devices=jax.devices()[:2]))
    with pytest.raises(ValueError, match=f"{word}.*state_slots"):
        _engine(net, **kw)


def test_the_block_path_answers_as_before():
    """``_CausalLM`` states blocks of rows: ``ceil(n / block_size)`` a
    request, the position table the context's bound."""
    from mxnet_tpu.gluon.model_zoo import bert

    lm = bert.gpt_like(vocab_size=37, units=16, hidden_size=32, num_layers=1,
                       num_heads=4, max_length=64)
    geom = lm.cache_geometry(4)
    assert geom.kind == "kv_blocks" and geom.max_positions == 64
    assert [geom.blocks_for(n) for n in (1, 4, 5, 64)] == [1, 1, 2, 16]
    assert geom.prefill_chunk is None and not geom.unsupported


@pytest.mark.parametrize("new", [
    ("mxnet_tpu.ops.retention", "mxnet_tpu.ops.pallas.power_retention",
     "mxnet_tpu.gluon.nn.retention", "mxnet_tpu.gluon.model_zoo.brumby"),
    ("mxnet_tpu.ops.gated_delta", "mxnet_tpu.ops.gated_attention",
     "mxnet_tpu.ops.experts", "mxnet_tpu.ops.pallas.gated_delta",
     "mxnet_tpu.ops.pallas.moe_ffn", "mxnet_tpu.gluon.nn.qwen3next",
     "mxnet_tpu.gluon.model_zoo.qwen3next"),
    ("mxnet_tpu.gluon.nn.laguna", "mxnet_tpu.gluon.model_zoo.laguna",
     "mxnet_tpu.ops.gated_attention"),
], ids=["brumby", "qwen3next", "laguna"])
def test_existing_imports_do_not_load_the_new_modules(new):
    """Nothing a new model brings is paid for by a program that serves no
    such model: the packages the benchmark's other cells import leave the
    model's modules out of ``sys.modules`` (PR 29's rule; every later
    model is a case)."""
    code = textwrap.dedent(f"""
        import sys
        import mxnet_tpu, mxnet_tpu.gluon, mxnet_tpu.serving
        import mxnet_tpu.ops.pallas
        from mxnet_tpu.gluon.model_zoo import bert
        from mxnet_tpu.serving import LLMEngine
        print([m for m in {new!r} if m in sys.modules])
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, check=True, env={
                             **__import__("os").environ,
                             "JAX_PLATFORMS": "cpu"})
    assert out.stdout.strip().splitlines()[-1] == "[]", out.stdout
