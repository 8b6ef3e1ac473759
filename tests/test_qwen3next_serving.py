"""Gated delta-rule layers beside gated softmax layers, and a mixture of
experts told which experts it holds, on the serving path — at toy sizes
on the CPU: the forms of the delta rule agree, the kernels agree with
their ``jax.numpy`` twins (interpret mode), the expert layer drops no
token and builds no dense dispatch tensor, and ``serving.LLMEngine``
serves a model with two families of cache through the one allocator — a
request reserves its blocks and its lane's state, a reused lane starts
from zero, prefill runs in chunks that write rows and carry the state —
and refuses what cannot carry a state yet. (The comparison with the
plain reference, and the mutants it must catch, are in
``tests/chipbench_tests/test_qwen3next.py``.)
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu.ops import experts as EX
from mxnet_tpu.ops import gated_attention as GA
from mxnet_tpu.ops import gated_delta as GD

HK, HV, D = 2, 4, 8
TOY = dict(vocab_size=256, units=64, num_layers=4, num_heads=4,
           num_kv_heads=2, head_dim=16, rotary_dim=4, linear_key_heads=HK,
           linear_value_heads=HV, linear_key_dim=D, linear_value_dim=D,
           num_experts=16, experts_per_token=4, expert_size=32,
           shared_expert_size=32, experts_held=4, first_expert=4,
           max_length=512, prefill_chunk=64)


def _inputs(rng, t, hk=HK, hv=HV, d=D):
    f = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)   # noqa: E731
    return (GD.l2norm(f(t, hk, d)) * d ** -0.5, GD.l2norm(f(t, hk, d)),
            f(t, hv, d), -jnp.abs(f(t, hv)) * 0.2,
            jax.nn.sigmoid(f(t, hv)))


def _token_by_token(q, k, v, g, beta, s):
    """The rule as written, one head and one token at a time."""
    hv = v.shape[1]
    out = []
    for t in range(q.shape[0]):
        row = []
        for j in range(hv):
            i = j // (hv // q.shape[1])
            sj = jnp.exp(g[t, j]) * s[j]
            d = beta[t, j] * (v[t, j] - sj.T @ k[t, i])
            sj = sj + jnp.outer(k[t, i], d)
            s = s.at[j].set(sj)
            row.append(sj.T @ q[t, i])
        out.append(jnp.stack(row))
    return jnp.stack(out), s


def _in_chunks(q, k, v, g, beta, pool, c, slot=1, layer=0, fill=7.0):
    """The chunked form over a prompt, its last chunk padded with
    ``fill`` (which must change nothing)."""
    t, out = q.shape[0], []
    for start in range(0, t, c):
        n = min(c, t - start)

        def pad(x):
            return jnp.concatenate([
                x[start:start + n],
                jnp.full((c - n,) + x.shape[1:], fill, x.dtype)])

        o, pool = GD.delta_chunk(pad(q), pad(k), pad(v), pad(g), pad(beta),
                                 pool, slot, layer, start == 0, n)
        out.append(o[:n])
    return jnp.concatenate(out), pool


# --- the delta rule's three forms ------------------------------------------
@pytest.mark.parametrize("tokens,chunk", [(40, 8), (40, 16), (40, 24),
                                          (40, 64), (150, 128)])
def test_chunked_recurrent_and_written_forms_agree(tokens, chunk):
    """Chunks that do (8, 16) and do not (24, 64, 128) divide the tokens,
    of one sub-chunk (8, 16, 64), of three of 8 (24) and of two of 64
    (128); the state a fresh chunk finds in its slot is garbage and must
    not count."""
    rng = onp.random.RandomState(chunk)
    q, k, v, g, beta = _inputs(rng, tokens)
    want, s_want = _token_by_token(q, k, v, g, beta, jnp.zeros((HV, D, D)))
    pool = jnp.asarray(rng.randn(2, 3, HV, D, D), jnp.float32)
    got, pool1 = _in_chunks(q, k, v, g, beta, pool, chunk)
    onp.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    onp.testing.assert_allclose(pool1[0, 1], s_want, rtol=2e-5, atol=2e-6)
    # the other slots and the other layer are as they were
    assert (pool1[0, 0] == pool[0, 0]).all() and (pool1[1] == pool[1]).all()
    # the recurrent step, from the chunked form's state
    o, pool2 = GD.delta_step_jnp(q[:1], k[:1], v[:1], g[:1], beta[:1], pool1,
                                 jnp.asarray([1]), 0)
    o_want, _ = _token_by_token(q[:1], k[:1], v[:1], g[:1], beta[:1], s_want)
    onp.testing.assert_allclose(o, o_want, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("hk,hv", [(2, 4), (1, 3), (16, 32)])
def test_step_kernel_matches_its_jnp_twin(hk, hv):
    """Heads that do and do not fill a grid step's sixteen."""
    from mxnet_tpu.ops.pallas.gated_delta import gated_delta_step

    rng = onp.random.RandomState(3)
    q, k, v, g, beta = _inputs(rng, 3, hk, hv, d=128)
    pool = jnp.asarray(rng.randn(2, 5, hv, 128, 128), jnp.float32)
    slots = jnp.asarray([3, 0, 2], jnp.int32)
    want = GD.delta_step_jnp(q, k, v, g, beta, pool, slots, 1)
    got = gated_delta_step(q, k, v, g, beta, pool, slots, 1, interpret=True)
    for a, b in zip(got, want):
        onp.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n_real", [1, 2, 3, 7, 16])
def test_convolution_carries_its_tail_over_chunks_and_short_prompts(n_real):
    """A chunk of 16 with ``n_real`` tokens (shorter than the 4 taps
    too), then a step: the tail is the last three real inputs."""
    rng = onp.random.RandomState(n_real)
    c, ch = 16, 12
    w = jnp.asarray(rng.randn(4, ch), jnp.float32)
    x = jnp.asarray(rng.randn(n_real + 1, ch), jnp.float32)
    xpad = jnp.concatenate([jnp.zeros((3, ch)), x])
    want = jax.nn.silu(sum(w[j] * xpad[j:j + n_real + 1] for j in range(4)))
    pool = jnp.asarray(rng.randn(2, 3, 3 * ch), jnp.float32)
    chunk = jnp.concatenate([x[:n_real], jnp.full((c - n_real, ch), 9.0)])
    y, pool = GD.conv_chunk(chunk, pool, 1, 0, w, True, n_real)
    onp.testing.assert_allclose(y[:n_real], want[:n_real], rtol=1e-5,
                                atol=1e-6)
    y, pool = GD.conv_step(x[n_real:], pool, jnp.asarray([1]), 0, w)
    onp.testing.assert_allclose(y[0], want[n_real], rtol=1e-5, atol=1e-6)
    onp.testing.assert_allclose(pool[0, 1].reshape(3, ch), xpad[-3:])


# --- attention in chunks through the table ----------------------------------
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_chunk_attention_through_the_table_matches_a_masked_softmax(dtype):
    """Two chunks of 32 written through a scattered table and attended in
    key blocks of 32 (the chunk's own size), against the masked softmax over all 64 rows;
    grouped heads (4 over 2)."""
    rng = onp.random.RandomState(5)
    h, hkv, d, bs, c = 4, 2, 16, 8, 32
    q = jnp.asarray(rng.randn(2 * c, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(2 * c, hkv * d), dtype)
    v = jnp.asarray(rng.randn(2 * c, hkv * d), dtype)
    pk = jnp.asarray(rng.randn(2, 12, bs, hkv * d), dtype)
    pv = jnp.asarray(rng.randn(2, 12, bs, hkv * d), dtype)
    table = jnp.asarray([7, 2, 9, 0, 4, 10, 1, 5, 11], jnp.int32)
    got = []
    for start in (0, c):
        pk = GA.store_rows(pk, k[start:start + c], table, start, 1)
        pv = GA.store_rows(pv, v[start:start + c], table, start, 1)
        got.append(GA.paged_chunk_attention(q[start:start + c], pk, pv,
                                            table, start, 1))
    got = jnp.concatenate(got)
    kf, vf = (x.astype(jnp.float32).reshape(2 * c, hkv, d) for x in (k, v))
    qf = q.astype(dtype).astype(jnp.float32).reshape(2 * c, hkv, h // hkv, d)
    s = jnp.einsum("tjgd,sjd->jgts", qf, kf) / 4.0
    s = jnp.where(jnp.tril(jnp.ones((2 * c, 2 * c), bool)), s, -jnp.inf)
    want = jnp.einsum("jgts,sjd->tjgd", jax.nn.softmax(s, -1), vf)
    tol = 2e-5 if dtype == "float32" else 3e-2
    onp.testing.assert_allclose(got, want.reshape(2 * c, h, d), rtol=tol,
                                atol=tol)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_chunk_attention_in_key_blocks_shorter_than_the_chunk(dtype):
    """A chunk of 1,024 takes its keys 512 at a time, as the cell's chunk
    of 2,048 does: a later chunk (start 1,024: four key blocks, the last
    two under the causal mask) against the masked softmax over the rows
    the table reaches."""
    rng = onp.random.RandomState(6)
    h, hkv, d, bs, c = 4, 2, 16, 16, 1024
    assert c > GA.KEY_BLOCK
    pk = jnp.asarray(rng.randn(2, 140, bs, hkv * d), dtype)
    pv = jnp.asarray(rng.randn(2, 140, bs, hkv * d), dtype)
    table = jnp.asarray(rng.permutation(139)[:130], jnp.int32)
    q = jnp.asarray(rng.randn(c, h, d), jnp.float32)
    got = GA.paged_chunk_attention(q, pk, pv, table, jnp.int32(c), 1)
    kf, vf = (x[1, table[:2 * c // bs]].astype(jnp.float32)
              .reshape(2 * c, hkv, d) for x in (pk, pv))
    qf = q.astype(dtype).astype(jnp.float32).reshape(c, hkv, h // hkv, d)
    s = jnp.einsum("tjgd,sjd->jgts", qf, kf) / 4.0
    seen = jnp.arange(2 * c)[None, :] <= c + jnp.arange(c)[:, None]
    want = jnp.einsum("jgts,sjd->tjgd",
                      jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), vf)
    tol = 2e-5 if dtype == "float32" else 3e-2
    onp.testing.assert_allclose(got, want.reshape(c, h, d), rtol=tol,
                                atol=tol)


# --- the expert layer -------------------------------------------------------
def _experts(rng, t=37, u=64, f=32, e=16, k=4):
    x = jnp.asarray(rng.randn(t, u), jnp.float32)
    w = [jnp.asarray(rng.randn(*s) * 0.1, jnp.float32)
         for s in ((e, u, f), (e, u, f), (e, f, u))]
    idx, wt = EX.route(x @ jnp.asarray(rng.randn(u, e) * 0.5, jnp.float32), k)
    return x, idx, wt, w


def _dense(x, idx, wt, w, first, held):
    """Every held expert over every token, masked by the routing."""
    y = jnp.zeros_like(x)
    for e in range(first, first + held):
        out = (jax.nn.silu(x @ w[0][e]) * (x @ w[1][e])) @ w[2][e]
        y = y + jnp.sum(jnp.where(idx == e, wt, 0.0), 1)[:, None] * out
    return y


def test_router_keeps_k_of_all_experts_and_renormalises():
    rng = onp.random.RandomState(0)
    logits = jnp.asarray(rng.randn(9, 16), jnp.float32)
    idx, w = EX.route(logits, 4)
    p = jax.nn.softmax(logits, -1)
    assert idx.shape == w.shape == (9, 4)
    onp.testing.assert_allclose(w.sum(-1), 1.0, rtol=1e-6)
    for t in range(9):
        assert set(onp.asarray(idx[t])) == set(onp.argsort(-p[t])[:4])
        onp.testing.assert_allclose(w[t], p[t, idx[t]] / p[t, idx[t]].sum(),
                                    rtol=1e-6)


@pytest.mark.parametrize("first,held", [(0, 16), (0, 4), (4, 4), (12, 4)])
def test_held_experts_part_drops_no_token(first, held):
    """The held experts' part equals the dense masked sum — every
    assignment that falls on a held expert is computed, however uneven
    the loads — and the counts say what was computed."""
    rng = onp.random.RandomState(first + held)
    x, idx, wt, w = _experts(rng)
    part = [m[first:first + held] for m in w]
    with jax.default_matmul_precision("highest"):
        y, counts = EX.moe_grouped_ffn(x, idx, wt, *part, first)
        want = _dense(x, idx, wt, w, first, held)
    onp.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)
    on_held = (idx >= first) & (idx < first + held)
    loads = onp.bincount(onp.asarray(idx[on_held]) - first, minlength=held)
    assert list(counts) == [int(on_held.sum()), int((loads > 0).sum()),
                            int(loads.max()), held]


def test_padding_rows_reach_no_expert():
    rng = onp.random.RandomState(1)
    x, idx, wt, w = _experts(rng)
    real = jnp.arange(x.shape[0]) < 20
    y, counts = EX.moe_grouped_ffn(x, idx, wt, *w, 0, real)
    y20, counts20 = EX.moe_grouped_ffn(x[:20], idx[:20], wt[:20], *w, 0)
    onp.testing.assert_allclose(y[:20], y20, rtol=1e-5, atol=1e-6)
    assert (y[20:] == 0).all() and list(counts) == list(counts20)


def test_no_dense_dispatch_tensor_is_built():
    """Nothing in the traced layer has a (tokens, experts, capacity)
    shape, nor any array with both the tokens' and the experts' axis
    beyond the router's own logits and probabilities."""
    rng = onp.random.RandomState(2)
    x, idx, wt, w = _experts(rng)
    t, e = x.shape[0], w[0].shape[0]
    jaxpr = jax.make_jaxpr(
        lambda a, i, p: EX.moe_grouped_ffn(a, i, p, *w, 0))(x, idx, wt)
    shapes = {tuple(v.aval.shape) for eqn in jaxpr.jaxpr.eqns
              for v in eqn.outvars}
    assert not [s for s in shapes if len(s) >= 3 and t in s and e in s]
    assert not [s for s in shapes if len(s) == 2 and s == (t, e)]


@pytest.mark.parametrize("rows,dtype", [(20, "float32"), (148, "float32"),
                                        (300, "bfloat16")])
def test_grouped_kernel_matches_ragged_dot(rows, dtype):
    from mxnet_tpu.ops.pallas.moe_ffn import grouped_ffn

    rng = onp.random.RandomState(rows)
    held, u, f = 6, 128, 128
    sizes = onp.zeros(held, onp.int32)
    for e in rng.randint(0, held - 1, rows * 3 // 4):   # the last is empty
        sizes[e] += 1
    x = jnp.asarray(rng.randn(rows, u), dtype)
    w = [jnp.asarray(rng.randn(*s) * 0.1, dtype)
         for s in ((held, u, f), (held, u, f), (held, f, u))]
    want = EX.grouped_ffn_jnp(x, jnp.asarray(sizes), *w)
    got = grouped_ffn(x, jnp.asarray(sizes), *w, interpret=True)
    n = int(sizes.sum())
    tol = 1e-4 if dtype == "float32" else 3e-2
    onp.testing.assert_allclose(onp.asarray(got[:n], onp.float32),
                                onp.asarray(want[:n], onp.float32),
                                rtol=tol, atol=tol)


# --- the engine: one allocator, two families --------------------------------
@pytest.fixture(scope="module")
def net():
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import qwen3next

    mx.random.seed(0)
    model = qwen3next.qwen3next_like(**TOY)
    model.initialize()
    rng = onp.random.RandomState(4)
    for name, p in model.collect_params().items():
        if len(p.shape) >= 2:       # so that every layer weighs something
            p.set_data(jnp.asarray(rng.randn(*p.shape) * 0.15, jnp.float32))
    return model


def _engine(net, **kw):
    from mxnet_tpu.serving import LLMEngine

    kw = {"max_running": 2, "block_size": 16, "max_context": 256,
          "num_blocks": 40, "kv_cache_dtype": "float32", **kw}
    return LLMEngine(net, **kw)


def _prompts():
    rng = onp.random.RandomState(9)
    return [rng.randint(0, 256, (n,)).astype(onp.int32)
            for n in (100, 3, 64, 130)]


def test_geometry_names_blocks_and_a_state_a_lane(net):
    geom = net.cache_geometry(16)
    assert geom.kind == "kv_blocks" and geom.lane_state
    assert [geom.blocks_for(n) for n in (1, 16, 17, 256)] == [1, 1, 2, 16]
    assert geom.prefill_chunk == 64
    k, v, s, tail = net.init_block_pool(7, 16, dtype="float32",
                                        state_slots=3)
    assert k.shape == v.shape == (1, 7, 16, 32)
    assert s.shape == (3, 3, HV, D, D) and tail.shape == (3, 3, 3 * 64)
    with pytest.raises(ValueError, match="whole number of blocks"):
        net.cache_geometry(24)


def test_a_request_reserves_its_blocks_and_its_lanes_state(net):
    eng = _engine(net)
    try:
        pools = eng._kv.pools[0]
        assert len(pools) == 4
        assert pools[0].shape[1] == 41 and pools[2].shape[1] == 3
        p = _prompts()[0]
        h = eng.submit(p, 30)
        h.wait()
        st = eng.stats()
        assert st["pool_blocks_free"] == 40     # all returned
        assert st["counters"]["prefill_chunks"] == 2
        assert st["counters"]["moe_assignments"] > 0
        assert st["counters"]["moe_experts_touched"] > 0
        assert st["expert_load_max_over_mean"]["count"] == 2 + 29
        with pytest.raises(ValueError, match="max_context"):
            eng.submit(onp.zeros((250,), onp.int32), 20)
    finally:
        eng.close()


def test_lanes_of_different_lengths_match_their_solo_runs(net):
    prompts = _prompts()
    eng = _engine(net, max_running=3, num_blocks=60)
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


def test_a_reused_lane_starts_from_zero(net):
    """One lane, four requests one after another: each finds the state
    and the rows the one before left, and answers as a fresh engine."""
    prompts = _prompts()
    eng = _engine(net, max_running=1, num_blocks=12)
    try:
        one = [eng.generate(p, 6) for p in prompts + prompts[:1]]
    finally:
        eng.close()
    for p, got in zip(prompts, one):
        fresh = _engine(net, max_running=1, num_blocks=12)
        try:
            assert (fresh.generate(p, 6) == got).all()
        finally:
            fresh.close()
    assert (one[0] == one[4]).all()


def test_snapshot_gives_the_lanes_state_family(net):
    import threading

    taken, done = [], threading.Event()

    def hook():
        if not taken and handle and not handle[0].done:
            snap = eng.snapshot_cache(handle[0])
            if snap is not None and snap[0] > 100:
                taken.append(snap)
                done.set()

    handle = []
    eng = _engine(net, step_hook=hook)
    try:
        handle.append(eng.submit(_prompts()[0], 40))
        assert done.wait(60)
        handle[0].wait()
    finally:
        eng.close()
    pos, emitted, s, tail = taken[0]
    assert pos == 100 + len(emitted) - 1
    assert s.shape == (3, 1, HV, D, D) and tail.shape == (3, 1, 3 * 64)
    assert float(jnp.abs(s).max()) > 0


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
    with pytest.raises(ValueError, match=f"{word}.*lane state"):
        _engine(net, **kw)


def test_forward_is_the_chunk_programs_logits(net):
    """``net(tokens)`` — one chunk from empty pools — chooses what the
    engine's first token is."""
    import mxnet_tpu as mx

    p = _prompts()[0]
    logits = net(mx.np.array(p[None]))
    assert logits.shape == (1, 100, 256)
    eng = _engine(net, max_running=1)
    try:
        first = eng.generate(p, 1)
    finally:
        eng.close()
    assert int(jnp.argmax(logits._data[0, -1])) == int(first[0])
