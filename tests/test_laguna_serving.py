"""Window layers that keep a ring beside full layers that keep every row,
72 and 48 query heads by layer kind, a leading dense layer and a mixture
of experts told which experts it holds — ``laguna_like`` at toy sizes on
the CPU against the plain reference (``chipbench/reference/laguna.py``:
one forward over the whole sequence, the band written as a mask), on
**logits**: the whole forward; prefill in chunks then decoding through
pools and rings, across the window's wrap; the YaRN frequencies against
their closed form; the eight shares of the experts adding up to the uncut
layer; controls that drop a term; the paged kernel at 72 and 48 query
rows over rings of every length; and ``serving.LLMEngine`` serving it
through the one allocator. (The cell's rehearsal through ``run.main()``
is ``tests/chipbench_tests/test_laguna.py``; this file is named apart
from it because two test files of one name cannot be collected.)
"""
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chipbench import flops_laguna as counts, harness        # noqa: E402
from chipbench.reference import laguna as reference          # noqa: E402
from mxnet_tpu.ops import gated_attention as GA              # noqa: E402

FULL, WINDOW = "full_attention", "sliding_attention"
# the dense layer and one period; experts 4-7 of 16; a window of 32: a ring
# of two blocks of 16 a lane, so a lane's table is a table
SZ = dict(vocab_size=256, units=64, num_layers=5,
          layer_types=(FULL, WINDOW, WINDOW, WINDOW, FULL),
          heads_per_layer=(4, 6, 6, 6, 4), num_kv_heads=2, head_dim=16,
          window=32, rope_theta=500000.0, rotary_dim=8, yarn_factor=128.0,
          yarn_original=32, yarn_beta_fast=32.0, yarn_beta_slow=1.0,
          yarn_attention_factor=1.4852030263919618,
          window_rope_theta=10000.0, window_rotary_dim=16,
          dense_layers=(0,), dense_size=128, num_experts=16,
          experts_per_token=4, expert_size=32, shared_expert_size=32,
          routed_scale=2.5, experts_held=4, first_expert=4, max_length=512,
          epsilon=1e-6)
CONFIG = {"assumed_values": {"norm_spread": 0.1}}
BS, ANSWER, PAD = 4, 10, 88


def make_net(dtype="float32", sz=SZ, chunk=12, seed=7):
    """The toy model with the benchmark's weights: everything random,
    the norms' weights 1 + normal(0, 0.1)."""
    from chipbench.runners import serve_model
    from mxnet_tpu.gluon.model_zoo import laguna

    return serve_model.make_net(
        laguna.laguna_like, dict(sz, prefill_chunk=chunk), dtype, seed, 0.2,
        counts.overrides(CONFIG, sz, seed))


def params_of(net):
    return {k: harness.raw(p.data()) for k, p in net.collect_params().items()}


def new_pools(net, blocks, dtype="float32"):
    return net.init_block_pool(blocks + 1, BS, dtype=dtype, state_slots=3)


def run_program(net, seq, prompt, chunk, pools=None, lane=1):
    """What the engine's two programs compute for one lane: the prompt in
    chunks of ``chunk`` through a scattered block table and the rings of
    slot ``lane`` (the last chunk padded), then the answer's tokens one
    decode step at a time beside an idle lane, teacher-forced. Returns
    the logits of the rows that choose the answer's tokens, and the
    pools."""
    import mxnet_tpu.numpy as mxnp

    i32 = lambda x: mxnp.array(onp.asarray(x, onp.int32))   # noqa: E731
    mb = -(-len(seq) // BS) + 1
    trash = mb + 5
    pools = new_pools(net, trash) if pools is None else pools
    table = onp.full((2, mb), trash, onp.int32)
    table[lane] = onp.random.RandomState(3).permutation(trash)[:mb]
    rows = []
    for start in range(0, prompt, chunk):
        n = min(chunk, prompt - start)
        toks = onp.full((1, chunk), 5, onp.int32)       # padding: a token
        toks[0, :n] = seq[start:start + n]
        last, _, *pools = net.prefill_chunk_step(
            i32(toks), *pools, i32(lane), i32(table[lane]), i32(start),
            i32(n))
    rows.append(harness.raw(last)[0])
    for pos in range(prompt, len(seq) - 1):
        toks, where = onp.zeros((2, 1), onp.int32), onp.zeros(2, onp.int32)
        toks[lane], where[lane] = seq[pos], pos
        logits, _, *pools = net.decode_step_paged(
            i32(toks), *pools, i32(table), i32(where))
        rows.append(harness.raw(logits)[lane, 0])
    return onp.asarray(jnp.stack(rows), onp.float32), pools


def sequence(prompt, seed=11, answer=ANSWER):
    return onp.random.RandomState(seed).randint(
        0, SZ["vocab_size"], (prompt + answer,)).astype(onp.int32)


def steps_apart(net, seq, prompt, chunk, sz=SZ, pools=None):
    """The largest distance between the program's and the reference's
    logits over the checked rows, in bf16 steps of each row's best logit
    (the unit of ``TIE_STEPS``)."""
    padded = onp.zeros((PAD,), onp.int32)      # one compiled reference
    padded[:len(seq)] = seq
    want = onp.asarray(reference.logits(
        params_of(net), padded, sz, onp.arange(prompt - 1, len(seq) - 1),
        len(seq)))
    got, pools = run_program(net, seq, prompt, chunk, pools)
    diff = onp.abs(got - want).max(-1)
    best = want.max(-1)
    return float(reference.bf16_steps_behind(best, best - diff).max()), pools


# What the comparison allows, in bf16 steps of a row's best logit (2^-8 of
# it). Float32 weights: the two formulations differ by float32 rounding
# and the order of sums (0.01 steps read). bfloat16 weights: the reference
# upcasts the same parameters, the program rounds every projection's
# result and every stored row to bfloat16 and routes by its own logits, so
# a token near a tie takes another expert here and there (at the toy's
# widths one such choice moves a logit by tens of steps: the bfloat16 case
# checks rows without one).
LIMIT = {"float32": 0.05, "bfloat16": 20.0}


# --- (a) the whole forward ---------------------------------------------------
def test_forward_matches_the_reference():
    import mxnet_tpu.numpy as mxnp

    net, seq = make_net(), sequence(PAD, answer=0)
    want = onp.asarray(reference.logits(params_of(net), seq, SZ))
    got = onp.asarray(harness.raw(net(mxnp.array(seq[None])))[0])
    # float32 on both sides: rounding and the order of sums, of logits
    # that reach 7
    onp.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# --- (b) chunks, then decoding, across the window's wrap ---------------------
@pytest.mark.parametrize("dtype,prompt,chunk", [
    ("float32", 70, 40),    # wider than the window and no multiple of it;
                            # over twice the window; a last chunk of 30 + 10
    ("float32", 50, 12),    # narrower than the window; a last chunk of 2
    ("float32", 64, 32),    # twice the window, no padding
    ("float32", 27, 40),    # shorter than the window: decoding wraps it
    ("bfloat16", 60, 40),   # rows 59..68: clear of the two positions of
                            # this sequence whose token takes another
                            # expert in bfloat16 (about 53 and 75: 36 and
                            # 75 steps, whatever the chunks before them)
])
def test_chunks_then_decode_match_the_reference(dtype, prompt, chunk):
    steps, _ = steps_apart(make_net(dtype, chunk=chunk), sequence(prompt),
                           prompt, chunk)
    print(f"{dtype}, {prompt} in chunks of {chunk}: {steps:.3f} bf16 steps")
    assert steps < LIMIT[dtype]


def test_a_reused_slot_starts_from_nothing():
    """A second request in the slot, blocks and rings a first one left
    full: a shorter prompt, whose window must not reach the first one's
    rows."""
    net = make_net()
    _, pools = steps_apart(net, sequence(70), 70, 12)
    steps, _ = steps_apart(net, sequence(3, seed=5), 3, 12, pools=pools)
    assert steps < LIMIT["float32"]


def test_padding_is_never_stored_in_the_ring():
    """The rings after a prompt of 70 in chunks of 40 (a last chunk of 30
    tokens and 10 rows of padding) hold rows 38..69 of the reference's K
    and V, position p in row p mod 32."""
    from mxnet_tpu.gluon.model_zoo import laguna

    net, seq = make_net(chunk=40), sequence(70, answer=1)
    _, pools = run_program(net, seq, 70, 40)
    got = laguna.ring_readings(harness.raw(pools[2])[:, 1],
                               harness.raw(pools[3])[:, 1], None)
    apart = reference.state_apart(params_of(net), seq, 70, got, SZ, PAD, 0)
    assert set(apart) == set(reference.STATE_LIMIT)
    assert max(apart.values()) < 1e-5       # float32 both sides


# --- (c) YaRN ----------------------------------------------------------------
def test_yarn_frequencies_against_the_closed_form():
    """At the published numbers: pair i turns by theta^(-2i/64) for i <=
    low, by that over 128 for i >= high, linearly between; the program's
    frequencies are the reference's."""
    sz = real_sizes()
    theta, span = 500000.0, 8192

    def pair(turns):
        return 64 * math.log(span / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low, high = math.floor(pair(32)), math.ceil(pair(1))
    assert (low, high) == (9, 18)
    got = GA.yarn_frequencies(64, theta, 128.0, span, 32.0, 1.0)
    assert got.shape == (32,) and got.dtype == onp.float32
    for i in (0, low, high, 31):
        f = theta ** (-2 * i / 64)
        r = min(max((i - low) / (high - low), 0.0), 1.0)
        assert got[i] == pytest.approx(f * (1 - r) + f / 128 * r, rel=1e-6)
    assert got[low] == pytest.approx(theta ** (-2 * low / 64), rel=1e-6)
    assert got[31] == pytest.approx(theta ** (-62 / 64) / 128, rel=1e-6)
    onp.testing.assert_array_equal(got, reference.yarn_frequencies(sz))
    assert sz["yarn_attention_factor"] == pytest.approx(
        0.1 * math.log(128) + 1, rel=1e-12)


# --- (d) the shares ----------------------------------------------------------
def test_the_eight_shares_and_the_shared_expert_once_add_up_to_the_layer():
    """The guide's share test at the deployment's division: one expert
    layer's rows as each of eight chips holds it (4 of 32 experts each):
    the eight routed parts, plus what every chip computes alike — the
    shared expert — counted once, equal the uncut layer of the reference
    (all 32 held)."""
    from mxnet_tpu.ops import experts as EX

    whole = dict(SZ, num_experts=32, experts_held=32, first_expert=0)
    net = make_net(sz=whole)
    p = {k[len("layer1."):]: v for k, v in params_of(net).items()
         if k.startswith("layer1.")}
    h2 = jnp.asarray(onp.random.RandomState(2).randn(50, SZ["units"]),
                     jnp.float32)
    idx, w = reference._route(h2, p, whole, 50)
    shared = reference._ffn(h2, p, "experts.shared.")
    want = reference._experts(h2, idx, w, p, whole) + shared
    ids, wt = EX.route(h2 @ p["experts.router.weight"].T, 4)
    parts = [EX.moe_grouped_ffn(
        h2, ids, wt * 2.5, *(p["experts." + n][first:first + 4]
                             for n in ("gate", "up", "down")), first)[0]
        for first in range(0, 32, 4)]
    onp.testing.assert_allclose(sum(parts) + shared, want, rtol=1e-4,
                                atol=1e-6)
    # and one share is not the layer
    assert float(jnp.abs(parts[1] + shared - want).max()) > 1e-3


# --- (e) controls: each must fail the comparison -----------------------------
def _patch(name, make):
    def apply(monkeypatch, net):
        monkeypatch.setattr(GA, name, make(getattr(GA, name)))
    return apply


def _dense_layer_dropped(monkeypatch, net):
    monkeypatch.setattr(net.layer0, "finish",
                        lambda x, h, real=None: (x + h, None))


def _scale_dropped(monkeypatch, net):
    for i in range(1, SZ["num_layers"]):
        getattr(net, f"layer{i}").experts._scale = 1.0


CONTROLS = {
    "head-gate-dropped": _patch(
        "head_gate", lambda f: lambda o, gate: o.astype(jnp.float32)),
    "yarn-factor-dropped": _patch(
        "rotary", lambda f: lambda x, pos, freq, scale=1.0: f(
            x, pos, freq, 1.0)),
    "plain-weight-read-as-one-plus-w": _patch(
        "rms", lambda f: lambda x, w, eps=1e-6: f(x, 1.0 + w, eps)),
    "ring-row-one-token-late": _patch(
        "ring_store", lambda f: lambda ring, rows, slots, pos, layer: f(
            ring, rows, slots, pos - 1, layer)),
    "dense-layer-dropped": _dense_layer_dropped,
    "routed-scale-dropped": _scale_dropped,
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_every_control_fails_the_comparison(control, monkeypatch):
    """On float32 weights, where the program reads under 0.05 steps,
    every control is far over the limit."""
    net = make_net()
    CONTROLS[control](monkeypatch, net)
    steps, _ = steps_apart(net, sequence(41, answer=6), 41, 24)
    print(f"{control}: {steps:.1f} bf16 steps")
    assert steps > 20 * LIMIT["float32"]


def replaced(mod, old, new):
    """``mod``'s namespace with one line of its source replaced — the
    form of the controls that ran on the chip (PERF.md, section 6, PR 36):
    what must fail is the program itself but for that line."""
    import inspect

    src = inspect.getsource(mod)
    assert src.count(old) == 1, old
    made = dict(vars(mod))
    exec(compile(src.replace(old, new), mod.__file__, "exec"), made)
    return made


BAND = "seen = (off > 0) & (off <= w)"
OFF_BY_ONE = {"short": "seen = (off > 1) & (off <= w)",
              "long": "seen = (off >= 0) & (off <= w)"}


@pytest.mark.parametrize("band", sorted(OFF_BY_ONE))
def test_a_window_off_by_one_fails_the_comparison(band, monkeypatch):
    """A chunk's band one position short or long, where the reference's
    mask admits 32."""
    monkeypatch.setattr(GA, "window_chunk_attention", replaced(
        GA, BAND, OFF_BY_ONE[band])["window_chunk_attention"])
    steps, _ = steps_apart(make_net(chunk=24), sequence(41, answer=6), 41, 24)
    print(f"band one {band}: {steps:.1f} bf16 steps")
    assert steps > 20 * LIMIT["float32"]


def test_a_window_that_is_no_whole_number_of_ring_blocks_is_refused():
    with pytest.raises(ValueError, match="whole number of a ring's blocks"):
        make_net(sz=dict(SZ, window=24))


# --- (f) the paged kernel over a ring ----------------------------------------
@pytest.mark.parametrize("heads", [72, 48])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_paged_kernel_over_rings_against_the_jnp_path(heads, dtype):
    """Interpret mode, 8 K/V heads of 128 (rows of 1,024: whole lanes, the
    hand-copied path), a window of 64 as 4 fixed blocks of 16 a lane;
    lanes whose rings hold 1, window - 1 and window rows and one that has
    wrapped (position 150: every row live, the newest in row 22)."""
    from mxnet_tpu.ops.nn import paged_attention

    rng = onp.random.RandomState(0)
    w, row, slots = 64, 8 * 128, 5
    ring_k = jnp.asarray(rng.randn(2, slots, w, row), dtype)
    ring_v = jnp.asarray(rng.randn(2, slots, w, row), dtype)
    q = jnp.asarray(rng.randn(4, heads, 128), jnp.float32)
    pos = jnp.asarray([0, w - 2, w - 1, 150], jnp.int32)
    lanes = jnp.asarray([3, 0, 1, 2], jnp.int32)
    (bk, table), (bv, _) = GA.ring_blocks(ring_k, lanes), \
        GA.ring_blocks(ring_v, lanes)
    assert bk.shape == (2, slots * 4, 16, row) and table.shape == (4, 4)
    live = jnp.minimum(pos + 1, w)
    want = paged_attention(q, bk, bv, table, live, 1, use_kernel=False)
    got = paged_attention(q, bk, bv, table, live, 1, use_kernel=True)
    # the kernel sums a group of blocks at a time with a running max; the
    # jnp path takes one softmax: float32 rounding, or one bf16 step of
    # values that reach 3
    tol = 2e-5 if dtype == "float32" else 2e-2
    onp.testing.assert_allclose(onp.asarray(got, onp.float32),
                                onp.asarray(want, onp.float32), atol=tol)
    # and the jnp path is the plain softmax over the live rows
    k0 = onp.asarray(ring_k, onp.float32)[1, 2].reshape(w, 8, 128)
    v0 = onp.asarray(ring_v, onp.float32)[1, 2].reshape(w, 8, 128)
    share = heads // 8
    s = onp.einsum("hd,shd->hs", onp.asarray(q[3]),
                   onp.repeat(k0, share, 1)) / math.sqrt(128)
    a = onp.exp(s - s.max(-1, keepdims=True))
    plain = onp.einsum("hs,shd->hd", a / a.sum(-1, keepdims=True),
                       onp.repeat(v0, share, 1))
    onp.testing.assert_allclose(onp.asarray(want, onp.float32)[3], plain,
                                atol=tol)


# --- the engine --------------------------------------------------------------
def test_engine_serves_rings_beside_blocks_and_counts_the_rows():
    """Five requests through three lanes: every slot is used twice, the
    prompts cross the window, twice the window and the chunk; each token
    within float32's
    distance of the reference's best logit; the two gauges read the rows
    the cache holds by family, and the pool's bytes count the rings."""
    from mxnet_tpu.serving import LLMEngine
    from mxnet_tpu.telemetry import tracing

    net = make_net(chunk=12)
    params = params_of(net)
    seen, box = [], []
    t0 = time.perf_counter()     # the ring holds other tests' ticks too
    eng = LLMEngine(net, max_running=3, block_size=BS, max_context=96,
                    num_blocks=72, kv_cache_dtype="float32",
                    step_hook=lambda: seen.extend(e.stats() for e in box))
    box.append(eng)
    try:
        assert eng.stats()["kv_rows_full"] == 0
        rng = onp.random.RandomState(4)
        lens = [70, 33, 41, 80, 9]
        prompts = [rng.randint(0, 256, (n,)).astype(onp.int32) for n in lens]
        handles = [eng.submit(p, 10) for p in prompts]
        outs = [h.wait() for h in handles]
        # the gauges are the tick's: the next one finds the lanes empty
        for _ in range(500):
            st = eng.stats()
            if not st["kv_rows_full"]:
                break
            time.sleep(0.01)
        pool_bytes = eng.metrics.shard_pool_bytes.get()
    finally:
        eng.close()
    for p, out in zip(prompts, outs):
        behind = reference.tokens_behind(params, p, out, SZ, 96, 10)
        assert behind.max() < LIMIT["float32"]
    # the gauges while lanes were full: 2 full layers x positions, 3
    # window layers x min(positions, 32)
    rows = [(s["kv_rows_full"], s["kv_rows_window"]) for s in seen
            if s["lanes_active"]]
    assert rows and max(f for f, _ in rows) > 2 * 80
    assert all(w <= 3 * 3 * 32 for _, w in rows)
    assert max(w for _, w in rows) == 3 * 3 * 32
    assert st["kv_rows_full"] == 0 and st["kv_rows_window"] == 0
    ticks = [a for _, _, _, a in tracing.rows(t0, float("inf"), "llm.tick")
             if "kv_rows_window" in a]
    assert ticks and max(a["kv_rows_window"] for a in ticks) == 288
    # K, V of 2 layers x 73 blocks x 4 rows and rings of 3 layers x 4
    # slots x 32 rows, rows of 32 float32: the rings are counted
    assert pool_bytes == 2 * (2 * 73 * 4 + 3 * 4 * 32) * 32 * 4
    assert counts.rows_held(SZ, [40, 9]) == (2 * 49, 3 * 41)


@pytest.mark.parametrize("feature,kw", [
    ("prefix_cache", dict(prefix_cache=True)),
    ("kv_spill", dict(prefix_cache=True, kv_spill=True)),
    ("role", dict(role="prefill")),
    ("draft_model", dict(draft_model="itself")),
    ("mesh", dict(mesh="two")),
])
def test_engine_refuses_what_a_ring_cannot_carry(feature, kw):
    from mxnet_tpu.serving import LLMEngine

    net = make_net()
    if "draft_model" in kw:
        kw = dict(draft_model=net)
    if "mesh" in kw:
        from mxnet_tpu.parallel import make_mesh

        kw = dict(mesh=make_mesh({"tp": 2}, devices=jax.devices()[:2]))
    with pytest.raises(ValueError, match="kv_blocks \\+ lane state"):
        LLMEngine(net, max_running=2, block_size=BS, max_context=32, **kw)


def test_geometry_and_pools():
    net = make_net(chunk=12)
    geom = net.cache_geometry(4)
    assert geom.lane_state and geom.kind == "kv_blocks"
    assert geom.row_layers == (2, 3, 32) and geom.blocks_for(9) == 3
    k, v, rk, rv = net.init_block_pool(10, 4, dtype="bfloat16",
                                       state_slots=4)
    assert k.shape == v.shape == (2, 10, 4, 32)
    assert rk.shape == rv.shape == (3, 4, 32, 32)
    assert str(rk.dtype) == "bfloat16"      # the cache's dtype, not float32
    with pytest.raises(ValueError, match="whole number of blocks"):
        net.cache_geometry(5)


def real_sizes():
    return counts.sizes(harness.load_json(
        REPO, "chipbench", "configs", "laguna-s-2.1-ep8-l12.json"))


def test_the_counts_are_the_models_own_parameter_shapes():
    """``flops_laguna``'s parameter counts, which the configuration's
    ``bytes`` are reckoned with, against ``collect_params()`` of the toy:
    by layer, the experts apart."""
    net = make_net()
    shapes = {k: int(onp.prod(p.shape))
              for k, p in net.collect_params().items()}
    for i in range(SZ["num_layers"]):
        mine = {k: n for k, n in shapes.items() if k.startswith(f"layer{i}.")}
        routed = sum(n for k, n in mine.items()
                     if k.endswith(("experts.gate", "experts.up",
                                    "experts.down")))
        assert sum(mine.values()) - routed == counts.layer_params(SZ, i)
        assert routed == (0 if i in SZ["dense_layers"]
                          else SZ["experts_held"] * counts.expert_params(SZ))
        assert sum(n for k, n in mine.items() if ".mixer." in k) \
            == counts.mixer_params(SZ, i)
    parts = counts.weight_bytes(SZ, itemsize=1)
    assert sum(parts.values()) == sum(shapes.values()) - SZ["units"]
