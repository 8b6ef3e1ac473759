"""The ``brumby-14b-l8`` configuration's pieces at toy sizes on the CPU:
the program's prefill-in-chunks-then-decode against the plain reference
(``chipbench/reference/brumby.py``, the quadratic form), the mutants the
comparison must catch, the operation counts against hand arithmetic, and
the cell rehearsed end to end through ``run.main()`` with
``test_chipbench.py``'s machinery over toy files of its own
(``data/tiny_brumby``). No topology is described in this file.
"""
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from test_chipbench import IGNORE, REPO, args, check_benchmark, last_line

from chipbench import flops_brumby, harness, run          # noqa: E402
from chipbench.reference import brumby as reference       # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "data", "tiny_brumby")

SZ = dict(vocab_size=96, units=64, hidden_size=96, num_layers=2, num_heads=4,
          num_kv_heads=2, head_dim=8, max_length=256, rope_theta=1e6,
          epsilon=1e-6)
CHUNK, PROMPT, ANSWER = 16, 41, 12      # 41 = 2 chunks and 9 tokens of a third


def make_net(dtype):
    """The toy model with the benchmark's weights: everything random, the
    gates' half-lives spread (4 to 64 tokens here) so that what a chunk
    hands on matters."""
    from chipbench.runners import serve_model
    from mxnet_tpu.gluon.model_zoo import brumby

    config = {"assumed_values": {"gate_half_life_tokens": [4, 64]}}
    return serve_model.make_net(
        brumby.brumby_like, dict(SZ, prefill_chunk=CHUNK), dtype, 7, 0.2,
        flops_brumby.overrides(config, SZ, 7))


def program_logits(net, seq):
    """What the engine's two programs compute for one lane: the prompt in
    chunks of ``CHUNK`` into slot 1 (the last chunk padded), then the
    answer's tokens one decode step at a time, teacher-forced. Logits of
    the rows that choose the answer's tokens."""
    import mxnet_tpu.numpy as mxnp

    pool_s, pool_z = net.init_block_pool(3, 0)
    i32 = lambda x: mxnp.array(onp.asarray(x, onp.int32))   # noqa: E731
    rows = []
    for start in range(0, PROMPT, CHUNK):
        n = min(CHUNK, PROMPT - start)
        chunk = onp.zeros((1, CHUNK), onp.int32)
        chunk[0, :n] = seq[start:start + n]
        last, pool_s, pool_z = net.prefill_chunk_step(
            i32(chunk), pool_s, pool_z, i32(1), i32(start), i32(n))
    rows.append(harness.raw(last)[0])
    for pos in range(PROMPT, PROMPT + ANSWER - 1):
        logits, pool_s, pool_z = net.decode_step_paged(
            i32([[seq[pos]], [0]]), pool_s, pool_z, i32([[1], [2]]),
            i32([pos, 0]))
        rows.append(harness.raw(logits)[0, 0])
    return onp.asarray(jnp.stack(rows), onp.float32)


def steps_apart(net, seq):
    """The largest distance between the program's and the reference's
    logits over the checked rows, in bf16 steps of each row's best logit
    (the unit of ``TIE_STEPS``), and in absolute terms."""
    params = {k: harness.raw(p.data())
              for k, p in net.collect_params().items()}
    want = onp.asarray(reference.logits(
        params, seq, SZ, onp.arange(PROMPT - 1, PROMPT + ANSWER - 1)))
    diff = onp.abs(program_logits(net, seq) - want).max(-1)
    best = want.max(-1)
    steps = reference.bf16_steps_behind(best, best - diff)
    return float(diff.max()), float(steps.max())


@pytest.fixture(scope="module")
def seq():
    return onp.random.RandomState(11).randint(
        0, SZ["vocab_size"], (PROMPT + ANSWER,)).astype(onp.int32)


# What the comparison allows, in bf16 steps of a row's best logit. Float32
# weights: the two formulations differ by float32 rounding, 0.000 steps
# (7.6e-6 absolute). bfloat16 weights: the reference upcasts the same
# parameters; the program's first norm runs on the bfloat16 embedding row
# in bfloat16 — 3.3 steps, so half of TIE_STEPS.
LIMIT = {"float32": 0.05, "bfloat16": 6.0}


@pytest.mark.parametrize("dtype", sorted(LIMIT))
def test_chunks_then_decode_match_the_quadratic_reference(dtype, seq):
    absolute, steps = steps_apart(make_net(dtype), seq)
    print(f"{dtype}: {steps:.3f} bf16 steps, {absolute:.2e} absolute")
    assert steps < LIMIT[dtype]


# --- mutants: each must fail the comparison --------------------------------
def _recurrence(q, k, v, lg, s, z, *, gate=True, normaliser=True,
                state_dtype=jnp.float32, power=2):
    """The layer one token at a time, with its parts switchable. ``q (T,
    Hq, d)`` ...; ``s (Hk, d, Dp)``, ``z (Hk, Dp)``. Unmutated it is the
    program's own arithmetic in another order."""
    from mxnet_tpu.ops import retention as R

    hk, dp = k.shape[1], s.shape[-1]

    def phi(u):
        if power == 2:
            return R.phi(u)
        return jnp.pad(u.astype(jnp.float32),
                       [(0, 0)] * (u.ndim - 1) + [(0, dp - u.shape[-1])])

    out = []
    for t in range(q.shape[0]):
        g = jnp.exp(lg[t]) if gate else jnp.ones_like(lg[t])
        pk, pq = phi(k[t]), phi(q[t]).reshape(hk, -1, dp)
        s = (g[:, None, None] * s + v[t][:, :, None] * pk[:, None, :]) \
            .astype(state_dtype).astype(jnp.float32)
        z = (g[:, None] * z + pk).astype(state_dtype).astype(jnp.float32)
        num = jnp.einsum("jgn,jvn->jgv", pq, s)
        den = jnp.einsum("jgn,jn->jg", pq, z)[..., None] \
            if normaliser else 1.0
        out.append((num / (den + R.EPS)).reshape(q.shape[1:]))
    return jnp.stack(out), s, z


def _mutate(monkeypatch, forget_between_chunks=False, **parts):
    """Put ``_recurrence`` in the place of both forms."""
    from mxnet_tpu.ops import retention as R

    def chunk(q, k, v, lg, pool_s, pool_z, slot, layer, fresh, n_real):
        keep = (jnp.arange(q.shape[0]) < n_real)
        lg = jnp.where(keep[:, None], lg, 0.0)
        k = jnp.where(keep[:, None, None], k, 0.0)
        zero = fresh | forget_between_chunks
        o, s, z = _recurrence(
            q, k, v, lg, jnp.where(zero, 0.0, pool_s[layer, slot]),
            jnp.where(zero, 0.0, pool_z[layer, slot]), **parts)
        return o, pool_s.at[layer, slot].set(s), \
            pool_z.at[layer, slot].set(z)

    def step(q, k, v, lg, pool_s, pool_z, slots, layer):
        o, s, z = zip(*(_recurrence(
            q[r][None], k[r][None], v[r][None], lg[r][None],
            pool_s[layer, slots[r]], pool_z[layer, slots[r]], **parts)
            for r in range(q.shape[0])))
        return jnp.concatenate(o), \
            pool_s.at[layer, slots].set(jnp.stack(s)), \
            pool_z.at[layer, slots].set(jnp.stack(z))

    monkeypatch.setattr(R, "retention_chunk", chunk)
    monkeypatch.setattr(R, "retention_step", step)


MUTANTS = {
    "state-zeroed-between-chunks": dict(forget_between_chunks=True),
    "gate-dropped": dict(gate=False),
    "normaliser-dropped": dict(normaliser=False),
    "state-in-bfloat16": dict(state_dtype=jnp.bfloat16),
    "power-1": dict(power=1),
}


@pytest.mark.parametrize("dtype", sorted(LIMIT))
def test_the_stand_in_is_the_program(dtype, monkeypatch, seq):
    """Unmutated, the switchable recurrence passes as the program does:
    what fails below fails for the part that was switched."""
    _mutate(monkeypatch)
    assert steps_apart(make_net(dtype), seq)[1] < LIMIT[dtype]


@pytest.mark.parametrize("mutant", sorted(MUTANTS))
def test_every_mutant_fails_the_comparison(mutant, monkeypatch, seq):
    """On float32 weights, where the program reads 0.000 steps, every
    mutant is far over the limit — a state kept in bfloat16 too (3.9 steps
    after 52 tokens; its error grows with the context). On bfloat16
    weights the four that change the mathematics read 400 to 500 steps,
    far over ``TIE_STEPS``, the rule of the cell's ``correct``."""
    _mutate(monkeypatch, **MUTANTS[mutant])
    for dtype in sorted(LIMIT):
        absolute, steps = steps_apart(make_net(dtype), seq)
        print(f"{mutant}, {dtype}: {steps:.1f} bf16 steps, "
              f"{absolute:.2e} absolute")
        if dtype == "float32":
            assert steps > 20 * LIMIT[dtype]
        elif mutant != "state-in-bfloat16":
            assert steps > 10 * reference.TIE_STEPS


# --- the state's own rule ---------------------------------------------------
# A longer toy than the one above (768 tokens, half-lives of 64 to 2,048):
# what a state kept in bfloat16 loses is the small increments a long memory
# is made of, so it shows where the memory is long.
LONG = dict(SZ, max_length=1024)
LONG_CHUNK, LONG_PROMPT, LONG_TOTAL = 64, 640, 768


def state_apart(dtype):
    """The reference's ``state_apart`` of the state the two programs leave
    in slot 1 after ``LONG_PROMPT`` tokens in chunks and the rest one
    decode step at a time."""
    import mxnet_tpu.numpy as mxnp
    from chipbench.runners import serve_model
    from mxnet_tpu.gluon.model_zoo import brumby
    from mxnet_tpu.ops import retention as R

    config = {"assumed_values": {"gate_half_life_tokens": [64, 2048]}}
    net = serve_model.make_net(
        brumby.brumby_like, dict(LONG, prefill_chunk=LONG_CHUNK), dtype, 7,
        0.2, flops_brumby.overrides(config, LONG, 7))
    seq = onp.random.RandomState(11).randint(
        0, LONG["vocab_size"], (LONG_TOTAL,)).astype(onp.int32)
    pool_s, pool_z = net.init_block_pool(3, 0)
    i32 = lambda x: mxnp.array(onp.asarray(x, onp.int32))   # noqa: E731
    for start in range(0, LONG_PROMPT, LONG_CHUNK):
        _, pool_s, pool_z = net.prefill_chunk_step(
            i32(seq[None, start:start + LONG_CHUNK]), pool_s, pool_z,
            i32(1), i32(start), i32(LONG_CHUNK))
    for pos in range(LONG_PROMPT, LONG_TOTAL):
        _, pool_s, pool_z = net.decode_step_paged(
            i32([[seq[pos]], [0]]), pool_s, pool_z, i32([[1], [2]]),
            i32([pos, 0]))
    got = R.state_readings(harness.raw(pool_s)[:, 1],
                           harness.raw(pool_z)[:, 1],
                           reference.probes(LONG, 7))
    params = {k: harness.raw(p.data())
              for k, p in net.collect_params().items()}
    return reference.state_apart(params, seq, LONG_TOTAL, got, LONG,
                                 LONG_TOTAL, 7)


@pytest.mark.parametrize("dtype", sorted(LIMIT))
def test_a_state_in_bfloat16_fails_the_states_rule(dtype, monkeypatch):
    """The rule that ``correct`` holds the timed engine's states to
    (``STATE_LIMIT``): the program passes it, on float32 weights by
    float32 rounding; a state rounded to bfloat16 after every token fails
    it on either weights — which no distance in logits shows on bfloat16
    weights (3.3 steps against 3.9 above)."""
    sound = state_apart(dtype)
    _mutate(monkeypatch, **MUTANTS["state-in-bfloat16"])
    mutant = state_apart(dtype)
    print(f"{dtype}: the program {sound}, the state in bfloat16 {mutant}")
    assert all(sound[k] < reference.STATE_LIMIT[k] for k in sound)
    assert any(mutant[k] > reference.STATE_LIMIT[k] for k in mutant)
    assert all(mutant[k] > 3 * sound[k] for k in sound)
    if dtype == "float32":
        assert max(sound.values()) < 1e-4


# --- the counts, against hand arithmetic -----------------------------------
def test_operation_and_byte_counts_against_hand_arithmetic():
    sz = flops_brumby.sizes(harness.load_json(
        REPO, "chipbench", "configs", "brumby-14b-l8.json"))
    parts = flops_brumby.layer_params(sz)
    # q 5120 x 5120, k and v 5120 x 1024, o 5120 x 5120, the gate 5120 x 8,
    # FFN 3 x 5120 x 17408, norms 2 x 5120 + 2 x 128; the gate's bias 8
    assert sum(parts.values()) - parts["gate_bias"] == 330_352_896
    assert parts["gate_bias"] == 8
    assert flops_brumby.layer_matmul_params(sz) == 330_352_896 - 10_496
    assert flops_brumby.matmul_params(sz) == \
        8 * 330_342_400 + 151_936 * 5_120
    assert flops_brumby.phi_exact(sz) == 8_256
    assert flops_brumby.state_bytes(sz) == 34_080_768
    ops, nbytes = flops_brumby.retention_step(sz, 16)
    assert nbytes == 16 * 8 * 2 * 34_080_768          # 8.72 GB a step
    assert ops == 16 * 8 * 2 * 8_256 * 128 * 48
    # a first chunk reads no state; a later one does, with all 40 heads
    first = flops_brumby.retention_chunk(sz, [(0, 1024)])
    later = flops_brumby.retention_chunk(sz, [(1024, 1024)])
    inside = 40 * 4 * 128 * 1024 * 1025 / 2
    assert first == 8 * (1024 * 8 * 2 * 8_256 * 128 + inside)
    assert later - first == 8 * 1024 * 40 * 2 * 8_256 * 128
    half = flops_brumby.overrides(
        {"assumed_values": {"gate_half_life_tokens": [64, 8192]}}, sz, 5)
    bias = half["layer3.retention.g_proj.bias"]
    assert onp.allclose(sorted(-1 / onp.log2(1 / (1 + onp.exp(-bias)))),
                        [64, 128, 256, 512, 1024, 2048, 4096, 8192],
                        rtol=1e-3)


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog's row for Brumby-14B-Base, as published,
    but the one that ``reduced`` lists."""
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 5120, "intermediate_size": 17408,
        "max_position_embeddings": 32768, "max_window_layers": 40,
        "model_type": "brumby", "num_attention_heads": 40,
        "num_hidden_layers": 40, "num_key_value_heads": 8,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    conf = harness.load_json(REPO, "chipbench", "configs",
                             "brumby-14b-l8.json")
    differ = {k for k, v in published.items() if conf.get(k) != v}
    assert differ == set(conf["reduced"]) == {"num_hidden_layers"}
    assert conf["published"] == {"num_hidden_layers": 40}
    assert set(conf["assumed"]) >= {"power", "gate", "gate_bias",
                                    "normaliser", "qk_norm_and_rope",
                                    "state_dtype", "initializer_range"}


# --- the cell, end to end, through run.main() ------------------------------
@pytest.fixture
def root(tmp_path, monkeypatch):
    import mxnet_tpu.base

    shutil.copytree(os.path.join(REPO, "chipbench"), tmp_path / "chipbench",
                    ignore=IGNORE)
    shutil.copytree(TOY, tmp_path, dirs_exist_ok=True)
    monkeypatch.setattr(run, "ROOT", str(tmp_path))
    monkeypatch.setattr(run, "PLATFORM", "cpu")
    monkeypatch.setattr(mxnet_tpu.base, "arm_compile_cache",
                        lambda: "(off in the tests)")
    return tmp_path


def test_cell_runs_end_to_end(root, capsys):
    line = last_line(capsys, args("tiny-brumby-backlog", 0))
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"serve_out_tokens_per_s", "setup_s"}
    assert line["device"]["platform"] == "cpu"


def test_traced_run_reports_the_layers_it_can(root, capsys):
    """Counts and host spans, never a device number: the two rooflines,
    ``serve_mfu`` and ``device_idle`` need a device plane."""
    line = last_line(capsys, args("tiny-brumby-backlog", 1))
    assert line["correct"] is True
    assert set(line["metrics"]) == {
        "prefill_chunk_ms.brumby", "lane_fill.brumby",
        "decode_step_ms.brumby", "decode_launch_ms.brumby",
        "tick_host_share.brumby", "prefill_time_share.brumby"}
    assert "busy_s" not in line["device"] and "breakdown" not in line


def test_benchmark_json_names_files_that_exist(root):
    check_benchmark(root)


def test_the_retention_readers_on_a_hand_written_trace():
    """The two rooflines find the kernels by their own names and divide
    the host's counts by the kernels' time; ``serve_mfu`` counts every
    token of the window."""
    import types

    from mxnet_tpu.telemetry import tracing

    sz = flops_brumby.sizes(harness.load_json(
        REPO, "chipbench", "configs", "brumby-14b-l8.json"))
    layers = {n: harness.load_module(REPO, "layers", n) for n in (
        "retention_step_roofline", "retention_chunk_roofline", "serve_mfu")}
    import time

    lo = time.perf_counter()
    for start, n in ((0, 1024), (1024, 1024), (2048, 300)):
        with tracing.span("llm.prefill.chunk",
                          args={"tokens": n, "pad": 1024 - n,
                                "start": start}):
            pass
    hi = time.perf_counter()
    sent = [types.SimpleNamespace(times=[lo] + [lo + 1e-6] * 100)]
    trace = {"by_name": {
        "%power_retention_step f32[8,17,8,128,8320] custom-call": 0.020,
        "%power_retention_chunk f32[8,40,1024,128] custom-call": 0.010,
        "%fusion f32[16,5120]": 5.0}}
    result = {"sizes": sz, "sent": sent, "trace_span": (lo, hi),
              "window": (lo, lo + 40.0)}
    ctx = types.SimpleNamespace(devices=[types.SimpleNamespace(
        device_kind="TPU v5 lite")])
    step = layers["retention_step_roofline"].read(result, trace, ctx)
    assert step == pytest.approx(
        100 * 100 * 8 * 2 * 34_080_768 / 819e9 / 0.020)
    chunk = layers["retention_chunk_roofline"].read(result, trace, ctx)
    assert chunk == pytest.approx(100 * flops_brumby.retention_chunk(
        sz, [(0, 1024), (1024, 1024), (2048, 300)]) / 197e12 / 0.010)
    mfu = layers["serve_mfu"].read(result, trace, ctx)
    assert 0 < mfu < 100
    # nothing to read: nothing reported, and nothing raised
    bare = {"by_name": {"%fusion f32[16,5120]": 5.0}}
    assert layers["retention_step_roofline"].read(result, bare, ctx) is None
    assert layers["retention_chunk_roofline"].read(result, bare, ctx) is None
    assert layers["serve_mfu"].read(result, None, ctx) is None


# --- where the rate's stretch ends ------------------------------------------
def _stretch(*a):
    return harness.load_module(REPO, "runners", "serve_model") \
        .balanced_stretch(*a)


@pytest.mark.parametrize("case", ["balanced-whole", "a-prompt-too-many",
                                  "no-prompt-taken-in", "too-short"])
def test_balanced_stretch_by_hand(case):
    """Ten ticks of a second, 4 tokens out in each, the pool's ratio 5
    prompt tokens to a token out."""
    bounds = [float(t) for t in range(11)]
    tokens = [t + 0.5 for t in range(10) for _ in range(4)]
    first = {"balanced-whole": [(2.5, 100), (7.5, 100)],
             "a-prompt-too-many": [(0.5, 100), (2.5, 100), (7.5, 60)],
             "no-prompt-taken-in": [],
             "too-short": [(4.5, 40)]}[case]
    got = _stretch(bounds, tokens, first, 5.0, 5.0)
    assert got == {"balanced-whole": (0.0, 10.0),
                   # 160 prompt tokens want 32 tokens out: ticks 1 to 9
                   "a-prompt-too-many": (1.0, 9.0),
                   "no-prompt-taken-in": None,
                   # 40 want 8: two ticks, under the least length
                   "too-short": None}[case]


def _simulated_backlog(seed, traffic, chunk_s=0.0556, step_s=0.0311,
                       lanes=16, until=56.0):
    """Tick boundaries, token times and first tokens of an engine whose
    lanes are always full: a tick takes in prompts for its free lanes
    (``chunk_s`` a chunk of 1,024), then decodes one step."""
    requests = harness.load_module(REPO, "traffic", "requests")
    stream = requests.draw(traffic, 8, seed)
    cut = onp.random.RandomState((seed + 2) % 2**32)
    t, left, sent = 0.0, [], 0
    bounds, tokens, first = [], [], []
    while t < until:
        bounds.append(t)
        while len(left) < lanes:
            prompt, new = next(stream)
            if sent < lanes:
                new = int(cut.randint(1, new + 1))
            sent += 1
            t += -(-len(prompt) // 1024) * chunk_s
            tokens.append(t)
            first.append((t, len(prompt)))
            left.append(new - 1)
        left = [n for n in left if n > 0]
        t += step_s
        tokens.extend([t] * len(left))
        left = [n - 1 for n in left]
    return bounds, tokens, first


def test_balanced_stretch_steadies_the_cells_traffic():
    """On the cell's own traffic, with the chip's times for a chunk and a
    step: a window cut by the clock follows the order of the sizes by
    more than half the bound; the balanced stretch by under a third of
    that, reads the same rate, and moves as the clock's window does with a
    faster step."""
    import statistics

    serve_model = harness.load_module(REPO, "runners", "serve_model")
    requests = harness.load_module(REPO, "traffic", "requests")
    traffic = harness.load_json(REPO, "chipbench", "traffic",
                                "backlog-8k.json")
    pool = requests.size_pool(traffic)
    ratio = sum(p for p, _, _ in pool) / sum(n for _, n, _ in pool)
    assert ratio == pytest.approx(283641 / 24576)

    def rates(step_s):
        clock, balanced = [], []
        for i in range(24):
            bounds, tokens, first = _simulated_backlog(
                2147480000 + 104729 * i, traffic, step_s=step_s)
            inside = [t for t in bounds if 15.0 <= t < 55.0]
            tokens = onp.asarray(tokens)
            lo, hi = inside[0], inside[-1]
            clock.append(((tokens >= lo) & (tokens < hi)).sum() / (hi - lo))
            lo, hi = serve_model.balanced_stretch(inside, tokens, first,
                                                  ratio, 20.0)
            assert hi - lo >= 20.0 and lo >= inside[0] and hi <= inside[-1]
            out = ((tokens >= lo) & (tokens < hi)).sum()
            taken = sum(n for t, n in first if lo <= t < hi)
            assert abs(taken / out - ratio) <= serve_model.BALANCE_TOL * ratio
            balanced.append(out / (hi - lo))
        return clock, balanced

    def spread(v):
        q = statistics.quantiles(v, n=4)
        return (q[2] - q[0]) / statistics.median(v)

    clock, balanced = rates(0.0311)
    assert spread(clock) > 0.015 > 3 * spread(balanced)
    assert statistics.median(balanced) == pytest.approx(
        statistics.median(clock), rel=0.01)
    faster_clock, faster = rates(0.0280)
    assert statistics.median(faster) / statistics.median(balanced) \
        == pytest.approx(statistics.median(faster_clock)
                         / statistics.median(clock), rel=0.005)
