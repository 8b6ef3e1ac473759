"""The ``qwen3-next-80b-ep4-l8`` configuration's pieces at toy sizes on
the CPU: the program's prefill-in-chunks-then-decode through blocks and
slots against the plain reference (``chipbench/reference/qwen3next.py``:
the delta rule token by token, every query against every key, the experts
one by one), **logits** compared; the mutants the comparison must catch;
the share test (the four chips' routed parts and the shared expert
counted once add up to the uncut layer); the operation counts against
hand arithmetic; and the cell rehearsed end to end through ``run.main()``
with ``test_chipbench.py``'s machinery over toy files of its own
(``data/tiny_qwen3next``). No topology is described in this file.
"""
import json
import os
import shutil
import time
import types

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from test_chipbench import IGNORE, REPO, args, check_benchmark, last_line

from chipbench import flops_qwen3next as counts, harness, run    # noqa: E402
from chipbench.reference import qwen3next as reference           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "data", "tiny_qwen3next")

# one period: three delta-rule layers and a full one; experts 4-7 of 16
SZ = dict(vocab_size=256, units=64, num_layers=4, full_attention_interval=4,
          num_heads=4, num_kv_heads=2, head_dim=16, rotary_dim=4,
          rope_theta=1e7, linear_key_heads=2, linear_value_heads=4,
          linear_key_dim=8, linear_value_dim=8, conv_width=4, num_experts=16,
          experts_per_token=4, expert_size=32, shared_expert_size=32,
          experts_held=4, first_expert=4, max_length=512, epsilon=1e-6)
CONFIG = {"assumed_values": {"half_life_tokens": [4, 64],
                             "norm_spread": 0.1}}
BS, ANSWER = 16, 10


def make_net(dtype, sz=SZ, chunk=64, config=CONFIG):
    """The toy model with the benchmark's weights: everything random, the
    delta rule's half-lives spread (4 to 64 tokens here) so that what a
    chunk hands on matters, norm weights and taps as the cell draws
    them."""
    from chipbench.runners import serve_model
    from mxnet_tpu.gluon.model_zoo import qwen3next

    net = serve_model.make_net(
        qwen3next.qwen3next_like, dict(sz, prefill_chunk=chunk), dtype, 7,
        0.2, counts.overrides(config, sz, 7))
    # at 64 units and experts of 32 drawn at 0.2 the expert half would be
    # four times the mixers' in the residual, and one choice of expert
    # that falls the other way in bfloat16 (the 4th and 5th of 16
    # probabilities lie 0.01 apart) would move a logit further than any
    # mutant. At the published widths it is a tenth of the mixers': so
    # here
    for name, p in net.collect_params().items():
        if name.endswith(("experts.down", "shared.down_proj.weight")):
            p.set_data((harness.raw(p.data()) * 0.15).astype(p.dtype))
    return net


def params_of(net):
    return {k: harness.raw(p.data()) for k, p in net.collect_params().items()}


def run_program(net, seq, prompt, chunk=64, lane=1, decode=None):
    """What the engine's two programs compute for one lane: the prompt in
    chunks of ``chunk`` through a scattered block table into slot
    ``lane`` (the last chunk padded), then the answer's tokens one decode
    step at a time beside an idle lane, teacher-forced. Returns the
    logits of the rows that choose the answer's tokens and the pools."""
    import mxnet_tpu.numpy as mxnp

    i32 = lambda x: mxnp.array(onp.asarray(x, onp.int32))   # noqa: E731
    dtype = "float32" if net.lm_head.weight.dtype == onp.float32 \
        else "bfloat16"
    mb = -(-len(seq) // BS) + 1
    trash = mb + 5
    pools = net.init_block_pool(trash + 1, BS, dtype=dtype, state_slots=3)
    table = onp.full((2, mb), trash, onp.int32)
    table[lane] = onp.random.RandomState(3).permutation(trash)[:mb]
    rows = []
    for start in range(0, prompt, chunk):
        n = min(chunk, prompt - start)
        toks = onp.zeros((1, chunk), onp.int32)
        toks[0, :n] = seq[start:start + n]
        last, _, *pools = net.prefill_chunk_step(
            i32(toks), *pools, i32(lane), i32(table[lane]), i32(start),
            i32(n))
    rows.append(harness.raw(last)[0])
    for pos in range(prompt, len(seq) - 1 if decode is None else decode):
        toks, where = onp.zeros((2, 1), onp.int32), onp.zeros(2, onp.int32)
        toks[lane], where[lane] = seq[pos], pos
        logits, _, *pools = net.decode_step_paged(
            i32(toks), *pools, i32(table), i32(where))
        rows.append(harness.raw(logits)[lane, 0])
    return onp.asarray(jnp.stack(rows), onp.float32), pools


def steps_apart(net, seq, prompt, chunk=64):
    """The largest distance between the program's and the reference's
    logits over the checked rows, in bf16 steps of each row's best logit
    (the unit of ``TIE_STEPS``), and in absolute terms."""
    want = onp.asarray(reference.logits(
        params_of(net), seq, SZ, onp.arange(prompt - 1, len(seq) - 1)))
    got, _ = run_program(net, seq, prompt, chunk)
    diff = onp.abs(got - want).max(-1)
    best = want.max(-1)
    steps = reference.bf16_steps_behind(best, best - diff)
    return float(diff.max()), float(steps.max())


def sequence(prompt):
    return onp.random.RandomState(11).randint(
        0, SZ["vocab_size"], (prompt + ANSWER,)).astype(onp.int32)


# What the comparison allows, in bf16 steps of a row's best logit. Float32
# weights: the two formulations differ by float32 rounding (0.004 steps).
# bfloat16 weights: the reference upcasts the same parameters, the
# program rounds every projection's result to bfloat16 and routes by its
# own logits, so a token near a tie takes another expert here and there:
# 12.0 steps on this toy, where the weakest mutant reads 27.
LIMIT = {"float32": 0.05, "bfloat16": 20.0}


@pytest.mark.parametrize("dtype,prompt,chunk", [
    ("float32", 150, 64),       # two chunks and 22 tokens of a third
    ("float32", 128, 64),       # the chunk divides the prompt
    ("float32", 2, 64),         # shorter than the convolution
    ("float32", 70, 128),       # one chunk, mostly padding
    ("bfloat16", 150, 64),
])
def test_chunks_then_decode_match_the_reference(dtype, prompt, chunk):
    absolute, steps = steps_apart(make_net(dtype, chunk=chunk),
                                  sequence(prompt), prompt, chunk)
    print(f"{dtype}, {prompt} in chunks of {chunk}: {steps:.3f} bf16 steps, "
          f"{absolute:.2e} absolute")
    assert steps < LIMIT[dtype]


def test_padding_rows_change_nothing():
    """The same prompt through chunks of 64 (22 real rows in the last)
    and of 192 (42 rows of padding in the one): the same logits."""
    net, seq = make_net("float32"), sequence(150)
    a, _ = run_program(net, seq, 150, 64)
    b, _ = run_program(make_net("float32", chunk=192), seq, 150, 192)
    onp.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


# --- mutants: each must fail the comparison --------------------------------
def _capacity(monkeypatch, cap):
    """A capacity of ``cap`` assignments an expert: the rest fall out."""
    from mxnet_tpu.ops import experts as EX

    sort = EX.sort_by_expert

    def capped(experts, first, held, real=None):
        e = experts - first
        here = (e >= 0) & (e < held)
        flat = jnp.where(here, e, held).reshape(-1)
        earlier = jnp.cumsum(jax.nn.one_hot(flat, held + 1, dtype=jnp.int32),
                             axis=0)
        place = jnp.take_along_axis(earlier, flat[:, None], 1)[:, 0]
        keep = (place <= cap).reshape(experts.shape)
        return sort(jnp.where(keep, experts, -1), first, held, real)

    monkeypatch.setattr(EX, "sort_by_expert", capped)


def _round_state(monkeypatch):
    """The state kept in bfloat16: rounded after every program."""
    from mxnet_tpu.ops import gated_delta as GD

    def rounded(fn):
        def run(*a, **kw):
            o, pool = fn(*a, **kw)
            return o, jax.lax.reduce_precision(pool, 8, 7)
        return run

    monkeypatch.setattr(GD, "delta_step", rounded(GD.delta_step))
    monkeypatch.setattr(GD, "delta_chunk", rounded(GD.delta_chunk))


def _patch(module, name, make):
    def apply(monkeypatch):
        import importlib

        mod = importlib.import_module("mxnet_tpu.ops." + module)
        monkeypatch.setattr(mod, name, make(getattr(mod, name)))
    return apply


MUTANTS = {
    "state-zeroed-between-chunks": _patch(
        "gated_delta", "delta_chunk", lambda f: lambda *a, **kw: f(
            *a[:8], True, *a[9:], **kw)),
    "tail-dropped-at-a-chunk-boundary": _patch(
        "gated_delta", "conv_chunk", lambda f: lambda x, p, s, ly, w, fresh,
        n: f(x, p, s, ly, w, True, n)),
    "beta-dropped": _patch(
        "gated_delta", "gates", lambda f: lambda *a: (
            f(*a)[0], jnp.ones_like(f(*a)[1]))),
    "decay-dropped": _patch(
        "gated_delta", "gates", lambda f: lambda *a: (
            jnp.zeros_like(f(*a)[0]), f(*a)[1])),
    "delta-output-gate-dropped": _patch(
        "gated_delta", "gated_norm", lambda f: lambda o, z, w, eps=1e-6: f(
            o, jnp.full_like(z, 1.2785), w, eps) / 1.0),
    "attention-output-gate-dropped": _patch(
        "gated_attention", "output_gate",
        lambda f: lambda o, gate: o.astype(jnp.float32)),
    "one-plus-w-read-as-w": _patch(
        "gated_attention", "rms0", lambda f: lambda x, w, eps=1e-6: f(
            x, w - 1.0, eps)),
    "renormalisation-dropped": _patch(
        "experts", "route", lambda f: lambda logits, k: (
            f(logits, k)[0], f(logits, k)[1] * jnp.sum(jax.lax.top_k(
                jax.nn.softmax(logits.astype(jnp.float32), -1), k)[0], -1,
                keepdims=True))),
    "shared-gate-dropped": _patch(
        "experts", "shared_gate",
        lambda f: lambda x, w: jnp.ones((x.shape[0], 1), jnp.float32)),
    "a-token-dropped-by-a-capacity": lambda mp: _capacity(mp, 20),
    "state-in-bfloat16": _round_state,
}


def _rotary_on_every_value(net):
    for i in range(SZ["num_layers"]):
        mixer = getattr(net, f"layer{i}").mixer
        if hasattr(mixer, "_rot"):
            mixer._rot = SZ["head_dim"]


@pytest.mark.parametrize("mutant", sorted(MUTANTS) + ["rotary-on-all"])
def test_every_mutant_fails_the_comparison(mutant, monkeypatch):
    """On float32 weights, where the program reads under 0.05 steps,
    every mutant is far over the limit; on bfloat16 weights those that
    change the mathematics are over the limit the program passes there
    (a state in bfloat16 fails the states' rule instead, below)."""
    if mutant in MUTANTS:
        MUTANTS[mutant](monkeypatch)
    seq = sequence(150)
    for dtype in sorted(LIMIT):
        net = make_net(dtype)
        if mutant == "rotary-on-all":
            _rotary_on_every_value(net)
        absolute, steps = steps_apart(net, seq, 150)
        print(f"{mutant}, {dtype}: {steps:.1f} bf16 steps, "
              f"{absolute:.2e} absolute")
        if dtype == "float32":
            assert steps > 20 * LIMIT[dtype]
        elif mutant != "state-in-bfloat16":
            assert steps > LIMIT[dtype]


# --- the states' own rule ---------------------------------------------------
LONG = dict(SZ, max_length=2048)
LONG_CONFIG = {"assumed_values": {"half_life_tokens": [64, 2048],
                                  "norm_spread": 0.1}}


def state_apart(dtype):
    """The reference's ``state_apart`` of the state the two programs
    leave in slot 1 after 640 tokens in chunks and 128 decode steps."""
    from mxnet_tpu.ops import gated_delta as GD

    net = make_net(dtype, LONG, 64, LONG_CONFIG)
    seq = onp.random.RandomState(11).randint(
        0, LONG["vocab_size"], (769,)).astype(onp.int32)
    _, pools = run_program(net, seq, 640, 64, decode=768)
    got = GD.state_readings(harness.raw(pools[2])[:, 1],
                            harness.raw(pools[3])[:, 1],
                            reference.probes(LONG, 7))
    return reference.state_apart(params_of(net), seq, 768, got, LONG, 768, 7)


def test_a_state_in_bfloat16_fails_the_states_rule(monkeypatch):
    """The reading that ``correct`` holds the timed engine's states to
    (``reference.state_apart``), on float32 weights: the program reads
    float32 rounding, a state rounded to bfloat16 after every program
    3.5% after 768 tokens at half-lives of 64 to 2,048 — four thousand
    times the program's, and over the float32 limit of this test. On
    bfloat16 weights a toy of 64 units cannot tell the two apart (the
    hidden rows' own rounding reads 6% on either), which is why
    ``STATE_LIMIT`` is set between two readings at the published widths
    on the chip: the program's worst 7.0%, this control's 12.6%."""
    sound = state_apart("float32")
    _round_state(monkeypatch)
    mutant = state_apart("float32")
    print(f"the program {sound}, the state in bfloat16 {mutant}")
    assert max(sound.values()) < 1e-4 < 1e-3 < mutant["S"]
    assert set(sound) == set(reference.STATE_LIMIT)
    assert all(sound[k] < reference.STATE_LIMIT[k] for k in sound)


# --- the share --------------------------------------------------------------
def test_the_four_shares_and_the_shared_expert_once_add_up_to_the_layer():
    """The guide's share test. One expert layer's rows through the
    program's ``SparseExperts`` as each of four chips holds it (experts
    0-3, 4-7, 8-11, 12-15 of 16): the four routed parts, plus what every
    chip computes alike — the shared expert — counted once, equal the
    uncut layer of the reference (all 16 experts held)."""
    from mxnet_tpu.ops import experts as EX

    net = make_net("float32", dict(SZ, experts_held=16, first_expert=0))
    p = {k[len("layer0."):]: v for k, v in params_of(net).items()
         if k.startswith("layer0.")}
    whole = dict(SZ, experts_held=16, first_expert=0)
    h2 = jnp.asarray(onp.random.RandomState(2).randn(50, SZ["units"]),
                     jnp.float32)
    with jax.default_matmul_precision("highest"):
        idx, w = reference._route(h2, p, whole, 50)
        want = reference._experts(h2, idx, w, p, whole) \
            + reference._shared(h2, p)
        logits = h2 @ p["experts.router.weight"].T
        ids, wt = EX.route(logits, SZ["experts_per_token"])
        parts = [EX.moe_grouped_ffn(
            h2, ids, wt, *(p["experts." + n][first:first + 4]
                           for n in ("gate", "up", "down")), first)[0]
            for first in (0, 4, 8, 12)]
        shared = reference._shared(h2, p)
    onp.testing.assert_allclose(sum(parts) + shared, want, rtol=1e-4,
                                atol=1e-6)
    # and one share is not the layer
    assert float(jnp.abs(parts[1] + shared - want).max()) > 1e-3


# --- the counts, against hand arithmetic ------------------------------------
def real_sizes():
    return counts.sizes(harness.load_json(
        REPO, "chipbench", "configs", "qwen3-next-80b-ep4-l8.json"))


def test_operation_and_byte_counts_against_hand_arithmetic():
    sz = real_sizes()
    assert counts.expert_params(sz) == 3_145_728
    assert counts.expert_bytes(sz) == 6_291_456
    assert counts.mixer_params(sz, full=False) == 33_718_464
    assert counts.mixer_params(sz, full=True) == 27_263_488
    assert counts.layer_params(sz, full=False) == 37_918_912
    assert counts.layer_params(sz, full=True) == 31_463_936
    assert (counts.full_layers(sz), counts.delta_layers(sz)) == (2, 6)
    assert 6 * 37_918_912 + 2 * 31_463_936 == 290_441_344
    assert counts.state_bytes(sz) == 2_097_152
    assert counts.tail_bytes(sz) == 98_304
    assert counts.kv_token_bytes(sz) == 2_048
    assert sz["num_experts"] == 512 and sz["experts_held"] == 128
    assert sz["rotary_dim"] == 64
    # matmul weights a token meets outside the routed experts: the layers'
    # less their norms, taps and the delta rule's three vectors, + head
    outside = 290_441_344 - 8 * 4_096 - 2 * 512 \
        - 6 * (4 * 8_192 + 32 + 32 + 128)
    assert counts.matmul_params(sz, head=False) == outside
    assert counts.matmul_params(sz) == outside + 37_984 * 2_048
    assert counts.expected_touched(sz, 64) == pytest.approx(91.78, abs=0.01)
    ops, nbytes = counts.expert_work(sz, 160, 92)
    assert ops == 160 * 6 * 2_048 * 512 and nbytes == 92 * 6_291_456
    ops, nbytes = counts.delta_step(sz, 64)
    assert nbytes == 64 * 6 * 2 * 2_097_152
    assert ops == 64 * 6 * 32 * 128 * 128 * 7
    ops, nbytes = counts.attention_decode(sz, [1000, 3000])
    assert nbytes == 2 * 2_048 * 4000 and ops == 2 * 4 * 16 * 256 * 4000
    assert counts.attention_chunks(sz, [(2048, 2048)]) == \
        2 * 4 * 16 * 256 * (2048 * 2048 + 2048 * 2049 / 2)
    drawn = counts.overrides({"assumed_values": {
        "half_life_tokens": [16, 4096], "norm_spread": 0.1}}, sz, 1)
    a_log = drawn["layer0.mixer.a_log"]
    half = onp.log(2) / (onp.exp(a_log) * onp.log1p(onp.e))
    onp.testing.assert_allclose(
        sorted(half), onp.exp(onp.linspace(onp.log(16), onp.log(4096), 32)),
        rtol=1e-5)
    assert "layer3.mixer.a_log" not in drawn
    assert "layer3.mixer.q_norm" in drawn


def test_the_configuration_keeps_every_published_number():
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    conf = harness.load_json(REPO, "chipbench", "configs",
                             "qwen3-next-80b-ep4-l8.json")
    differ = {k for k, v in published.items() if conf.get(k) != v}
    assert differ == set(conf["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert conf["published"] == {k: published[k] for k in differ}
    assert (conf["num_hidden_layers"], conf["num_experts"],
            conf["vocab_size"]) == (8, 128, 37984)
    assert set(conf["assumed"]) >= {
        "bias", "rotary", "fused_order", "state_dtype", "initializer_range",
        "a_log_dt_bias", "norms_and_taps", "mtp"}
    assert "six pipeline stages" in conf["deployment"] and conf["mtp"]


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
    line = last_line(capsys, args("tiny-qwen3next-backlog", 0, seconds=2.0))
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"serve_out_tokens_per_s", "setup_s"}
    assert line["device"]["platform"] == "cpu"


def test_traced_run_reports_the_layers_it_can(root, capsys):
    """Counts and host spans, never a device number: the three rooflines,
    ``serve_step_mfu`` and ``device_idle`` need a device plane."""
    line = last_line(capsys, args("tiny-qwen3next-backlog", 1, seconds=2.0))
    assert line["correct"] is True
    assert set(line["metrics"]) == {
        "expert_load_max_over_mean.q3next", "prefill_chunk_ms.q3next",
        "lane_fill.q3next", "decode_step_ms.q3next",
        "decode_launch_ms.q3next", "tick_host_share.q3next",
        "prefill_time_share.q3next"}
    assert line["metrics"]["expert_load_max_over_mean.q3next"]["value"] >= 1
    assert "busy_s" not in line["device"] and "breakdown" not in line


def test_benchmark_json_names_files_that_exist(root):
    check_benchmark(root)


def test_the_readers_on_a_hand_written_trace():
    """The three rooflines find the kernels by the names the trace prints
    and divide the program's and the host's counts by the kernels' time;
    ``serve_step_mfu`` counts every token of the window and the counted
    assignments; a program without the kernels or the counters gives
    nothing to read and nothing is raised."""
    from mxnet_tpu.telemetry import tracing

    sz = real_sizes()
    names = ("moe_experts_roofline", "delta_step_roofline",
             "paged_gqa_roofline", "serve_step_mfu",
             "expert_load_max_over_mean")
    layers = {n: harness.load_module(REPO, "layers", n) for n in names}
    lo = time.perf_counter()
    for start, n in ((0, 2048), (2048, 300)):
        with tracing.span("llm.prefill.chunk", args={
                "tokens": n, "pad": 2048 - n, "start": start,
                "moe_assignments": n * 20, "moe_experts_touched": 1024}):
            pass
    for _ in range(3):
        with tracing.span("llm.decode.fetch", args={
                "step": 1, "moe_assignments": 1280,
                "moe_experts_touched": 730}):
            pass
    hi = time.perf_counter()
    sent = [types.SimpleNamespace(prompt=onp.zeros(4000),
                                  times=[lo] + [lo + 1e-6] * 100)]
    trace = {"by_name": {
        "%moe_grouped_ffn bf16[640,2048] custom-call": 0.030,
        "%moe_grouped_ffn bf16[20480,2048] custom-call": 0.020,
        "%gated_delta_step f32[6,65,32,128,128] custom-call": 0.004,
        "%run bf16[64,16,256] custom-call": 0.002,
        "%fusion f32[64,2048]": 5.0}}
    stats = [{"counters": {"moe_assignments": n},
              "expert_load_max_over_mean": {"count": c, "mean": m}}
             for n, c, m in ((1000, 10, 3.0), (51000, 30, 4.0))]
    result = {"sizes": sz, "sent": sent, "trace_span": (lo, hi),
              "window": (lo, lo + 40.0), "kv_dtype": "bfloat16",
              "stats_open": stats[0], "stats_close": stats[1]}
    ctx = types.SimpleNamespace(devices=[types.SimpleNamespace(
        device_kind="TPU v5 lite")])
    hit, touched = 2348 * 20 + 3 * 1280, 2 * 1024 + 3 * 730
    floor = max(touched * 6_291_456 / 819e9, hit * 6 * 2048 * 512 / 197e12)
    assert layers["moe_experts_roofline"].read(result, trace, ctx) == \
        pytest.approx(100 * floor / 0.050)
    assert layers["delta_step_roofline"].read(result, trace, ctx) == \
        pytest.approx(100 * 100 * 6 * 2 * 2_097_152 / 819e9 / 0.004)
    contexts = sum(4000 + i for i in range(1, 101))
    assert layers["paged_gqa_roofline"].read(result, trace, ctx) == \
        pytest.approx(100 * contexts * 2 * 2_048 / 819e9 / 0.002)
    mfu = layers["serve_step_mfu"].read(result, trace, ctx)
    assert 0 < mfu < 100
    assert layers["expert_load_max_over_mean"].read(result, trace, ctx) == \
        pytest.approx((30 * 4.0 - 10 * 3.0) / 20)
    # nothing to read: nothing reported, and nothing raised
    bare = {"by_name": {"%fusion f32[16,5120]": 5.0}}
    old = dict(result, trace_span=(hi, hi + 1e-9), stats_open={
        "counters": {}}, stats_close={"counters": {}})
    for name in names[:3]:
        assert layers[name].read(result, bare, ctx) is None
        assert layers[name].read(result, None, ctx) is None
    assert layers["moe_experts_roofline"].read(old, trace, ctx) is None
    assert layers["serve_step_mfu"].read(old, trace, ctx) is None
    assert layers["serve_step_mfu"].read(result, None, ctx) is None
    assert layers["expert_load_max_over_mean"].read(old, None, ctx) is None


@pytest.mark.parametrize("stall", [False, True])
def test_stretch_padding_counts_a_runs_chunks_from_its_ticks(stall):
    """``benchmark/serve_stretch_padding.py`` on a run's notes written by
    hand: 100 ticks of 20 ms for 4 lanes, ten of them admitting a prompt
    of 3 chunks of 64 at 50 ms a chunk; the stretch is ticks 10 to 89
    (eight of the prompts), which took in 8 x 150 prompt tokens: 21.9% of
    its 24 chunks' rows are padding. A tick of 2 s is named a stall."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "serve_stretch_padding",
        os.path.join(REPO, "benchmark", "serve_stretch_padding.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    ticks = [20.0] * 100
    for i in range(5, 100, 10):
        ticks[i] = 20.0 + 3 * 50.0
    if stall:
        ticks[50] = 2000.0
    sec = sum(ticks[10:90]) / 1e3
    text = "\n".join([
        "chipbench: window: 9 requests sent in all, 8 inside the window, 8 "
        f"ended inside it (0 failed), 320 tokens out and 1200 prompt tokens "
        f"in over the balanced stretch of {sec:.3f} s (the pool's ratio "
        f"3.750; all 100 whole ticks, {sum(ticks) / 1e3:.3f} s: 400 tokens "
        "out, 1.0 a second), 0 gaps",
        "chipbench: step_ms: " + " ".join(f"{t:.1f}" for t in ticks),
        json.dumps({"correct": True, "metrics": {"serve_out_tokens_per_s": {
            "value": 320 / sec, "unit": "tokens/s"}}})])
    rate, pad, ms, step, ratio, lanes, stalled = tool.read_run(
        text, 64, 50.0, 1200.0)
    assert (step, ratio, lanes, stalled) == (20.0, 3.75, 4, stall)
    assert rate == pytest.approx(320 / sec)
    if not stall:
        assert pad == pytest.approx(1 - 1200 / (24 * 64))
        assert ms == pytest.approx(50.0)
