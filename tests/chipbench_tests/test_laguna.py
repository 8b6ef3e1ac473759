"""The ``laguna-s-2.1-ep8-l12`` configuration's cell rehearsed on the CPU:
a toy of the dense layer and two periods with a window of 16
(``data/tiny_laguna``) through ``run.main()`` with ``test_chipbench.py``'s
machinery — ``correct`` true by the tokens' rule and the rings' rule, the
readers that can report without a device reporting — a control whose
band is off by one reading ``correct`` false, every new reader on
trace rows written by hand, the operation and byte counts against hand
arithmetic, and the configuration file against the published keys. (The
model against the reference on logits: ``tests/test_laguna_serving.py``.)
"""
import os
import shutil
import time
import types

import numpy as onp
import pytest

from test_chipbench import IGNORE, REPO, args, check_benchmark, last_line

from chipbench import flops_laguna as counts, harness, run    # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "data", "tiny_laguna")
CELL = "tiny-laguna-backlog"


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


# --- the cell, end to end, through run.main() ------------------------------
def test_cell_runs_end_to_end(root, capsys):
    line = last_line(capsys, args(CELL, 0, seconds=2.0))
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"serve_out_tokens_per_s", "setup_s"}
    assert line["device"]["platform"] == "cpu"


def test_traced_run_reports_the_layers_it_can(root, capsys):
    """Counts and host spans, never a device number: the three rooflines,
    ``mixed_attn_step_mfu`` and ``device_idle`` need a device plane."""
    line = last_line(capsys, args(CELL, 1, seconds=2.0))
    assert line["correct"] is True
    assert set(line["metrics"]) == {
        "window_rows_share.laguna", "expert_load_max_over_mean.laguna",
        "prefill_chunk_ms.laguna", "lane_fill.laguna",
        "decode_step_ms.laguna", "decode_launch_ms.laguna",
        "tick_host_share.laguna", "prefill_time_share.laguna"}
    # 3 full layers keep every row, 6 window layers at most 16 of 24 to
    # 58: between 3/9 of the rows and all of them
    assert 40 < line["metrics"]["window_rows_share.laguna"]["value"] < 100
    assert "busy_s" not in line["device"] and "breakdown" not in line


@pytest.mark.parametrize("band", ["short", "long"])
def test_a_window_off_by_one_reads_not_correct(root, capsys, monkeypatch,
                                               band):
    """A chunk's band of 15 or 17 positions where the configuration, and
    so the reference, says 16: the control that ran on the chip, one line
    of the program's source replaced. Every prompt of the toy's traffic
    is longer than the window, and the rows of the sixth window layer
    carry what five before it did with a key too few or too many (24-42%
    apart against ``STATE_LIMIT``'s 7%; one period reads 7-17%)."""
    from mxnet_tpu.ops import gated_attention as GA
    from test_laguna_serving import BAND, OFF_BY_ONE, replaced

    monkeypatch.setattr(GA, "window_chunk_attention", replaced(
        GA, BAND, OFF_BY_ONE[band])["window_chunk_attention"])
    line = last_line(capsys, args(CELL, 0, seconds=2.0))
    assert line["correct"] is False and line["failed"] == 0


def test_benchmark_json_names_files_that_exist(root):
    check_benchmark(root)


def test_the_real_benchmark_has_the_cell_as_the_issue_names_it():
    bench = harness.load_json(REPO, "BENCHMARK.json")
    cell = [w for w in bench["workloads"]
            if w["name"] == "laguna-serve-backlog-32k"]
    assert cell == [dict(cell[0], config="laguna-s-2.1-ep8-l12",
                         traffic="backlog-32k", chips=1)]
    assert len(cell[0]["why"]) <= 200 and "8x" in cell[0]["why"]
    traffic = harness.load_json(REPO, "chipbench", "traffic",
                                "backlog-32k.json")
    assert (traffic["kind"], traffic["waiting"], traffic["pool"]) == (
        "backlog", 24, 256)
    assert traffic["classes"] == [{
        "share": 1.0, "prompt": {"lo": 1024, "hi": 32768},
        "new_tokens": {"lo": 512, "hi": 1024}}]
    work = harness.load_json(REPO, "chipbench", "workloads",
                             "laguna-serve-backlog-32k.json")
    assert work["runner"] == "serve_model"
    assert work["model"]["extra"] == {"prefill_chunk": 1024}
    eng = work["engine"]
    assert (eng["max_running"], eng["block_size"], eng["kv_cache_dtype"],
            eng["max_context"], eng["num_blocks"]) == (
        24, 16, "bfloat16", 33792, 25800)
    rate = [m for m in bench["end_to_end"]
            if m["name"] == "serve_out_tokens_per_s"][0]
    assert rate["workloads"][-1] == "laguna-serve-backlog-32k"
    mine = {m["name"]: m for m in bench["per_layer"]
            if m["name"].endswith(".laguna")}
    assert len(mine) == 13 and all(
        m["workloads"] == ["laguna-serve-backlog-32k"]
        and m["moves"] == "serve_out_tokens_per_s" for m in mine.values())
    assert "mfu" in "mixed_attn_step_mfu.laguna" in mine
    assert {"paged_full_roofline.laguna", "paged_window_roofline.laguna",
            "window_rows_share.laguna"} <= set(mine)


# --- the readers, on rows written by hand ------------------------------------
def real_sizes():
    return counts.sizes(harness.load_json(
        REPO, "chipbench", "configs", "laguna-s-2.1-ep8-l12.json"))


def test_the_readers_on_a_hand_written_trace():
    """The two paged rooflines find their kernel's calls by the shapes
    the calls print and divide the live rows' bytes by their time;
    ``mixed_attn_step_mfu`` counts every token of the window, the counted
    assignments and both kinds of attention; ``window_rows_share`` reads
    the two gauges from the ticks; the accepted ``moe_experts_roofline``
    reads this model's experts under its suffix. A program without the
    kernels, the counters or the gauges gives nothing to read and nothing
    is raised."""
    from mxnet_tpu.telemetry import tracing

    sz = real_sizes()
    names = ("paged_full_roofline", "paged_window_roofline",
             "mixed_attn_step_mfu", "window_rows_share",
             "moe_experts_roofline")
    layers = {n: harness.load_module(REPO, "layers", n) for n in names}
    lo = time.perf_counter()
    for start, n in ((0, 1024), (1024, 300)):
        with tracing.span("llm.prefill.chunk", args={
                "tokens": n, "pad": 1024 - n, "start": start,
                "moe_assignments": n, "moe_experts_touched": 352}):
            pass
    for _ in range(3):
        with tracing.span("llm.decode.fetch", args={
                "step": 1, "moe_assignments": 30,
                "moe_experts_touched": 200}):
            pass
    for held in ((3 * 10000, 9 * 512), (3 * 300, 9 * 300)):
        with tracing.span("llm.tick", args={
                "active": 1, "kv_rows_full": held[0],
                "kv_rows_window": held[1]}):
            pass
    with tracing.span("llm.tick", args={"active": 0, "kv_rows_full": 0,
                                        "kv_rows_window": 0}):
        pass
    hi = time.perf_counter()
    sent = [types.SimpleNamespace(prompt=onp.zeros(4000),
                                  times=[lo] + [lo + 1e-6] * 100),
            types.SimpleNamespace(prompt=onp.zeros(100),
                                  times=[lo] + [lo + 1e-6] * 100)]
    trace = {"by_name": {
        "%run bf16[24,48,128] custom-call": 0.004,
        "%run bf16[24,72,128] custom-call": 0.002,
        "%moe_grouped_ffn bf16[240,3072] custom-call": 0.030,
        "%fusion f32[24,3072]": 5.0}}
    stats = [{"counters": {"moe_assignments": n}} for n in (1000, 51000)]
    result = {"sizes": sz, "sent": sent, "trace_span": (lo, hi),
              "window": (lo, lo + 40.0), "kv_dtype": "bfloat16",
              "lanes": 24, "stats_open": stats[0], "stats_close": stats[1]}
    ctx = types.SimpleNamespace(devices=[types.SimpleNamespace(
        device_kind="TPU v5 lite")])
    contexts = [4000 + i for i in range(1, 101)] \
        + [100 + i for i in range(1, 101)]
    assert layers["paged_full_roofline"].read(result, trace, ctx) == \
        pytest.approx(100 * sum(contexts) * 3 * 4096 / 819e9 / 0.004)
    live = sum(min(c, 512) for c in contexts)
    assert live == 100 * 512 + sum(range(101, 201))
    assert layers["paged_window_roofline"].read(result, trace, ctx) == \
        pytest.approx(100 * live * 9 * 4096 / 819e9 / 0.002)
    mfu = layers["mixed_attn_step_mfu"].read(result, trace, ctx)
    assert 0 < mfu < 100
    assert layers["window_rows_share"].read(result, trace, ctx) == \
        pytest.approx(100 * ((30000 + 4608) / 120000 + 1.0) / 2)
    hit, touched = 1324 + 90, 2 * 352 + 3 * 200
    assert layers["moe_experts_roofline"].read(result, trace, ctx) == \
        pytest.approx(100 * max(touched * 18_874_368 / 819e9,
                                hit * 6 * 3072 * 1024 / 197e12) / 0.030)
    # nothing to read: nothing reported, and nothing raised
    bare = {"by_name": {"%fusion f32[16,5120]": 5.0,
                        "%run bf16[64,16,256] custom-call": 1.0}}
    old = dict(result, window=(hi, hi + 1e-9), trace_span=(hi, hi + 1e-9),
               stats_open={"counters": {}}, stats_close={"counters": {}})
    for name in names[:2]:
        assert layers[name].read(result, bare, ctx) is None
        assert layers[name].read(result, None, ctx) is None
        assert layers[name].read(old, trace, ctx) is None
    assert layers["mixed_attn_step_mfu"].read(old, trace, ctx) is None
    assert layers["mixed_attn_step_mfu"].read(result, None, ctx) is None
    assert layers["window_rows_share"].read(old, None, ctx) is None


# --- the counts, against hand arithmetic -------------------------------------
def test_operation_and_byte_counts_against_hand_arithmetic():
    sz = real_sizes()
    assert counts.expert_params(sz) == 9_437_184
    assert counts.mixer_params(sz, 0) == counts.mixer_params(sz, 4) \
        == 44_187_648
    assert counts.mixer_params(sz, 1) == 63_135_744
    assert counts.layer_params(sz, 0) == 157_440_000
    assert counts.layer_params(sz, 4) == 54_417_408
    assert counts.layer_params(sz, 1) == 73_365_504
    assert 157_440_000 + 2 * 54_417_408 + 9 * 73_365_504 == 926_564_352
    assert counts.weight_bytes(sz) == {
        "experts": 11 * 32 * 18_874_368, "layers": 2 * 926_564_352,
        "embedding_head": 2 * 2 * 12_544 * 3_072}
    assert sum(counts.weight_bytes(sz).values()) == 8_651_046_912
    assert (counts.layers_of(sz, True), len(counts.layers_of(sz, False))) \
        == ([0, 4, 8], 9)
    assert (counts.kind_heads(sz, True), counts.kind_heads(sz, False)) \
        == (48, 72)
    assert counts.kv_token_bytes(sz) == 4_096
    assert counts.ring_bytes(sz) == 9 * 512 * 4_096 == 18_874_368
    assert sz["num_experts"] == 256 and sz["experts_held"] == 32
    assert (sz["rotary_dim"], sz["window_rotary_dim"]) == (64, 128)
    assert sz["dense_layers"] == (0,) and sz["routed_scale"] == 2.5
    # matmul weights a token meets outside the routed experts: the layers'
    # less their norms, + the head
    assert counts.matmul_params(sz, head=False) == 926_564_352 - 12 * 6_144
    assert counts.matmul_params(sz) == 926_564_352 - 12 * 6_144 \
        + 12_544 * 3_072
    assert counts.rows_held(sz, [10_000, 300]) == (3 * 10_300,
                                                   9 * (512 + 300))
    ops, nbytes = counts.attention_decode(sz, [1000, 300], full=True)
    assert nbytes == 3 * 4_096 * 1300 and ops == 3 * 4 * 48 * 128 * 1300
    ops, nbytes = counts.attention_decode(sz, [1000, 300], full=False)
    assert nbytes == 9 * 4_096 * 812 and ops == 9 * 4 * 72 * 128 * 812
    assert counts.attention_chunks(sz, [(1024, 1024)], full=True) == \
        3 * 4 * 48 * 128 * (1024 * 1024 + 1024 * 1025 / 2)
    assert counts.attention_chunks(sz, [(1024, 1024)], full=False) == \
        9 * 4 * 72 * 128 * 1024 * 512
    # a first chunk: tokens 0..511 see 1..512 positions, the rest 512
    assert counts.attention_chunks(sz, [(0, 1024)], full=False) == \
        9 * 4 * 72 * 128 * (512 * 513 / 2 + 512 * 512)
    drawn = counts.overrides({"assumed_values": {"norm_spread": 0.1}}, sz, 1)
    assert set(drawn) == {"final_norm"} | {
        f"layer{i}.{n}_norm" for i in range(12) for n in ("input", "post")}
    assert abs(float(drawn["layer3.post_norm"].mean()) - 1.0) < 0.02


def test_the_configuration_keeps_every_published_number():
    import json

    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f if '"Laguna-S-2.1"' in line]
    if not rows:
        pytest.skip("the catalog is not beside the guide here")
    published = rows[0]["config"]
    conf = harness.load_json(REPO, "chipbench", "configs",
                             "laguna-s-2.1-ep8-l12.json")
    assert conf["source"] == rows[0]["source_url"]
    differ = {k for k, v in published.items() if conf.get(k) != v}
    assert differ == set(conf["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert conf["published"] == {k: published[k] for k in differ}
    assert (conf["num_hidden_layers"], conf["num_experts"],
            conf["vocab_size"]) == (12, 32, 12544)
    assert set(conf["assumed"]) >= {
        "router_score", "shared_expert", "qk_norm", "initializer_range",
        "rotary_pairing", "fused_order", "weights", "norm_weights"}
    assert "four pipeline stages" in conf["deployment"]
    assert "eight" in conf["deployment"] and "0.94" in conf["deployment"]
    # the bytes the file states are the model's own parameter shapes'
    sz = counts.sizes(conf)
    parts = counts.weight_bytes(sz)
    for n in (parts["experts"], parts["layers"], parts["embedding_head"],
              sum(parts.values()), counts.ring_bytes(sz)):
        assert f"{n:,}" in conf["bytes"], n
