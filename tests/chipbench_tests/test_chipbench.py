"""The benchmark's harness, rehearsed on the CPU at toy sizes.

Nothing here says a word about the chip: ``run.PLATFORM`` is steered from
the test (never by an option of the harness). The toy checkout is a copy
of ``chipbench/`` in ``tmp_path`` with ``data/tiny`` laid over it: a toy
configuration, toy cells and traffic, and a ``BENCHMARK.json`` of their
own in which every reader of ``chipbench/layers/`` has a metric. No test
holds the repo's ``BENCHMARK.json`` to a list of cells, configurations or
metrics: a later PR adds entries and files and edits none that is there,
this file included. No topology is described in this file.
"""
import hashlib
import json
import os
import re
import shutil
import sys

import numpy as onp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chipbench import flops, harness, run, trace_reduce   # noqa: E402
from chipbench.reference import gpt as reference          # noqa: E402

IGNORE = shutil.ignore_patterns("__pycache__")


def make_root(root) -> None:
    """A checkout in miniature under ``root``: a copy of ``chipbench/``
    and the toy files, ``BENCHMARK.json`` among them, over it."""
    shutil.copytree(os.path.join(REPO, "chipbench"),
                    os.path.join(root, "chipbench"), ignore=IGNORE)
    shutil.copytree(os.path.join(HERE, "data", "tiny"), root,
                    dirs_exist_ok=True)


def digest(top) -> dict:
    out = {}
    for d, _, files in os.walk(top):
        for f in files:
            if "__pycache__" not in d:
                p = os.path.join(d, f)
                out[os.path.relpath(p, top)] = hashlib.sha1(
                    open(p, "rb").read()).hexdigest()
    return out


@pytest.fixture
def root(tmp_path, monkeypatch):
    """The toy checkout, with the harness pointed at it and at the CPU.
    The persistent compile cache stays off: arming it would change jax's
    configuration for every other test of this worker."""
    import mxnet_tpu.base

    make_root(tmp_path)
    monkeypatch.setattr(run, "ROOT", str(tmp_path))
    monkeypatch.setattr(run, "PLATFORM", "cpu")
    monkeypatch.setattr(mxnet_tpu.base, "arm_compile_cache",
                        lambda: "(off in the tests)")
    return tmp_path


def last_line(capsys, argv) -> dict:
    assert run.main(argv) == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def args(cell, trace, seconds=1.0, seed=2**31 + 17):
    return ["--workload", cell, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]


# --- every cell, end to end, through run.main() ------------------------------
E2E = {"tiny-train": {"train_tokens_per_s"},
       "tiny-serve-backlog": {"serve_out_tokens_per_s"},
       "tiny-serve-closed": {"ttft_p95_ms", "itl_p95_ms"},
       "tiny-serve-poisson": {"ttft_p95_ms", "itl_p95_ms"},
       "tiny-train-tp4": {"train_tokens_per_s"},
       "tiny-serve-tp4": {"serve_out_tokens_per_s"}}
# what a CPU run may report of the per-layer metrics: counts and host
# spans, never a device number (no device plane in a CPU trace)
LAYERS = {"tiny-train": {"dispatch_ms.train", "step_ms.train"},
          "tiny-serve-backlog": {"lane_fill.backlog",
                                 "decode_step_ms.backlog"},
          "tiny-serve-closed": {"prefill_time_share.closed",
                                "prefill_ms_per_ktok.closed"}}


@pytest.mark.parametrize("cell", sorted(E2E))
def test_cell_runs_end_to_end(root, capsys, cell):
    line = last_line(capsys, args(cell, 0))
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == E2E[cell] | {"setup_s"}
    for m in line["metrics"].values():
        assert m["value"] > 0 and isinstance(m["unit"], str)
    chips = {w["name"]: w["chips"] for w in harness.load_json(
        root, "BENCHMARK.json")["workloads"]}[cell]
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == chips
    assert "memory_peak_bytes" in line["device"]


@pytest.mark.parametrize("cell", sorted(LAYERS))
def test_traced_run_reports_the_layers_it_can(root, capsys, cell):
    line = last_line(capsys, args(cell, 1))
    assert line["correct"] is True
    assert set(line["metrics"]) == LAYERS[cell]
    # no device operation in a CPU trace: no busy time is made up
    assert "busy_s" not in line["device"] and "breakdown" not in line


def test_without_a_tpu_it_refuses_and_prints_no_result(root, capsys,
                                                       monkeypatch):
    monkeypatch.setattr(run, "PLATFORM", "tpu")
    assert run.main(args("tiny-train", 0)) != 0
    out = capsys.readouterr()
    assert out.out.strip() == "" and "needs 1 tpu" in out.err


def test_new_files_are_found_by_name_and_none_is_edited(root, capsys):
    """A configuration, a cell, a traffic mix and kind, a runner and a
    per-layer metric come as new files and new BENCHMARK.json entries."""
    before = digest(root / "chipbench")
    cb = root / "chipbench"
    conf = json.loads((cb / "configs" / "tiny.json").read_text())
    conf.update(name="tiny-wide", n_embd=48, n_head=6)
    (cb / "configs" / "tiny-wide.json").write_text(json.dumps(conf))
    (cb / "traffic" / "rows.py").write_text(
        "import numpy as onp\n"
        "def batches(params, vocab, seed):\n"
        "    x = onp.arange(params['batch'] * params['seq'], dtype=onp.int32)"
        ".reshape(params['batch'], params['seq']) % vocab\n"
        "    y = onp.concatenate([x[:, 1:], onp.full((len(x), 1), -1, "
        "onp.int32)], 1)\n"
        "    return [(x, y.reshape(-1))]\n")
    (cb / "traffic" / "new-rows.json").write_text(
        json.dumps({"kind": "rows", "batch": 1, "seq": 16}))
    (cb / "runners" / "train_twice.py").write_text(
        "from chipbench import harness\n"
        "def run(ctx):\n"
        "    out = harness.load_module(ctx.root, 'runners', 'train').run(ctx)\n"
        "    out['twice'] = 2 * out['attempted']\n"
        "    return out\n")
    cell = json.loads((cb / "workloads" / "tiny-train.json").read_text())
    cell["runner"] = "train_twice"
    (cb / "workloads" / "new-cell.json").write_text(json.dumps(cell))
    (cb / "layers" / "twice.py").write_text(
        "def read(result, trace, ctx):\n    return result['twice']\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-wide", "source": "none",
                             "file": "chipbench/configs/tiny-wide.json",
                             "reduced": [], "why": "toy"})
    bench["workloads"].append({"name": "new-cell", "config": "tiny-wide",
                               "traffic": "new-rows", "chips": 1,
                               "why": "toy"})
    bench["per_layer"].append({
        "name": "twice.new", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "toy",
        "moves": "train_tokens_per_s", "workloads": ["new-cell"]})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("new-cell")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    line = last_line(capsys, args("new-cell", 1))
    assert line["correct"] is True
    assert line["metrics"]["twice.new"] == {
        "value": 2.0 * line["attempted"], "unit": "steps"}
    after = digest(root / "chipbench")
    assert {p: h for p, h in after.items() if p in before} == before
    assert len(after) == len(before) + 6


# --- the reference against the program ---------------------------------------
TINY_KW = dict(vocab_size=96, units=32, hidden_size=64, num_layers=2,
               num_heads=4, max_length=64)


@pytest.fixture(scope="module")
def tiny_net():
    from chipbench.harness import make_net, raw

    net = make_net(TINY_KW, "float32", 11)
    params = {k: raw(p.data()) for k, p in net.collect_params().items()}
    return net, params


def test_reference_matches_gpt_like_in_float32(tiny_net):
    """Logits, loss and the three named gradients. Both sides are float32
    at "highest" matmul precision (conftest sets it for the program), so
    only the order of summation differs: 1e-4 relative on logits of order
    one is a hundred float32 steps — and a missing bias, a wrong mask or
    the tanh GELU in place of the exact one moves them by 1e-2 or more."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd
    from chipbench.harness import raw

    net, params = tiny_net
    rng = onp.random.RandomState(3)
    x = rng.randint(0, 96, (2, 40)).astype(onp.int32)
    labels = onp.concatenate([x[:, 1:], onp.full((2, 1), -1, onp.int32)], 1)
    names = ["encoder.layer0.attn.qkv.weight",
             "encoder.layer1.ffn.ffn_2.weight", "pos_embed"]
    with autograd.record():
        logits = net(mx.np.array(x))
        loss = mx.npx.softmax_cross_entropy(
            logits.reshape(-1, 96), mx.np.array(labels.reshape(-1)))
    loss.backward()
    for i in range(2):
        want = onp.asarray(reference.logits(params, x[i], heads=4))
        onp.testing.assert_allclose(logits.asnumpy()[i], want, rtol=1e-4,
                                    atol=1e-4)
    want_loss, want = reference.loss_and_grads(params, x, labels, names, 4)
    assert abs(float(loss.asnumpy()[0]) - want_loss) <= 1e-5 * want_loss
    for name in names:
        cos, ratio = reference.compare_grad(
            raw(net.collect_params()[name].grad()), want[name])
        assert cos > 1 - 1e-6 and abs(ratio - 1) < 1e-4, (name, cos, ratio)


def _drop_bias(net):
    """The program runs without one bias; the reference gets it back."""
    from chipbench.harness import raw

    p = net.collect_params()["encoder.layer0.ffn.ffn_2.bias"]
    kept = raw(p.data())
    p.set_data(kept * 0)
    return lambda: p.set_data(kept)


MUTATIONS = {
    "none": lambda net: None,
    # the last block attends to later positions too
    "mask": lambda net: setattr(net.encoder.layer1.attn, "_causal", False),
    # two heads of 16 where there are four of 8: another grouping and
    # another 1/sqrt(head size)
    "heads": lambda net: setattr(net.encoder.layer0.attn, "_heads", 2),
    "bias": _drop_bias,
}


@pytest.mark.parametrize("mutation", sorted(MUTATIONS))
def test_the_train_check_passes_the_program_and_fails_a_mutated_one(
        mutation, capsys):
    """``runners/train.check_first_step`` with the limits of
    ``reference/gpt.py``, on a bf16 net: the program as it is passes; a
    wrong mask, a wrong head size and scale, and a dropped bias each
    fail."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd
    from chipbench.harness import make_net

    train = harness.load_module(REPO, "runners", "train")
    net = make_net(TINY_KW, "bfloat16", 2**31 + 5, std=0.1)
    undo = MUTATIONS[mutation](net)
    x, labels = harness.load_module(REPO, "traffic", "steps").batches(
        {"batch": 4, "seq": 64, "distinct_batches": 1}, 96, 7)[0]
    with autograd.record():
        loss = mx.npx.softmax_cross_entropy(
            net(mx.np.array(x)).reshape(-1, 96), mx.np.array(labels))
    loss.backward()
    if undo:
        undo()
    names = ["encoder.layer0.attn.qkv.weight",
             "encoder.layer1.ffn.ffn_2.weight", "pos_embed"]
    ok = train.check_first_step(net, x, labels,
                                float(loss.asnumpy()[0]), names, heads=4)
    print(capsys.readouterr().out)
    assert ok is (mutation == "none")



@pytest.mark.parametrize("kv_cache_dtype, limit", [
    # float32 pools: the engine must choose the reference's own best token
    # up to float32 summation order - a hundredth of a bf16 step
    (None, 0.01),
    # int8 rows round K and V to 8 bits: the benchmark's own limit
    ("int8", reference.TIE_STEPS)])
def test_prefill_and_paged_decode_choose_the_references_tokens(
        tiny_net, kv_cache_dtype, limit):
    """Prefill + paged decode through LLMEngine against the reference's
    full forward: every emitted token's reference logit within ``limit``
    bf16 steps of the reference's best at that position."""
    from mxnet_tpu.serving import LLMEngine

    net, params = tiny_net
    rng = onp.random.RandomState(5)
    prompts = [rng.randint(0, 96, (n,)).astype(onp.int32)
               for n in (3, 9, 17, 40)]
    with LLMEngine(net, kv_cache_dtype=kv_cache_dtype, block_size=4,
                   max_context=64, max_running=4, num_blocks=64) as eng:
        got = [h.wait(timeout=120)
               for h in [eng.submit(p, 12) for p in prompts]]
    for p, g in zip(prompts, got):
        assert len(g) == 12
        behind = reference.tokens_behind(params, p, g, 4, pad_to=64)
        assert behind.max() <= limit, (len(p), behind)
    # and the rule has teeth: a token that is not the best fails the
    # benchmark's limit
    wrong = (onp.asarray(got[0]) + 1) % 96
    assert reference.tokens_behind(params, prompts[0], wrong, 4,
                                   pad_to=64).max() > reference.TIE_STEPS


# --- trace_reduce on a hand-written trace -----------------------------------
def test_trace_reduce_on_a_hand_written_event_list():
    """Two chips, a 100 ns window given by the host span.

    chip 0: a [10,30) b [20,50) a [70,90)   busy 40+20 = 60, gaps
            [0,10) [50,70) [90,100)
    chip 1: a [0,100)                       busy 100, no gap
    host:   step [0,60), inner [45,75) (the shorter wins where both cover)
    """
    events = {
        "device": {
            "/device:TPU:0": [("a", 10.0, 20.0), ("b", 20.0, 30.0),
                              ("a", 70.0, 20.0)],
            "/device:TPU:1": [("a", 0.0, 100.0)],
            "/device:TPU:2": []},
        "host": [("chipbench.win", 0.0, 100.0), ("chipbench.step", 0.0, 60.0),
                 ("chipbench.inner", 45.0, 30.0)]}
    red = trace_reduce.reduce(events, "chipbench.win")
    assert red["chips"] == 2
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx((60 + 100) / 2 * 1e-9)
    assert red["by_name"]["a"] == pytest.approx((20 + 20 + 100) / 2 * 1e-9)
    assert red["by_name"]["b"] == pytest.approx(30 / 2 * 1e-9)
    assert red["idle_by_span"] == pytest.approx({
        "chipbench.inner": 20 / 2 * 1e-9,         # [50,70)
        "chipbench.step": 10 / 2 * 1e-9,          # [0,10)
        "chipbench.win": 10 / 2 * 1e-9})          # [90,100)
    bd = trace_reduce.breakdown(red)
    assert bd["device_ops"][0] == ["a", pytest.approx(70e-9)]
    assert bd["idle_gaps"][0] == ["chipbench.inner", pytest.approx(10e-9)]
    # without the host span the window is first event to last event
    red = trace_reduce.reduce({"device": events["device"], "host": []})
    assert red["window_s"] == pytest.approx(100e-9)
    assert trace_reduce.reduce({"device": {}, "host": []}) is None
    assert trace_reduce.matching(red["by_name"], ("a",)) == \
        pytest.approx(70e-9)


def test_an_operation_is_named_without_its_operands():
    text = ("%transpose_jvp_jit__flash_bwd_pallas___.19 = (f32[96,1024,64]"
            "{2,1,0:T(8,128)}, f32[96,1024,64]{2,1,0}) custom-call(f32[96,"
            "1024,64]{2,1,0} %_flash_forward.3), custom_call_target=\"tpu\"")
    assert trace_reduce.short_name(text) == \
        "%transpose_jvp_jit__flash_bwd_pallas___ f32[96,1024,64] custom-call"
    assert trace_reduce.short_name(
        "%fusion.10 = bf16[50257,768]{1,0:T(8,128)(2,1)} fusion(f32[8] "
        "%_flash_forward.1)") == "%fusion bf16[50257,768]"
    assert trace_reduce.short_name("jit_step") == "jit_step"


# --- peaks and operation counts ----------------------------------------------
def test_peaks_table_knows_the_v5e_and_refuses_the_rest():
    p = flops.peaks("TPU v5 lite")
    assert (p["bf16_tflops"], p["int8_tops"], p["hbm_gbps"], p["hbm_gb"]) \
        == (197.0, 393.0, 819.0, 16.0)
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError, match="not in"):
        flops.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        flops.peaks("cpu")


@pytest.mark.parametrize("name, matmul, train_per_token", [
    # 12 x (4 x 768^2 + 2 x 768 x 3072) + 50257 x 768 = 123,532,032
    # 3 x (2 x 123,532,032 + 12 x 4 x 768 x 512.5) = 797,870,592
    ("gpt2-small", 123_532_032, 797_870_592.0),
    # 36 x (4 x 1280^2 + 2 x 1280 x 5120) + 50257 x 1280 = 772,117,760
    # 3 x (2 x 772,117,760 + 36 x 4 x 1280 x 512.5) = 4,916,098,560
    ("gpt2-large", 772_117_760, 4_916_098_560.0)])
def test_operation_counts_against_hand_arithmetic(name, matmul,
                                                  train_per_token):
    with open(os.path.join(REPO, "chipbench", "configs", name + ".json")) as f:
        sz = flops.sizes(json.load(f))
    assert flops.matmul_params(sz) == matmul
    assert flops.train_flops_per_token(sz, 1024) == train_per_token


def test_kernel_floors_against_hand_arithmetic():
    peak = flops.peaks("TPU v5 lite")
    # flash forward at 8 x 12 heads x 1024 x 64: 96 x 1024 x 1025 / 2
    # pairs x 4 x 64 = 12,897,484,800 operations -> 65.5 us at 197 TFLOP/s;
    # 4 x 96 x 1024 x 64 x 2 B = 50,331,648 B -> 61.5 us at 819 GB/s
    ops, nbytes = flops.flash_fwd(8, 12, 1024, 64)
    assert (ops, nbytes) == (12_897_484_800.0, 50_331_648.0)
    t, bound = flops.floor_seconds(ops, nbytes, peak)
    assert bound == "compute" and t == pytest.approx(65.469e-6, rel=1e-4)
    ops, nbytes = flops.flash_bwd(8, 12, 1024, 64)
    assert (ops, nbytes) == (2.5 * 12_897_484_800.0, 2 * 50_331_648.0)
    # one int8 block of gpt2-large: K and V x 20 heads x 16 rows x 68 B
    assert flops.kv_block_bytes(20, 64, 16, "int8") == 43_520
    assert flops.kv_block_bytes(20, 64, 16, "bfloat16") == 81_920
    t, bound = flops.floor_seconds(0.0, 819e9, peak)
    assert bound == "bandwidth" and t == pytest.approx(1.0)


# --- traffic -------------------------------------------------------------------
TOY = os.path.join(HERE, "data", "tiny")


@pytest.mark.parametrize("root, name, max_context", [
    (REPO, "backlog-long", 1024), (TOY, "tiny-closed", 64)])
def test_every_seed_gets_the_same_sizes_in_another_order(root, name,
                                                         max_context):
    req = harness.load_module(REPO, "traffic", "requests")
    params = harness.load_json(root, "chipbench", "traffic", name + ".json")
    pool = req.size_pool(params)
    assert len(pool) == params["pool"]
    for c, spec in enumerate(params["classes"]):
        mine = [(p, n) for p, n, k in pool if k == c]
        assert len(mine) == round(params["pool"] * spec["share"])
        assert min(p for p, _ in mine) >= spec["prompt"]["lo"]
        assert max(p for p, _ in mine) <= spec["prompt"]["hi"]
        assert min(n for _, n in mine) >= spec["new_tokens"]["lo"]
        assert max(n for _, n in mine) <= spec["new_tokens"]["hi"]
        assert max(p + n for p, n in mine) <= max_context
    a, b = req.draw(params, 50257, 1), req.draw(params, 50257, 2**31 + 5)
    first = [[(len(p), n) for p, n in (next(g) for _ in pool)]
             for g in (a, b)]
    assert first[0] != first[1]
    assert sorted(first[0]) == sorted(first[1]) == \
        sorted((p, n) for p, n, _ in pool)


def test_poisson_schedule_is_the_seeds_and_keeps_its_rate():
    kind = harness.load_module(REPO, "traffic", "poisson")
    params = {"rate_per_s": 50.0, "burst": 2}
    a, b = kind.plan(params, 7), kind.plan(params, 8)
    assert onp.array_equal(a, kind.plan(params, 7)) and not \
        onp.array_equal(a, b)
    assert (onp.diff(a) >= 0).all() and a[0] == a[1]        # bursts of two
    assert len(a) / a[-1] == pytest.approx(50.0, rel=0.01)
    assert len(b) / b[-1] == pytest.approx(50.0, rel=0.01)  # the same gaps
    assert kind.due(a, params, float(a[10]), 4, 0, 0) == 12 - 4


# --- BENCHMARK.json against the files ----------------------------------------
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def check_benchmark(root) -> dict:
    """What the harness needs of a ``BENCHMARK.json`` at ``root``: every
    name is one the contract allows and leads to a file that exists. It
    holds the file to no list of cells, configurations or metrics."""
    bench = harness.load_json(root, "BENCHMARK.json")
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert {w["config"] for w in bench["workloads"]} == set(configs)
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(root, configs[w["config"]]["file"]))
        cell = harness.load_json(root, "chipbench", "workloads",
                                 w["name"] + ".json")
        traffic = harness.load_json(root, "chipbench", "traffic",
                                    w["traffic"] + ".json")
        for group, file in (("runners", cell["runner"]),
                            ("traffic", traffic["kind"])):
            assert os.path.isfile(os.path.join(root, "chipbench", group,
                                               file + ".py"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"])
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(
            root, "chipbench", "layers", m["name"].split(".")[0] + ".py"))
        moved = e2e[m["moves"]]
        assert set(m.get("workloads", cells)) <= \
            set(moved.get("workloads", cells))
    for cell in cells:      # setup_s, one more end-to-end, one per-layer
        assert len(run.metrics_of(bench, "end_to_end", cell)) >= 2
        assert len(run.metrics_of(bench, "per_layer", cell)) >= 1
    return bench


@pytest.mark.parametrize("root", [REPO, TOY], ids=["repo", "toy"])
def test_benchmark_json_names_files_that_exist(root, tmp_path):
    if root != REPO:        # the toy files stand on a copy of chipbench/
        make_root(tmp_path)
        root = tmp_path
    check_benchmark(root)


def test_a_later_pr_adds_a_cell_and_edits_no_file(tmp_path):
    """The next cell of PERF.md's Open questions, a four-chip training
    cell on a second configuration, laid over a copy of the repo's own
    benchmark as a later PR would add it: new files, new ``BENCHMARK.json``
    entries, its name appended to the metrics it reports. Nothing that was
    there is edited — no file under ``paths``, this one included — and
    what these tests ask of ``BENCHMARK.json`` still holds."""
    for path in harness.load_json(REPO, "BENCHMARK.json")["paths"]:
        shutil.copytree(os.path.join(REPO, path), tmp_path / path,
                        ignore=IGNORE)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    before = digest(tmp_path)
    bench = check_benchmark(tmp_path)
    seen = bench["workloads"][0]            # a training cell to start from

    cb = tmp_path / "chipbench"
    conf = harness.load_json(REPO, bench["configs"][0]["file"])
    conf.update(n_layer=conf["n_layer"] + 1)
    (cb / "configs" / "later-config.json").write_text(json.dumps(conf))
    cell = harness.load_json(cb, "workloads", seen["name"] + ".json")
    cell["mesh"] = {"tp": 4}
    (cb / "workloads" / "later-train-tp4.json").write_text(json.dumps(cell))
    bench["configs"].append({
        "name": "later-config", "source": "https://example.org/later",
        "file": "chipbench/configs/later-config.json", "reduced": [],
        "why": "a second configuration"})
    bench["workloads"].append({
        "name": "later-train-tp4", "config": "later-config",
        "traffic": seen["traffic"], "chips": 4, "why": "sharded state"})
    bench["end_to_end"].append({
        "name": "later_metric", "unit": "s", "better": "lower",
        "bound": 0.05, "source": "host_clock",
        "workloads": ["later-train-tp4"]})
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if seen["name"] in m.get("workloads", ()):
                m["workloads"].append("later-train-tp4")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    again = check_benchmark(tmp_path)
    reports = {m["name"] for g in ("end_to_end", "per_layer")
               for m in run.metrics_of(again, g, "later-train-tp4")}
    assert reports == {"later_metric"} | {
        m["name"] for g in ("end_to_end", "per_layer")
        for m in run.metrics_of(again, g, seen["name"])}
    after = digest(tmp_path)
    changed = {p for p in before if after[p] != before[p]}
    assert changed == {"BENCHMARK.json"}
    assert len(after) == len(before) + 2
