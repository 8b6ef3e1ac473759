"""The per-layer metrics that read the program's own spans (ISSUE 26):
each reader against hand-written rows, and a toy traced run on the CPU
that reports all five.

The hand-written rows go through the program's own ``tracing.rows``, on a
ring of the test's own: the window cuts a span at each end, as a run's
does. The toy run lays ``data/program_spans`` (a ``BENCHMARK.json`` with
the five metrics on two of ``data/tiny``'s cells) over the toy checkout;
``data/tiny`` itself is not edited.
"""
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chipbench import harness, run                         # noqa: E402
from mxnet_tpu.telemetry import tracing                    # noqa: E402

WINDOW = (100.0, 200.0)


def emit(name, start_s, end_s, **args):
    tracing.emit_complete(name, start_s * 1e6, (end_s - start_s) * 1e6,
                          args=args)


def train_rows():
    """Five steps of 10 s; the first backward is cut by the window's
    start, the last by its end, and the step at 140 has no span at all."""
    for i, (start, dur, cpu_us, nodes) in enumerate([
            (99.0, 3.0, 1e6, 3), (112.0, 2.0, 0.5e6, 3),
            (122.0, 4.0, 3.0e6, 5), (132.0, 3.0, 1.5e6, 3),
            (152.0, 1.0, 1.0e6, 3), (198.0, 3.0, 1e6, 3)]):
        emit("autograd.backward", start, start + dur, id=i + 1,
             cpu_us=cpu_us, nodes=nodes, ran=nodes,
             by_op={"net_cached": [dur * 900.0, 1],
                    "softmax_cross_entropy": [dur * 100.0, 1]})
    emit("step[train]", 125.0, 126.0, id=50, wall_ms=1000.0)


def serve_rows():
    """Three ticks inside the window and one cut by its end. Tick 1 admits
    (prefill 2 s of an admit of 2.5 s) and decodes; tick 2 only decodes;
    tick 3 holds nothing but a sweep (every lane retired); a launch of
    another engine's tick (id 99, not in the window) is not counted."""
    def tick(tid, start, end):
        emit("llm.tick", start, end, id=tid, active=2,
             queue_len=1, blocks_in_use=8)

    def decode(base, tick_id, start, launch, fetch, emit_s):
        emit("step[llm_decode]", start, start + launch + fetch,
             id=base, parent=tick_id)
        emit("llm.decode.launch", start, start + launch, id=base + 1,
             parent=base, step=base)
        emit("llm.decode.fetch", start + launch, start + launch + fetch,
             id=base + 2, parent=base, step=base)
        emit("llm.emit", start + launch + fetch,
             start + launch + fetch + emit_s, id=base + 3, parent=tick_id,
             tokens=2)

    tick(1, 110.0, 120.0)
    emit("llm.sweep", 110.0, 110.5, id=2, parent=1, retired=0)
    emit("llm.admit", 110.5, 113.0, id=3, parent=1,
         queue_wait_ms=5.0)
    emit("step[llm_prefill]", 110.75, 112.75, id=4, parent=3)
    emit("llm.prefill", 110.75, 112.75, id=5, parent=4)
    decode(10, 1, 113.0, 1.0, 5.0, 1.0)        # ends at 120
    tick(20, 130.0, 140.0)
    decode(30, 20, 130.0, 3.0, 6.0, 1.0)
    tick(40, 150.0, 152.0)
    emit("llm.sweep", 150.0, 151.0, id=41, parent=40, retired=2)
    tick(60, 195.0, 205.0)
    decode(70, 60, 195.0, 2.0, 2.0, 0.5)       # launch and fetch complete
    decode(90, 99, 160.0, 0.5, 0.5, 0.5)       # in the window; no tick of it


CASES = {
    # medians over the four complete spans: 2, 4, 3, 1 s
    "backward_host_ms": (train_rows, 2500.0),
    # (2 - .5) + (4 - 3) + (3 - 1.5) + (1 - 1) = 4 of 10 s
    "backward_offcpu_share": (train_rows, 40.0),
    "tape_nodes_per_step": (train_rows, 3.0),
    # ticks 10 + 10 + 2 s; under them prefill 2, launch 1 + 3, fetch 5 + 6
    "tick_host_share": (serve_rows, 100.0 * (22.0 - 17.0) / 22.0),
    # launches inside the window: 1, 3, 2 (tick cut, span whole), 0.5 s
    "decode_launch_ms": (serve_rows, 1500.0),
}


@pytest.mark.parametrize("reader", sorted(CASES))
def test_reader_on_hand_written_rows(monkeypatch, capsys, reader):
    monkeypatch.setattr(tracing, "_buffer", tracing.TraceBuffer(1000))
    mod = harness.load_module(REPO, "layers", reader)
    result = {"window": WINDOW}
    assert mod.read(result, None, None) is None      # an empty ring
    make, want = CASES[reader]
    make()
    assert mod.read(result, None, None) == pytest.approx(want)
    # a window that holds no span of the reader's reads nothing
    assert mod.read({"window": (141.0, 149.0)}, None, None) is None
    capsys.readouterr()


def test_a_program_without_rows_reads_nothing(monkeypatch):
    """The parent commit's ``tracing`` has no ``rows``: every reader then
    reports nothing and raises nothing."""
    monkeypatch.delattr(tracing, "rows")
    for reader in CASES:
        mod = harness.load_module(REPO, "layers", reader)
        assert mod.read({"window": WINDOW}, None, None) is None


@pytest.fixture
def root(tmp_path, monkeypatch):
    """The toy checkout of ``test_chipbench.py`` with this file's overlay
    on top, the harness pointed at it and at the CPU."""
    import mxnet_tpu.base

    ignore = shutil.ignore_patterns("__pycache__")
    shutil.copytree(os.path.join(REPO, "chipbench"),
                    os.path.join(tmp_path, "chipbench"), ignore=ignore)
    for overlay in ("tiny", "program_spans"):
        shutil.copytree(os.path.join(HERE, "data", overlay), tmp_path,
                        dirs_exist_ok=True)
    monkeypatch.setattr(run, "ROOT", str(tmp_path))
    monkeypatch.setattr(run, "PLATFORM", "cpu")
    monkeypatch.setattr(mxnet_tpu.base, "arm_compile_cache",
                        lambda: "(off in the tests)")
    return tmp_path


@pytest.mark.parametrize("cell, reports", [
    ("tiny-train", {"backward_host_ms.train", "backward_offcpu_share.train",
                    "tape_nodes_per_step.train"}),
    ("tiny-serve-backlog", {"tick_host_share.backlog",
                            "decode_launch_ms.backlog"})])
def test_a_toy_traced_run_reports_them(root, capsys, cell, reports):
    assert run.main(["--workload", cell, "--seed", str(2**31 + 29),
                     "--seconds", "1.0", "--trace", "1"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    assert line["correct"] is True
    assert set(line["metrics"]) == reports
    m = {k: v["value"] for k, v in line["metrics"].items()}
    if cell == "tiny-train":
        assert m["backward_host_ms.train"] > 0
        assert 0 <= m["backward_offcpu_share.train"] <= 100
        # the hybridized net, the reshape of its logits, the loss
        assert m["tape_nodes_per_step.train"] == 3
        assert any("autograd.backward by_op" in ln for ln in out)
    else:
        assert 0 < m["tick_host_share.backlog"] < 100
        assert m["decode_launch_ms.backlog"] > 0
        assert any("llm.tick:" in ln for ln in out)
    units = {e["name"]: e["unit"] for e in harness.load_json(
        REPO, "BENCHMARK.json")["per_layer"]}
    assert all(v["unit"] == units[k] for k, v in line["metrics"].items())
