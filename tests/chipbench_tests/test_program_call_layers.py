"""The per-layer metrics that read the serving program's calls
(``chipbench/layers/_calls.py``: ``host_gap_ms``, ``launch_upload_ms``,
``call_idle_ms``): each reader against hand-written rows, ``call_idle_ms``
also against a hand-written reduced trace, and a toy traced run on the CPU
that reports the two that need no device trace.

The rows go through the program's own ``tracing.rows``, on a ring of the
test's own. The toy run lays ``data/program_calls`` (a ``BENCHMARK.json``
with the three metrics on ``data/tiny``'s backlog cell) over the toy
checkout.
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


def call_rows():
    """Six calls inside the window, A to F (first upload to result):

    tick 1 [110, 120]: A, a whole-prompt prefill [111, 112.5] under its
    admission [110.5, 113]; B, a decode step [113.25, 118.5];
    tick 2 [130, 140]: C, a decode step of two uploads (a speculative
    round) [131, 138.5];
    tick 3 [150, 160]: D and E, two chunks of a prompt [152.2, 154] and
    [155.1, 157] under its admission [151, 158];
    tick 4 [195, 205], cut by the window's end: F [195.2, 198.9], and an
    upload whose result the window's end cuts.
    Before them a call whose upload the window's start cuts."""
    emit("llm.upload", 99.2, 99.4, id=901, bytes=80)
    emit("llm.result", 100.2, 101.0, id=902)

    emit("llm.tick", 110.0, 120.0, id=1)
    emit("llm.sweep", 110.0, 110.5, id=2, parent=1)
    emit("llm.admit", 110.5, 113.0, id=3, parent=1)
    emit("step[llm_prefill]", 110.75, 112.75, id=4, parent=3)
    emit("llm.prefill", 110.75, 112.75, id=5, parent=4)
    emit("llm.upload", 111.0, 111.25, id=6, parent=5, bytes=100)
    emit("llm.result", 111.5, 112.5, id=7, parent=5)
    emit("step[llm_decode]", 113.0, 119.0, id=10, parent=1)
    emit("llm.decode.launch", 113.0, 114.0, id=11, parent=10)
    emit("llm.upload", 113.25, 113.75, id=12, parent=11, bytes=80)
    emit("llm.decode.fetch", 114.0, 119.0, id=13, parent=10)
    emit("llm.result", 114.5, 118.5, id=14, parent=13)
    emit("llm.emit", 119.0, 119.5, id=15, parent=1)

    emit("llm.tick", 130.0, 140.0, id=20)
    emit("step[llm_decode]", 130.0, 139.0, id=21, parent=20)
    emit("llm.decode.launch", 130.0, 133.0, id=22, parent=21)
    emit("llm.upload", 131.0, 132.0, id=23, parent=22, bytes=80)
    emit("llm.upload", 132.2, 132.5, id=24, parent=22, bytes=40)
    emit("llm.decode.fetch", 133.0, 139.0, id=25, parent=21)
    emit("llm.result", 133.5, 138.5, id=26, parent=25)
    emit("llm.emit", 139.0, 140.0, id=27, parent=20)

    emit("llm.tick", 150.0, 160.0, id=40)
    emit("llm.sweep", 150.0, 151.0, id=41, parent=40)
    emit("llm.admit", 151.0, 158.0, id=42, parent=40)
    emit("step[llm_prefill]", 151.5, 157.5, id=43, parent=42)
    emit("llm.prefill", 151.5, 157.5, id=44, parent=43)
    emit("llm.prefill.chunk", 152.0, 154.0, id=45, parent=44)
    emit("llm.upload", 152.2, 152.4, id=46, parent=45, bytes=76)
    emit("llm.result", 153.0, 154.0, id=47, parent=45)
    emit("llm.prefill.chunk", 155.0, 157.0, id=48, parent=44)
    emit("llm.upload", 155.1, 155.2, id=49, parent=48, bytes=76)
    emit("llm.result", 156.0, 157.0, id=50, parent=48)

    emit("llm.tick", 195.0, 205.0, id=60)
    emit("step[llm_decode]", 195.0, 199.0, id=61, parent=60)
    emit("llm.decode.launch", 195.0, 196.0, id=62, parent=61)
    emit("llm.upload", 195.2, 195.6, id=63, parent=62, bytes=80)
    emit("llm.decode.fetch", 196.0, 199.0, id=64, parent=61)
    emit("llm.result", 196.1, 198.9, id=65, parent=64)
    emit("llm.emit", 199.0, 199.5, id=66, parent=60)
    emit("llm.decode.launch", 199.55, 199.85, id=70, parent=60)
    emit("llm.upload", 199.6, 199.8, id=71, parent=70, bytes=80)
    emit("llm.result", 199.9, 200.5, id=72)


# reader[case] -> (trace, what the result holds beside the window, value);
# the gaps between the calls are A-B 0.75, B-C 12.5, C-D 13.7, D-E 1.1
# and E-F 38.2 s
CASES = {
    "host_gap_ms": (None, {}, 12500.0),
    # upload time of the decode steps' launches: 0.5, 1 + 0.3, 0.4, 0.2 s
    "launch_upload_ms": (None, {}, 450.0),
    # the stretch [125, 160] cuts B-C to 6 s and E-F to 3; C, D and E
    # start in it: (30 s idle - 6 - 13.7 - 1.1 - 3) / 3
    "call_idle_ms[gaps-cut-at-both-ends]": (
        {"window_s": 35.0, "busy_s": 5.0}, {"trace_span": (125.0, 160.0)},
        1e3 * 6.2 / 3),
    # the stretch [125, 156] ends inside E, which counts:
    # (27 s idle - 6 - 13.7 - 1.1) / 3
    "call_idle_ms[a-call-cut-by-the-end]": (
        {"window_s": 31.0, "busy_s": 4.0}, {"trace_span": (125.0, 156.0)},
        1e3 * 6.2 / 3),
}

# a result that holds no call of the reader's: a window of no call, or a
# traced stretch inside the gap E-F
NOTHING = {"host_gap_ms": {"window": (141.0, 149.0)},
           "launch_upload_ms": {"window": (141.0, 149.0)},
           "call_idle_ms": {"window": WINDOW, "trace_span": (160.0, 190.0)}}


@pytest.mark.parametrize("case", sorted(CASES))
def test_reader_on_hand_written_rows(monkeypatch, capsys, case):
    monkeypatch.setattr(tracing, "_buffer", tracing.TraceBuffer(1000))
    reader = case.split("[")[0]
    mod = harness.load_module(REPO, "layers", reader)
    trace, extra, want = CASES[case]
    result = {"window": WINDOW, **extra}
    assert mod.read(result, trace, None) is None        # an empty ring
    call_rows()
    assert mod.read(result, trace, None) == pytest.approx(want)
    assert mod.read(NOTHING[reader], trace, None) is None
    capsys.readouterr()


def test_call_idle_reads_nothing_without_a_device_trace(monkeypatch):
    monkeypatch.setattr(tracing, "_buffer", tracing.TraceBuffer(1000))
    call_rows()
    mod = harness.load_module(REPO, "layers", "call_idle_ms")
    assert mod.read({"window": WINDOW, "trace_span": (125.0, 160.0)},
                    None, None) is None
    assert mod.read({"window": WINDOW, "trace_span": (None, None)},
                    {"window_s": 1.0, "busy_s": 0.5}, None) is None


def test_the_notes_split_the_gaps(monkeypatch, capsys):
    """``host_gap_ms``'s note: the gaps' median parts (A-B is 0.5 s of
    admission; B-C 0.5 of emit and 11 between ticks; C-D 1 of emit, 1 of
    sweep, 1.2 of admission and 10 between ticks; D-E 1.1 of admission;
    E-F 1 of admission and 35 between ticks), the longest tick and the
    ring's dropped rows."""
    monkeypatch.setattr(tracing, "_buffer", tracing.TraceBuffer(1000))
    call_rows()
    harness.load_module(REPO, "layers", "host_gap_ms").read(
        {"window": WINDOW}, None, None)
    note = capsys.readouterr().out
    for part in ("host gaps: 5,", "llm.emit 0.0000", "llm.sweep 0.0000",
                 "llm.admit 1000.0000", "between ticks 10000.0000",
                 "longest llm.tick 10000.000 ms",
                 "ring rows dropped 0"):
        assert part in note, (part, note)


def test_a_program_without_the_spans_reads_nothing(monkeypatch):
    """The parent commit's program has ``tracing.rows`` but no
    ``llm.upload`` and ``llm.result``; an older one has no ``rows``."""
    monkeypatch.setattr(tracing, "_buffer", tracing.TraceBuffer(1000))
    emit("llm.tick", 110.0, 120.0, id=1)
    emit("llm.decode.launch", 113.0, 114.0, id=11, parent=1)
    emit("llm.decode.fetch", 114.0, 119.0, id=13, parent=1)
    for case, (trace, extra, _) in CASES.items():
        mod = harness.load_module(REPO, "layers", case.split("[")[0])
        assert mod.read({"window": WINDOW, **extra}, trace, None) is None
    monkeypatch.delattr(tracing, "rows")
    for case, (trace, extra, _) in CASES.items():
        mod = harness.load_module(REPO, "layers", case.split("[")[0])
        assert mod.read({"window": WINDOW, **extra}, trace, None) is None


@pytest.fixture
def root(tmp_path, monkeypatch):
    """The toy checkout of ``test_chipbench.py`` with this file's overlay
    on top, the harness pointed at it and at the CPU."""
    import mxnet_tpu.base

    ignore = shutil.ignore_patterns("__pycache__")
    shutil.copytree(os.path.join(REPO, "chipbench"),
                    os.path.join(tmp_path, "chipbench"), ignore=ignore)
    for overlay in ("tiny", "program_calls"):
        shutil.copytree(os.path.join(HERE, "data", overlay), tmp_path,
                        dirs_exist_ok=True)
    monkeypatch.setattr(run, "ROOT", str(tmp_path))
    monkeypatch.setattr(run, "PLATFORM", "cpu")
    monkeypatch.setattr(mxnet_tpu.base, "arm_compile_cache",
                        lambda: "(off in the tests)")
    return tmp_path


def test_a_toy_traced_run_reports_them(root, capsys):
    """On the CPU there is no device trace: ``call_idle_ms`` reports
    nothing, the two that read the program's spans do."""
    assert run.main(["--workload", "tiny-serve-backlog", "--seed",
                     str(2**31 + 31), "--seconds", "1.0",
                     "--trace", "1"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    assert line["correct"] is True
    assert set(line["metrics"]) == {"host_gap_ms.backlog",
                                    "launch_upload_ms.backlog"}
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["host_gap_ms.backlog"] > 0
    assert m["launch_upload_ms.backlog"] > 0
    assert any("host gaps:" in ln and "ring rows dropped 0" in ln
               for ln in out)
    assert any("llm.upload:" in ln for ln in out)
    units = {e["name"]: e["unit"] for e in harness.load_json(
        REPO, "BENCHMARK.json")["per_layer"]}
    assert all(v["unit"] == units[k] for k, v in line["metrics"].items())
