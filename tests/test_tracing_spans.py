"""The span primitive of ``telemetry/tracing.py`` and the sites that use it
(ISSUE 26): ids, parents, CPU time and trace ids in the ring; ``rows``;
the same spans as ``mxnet_tpu.*`` annotations in a profiler trace; the
serving tick's children; queue wait and its SLO rule; ``backward()``.

Clocks are never compared with constants here: a span is held to its
parent's interval, a queue wait to another request's own time.
"""
import glob
import os
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, telemetry
from mxnet_tpu.gluon.model_zoo import bert
from mxnet_tpu.serving.llm import LLMEngine
from mxnet_tpu.telemetry import tracing


def _rows(t0, name=None):
    return tracing.rows(t0, time.perf_counter(), name)


def _tiny_lm(vocab=37, units=16, heads=4, layers=2, max_length=64):
    onp.random.seed(0)
    net = bert.gpt_like(vocab_size=vocab, units=units, hidden_size=2 * units,
                        num_layers=layers, num_heads=heads,
                        max_length=max_length, dropout=0.0)
    net.initialize()
    return net


def _engine(net, lanes):
    return LLMEngine(net, max_running=lanes, block_size=4, max_context=32,
                     kv_cache_dtype="float32")


def _train_step(net, trainer, x, y):
    with autograd.record():
        loss = mx.npx.softmax_cross_entropy(net(x), y)
    loss.backward()
    trainer.step(x.shape[0])
    return loss


@pytest.fixture(scope="module")
def toy_train():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
    net.initialize()
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-3})
    x, y = mx.np.ones((8, 8)), mx.np.zeros((8,), dtype="int32")
    t0 = time.perf_counter()
    _train_step(net, trainer, x, y).asnumpy()          # traces and compiles
    first = _rows(t0)
    return net, trainer, x, y, first


# ---------------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------------
def test_nested_span_records_id_parent_cpu_and_trace_id():
    t0 = time.perf_counter()
    with telemetry.trace_scope(telemetry.TraceContext("req-7")):
        with telemetry.span("outer", args={"k": 1}, cpu=True) as outer:
            with telemetry.span("inner", cpu=True) as inner:
                sum(range(20000))
    by_name = {r[0]: r for r in _rows(t0)}
    o, i = by_name["outer"], by_name["inner"]
    assert o[3]["id"] == outer.id and i[3]["id"] == inner.id != outer.id
    assert i[3]["parent"] == outer.id and "parent" not in o[3]
    assert o[3]["k"] == 1
    assert o[3]["trace_id"] == i[3]["trace_id"] == "req-7"
    for _, start, end, args in (o, i):
        assert 0 <= args["cpu_us"] <= (end - start) * 1e6 + 0.1
    assert o[1] <= i[1] and i[2] <= o[2]
    assert i[3]["cpu_us"] > 0          # the loop ran on this thread


def test_a_span_keeps_the_trace_id_it_was_given():
    t0 = time.perf_counter()
    with telemetry.trace_scope(telemetry.TraceContext("ambient")):
        with telemetry.span("given", args={"trace_id": "the-request"}):
            pass
    assert _rows(t0, "given")[0][3]["trace_id"] == "the-request"


def test_a_span_on_another_thread_has_no_parent_from_this_one():
    t0 = time.perf_counter()
    with telemetry.span("here") as here:
        t = threading.Thread(target=lambda: telemetry.span("there")
                             .__enter__().__exit__(None, None, None))
        t.start()
        t.join(10)
        assert not t.is_alive()
        with telemetry.span("child"):
            pass
    by_name = {r[0]: r[3] for r in _rows(t0)}
    assert "parent" not in by_name["there"]
    assert by_name["child"]["parent"] == here.id


def test_rows_returns_only_complete_spans_inside_the_interval():
    with telemetry.span("before"):
        pass
    lo = time.perf_counter()
    with telemetry.span("cut-at-the-end"):
        with telemetry.span("inside"):
            pass
        hi = time.perf_counter()
        # still open: not in the ring at all
        assert [r[0] for r in tracing.rows(lo, hi)] == ["inside"]
    with telemetry.span("after"):
        pass
    # complete now, but it ends after hi
    assert [r[0] for r in tracing.rows(lo, hi)] == ["inside"]
    assert tracing.rows(lo, hi, "before") == []
    got = tracing.rows(lo, time.perf_counter(), "after")
    assert len(got) == 1 and got[0][1] >= hi and got[0][2] >= got[0][1]


def test_ring_false_times_the_interval_writes_no_row_and_is_no_parent():
    t0 = time.perf_counter()
    with telemetry.span("quiet", ring=False) as quiet:
        with telemetry.span("under-quiet"):
            pass
    with telemetry.span("dropped") as dropped:
        dropped.ring = False
    got = {r[0]: r[3] for r in _rows(t0)}
    assert set(got) == {"under-quiet"} and "parent" not in got["under-quiet"]
    assert quiet.dur_s >= 0 and quiet.id is None
    # the dropped row's span was on the stack and is off it again
    with telemetry.span("after-dropped"):
        pass
    assert "parent" not in _rows(t0, "after-dropped")[0][3]


@pytest.mark.parametrize("cpu", [False, True])
def test_the_cpu_clock_is_read_only_where_asked(monkeypatch, cpu):
    """``time.thread_time()`` is a system call (6 us on the chip's host):
    a span reads it twice with ``cpu=True`` and never otherwise."""
    reads = []
    real = time.thread_time
    monkeypatch.setattr(tracing.time, "thread_time",
                        lambda: reads.append(1) or real())
    t0 = time.perf_counter()
    with telemetry.span("clocked", cpu=cpu):
        with telemetry.span("quiet", ring=False):
            pass
    assert len(reads) == (2 if cpu else 0)
    assert ("cpu_us" in _rows(t0, "clocked")[0][3]) is cpu


def test_a_forked_child_writes_its_own_pid_into_its_rows():
    """The ring's rows carry a cached process id (``os.getpid()`` is a
    system call a row); a fork must not inherit the parent's."""
    import select
    import signal

    r, w = os.pipe()
    child = os.fork()
    if child == 0:
        try:
            tracing.emit_instant("in-the-child")
            os.write(w, str(tracing.buffer().tail(1)[0]["pid"]).encode())
        finally:
            os._exit(0)
    os.close(w)
    try:
        ready, _, _ = select.select([r], [], [], 30)
        if not ready:
            os.kill(child, signal.SIGKILL)
        got = os.read(r, 32) if ready else b""
    finally:
        os.close(r)
        os.waitpid(child, 0)
    assert got.decode() == str(child) != str(os.getpid())
    with telemetry.span("in-the-parent"):
        pass
    assert tracing.buffer().tail(1)[0]["pid"] == os.getpid()


def test_a_phase_is_a_span_under_its_step_and_fills_the_bucket():
    t0 = time.perf_counter()
    with telemetry.step("toy", 3) as st:
        with st.phase("device", "toy.run", {"n": 2}) as ph:
            with st.phase("device", "toy.nested") as nested:
                pass
    rows = {r[0]: r for r in _rows(t0)}
    step, run = rows["step[toy]"], rows["toy.run"]
    assert run[3]["parent"] == step[3]["id"] and run[3]["n"] == 2
    assert rows["toy.nested"][3]["parent"] == run[3]["id"]
    # the nested phase adds nothing: the bucket is the outer phase's time
    assert st.attribution()["device"] == pytest.approx(ph.dur_s)
    assert nested.dur_s <= ph.dur_s <= st.wall_s
    assert step[3]["step"] == 3 and step[3]["wall_ms"] >= step[3]["device"]


def test_a_steps_row_is_its_wall_time_and_a_cancelled_step_has_none():
    t0 = time.perf_counter()
    with telemetry.step("kept") as kept:
        with kept.phase("device"):
            pass
    with telemetry.step("dropped") as dropped:
        dropped.cancel()
    with telemetry.span("next"):
        pass
    rows = {r[0]: r for r in _rows(t0)}
    assert "step[dropped]" not in rows and "parent" not in rows["next"][3]
    row = rows["step[kept]"]
    # the buckets are filled before the span's one exit: the row ends a
    # few microseconds after the wall time it reports, never before
    assert row[3]["wall_ms"] == pytest.approx(kept.wall_s * 1e3, abs=1e-3)
    assert kept.wall_s <= row[2] - row[1] <= kept.wall_s + 0.005
    assert sum(kept.attribution().values()) == pytest.approx(kept.wall_s)


# ---------------------------------------------------------------------------
# the trainer side
# ---------------------------------------------------------------------------
def test_train_step_spans_and_the_tape_sums(toy_train):
    net, trainer, x, y, first = toy_train
    assert [r[0] for r in first] == ["autograd.backward"]
    t0 = time.perf_counter()
    with telemetry.step("toy_train") as st:
        _train_step(net, trainer, x, y)
    rows = _rows(t0)
    # one row a step from backward(), whatever the tape holds; the
    # pullbacks are annotations only, the fused update a phase of the step
    assert sorted(r[0] for r in rows) == [
        "autograd.backward", "step[toy_train]", "trainer.fused_update"]
    by_name = {r[0]: r for r in rows}
    bwd = by_name["autograd.backward"]
    assert bwd[3]["parent"] == by_name["step[toy_train]"][3]["id"]
    assert bwd[3]["nodes"] == bwd[3]["ran"] == 2
    assert 0 <= bwd[3]["cpu_us"] <= (bwd[2] - bwd[1]) * 1e6 + 0.1
    by_op = bwd[3]["by_op"]
    assert set(by_op) == {"HybridSequential_cached", "softmax_cross_entropy"}
    assert all(wall >= 0 and calls == 1 for wall, calls in by_op.values())
    assert sum(v[0] for v in by_op.values()) \
        <= (bwd[2] - bwd[1]) * 1e3 + 1e-3
    assert list(by_op) == sorted(by_op, key=lambda k: -by_op[k][0])
    assert st.attribution()["device"] > 0


def test_by_op_keeps_the_eight_longest_of_an_eager_tape():
    x = mx.np.ones((4, 4))
    x.attach_grad()
    t0 = time.perf_counter()
    with autograd.record():
        y = x
        for f in (mx.np.exp, mx.np.tanh, mx.np.sin, mx.np.cos, mx.np.sqrt,
                  mx.np.abs, mx.np.negative, mx.np.square, mx.np.arctan):
            y = f(y) + 1.5
        loss = y.sum()
    loss.backward()
    args = _rows(t0, "autograd.backward")[0][3]
    assert args["nodes"] == args["ran"] == 19       # 9 ops, 9 adds, the sum
    assert len(args["by_op"]) == 8
    assert sum(v[1] for v in args["by_op"].values()) <= args["ran"]


# ---------------------------------------------------------------------------
# the serving side
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def served():
    """Two lanes, five requests of 6 tokens with trace ids: the ring's rows
    of the whole run and the engine's last stats."""
    t0 = time.perf_counter()      # before the scheduler's first tick
    eng = _engine(_tiny_lm(), lanes=2)
    try:
        handles = [eng.submit(onp.arange(1, 4 + i), 6, trace_id=f"r{i}")
                   for i in range(5)]
        for h in handles:
            h.wait()
        time.sleep(0.05)               # idle ticks after the last request
        stats = eng.stats()
    finally:
        eng.close()
    return _rows(t0), stats, handles


def test_every_tick_holds_its_children_and_they_do_not_overlap(served):
    rows, _, _ = served
    ticks = [r for r in rows if r[0] == "llm.tick"]
    assert ticks
    by_id = {r[3]["id"]: r for r in rows}
    for r in rows:
        if r[0].startswith(("llm.", "step[llm_")) and r[0] != "llm.tick":
            assert r[3]["parent"] in by_id, r
    for tick in ticks:
        kids = sorted((r for r in rows
                       if r[3].get("parent") == tick[3]["id"]),
                      key=lambda r: r[1])
        assert kids and {k[0] for k in kids} <= {
            "llm.sweep", "llm.admit", "step[llm_decode]", "llm.emit"}
        for k in kids:
            assert tick[1] <= k[1] and k[2] <= tick[2]
        for a, b in zip(kids, kids[1:]):
            assert a[2] <= b[1]
        assert tick[3]["active"] <= 2 and tick[3]["blocks_in_use"] >= 0
    # nothing is left of the ticks in which the engine only waited
    assert all(t[3]["active"] or any(
        r[3].get("parent") == t[3]["id"] and r[0] == "llm.admit"
        for r in rows) for t in ticks)


def test_a_decode_step_is_launch_fetch_and_emit_timed_once(served):
    rows, stats, _ = served
    steps = [r for r in rows if r[0] == "step[llm_decode]"]
    assert len(steps) == stats["counters"]["decode_steps"] \
        == stats["decode_step_ms"]["count"]
    emits = sorted((r for r in rows if r[0] == "llm.emit"),
                   key=lambda r: r[1])
    assert len(emits) == len(steps)
    total = 0.0
    for st, emit in zip(sorted(steps, key=lambda r: r[1]), emits):
        kids = sorted((r for r in rows if r[3].get("parent") == st[3]["id"]),
                      key=lambda r: r[1])
        assert [k[0] for k in kids] == [
            "llm.decode.launch", "llm.decode.fetch"]
        launch, fetch = kids
        assert launch[3]["step"] == fetch[3]["step"] == st[3]["step"]
        assert set(launch[3]["trace_ids"]) <= {f"r{i}" for i in range(5)}
        # the token loop runs after the step has closed, as it always
        # did: the step's wall time (telemetry_step_ms) does not hold it
        assert emit[3]["parent"] == st[3]["parent"] and st[2] <= emit[1]
        assert 1 <= emit[3]["tokens"] <= 2
        total += (launch[2] - launch[1]) + (fetch[2] - fetch[1])
        # the step's device bucket is the two phases, and nothing else
        assert st[3]["device"] + st[3]["compile"] == pytest.approx(
            ((launch[2] - launch[1]) + (fetch[2] - fetch[1])) * 1e3,
            abs=0.01)
    # the histogram the benchmark reads is fed from those same spans
    # (a summary rounds its mean to four places)
    assert stats["decode_step_ms"]["mean"] * len(steps) == pytest.approx(
        total * 1e3, abs=1e-4 * len(steps))
    assert sum(r[3]["tokens"] for r in rows if r[0] == "llm.emit") \
        == 5 * 6 - 5                  # prefill makes each request's first


def test_admission_and_prefill_carry_the_request(served):
    rows, stats, handles = served
    admits = sorted((r for r in rows if r[0] == "llm.admit"),
                    key=lambda r: r[1])
    assert [a[3]["trace_id"] for a in admits] == [f"r{i}" for i in range(5)]
    for i, a in enumerate(admits):
        assert a[3]["prompt_tokens"] == 3 + i
        assert a[3]["bucket"] >= a[3]["prompt_tokens"]
        assert a[3]["blocks"] == -(-(3 + i + 6) // 4)
        assert a[3]["prefix_hit_blocks"] == 0
        step = [r for r in rows if r[0] == "step[llm_prefill]"
                and r[3]["parent"] == a[3]["id"]]
        assert len(step) == 1
        pre = [r for r in rows if r[0] == "llm.prefill"
               and r[3]["parent"] == step[0][3]["id"]]
        assert len(pre) == 1 and pre[0][3]["trace_id"] == f"r{i}"
        assert handles[i].prefill_s == pytest.approx(pre[0][2] - pre[0][1])
    assert stats["prefill_ms"]["count"] == 5
    assert stats["queue_wait_ms"]["count"] == 5
    waits = [a[3]["queue_wait_ms"] for a in admits]
    assert stats["queue_wait_ms"]["mean"] == pytest.approx(
        sum(waits) / 5, abs=1e-4)
    for h, w in zip(handles, waits):
        assert (h.admitted_s - h.enqueue_t) * 1e3 == pytest.approx(w,
                                                                   abs=1e-3)


def test_one_lane_the_second_request_waits_out_the_first():
    t0 = time.perf_counter()
    eng = _engine(_tiny_lm(), lanes=1)
    try:
        eng.warmup(prompt_lengths=[4])
        done = []
        first = eng.submit(onp.arange(1, 5), 8,
                           on_token=lambda t: done.append(time.monotonic()))
        second = eng.submit(onp.arange(2, 6), 2)
        first.wait()
        second.wait()
        stats = eng.stats()
    finally:
        eng.close()
    in_lane = done[-1] - first.admitted_s
    wait = second.admitted_s - second.enqueue_t
    assert wait >= in_lane - (second.enqueue_t - first.admitted_s) > 0
    admits = sorted(_rows(t0, "llm.admit"), key=lambda r: r[1])
    assert admits[-1][3]["queue_wait_ms"] == pytest.approx(wait * 1e3,
                                                           abs=1e-3)
    assert stats["queue_wait_ms"]["count"] == 2
    assert stats["queue_wait_ms"]["max"] >= wait * 1e3 - 1e-3
    assert stats["decode_step_ms"]["count"] \
        == stats["counters"]["decode_steps"]


def test_an_slo_rule_on_queue_wait_fires_when_requests_wait_for_a_lane():
    """The operator's reader of ``llm_queue_wait_ms``
    (``docs/observability.md``, "Slow first token"): the rule
    ``p99:llm_queue_wait_ms<=...`` over the engine's own series."""
    from mxnet_tpu.telemetry import cluster as tcluster
    from mxnet_tpu.telemetry import slo as tslo

    eng = _engine(_tiny_lm(), lanes=1)
    try:
        handles = [eng.submit(onp.arange(1, 5), 6) for _ in range(3)]
        for h in handles:
            h.wait()
        name = eng.metrics.engine_id
    finally:
        eng.close()
    waited = max(h.admitted_s - h.enqueue_t for h in handles) * 1e3
    snap = {"schema": tcluster.SNAPSHOT_SCHEMA, "ts_unix": time.time(),
            "processes": {"p0": {"metrics": telemetry.snapshot()}},
            "cluster": {}}
    tight, loose = tslo.parse_slo_spec(
        f"p99:llm_queue_wait_ms<={waited / 2};"
        f"p99:llm_queue_wait_ms<={waited * 1e3 + 1e6}")
    tight.labels = loose.labels = {"engine": name}
    sent = tslo.SloSentinel([tight, loose], scraper=object.__new__(
        tcluster.ClusterScraper), bundle=False)
    fired = sent.evaluate(snap)
    assert [v.rule for v in fired] == [tight.name]
    assert fired[0].observed > waited / 2


# ---------------------------------------------------------------------------
# the same spans in the profiler's trace
# ---------------------------------------------------------------------------
def _host_events(directory):
    import jax

    path = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out.extend((line.name, e.name, e.start_ns,
                            e.start_ns + e.duration_ns)
                           for e in line.events
                           if e.name.startswith("mxnet_tpu."))
    return out


def _inside(events, child, parent):
    """Every ``child*`` event lies inside a ``parent`` event of its own
    thread's line; returns how many there were. A parent that was open
    when the profiler started or stopped is not in the trace (the tick
    that waited for the first request; the tick whose last token let the
    test go on and stop the trace): children before the first recorded
    parent or after the last are left out."""
    spans = [(p[2], p[3]) for p in events if p[1] == parent]
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    kids = [e for e in events
            if e[1].startswith(child) and e[2] >= lo and e[3] <= hi]
    for line, _, start, end in kids:
        assert any(p[0] == line and p[1] == parent
                   and p[2] <= start and end <= p[3] for p in events), \
            (child, parent)
    return len(kids)


def test_the_spans_are_annotations_on_the_profilers_clock(tmp_path,
                                                         toy_train):
    import jax

    net, trainer, x, y, _ = toy_train
    eng = _engine(_tiny_lm(), lanes=2)
    try:
        eng.warmup(prompt_lengths=[4])
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            _train_step(net, trainer, x, y).asnumpy()
            for h in [eng.submit(onp.arange(1, 5), 4) for _ in range(3)]:
                h.wait()
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.close()
    events = _host_events(str(tmp_path))
    names = {e[1] for e in events}
    for want in ("mxnet_tpu.llm.tick", "mxnet_tpu.llm.decode.fetch",
                 "mxnet_tpu.autograd.backward"):
        assert want in names, sorted(names)
    assert _inside(events, "mxnet_tpu.autograd.node:",
                   "mxnet_tpu.autograd.backward") == 2
    assert "mxnet_tpu.autograd.node:softmax_cross_entropy" in names
    for child, parent in (
            ("mxnet_tpu.llm.decode.fetch", "mxnet_tpu.step[llm_decode]"),
            ("mxnet_tpu.llm.decode.launch", "mxnet_tpu.step[llm_decode]"),
            ("mxnet_tpu.llm.emit", "mxnet_tpu.llm.tick"),
            ("mxnet_tpu.step[llm_decode]", "mxnet_tpu.llm.tick"),
            ("mxnet_tpu.llm.prefill", "mxnet_tpu.llm.admit"),
            ("mxnet_tpu.llm.admit", "mxnet_tpu.llm.tick")):
        assert _inside(events, child, parent) >= 1
