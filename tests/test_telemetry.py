"""Unified telemetry layer (ISSUE 6): metrics registry label/threading
semantics, Prometheus exposition golden, Chrome trace schema validity,
step-timeline attribution summing to wall time, flight-recorder dumps on
injected stall/fatal/chaos-kill, and the exporter's degrade-to-warn-once
contract."""
import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.resilience import chaos
from mxnet_tpu.telemetry import MetricsRegistry
from mxnet_tpu.telemetry import exporter as texp
from mxnet_tpu.telemetry import flight as tflight
from mxnet_tpu.telemetry import mfu as tmfu
from mxnet_tpu.telemetry import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
def test_registry_counter_gauge_histogram_labels():
    reg = MetricsRegistry()
    c = reg.counter("req_total", "requests", ("kind",))
    c.labels(kind="a").inc()
    c.labels(kind="a").inc(2)
    c.labels(kind="b").inc()
    assert c.labels(kind="a").value == 3
    assert c.labels(kind="b").value == 1
    with pytest.raises(ValueError):
        c.labels(wrong="x")
    with pytest.raises(ValueError):
        c.labels(kind="a").inc(-1)  # counters are monotonic

    g = reg.gauge("depth", "queue depth")
    g.set(7)
    g.inc(3)
    g.dec()
    assert g.get() == 9
    g.set_fn(lambda: 42)
    assert g.get() == 42  # callback gauges read at scrape time

    h = reg.histogram("lat_ms", "latency", buckets=(1, 10, 100))
    for v in (0.5, 3, 250):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 3 and s["min"] == 0.5 and s["max"] == 250
    assert h.cumulative_buckets()[-1] == (float("inf"), 3)


def test_registry_idempotent_and_kind_conflict():
    reg = MetricsRegistry()
    a = reg.counter("x_total", "x", ("k",))
    b = reg.counter("x_total", "other help ignored", ("k",))
    assert a is b  # same family: subsystems may re-register freely
    with pytest.raises(ValueError):
        reg.gauge("x_total")  # kind conflict
    with pytest.raises(ValueError):
        reg.counter("x_total", labels=("other",))  # label-set conflict
    with pytest.raises(ValueError):
        reg.counter("bad.name")  # Prometheus grammar enforced
    assert telemetry.sanitize_name("serving.queue_depth") == \
        "serving_queue_depth"


def test_registry_threading_exact_counts():
    reg = MetricsRegistry()
    c = reg.counter("hits_total", "t", ("who",)).labels(who="x")
    h = reg.histogram("obs_ms", "t")
    n_threads, per = 8, 500

    def work():
        for _ in range(per):
            c.inc()
            h.observe(1.0)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * per  # no lost read-modify-writes
    assert h.child().count == n_threads * per


def test_prometheus_exposition_golden():
    """The exact exposition text for a fixed registry — the scrape
    contract a Prometheus server parses."""
    reg = MetricsRegistry()
    reg.counter("req_total", "requests served",
                ("kind",)).labels(kind="a").inc(3)
    reg.gauge("depth", "queue depth").set(7)
    h = reg.histogram("lat_ms", "latency", ("e",), buckets=(1, 10))
    h.labels(e="0").observe(0.5)
    h.labels(e="0").observe(5)
    assert reg.prometheus_text() == textwrap.dedent("""\
        # HELP depth queue depth
        # TYPE depth gauge
        depth 7
        # HELP lat_ms latency
        # TYPE lat_ms histogram
        lat_ms_bucket{e="0",le="1"} 1
        lat_ms_bucket{e="0",le="10"} 2
        lat_ms_bucket{e="0",le="+Inf"} 2
        lat_ms_sum{e="0"} 5.5
        lat_ms_count{e="0"} 2
        # TYPE lat_ms_p50 gauge
        lat_ms_p50{e="0"} 0.5
        # TYPE lat_ms_p95 gauge
        lat_ms_p95{e="0"} 5
        # TYPE lat_ms_p99 gauge
        lat_ms_p99{e="0"} 5
        # HELP req_total requests served
        # TYPE req_total counter
        req_total{kind="a"} 3
        """)


def test_snapshot_roundtrip_and_deltas():
    reg = MetricsRegistry()
    c = reg.counter("ops_total", "t").child()
    c.inc(2)
    s1 = reg.snapshot()
    json.loads(json.dumps(s1))  # JSON-clean
    c.inc(5)
    reg.histogram("h_ms", "t").observe(1)
    d = MetricsRegistry.deltas_since(s1, reg.snapshot())
    assert d["ops_total"]["ops_total"] == 5
    assert d["h_ms"]["h_ms"] == 1


# ---------------------------------------------------------------------------
# serving facade / dedup
# ---------------------------------------------------------------------------
def test_serving_histogram_is_telemetry_histogram():
    from mxnet_tpu.serving.metrics import Histogram, ServingMetrics
    from mxnet_tpu.telemetry.registry import Histogram as TH

    h = Histogram(cap=16)  # old signature preserved
    assert isinstance(h, TH)
    for v in range(20):
        h.observe(float(v))
    assert h.count == 20 and len(h._recent) == 16  # bounded reservoir
    assert set(h.summary()) == {"count", "mean", "min", "max",
                                "p50", "p90", "p95", "p99"}

    m = ServingMetrics()
    m.count("submitted", 3)
    m.observe_batch(3, 4, 0.01)
    m.observe_done(0.005, ok=True)
    snap = m.snapshot()  # the serve_bench row schema, unchanged
    assert set(snap) == {"counters", "latency_ms", "batch_occupancy",
                         "pad_waste", "queue_depth", "ts_unix",
                         "shed_rate"}
    assert snap["counters"]["submitted"] == 3
    assert snap["counters"]["batches"] == 1
    assert snap["counters"]["completed"] == 1
    # and the same numbers are scrapeable from the process registry
    fam = telemetry.get_registry().get("serving_events_total")
    assert fam.labels(engine=m.engine_id, event="submitted").value == 3


# ---------------------------------------------------------------------------
# tracing / step timelines
# ---------------------------------------------------------------------------
def _validate_chrome(payload):
    sys.path.insert(0, REPO)
    from tools.trace_view import validate_events

    return validate_events(payload, "<mem>")


def test_trace_schema_validity(tmp_path):
    with tracing.span("unit.span", cat="test", args={"k": 1}):
        time.sleep(0.001)
    tracing.emit_counter("unit.counter", 5)
    path = str(tmp_path / "trace.json")
    telemetry.dump_chrome(path)
    payload = json.load(open(path))
    events = _validate_chrome(payload)  # required keys per event
    assert payload["displayTimeUnit"] == "ms"
    names = {e["name"] for e in events}
    assert {"unit.span", "unit.counter"} <= names
    ev = next(e for e in events if e["name"] == "unit.span")
    assert ev["ph"] == "X" and ev["dur"] > 0 and ev["args"]["k"] == 1


def test_step_attribution_sums_to_wall():
    with telemetry.step("unit", 0) as st:
        with st.phase("device"):
            time.sleep(0.02)
        with st.phase("input_starved"):
            time.sleep(0.01)
        time.sleep(0.01)  # unattributed -> host remainder
    att = st.attribution()
    wall = st.wall_s
    assert att["device"] == pytest.approx(0.02, rel=0.5)
    assert att["input_starved"] == pytest.approx(0.01, rel=0.5)
    assert att["host"] >= 0.009
    # the acceptance invariant: buckets reconstruct the wall exactly
    assert sum(att.values()) == pytest.approx(wall, rel=1e-6)
    # and the registry saw the step
    fam = telemetry.get_registry().get("telemetry_step_ms")
    assert fam.labels(name="unit").count >= 1


def test_step_compile_inside_device_phase_not_double_counted():
    with telemetry.step("unit2", 0) as st:
        with st.phase("device"):
            time.sleep(0.02)
            st.add("compile", 0.015)  # what the jax listener does on a
            # cold first call INSIDE the jitted-call phase
    att = st.attribution()
    assert att["compile"] == pytest.approx(0.015, abs=1e-6)
    assert att["device"] == pytest.approx(0.005, abs=0.01)
    assert sum(att.values()) == pytest.approx(st.wall_s, rel=1e-6)


def test_step_nested_phase_noop():
    with telemetry.step("unit3", 0) as st:
        with st.phase("device"):
            with st.phase("device"):  # e.g. Trainer's internal phase
                time.sleep(0.005)     # inside a bench's outer phase
    assert st.attribution()["device"] == pytest.approx(
        st.wall_s - st.attribution()["host"], rel=1e-6)
    assert sum(st.attribution().values()) == pytest.approx(
        st.wall_s, rel=1e-6)


def test_trainer_step_records_compile_and_device():
    """A real Trainer step under telemetry.step: the first step's
    compile bucket sees the fused-update (and eager-op) compiles via
    jax.monitoring; buckets always sum to wall."""
    from mxnet_tpu import autograd, gluon

    net = gluon.nn.Dense(4)
    net.initialize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1})
    x = mx.np.array(onp.ones((8, 16), "float32"))
    atts = []
    for i in range(2):
        with telemetry.step("trainer_unit", i) as st:
            with autograd.record():
                loss = (net(x) ** 2).mean()
            loss.backward()
            tr.step(8)
        atts.append((st.attribution(), st.wall_s))
    first, wall0 = atts[0]
    assert first["compile"] > 0  # the cold step paid visible compiles
    for att, wall in atts:
        assert sum(att.values()) == pytest.approx(wall, rel=1e-6)


def test_prefetch_starved_wait_attributed_and_gauged():
    from mxnet_tpu.io import DevicePrefetch

    def slow_src():
        for i in range(3):
            time.sleep(0.05)
            yield onp.full((2, 2), i, "float32")

    dp = DevicePrefetch(slow_src(), depth=2)
    with telemetry.step("starved_unit", 0) as st:
        for _ in dp:
            pass
    dp.close()
    att = st.attribution()
    assert att["input_starved"] > 0.05  # the consumer's waits landed
    assert sum(att.values()) == pytest.approx(st.wall_s, rel=1e-6)
    # gauges live in the registry without the profiler running
    reg = telemetry.get_registry()
    assert reg.get("io_prefetch_starved_ms").get() > 0
    assert reg.get("io_prefetch_bytes").get() >= 3 * 16


# ---------------------------------------------------------------------------
# profiler thread-safety + re-registration
# ---------------------------------------------------------------------------
def test_profiler_counter_concurrent_increment_exact():
    from mxnet_tpu import profiler

    c = profiler.Counter(name="unit.concurrency")
    n_threads, per = 8, 400

    def work():
        for _ in range(per):
            c.increment()

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * per  # RMW was racy before ISSUE 6
    # re-registered: the registry gauge carries the value with the
    # profiler stopped
    assert telemetry.get_registry().get("unit_concurrency").get() == \
        n_threads * per


def test_profiler_dumps_reset_under_concurrent_record_op():
    from mxnet_tpu import profiler

    stop = threading.Event()
    errs = []

    def recorder():
        try:
            while not stop.is_set():
                profiler.record_op("unit.op", 1e-5)
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=recorder) for _ in range(4)]
    for t in threads:
        t.start()
    for _ in range(20):
        table = profiler.dumps(reset=True)
        assert "Name" in table
    stop.set()
    for t in threads:
        t.join()
    assert not errs
    profiler.dumps(reset=True)  # drain


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------
def test_flight_dump_atomic_and_parseable(tmp_path):
    rec = tflight.FlightRecorder(directory=str(tmp_path), span_tail=64)
    telemetry.get_registry().counter(
        "flight_unit_total", "t").child().inc(3)
    with tracing.span("flight.unit.span"):
        pass
    path = rec.dump("unit-test")
    payload = json.load(open(path))
    assert payload["schema"] == tflight.SCHEMA
    assert payload["reason"] == "unit-test"
    assert payload["pid"] == os.getpid()
    assert any(e["name"] == "flight.unit.span" for e in payload["spans"])
    assert "flight_unit_total" in payload["metrics"]["metrics"]
    assert not [n for n in os.listdir(tmp_path) if ".tmp." in n]
    latest = json.load(open(tmp_path / "flight_latest.json"))
    assert latest["reason"] == "unit-test"
    # second dump: deltas window restarts at the previous dump
    telemetry.get_registry().get("flight_unit_total").child().inc(2)
    p2 = rec.dump("second")
    d = json.load(open(p2))["metric_deltas"]
    assert d["flight_unit_total"]["flight_unit_total"] == 2


def test_flight_try_dump_unarmed_noop(monkeypatch):
    monkeypatch.delenv("MXNET_TPU_FLIGHT_DIR", raising=False)
    rec = tflight.FlightRecorder()
    assert not rec.armed()
    assert rec.try_dump("nothing") is None


def test_flight_dump_on_stall(tmp_path, monkeypatch):
    from mxnet_tpu.base import StallDetected
    from mxnet_tpu.resilience import run_with_watchdog

    monkeypatch.setenv("MXNET_TPU_FLIGHT_DIR", str(tmp_path))
    with pytest.raises(StallDetected):
        run_with_watchdog(time.sleep, 0.05, 0.5, name="hung-unit")
    dumps = tflight.FlightRecorder.list_dumps(str(tmp_path))
    assert dumps
    reasons = {json.load(open(p))["reason"] for p in dumps}
    assert "stall:hung-unit" in reasons


def test_flight_dump_on_fatal_classification(tmp_path, monkeypatch):
    from mxnet_tpu.resilience import call_with_retry

    monkeypatch.setenv("MXNET_TPU_FLIGHT_DIR", str(tmp_path))

    def boom():
        raise ValueError("programming bug")

    with pytest.raises(ValueError):
        call_with_retry(boom)
    dumps = tflight.FlightRecorder.list_dumps(str(tmp_path))
    assert any(json.load(open(p))["reason"] == "fatal:ValueError"
               for p in dumps)


_KILL_CHILD = textwrap.dedent("""
    import sys
    sys.path.insert(0, {repo!r})
    import numpy as onp
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    from mxnet_tpu.resilience import Supervisor

    def step(state, i):
        return jax.tree_util.tree_map(lambda a: a + 1.0, state)

    sup = Supervisor(sys.argv[1], save_every_n_batches=2,
                     handle_sigterm=False)
    out = sup.run_steps(step, {{"w": jnp.zeros((4,))}}, n_steps=20)
    print("done", float(out["w"][0]))
""")


@pytest.mark.chaos
def test_supervisor_chaos_kill_leaves_flight_dump(tmp_path):
    """The ISSUE 6 acceptance drill: a chaos kill (`os._exit(137)`,
    pod-eviction semantics) during supervised training leaves a
    parseable flight-recorder post-mortem under the Supervisor's
    auto-armed `<ckpt>/flight` directory."""
    script = tmp_path / "child.py"
    script.write_text(_KILL_CHILD.format(repo=REPO))
    ckpt = tmp_path / "ckpt"
    env = {k: v for k, v in os.environ.items()
           if k not in ("MXNET_TPU_CHAOS", "MXNET_TPU_FLIGHT_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    env["MXNET_TPU_CHAOS"] = "checkpoint.write=kill:3"
    r = subprocess.run([sys.executable, str(script), str(ckpt)],
                       capture_output=True, text=True, timeout=240,
                       env=env, cwd=REPO)
    assert r.returncode == 137, r.stderr[-2000:]  # chaos kill fired
    flight_dir = ckpt / "flight"
    dumps = tflight.FlightRecorder.list_dumps(str(flight_dir))
    assert dumps, "chaos kill must leave a post-mortem artifact"
    payload = json.load(open(dumps[-1]))
    assert payload["schema"] == tflight.SCHEMA
    assert payload["reason"] == "chaos_kill:checkpoint.write"
    # the black box carries the supervised step spans + live metrics
    assert any(e["name"].startswith("step[supervised_steps]")
               for e in payload["spans"])
    assert "resilience_saves" in payload["metrics"]["metrics"]
    assert payload["chaos"]["checkpoint.write"]["kill"] == 1


# ---------------------------------------------------------------------------
# exporter
# ---------------------------------------------------------------------------
def test_exporter_parse_spec():
    assert texp.parse_spec("") is None
    assert texp.parse_spec("off") is None
    assert texp.parse_spec("/tmp/t") == \
        {"mode": "file", "dir": "/tmp/t", "period_s": 10.0}
    assert texp.parse_spec("/tmp/t:2.5") == \
        {"mode": "file", "dir": "/tmp/t", "period_s": 2.5}
    assert texp.parse_spec("http:9100") == {"mode": "http", "port": 9100}
    with pytest.warns(RuntimeWarning):
        assert texp.parse_spec("http:nope") is None


def test_exporter_file_mode_and_chaos_degrades_warn_once(tmp_path):
    d = str(tmp_path / "metrics")
    ex = texp.Exporter({"mode": "file", "dir": d, "period_s": 0.05})
    ex.start()
    try:
        deadline = time.time() + 5
        while ex.exports == 0 and time.time() < deadline:
            time.sleep(0.02)
        assert ex.exports > 0
        prom = open(os.path.join(d, "metrics.prom")).read()
        assert "# TYPE" in prom
        json.load(open(os.path.join(d, "metrics.json")))

        # chaos: every export now faults — exactly ONE warning, the
        # thread survives, nothing propagates anywhere
        with pytest.warns(RuntimeWarning, match="exposition failed"):
            with chaos.scope("telemetry.export", fail="oserror"):
                f0 = ex.failures
                deadline = time.time() + 5
                while ex.failures < f0 + 3 and time.time() < deadline:
                    time.sleep(0.02)
                assert ex.failures >= f0 + 3
        assert ex._warned  # later faults are silent (warn-once)
        # disarmed again: exposition resumes
        e0 = ex.exports
        deadline = time.time() + 5
        while ex.exports == e0 and time.time() < deadline:
            time.sleep(0.02)
        assert ex.exports > e0
    finally:
        ex.stop()


def test_exporter_http_mode():
    from urllib.request import urlopen

    ex = texp.Exporter({"mode": "http", "port": 0})
    ex.start()
    try:
        body = urlopen(
            f"http://127.0.0.1:{ex.port}/metrics", timeout=10).read()
        assert b"# TYPE" in body
        js = json.loads(urlopen(
            f"http://127.0.0.1:{ex.port}/metrics.json",
            timeout=10).read())
        assert "metrics" in js
    finally:
        ex.stop(final_flush=False)


# ---------------------------------------------------------------------------
# mfu / roofline gauges
# ---------------------------------------------------------------------------
def test_mfu_observe_step_sets_gauges():
    out = tmfu.observe_step("unit_loop", examples=1000, dt_s=2.0,
                            flops=2e9, device_kind="TPU v5 lite")
    assert out["examples_per_s"] == 500.0
    assert out["achieved_tflops"] == pytest.approx(1.0, rel=1e-6)
    assert out["mfu"] == pytest.approx(1.0 / 197.0, abs=5e-5)
    reg = telemetry.get_registry()
    assert reg.get("telemetry_mfu").labels(
        name="unit_loop").get() == pytest.approx(1.0 / 197.0, rel=1e-3)


def test_roofline_bank_reads_banked_corpus():
    bank = tmfu.RooflineBank(os.path.join(REPO, "benchmark"))
    # the measured HBM row (results_hbm_tpu.json) beats the spec table
    assert bank.hbm_gbps("TPU v5 lite") == pytest.approx(542.8)
    anchor = bank.anchor("resnet50_v1_infer_bs256_bf16")
    assert anchor and anchor["value"] > 0
    out = tmfu.observe_step(
        "unit_vs_banked", examples=anchor["value"], dt_s=1.0,
        banked_metric="resnet50_v1_infer_bs256_bf16")
    assert out["vs_banked"] == pytest.approx(1.0, rel=1e-6)


def test_roofline_bank_missing_dir_degrades():
    bank = tmfu.RooflineBank("/nonexistent/dir")
    assert bank.anchor("anything") is None
    assert bank.hbm_gbps("TPU v4") == 1228.0  # spec fallback


# ---------------------------------------------------------------------------
# trace_view tool
# ---------------------------------------------------------------------------
def test_trace_view_merge_and_summary(tmp_path):
    sys.path.insert(0, REPO)
    from tools.trace_view import load, summarize, validate_events

    with telemetry.step("view_unit", 0) as st:
        with st.phase("device"):
            time.sleep(0.005)
    p1 = str(tmp_path / "a.json")
    telemetry.dump_chrome(p1)
    events = load(p1)
    summary = summarize(events)
    assert summary["events"] == len(events)
    sa = summary["step_attribution"]
    assert sa["steps"] >= 1
    assert sa["attributed_ratio"] == pytest.approx(1.0, abs=0.01)
    # schema violations are named, not silently merged
    with pytest.raises(ValueError, match="missing required key"):
        validate_events({"traceEvents": [{"ph": "X", "ts": 0}]}, "x")
    with pytest.raises(ValueError, match="no 'dur'"):
        validate_events(
            {"traceEvents": [
                {"name": "a", "ph": "X", "ts": 0, "pid": 1}]}, "x")
