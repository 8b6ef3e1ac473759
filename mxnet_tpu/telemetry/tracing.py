"""Step-timeline tracing: spans, a bounded trace ring, Chrome export.

The process keeps ONE bounded ring of ``trace_event`` dicts
(:func:`buffer`) that every instrumented subsystem appends into — the
profiler's per-op timeline (``mx.profiler.record_op``), serving
micro-batch spans, Supervisor restore spans, chaos fires, and the step
timelines below. One ring means one merged timeline: :func:`dump_chrome`
writes a Chrome ``trace_event`` JSON loadable in Perfetto / chrome://
tracing, and the flight recorder dumps the ring's tail as the
"what was happening" record.

**Step timelines** (:func:`step`) attribute a training/serving step's
wall time into four buckets:

- ``compile``  — jaxpr trace + lowering + XLA backend compile, observed
  via a ``jax.monitoring`` duration listener (fires on the caller's
  thread, so attribution lands on the step that paid it);
- ``device``   — time blocked in compiled executables
  (``Trainer``'s fused update phase, or any explicit
  ``st.phase('device')``), with compile time that occurred *inside* the
  phase subtracted so the two buckets never double-count;
- ``input_starved`` — time the consumer waited on an empty input queue
  (``io.DevicePrefetch`` attributes its wait automatically);
- ``host``     — the remainder: eager op dispatch, metric updates,
  Python glue. Computed as ``wall - (compile + device + input_starved)``
  so the buckets sum to the measured wall time by construction.

**Spans** (:class:`span`) are the one timing primitive of the package:
a span records its name, start and end on ``time.perf_counter``, a
process-wide ``id``, the ``id`` of its ``parent`` (the span open on the
same thread when it started) and the ambient ``trace_id``; where the
site asks for it (``cpu=True``) also the thread's CPU time inside it
(``cpu_us``; ``dur - cpu_us`` is time the thread was off the CPU). For
the same interval it holds a
``jax.profiler.TraceAnnotation("mxnet_tpu.<name>")``, so that the span is
also an event on its thread's line of ``/host:CPU`` in a profiler trace,
on the clock of the device's operations (a no-op of well under a
microsecond while no profiler session runs). A step's phases and the
step itself are spans. :func:`rows` reads the ring's spans back.

All recording is host arithmetic + one bounded-deque append — no device
syncs (tpulint A001) and cheap enough to leave on permanently at step
granularity.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation as _Annotation

from .registry import get_registry

__all__ = [
    "BUCKETS", "StepTimeline", "TraceBuffer", "TraceContext", "buffer",
    "span", "step", "current_step", "attribute", "phase_if_active",
    "chrome_trace", "dump_chrome", "now_us", "emit_complete",
    "emit_counter", "emit_instant", "new_trace_id", "current_trace",
    "trace_scope", "bind_trace", "clock_anchor", "rows",
]

#: Step attribution buckets (``host`` is the computed remainder).
BUCKETS = ("compile", "device", "input_starved", "host")

#: per-thread state: the bound trace context (``trace``), the innermost
#: open step (``step``) and the ids of the open spans (``spans``)
_tls = threading.local()


def _env_int(name: str, default: int) -> int:
    """Malformed-knob contract: a typo'd value (unparseable OR negative
    — deque(maxlen=-5) raises) must not kill `import mxnet_tpu`."""
    try:
        v = int(os.environ.get(name, "") or default)
    except ValueError:
        return default
    return v if v >= 0 else default


def now_us() -> float:
    """The trace clock (µs). Same clock as ``profiler.record_op`` so
    both streams merge into one consistent timeline."""
    return time.perf_counter() * 1e6


def clock_anchor() -> Dict[str, float]:
    """One ``(trace clock, wall clock)`` sample — the monotonic-epoch
    anchor every process exports so ``tools/trace_view.py
    --merge-root`` can shift each per-process trace onto ONE shared
    (unix-epoch µs) timeline. ``perf_counter`` has an arbitrary,
    per-process zero; the pair below is the bridge:
    ``ts_unix_us = ts + (anchor_unix_us - anchor_mono_us)``."""
    # read the two clocks back-to-back; the instruction gap between
    # them (sub-µs) is the alignment error floor
    mono_us = time.perf_counter() * 1e6
    unix_us = time.time() * 1e6
    return {"mono_us": mono_us, "unix_us": unix_us}


# ---------------------------------------------------------------------------
# request-scoped trace context
# ---------------------------------------------------------------------------
_trace_seq_lock = threading.Lock()
_trace_seq = 0


def new_trace_id(prefix: str = "t") -> str:
    """Mint a cluster-unique trace id (``<prefix>-<pid>-<seq>`` — the
    pid namespaces concurrent minters across processes sharing one
    telemetry root). Minted at the request's FIRST entry point (Router
    admission, ``io.service`` dispatch) and propagated — never re-mint
    for a request that already carries one."""
    global _trace_seq
    with _trace_seq_lock:
        _trace_seq += 1
        seq = _trace_seq
    return f"{prefix}-{os.getpid()}-{seq}"


class TraceContext:
    """One request's distributed-trace identity: the ``trace_id``
    minted at admission plus the identity of the process/component
    currently serving it. Carried across process boundaries as a plain
    dict (:meth:`to_dict` / :meth:`from_dict` — the ``_ProcHost``
    JSON-lines pipe and the io.service worker cfg both ride it), and
    stamped into span/step args so the merged cluster timeline can be
    filtered down to ONE request's path through N processes."""

    __slots__ = ("trace_id", "parent_span", "role", "rank", "replica")

    def __init__(self, trace_id: Optional[str] = None,
                 parent_span: Optional[str] = None,
                 role: Optional[str] = None, rank: Optional[int] = None,
                 replica: Optional[str] = None):
        self.trace_id = trace_id or new_trace_id()
        self.parent_span = parent_span
        self.role = role
        self.rank = rank
        self.replica = replica

    def to_dict(self) -> Dict:
        out: Dict = {"trace_id": self.trace_id}
        for k in ("parent_span", "role", "rank", "replica"):
            v = getattr(self, k)
            if v is not None:
                out[k] = v
        return out

    @classmethod
    def from_dict(cls, d: Optional[Dict]) -> Optional["TraceContext"]:
        if not isinstance(d, dict) or not d.get("trace_id"):
            return None
        return cls(trace_id=str(d["trace_id"]),
                   parent_span=d.get("parent_span"),
                   role=d.get("role"), rank=d.get("rank"),
                   replica=d.get("replica"))

    def child(self, parent_span: str) -> "TraceContext":
        """The same trace, one hop deeper (new parent span label)."""
        return TraceContext(self.trace_id, parent_span, self.role,
                            self.rank, self.replica)

    def __repr__(self) -> str:  # pragma: no cover — debugging aid
        return f"TraceContext({self.to_dict()!r})"


def current_trace() -> Optional[TraceContext]:
    """The trace context bound to this thread (or None)."""
    return getattr(_tls, "trace", None)


def bind_trace(ctx: Optional[TraceContext]) -> None:
    """Bind ``ctx`` to this thread un-scoped — for worker processes
    whose whole lifetime serves one trace (io.service decode workers);
    request-scoped callers use :class:`trace_scope`."""
    _tls.trace = ctx


class trace_scope:
    """Bind a :class:`TraceContext` to the current thread for the
    duration of a ``with`` block — spans/steps recorded inside pick it
    up (``StepTimeline`` stamps the ambient trace id into its args)."""

    __slots__ = ("_ctx", "_prev")

    def __init__(self, ctx: Optional[TraceContext]):
        self._ctx = ctx

    def __enter__(self) -> Optional[TraceContext]:
        self._prev = getattr(_tls, "trace", None)
        _tls.trace = self._ctx
        return self._ctx

    def __exit__(self, *exc) -> bool:
        _tls.trace = self._prev
        return False


class TraceBuffer:
    """Bounded, thread-safe ring of Chrome ``trace_event`` dicts."""

    def __init__(self, maxlen: int):
        self._dq: deque = deque(maxlen=maxlen)
        self._lock = threading.Lock()
        self.dropped = 0
        #: total events ever appended — a cheap change detector (the
        #: exporter skips rewriting trace.json when the ring hasn't
        #: moved since the last exposition; length alone can't tell,
        #: a full ring keeps the same length forever)
        self.seq = 0

    def append(self, ev: dict) -> None:
        with self._lock:
            if len(self._dq) == self._dq.maxlen:
                self.dropped += 1
            self._dq.append(ev)
            self.seq += 1

    def snapshot(self) -> List[dict]:
        with self._lock:
            return list(self._dq)

    def tail(self, n: int) -> List[dict]:
        with self._lock:
            if n >= len(self._dq):
                return list(self._dq)
            return list(self._dq)[-n:]

    def clear(self) -> None:
        with self._lock:
            self._dq.clear()
            self.dropped = 0

    def __len__(self) -> int:
        return len(self._dq)


#: Ring capacity: ~260k events ≈ a few hundred MB of JSON at most; the
#: ring bounds memory where the old profiler list grew without limit.
_buffer = TraceBuffer(_env_int("MXNET_TPU_TRACE_EVENTS", 262144))


def buffer() -> TraceBuffer:
    """The process trace ring (shared with ``mx.profiler``)."""
    return _buffer


#: this process's id for the ring's rows: ``os.getpid()`` is a system call
#: on every row (5.7 us on the chip's host, PR 26); a forked child gets
#: its own
_pid = os.getpid()


def _pid_after_fork() -> None:
    global _pid
    _pid = os.getpid()


os.register_at_fork(after_in_child=_pid_after_fork)


def emit_complete(name: str, ts_us: float, dur_us: float,
                  cat: str = "telemetry",
                  args: Optional[dict] = None,
                  tid: Optional[int] = None) -> None:
    ev = {"name": name, "cat": cat, "ph": "X", "ts": ts_us,
          "dur": dur_us, "pid": _pid,
          "tid": tid if tid is not None
          else threading.get_ident() % 10000}
    if args:
        ev["args"] = args
    _buffer.append(ev)


def emit_counter(name: str, value: float,
                 ts_us: Optional[float] = None) -> None:
    _buffer.append({"name": name, "ph": "C",
                    "ts": now_us() if ts_us is None else ts_us,
                    "pid": _pid, "args": {"value": value}})


def emit_instant(name: str, cat: str = "telemetry",
                 args: Optional[dict] = None) -> None:
    ev = {"name": name, "cat": cat, "ph": "i", "ts": now_us(),
          "pid": _pid, "tid": threading.get_ident() % 10000,
          "s": "p"}
    if args:
        ev["args"] = args
    _buffer.append(ev)


#: span ids: one process-wide sequence (``next`` is atomic under the GIL)
_span_ids = itertools.count(1)

#: every annotation the program writes into a profiler trace starts so
ANNOTATION_PREFIX = "mxnet_tpu."


class span:
    """Context manager timing one named interval on this thread.

    On exit one complete event goes to the ring: ``ts``/``dur`` from
    ``time.perf_counter`` (read once at enter, once at exit) and, in
    ``args``, what the caller gave plus ``id``, ``parent`` (absent for a
    root) and the ambient ``trace_id`` (unless ``args`` already has one —
    the LLM scheduler passes its request's). The same interval is a
    ``TraceAnnotation`` named ``mxnet_tpu.<name>``. ``sp.dur_s`` is set at
    exit, for a caller that feeds a histogram from it.

    ``args`` may be filled while the span is open (``sp.args["n"] = 3``).
    ``cpu=True`` also records ``cpu_us``, the thread's CPU time inside the
    span (``dur - cpu_us`` is time the thread was off the CPU): two
    ``time.thread_time()`` system calls, 6 us each on the chip's host, so
    only where a reader wants it (``autograd.backward``).
    ``ring=False`` keeps the annotation and ``dur_s`` but writes no ring
    row, and the span is nobody's parent: for sites that run thousands of
    times a step (``autograd.node:<op>``). Set before the exit
    (``sp.ring = False``) it drops the row of a span that turned out to
    hold nothing (an idle scheduler tick).
    """

    __slots__ = ("name", "cat", "args", "ring", "id", "parent", "start_s",
                 "dur_s", "_cpu0", "_ann")

    def __init__(self, name: str, cat: str = "telemetry",
                 args: Optional[dict] = None, ring: bool = True,
                 cpu: bool = False):
        self.name, self.cat, self.ring = name, cat, ring
        self.args = dict(args) if args else {}
        self.id = self.parent = None
        self._cpu0 = 0.0 if cpu else None

    def __enter__(self) -> "span":
        if self.ring:
            try:
                stack = _tls.spans
            except AttributeError:
                stack = _tls.spans = []
            self.id = next(_span_ids)
            self.parent = stack[-1] if stack else None
            stack.append(self.id)
        self._ann = _Annotation(ANNOTATION_PREFIX + self.name)
        self._ann.__enter__()
        # the wall clock is read outside the CPU clock, so cpu <= dur
        self.start_s = time.perf_counter()
        if self._cpu0 is not None:
            self._cpu0 = time.thread_time()
        return self

    def __exit__(self, *exc) -> bool:
        if self._cpu0 is not None:
            cpu = time.thread_time() - self._cpu0
        self.dur_s = time.perf_counter() - self.start_s
        self._ann.__exit__(None, None, None)
        if self.id is None:
            return False
        # () where this thread never opened a span: one entered on
        # another thread is on that thread's stack, not on ours
        stack = getattr(_tls, "spans", ())
        if stack and stack[-1] == self.id:
            stack.pop()
        elif self.id in stack:      # left out of order: drop the orphans
            del stack[stack.index(self.id):]
        if not self.ring:
            return False
        args = self.args
        args["id"] = self.id
        if self.parent is not None:
            args["parent"] = self.parent
        if self._cpu0 is not None:
            args["cpu_us"] = round(
                max(0.0, min(cpu, self.dur_s)) * 1e6, 1)
        ctx = getattr(_tls, "trace", None)
        if ctx is not None and "trace_id" not in args:
            args["trace_id"] = ctx.trace_id
        emit_complete(self.name, self.start_s * 1e6, self.dur_s * 1e6,
                      self.cat, args)
        return False


def rows(lo_s: float, hi_s: float,
         name: Optional[str] = None) -> List[Tuple[str, float, float, dict]]:
    """The ring's complete spans that lie wholly inside the
    ``perf_counter`` interval ``[lo_s, hi_s]`` (all of them, or those
    called ``name``), as ``(name, start_s, end_s, args)`` — how a reader
    or a test gets at the spans. A span still open, or cut by an end of
    the interval, is not returned."""
    out = []
    for ev in _buffer.snapshot():
        if ev.get("ph") != "X" or (name is not None and ev["name"] != name):
            continue
        start, end = ev["ts"] / 1e6, (ev["ts"] + ev["dur"]) / 1e6
        if start >= lo_s and end <= hi_s:
            out.append((ev["name"], start, end, ev.get("args") or {}))
    return out


# ---------------------------------------------------------------------------
# step timelines
# ---------------------------------------------------------------------------
# registry families (registered once at import; children created lazily)
_reg = get_registry()
_step_ms = _reg.histogram(
    "telemetry_step_ms", "Step wall time (ms)", ("name",))
_bucket_ms = _reg.histogram(
    "telemetry_step_bucket_ms",
    "Per-step wall-time attribution (ms) by bucket", ("name", "bucket"))

_compile_listener_installed = False
_compile_listener_lock = threading.Lock()

#: jax.monitoring duration events counted as compile work: MLIR
#: lowering + the XLA backend compile, the two sequential stages of one
#: top-level compilation. Deliberately NOT jaxpr_trace_duration — it
#: fires for nested sub-traces too (a hybridized block traces inner
#: jaxprs inside the outer trace), which would double-count and let the
#: compile bucket exceed the step's wall time.
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


def _ensure_compile_listener() -> None:
    """Install the jax.monitoring listener that routes compile durations
    into the current step's ``compile`` bucket. Installed lazily on the
    first StepTimeline so processes that never use telemetry pay
    nothing; once installed it costs one thread-local read per compile
    event (compiles are rare by definition)."""
    global _compile_listener_installed
    if _compile_listener_installed:
        return
    with _compile_listener_lock:
        if _compile_listener_installed:
            return
        try:
            import jax.monitoring as _mon

            def _on_duration(event: str, duration_s: float, **kw) -> None:
                if event in _COMPILE_EVENTS:
                    st = current_step()
                    if st is not None:
                        st.add("compile", duration_s)

            _mon.register_event_duration_secs_listener(_on_duration)
            _compile_listener_installed = True
        except Exception:  # noqa: BLE001 — no jax / exotic version:
            _compile_listener_installed = True  # degrade to hook-less


class _Phase(span):
    """A span that also adds its time to a bucket of its step."""

    __slots__ = ("_st", "_bucket", "_nested")

    def __init__(self, st: "StepTimeline", bucket: str, label: str,
                 args: Optional[dict] = None):
        super().__init__(label, f"step.{bucket}", args)
        self._st = st
        self._bucket = bucket

    def __enter__(self) -> "_Phase":
        # a phase nested inside an open phase adds nothing to a bucket —
        # the outer phase already owns this wall time (e.g. a bench
        # wrapping trainer.step + barrier in phase('device') around the
        # Trainer's own internal device phase must not double-count); it
        # is still a span, a child of the outer one
        self._nested = self._st._open_phase is not None
        if not self._nested:
            self._st._open_phase = self._bucket
        return super().__enter__()

    def __exit__(self, *exc) -> bool:
        super().__exit__(*exc)
        if not self._nested:
            self._st._open_phase = None
            self._st.add(self._bucket, self.dur_s)
        return False


class StepTimeline:
    """One step's wall-time attribution. Use via :func:`step`::

        with telemetry.step("train", i) as st:
            batch = next(prefetch)          # input_starved: automatic
            loss = trainer_driven_step(...) # device/compile: automatic

    or attribute manually with :meth:`phase` / :meth:`add`.
    """

    __slots__ = ("name", "index", "_span", "_wall", "_buckets",
                 "_open_phase", "_compile_in_device", "_prev",
                 "_cancelled", "_annotations")

    def __init__(self, name: str = "step", index: Optional[int] = None):
        _ensure_compile_listener()
        self.name = name
        self.index = index
        self._buckets: Dict[str, float] = {
            "compile": 0.0, "device": 0.0, "input_starved": 0.0}
        self._open_phase: Optional[str] = None
        self._compile_in_device = 0.0
        self._wall: Optional[float] = None
        self._prev = None
        self._cancelled = False
        self._annotations: Optional[Dict] = None
        # the step is itself a span: the parent of its phases and of
        # whatever else opens inside it
        self._span = span(f"step[{name}]", cat="step")

    # -- recording --------------------------------------------------------
    def phase(self, bucket: str, label: Optional[str] = None,
              args: Optional[dict] = None) -> _Phase:
        if bucket not in self._buckets:
            raise ValueError(
                f"unknown bucket {bucket!r} (one of "
                f"{tuple(self._buckets)}; 'host' is the remainder)")
        return _Phase(self, bucket, label or f"{self.name}.{bucket}", args)

    def add(self, bucket: str, dur_s: float) -> None:
        """Attribute ``dur_s`` seconds to ``bucket`` (hook entry point:
        the jax compile listener and ``DevicePrefetch`` call this)."""
        if bucket not in self._buckets:
            return  # hooks must never raise into the training loop
        self._buckets[bucket] += dur_s
        if bucket == "compile" and self._open_phase == "device":
            # the compile happened inside a timed device phase (the
            # first call of a jitted step): subtract at finish so the
            # two buckets never double-count the same wall time
            self._compile_in_device += dur_s

    def annotate(self, key: str, value) -> None:
        """Attach a JSON-friendly key/value to the step's span args —
        how the LLM scheduler stamps the ``trace_ids`` of the lanes a
        ``step[llm_decode]`` served, so the merged cluster timeline can
        be filtered to one request's path. Never raises (hook
        discipline: instrumentation must not fault the loop)."""
        try:
            if self._annotations is None:
                self._annotations = {}
            self._annotations[str(key)] = value
        except Exception:  # noqa: BLE001 — annotation is best-effort
            pass

    def cancel(self) -> None:
        """Record nothing on exit — for a step opened around a data
        pull that turned out to be the iterator's exhaustion (loops
        open the step BEFORE ``next()`` so starved waits attribute;
        the final empty pull is not a step)."""
        self._cancelled = True

    # -- context ----------------------------------------------------------
    def __enter__(self) -> "StepTimeline":
        self._prev = getattr(_tls, "step", None)
        _tls.step = self
        self._span.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        self._wall = time.perf_counter() - self._span.start_s
        _tls.step = self._prev
        if self._cancelled:
            self._span.ring = False
            self._span.__exit__(*exc)
            return False
        att = self.attribution()
        args = self._span.args
        args.update((k, round(v * 1e3, 3)) for k, v in att.items())
        args["wall_ms"] = round(self._wall * 1e3, 3)
        if self.index is not None:
            args["step"] = self.index
        if self._annotations:
            args.update(self._annotations)
        # the row and the annotation end here; the registry is not in them
        self._span.__exit__(*exc)
        _step_ms.labels(name=self.name).observe(self._wall * 1e3)
        for bucket, dur in att.items():
            _bucket_ms.labels(name=self.name,
                              bucket=bucket).observe(dur * 1e3)
        return False

    # -- reading ----------------------------------------------------------
    @property
    def wall_s(self) -> Optional[float]:
        return self._wall

    def attribution(self) -> Dict[str, float]:
        """Seconds per bucket. After the step closes, buckets sum to the
        measured wall time exactly (``host`` is the remainder, and
        compile observed inside a device phase is subtracted from
        ``device``); while the step is open, the measured buckets so
        far."""
        compile_s = self._buckets["compile"]
        device = max(0.0, self._buckets["device"] - self._compile_in_device)
        inp = self._buckets["input_starved"]
        out = {"compile": compile_s, "device": device,
               "input_starved": inp}
        if self._wall is not None:
            out["host"] = max(0.0, self._wall - compile_s - device - inp)
        return out


def step(name: str = "step", index: Optional[int] = None) -> StepTimeline:
    """A new :class:`StepTimeline` context for one step."""
    return StepTimeline(name, index)


def current_step() -> Optional[StepTimeline]:
    """The innermost open step on this thread (hooks attribute into
    it), or None."""
    return getattr(_tls, "step", None)


def attribute(bucket: str, dur_s: float) -> None:
    """Attribute ``dur_s`` to ``bucket`` of the current step, if any —
    the one-line hook instrumented code calls (never raises)."""
    st = getattr(_tls, "step", None)
    if st is not None:
        st.add(bucket, dur_s)


class _NullPhase:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_PHASE = _NullPhase()


def phase_if_active(bucket: str, label: Optional[str] = None):
    """``current_step().phase(...)`` when a step is open on this thread,
    else a reusable no-op context — the cheap guard hot seams
    (``Trainer._update``) use."""
    st = getattr(_tls, "step", None)
    if st is None:
        return _NULL_PHASE
    return st.phase(bucket, label)


# ---------------------------------------------------------------------------
# Chrome export
# ---------------------------------------------------------------------------
def chrome_trace(events: Optional[List[dict]] = None) -> dict:
    """A Chrome ``trace_event`` JSON object (Perfetto/chrome://tracing
    loadable) of ``events`` (default: the whole ring)."""
    return {"traceEvents": _buffer.snapshot() if events is None
            else list(events),
            "displayTimeUnit": "ms"}


def dump_chrome(path: str, events: Optional[List[dict]] = None) -> str:
    """Write :func:`chrome_trace` to ``path`` atomically
    (tmp → ``os.replace``). Returns ``path``."""
    payload = chrome_trace(events)
    tmp = f"{path}.tmp.{os.getpid()}"
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(tmp, "w") as f:
        json.dump(payload, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return path
