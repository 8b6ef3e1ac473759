"""Profiler (reference ``src/profiler/`` + ``python/mxnet/profiler.py``).

Keeps the reference contract — ``set_config(filename=...)``,
``set_state('run'/'stop')``, chrome://tracing JSON output (`profile.json`,
reference ``profiler.h:451``), per-op aggregate stat table
(``aggregate_stats.cc``) — implemented over jax.profiler (XPlane/Perfetto
traces for device-side detail) plus our own host-side op timeline: the
dispatch layer calls :func:`record_op` around every eager op when profiling
is on, mirroring how the reference engine times every OprBlock
(``threaded_engine.h:85``) without operator cooperation.

The event store and counters are **no longer private**: op spans land in
the process trace ring (:func:`mxnet_tpu.telemetry.tracing.buffer`) —
one merged timeline with the telemetry step spans — and every
:class:`Counter` re-registers as a gauge in the
:mod:`mxnet_tpu.telemetry` metrics registry, so the Prometheus/JSON
exposition sees ``serving.queue_depth`` / ``aot.aot_hits`` / the
``resilience.*`` counters without the profiler running. ``dump()``
therefore writes the merged timeline, atomically (tmp → ``os.replace``).
"""
from __future__ import annotations

import os
import threading
import time
from collections import defaultdict
from typing import Dict, List

import jax

from .telemetry import registry as _registry
from .telemetry import tracing as _tracing

__all__ = [
    "set_config",
    "set_state",
    "state",
    "dump",
    "dumps",
    "pause",
    "resume",
    "Scope",
    "Task",
    "Frame",
    "Counter",
    "Marker",
]

_lock = threading.Lock()
_config = {
    "filename": "profile.json",
    "profile_all": False,
    "profile_symbolic": True,
    "profile_imperative": True,
    "profile_memory": False,
    "profile_api": False,
    "aggregate_stats": False,
}
_state = "stop"
# the process trace ring (shared with telemetry step spans; bounded —
# the old private list grew without limit). len()/append keep working
# for code that reaches in.
_events = _tracing.buffer()
_agg: Dict[str, List[float]] = defaultdict(list)
_agg_mem: Dict[str, int] = {}
_jax_tracing = False


def set_config(**kwargs):
    """reference python/mxnet/profiler.py:66"""
    with _lock:
        _config.update(kwargs)


def set_state(state_: str = "stop", profile_process: str = "worker"):
    global _state, _jax_tracing
    if state_ not in ("run", "stop"):
        raise ValueError("state must be 'run' or 'stop'")
    prev, _state = _state, state_
    if state_ == "run" and prev == "stop":
        trace_dir = os.environ.get("MXNET_PROFILER_TRACE_DIR")
        if trace_dir:
            try:
                jax.profiler.start_trace(trace_dir)
                _jax_tracing = True
            except Exception:
                pass
    elif state_ == "stop" and prev == "run":
        if _jax_tracing:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            _jax_tracing = False
        if _config.get("filename"):
            dump()


def state() -> str:
    return _state


def is_running() -> bool:
    return _state == "run"


def pause(profile_process="worker"):
    set_state("stop")


def resume(profile_process="worker"):
    set_state("run")


_mem_probe = None  # None = unprobed; False = backend has no stats


def device_memory(device=None) -> dict:
    """Live device-memory counters (the storage_profiler.cc analog):
    ``bytes_in_use`` / ``peak_bytes_in_use`` etc. from the XLA allocator.
    Returns {} on backends that expose no stats (virtual CPU devices)."""
    import jax

    d = device or jax.devices()[0]
    try:
        return dict(d.memory_stats() or {})
    except Exception:
        return {}


def _mem_in_use() -> int:
    """Per-op memory probe with the no-stats case cached (record_op is on
    the profiled hot path; don't pay device resolution per op for {})."""
    global _mem_probe
    if _mem_probe is False:
        return 0
    if _mem_probe is None:
        import jax

        try:
            dev = jax.devices()[0]
            if not (dev.memory_stats() or {}):
                _mem_probe = False
                return 0
            _mem_probe = dev
        except Exception:
            _mem_probe = False
            return 0
    try:
        return int((_mem_probe.memory_stats() or {}).get("bytes_in_use", 0))
    except Exception:
        return 0


def record_op(name: str, dur_s: float, cat: str = "operator"):
    """Called by the dispatch layer per eager op while profiling."""
    ts = time.perf_counter() * 1e6
    mem = _mem_in_use()
    # span into the shared ring (its own lock); aggregates under ours
    _tracing.emit_complete(
        name, ts - dur_s * 1e6, dur_s * 1e6, cat=cat,
        args={"bytes_in_use": mem} if mem else None)
    with _lock:
        _agg[name].append(dur_s * 1e3)
        if mem:
            _agg_mem[name] = max(_agg_mem.get(name, 0), mem)


def dumps(reset: bool = False) -> str:
    """Aggregate per-op stats table (reference aggregate_stats.cc), with a
    peak device-memory column when the backend reports allocator stats.
    Thread-safe against concurrent :func:`record_op` callers (serving
    worker + feeder threads): the table renders from one consistent
    snapshot, and ``reset=True`` clears exactly what was rendered."""
    with _lock:
        agg = {name: list(times) for name, times in _agg.items()}
        agg_mem = dict(_agg_mem)
        if reset:
            _agg.clear()
            _agg_mem.clear()
    lines = [f"{'Name':<30}{'Calls':>8}{'Total(ms)':>12}{'Mean(ms)':>12}"
             f"{'Max(ms)':>12}{'PeakMem(MB)':>13}"]
    for name, times in sorted(agg.items(), key=lambda kv: -sum(kv[1])):
        peak = agg_mem.get(name, 0) / (1024 * 1024)
        lines.append(
            f"{name:<30}{len(times):>8}{sum(times):>12.3f}"
            f"{sum(times) / len(times):>12.3f}{max(times):>12.3f}"
            f"{peak:>13.2f}"
        )
    return "\n".join(lines)


def dump(finished: bool = True, profile_process: str = "worker"):
    """Write chrome://tracing JSON (reference profiler.h:432) — the
    merged ring (op spans + telemetry step/serving/resilience spans),
    published atomically."""
    with _lock:
        filename = _config["filename"]
    _tracing.dump_chrome(filename)


class Scope:
    """Context manager adding a named span to the trace (ProfileTask/Frame)."""

    def __init__(self, name: str, cat: str = "user"):
        self.name, self.cat = name, cat

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if is_running():
            record_op(self.name, time.perf_counter() - self._t0, self.cat)


class Task(Scope):
    def __init__(self, domain=None, name="task"):
        super().__init__(name, "task")

    def start(self):
        self._t0 = time.perf_counter()

    def stop(self):
        if is_running():
            record_op(self.name, time.perf_counter() - self._t0, self.cat)


class Frame(Task):
    pass


class Counter:
    """reference ProfileCounter profiler.h:557 — re-registered as a
    gauge in the telemetry registry (sanitized name: dots become
    underscores), so the value is scrapeable whether or not the profiler
    runs; the chrome counter-event stream still only flows while
    profiling. Same-named counters share one registry series
    (process-wide gauge semantics: last write wins).

    Thread-safe: ``increment``/``decrement`` are atomic
    read-modify-writes (concurrent serving worker + feeder threads used
    to lose updates)."""

    def __init__(self, domain=None, name="counter", value=0):
        self.name = name
        self._lock = threading.Lock()
        self._gauge = _registry.get_registry().gauge(
            _registry.sanitize_name(name),
            "profiler counter (mx.profiler.Counter)")
        self.value = value
        if value:
            self._gauge.set(value)

    def _set(self, v):
        self.value = v
        self._gauge.set(v)
        if is_running():
            _tracing.emit_counter(self.name, v)

    def set_value(self, v):
        with self._lock:
            self._set(v)

    def increment(self, delta=1):
        with self._lock:
            self._set(self.value + delta)

    def decrement(self, delta=1):
        with self._lock:
            self._set(self.value - delta)


class Marker:
    def __init__(self, domain=None, name="marker"):
        self.name = name

    def mark(self, scope="process"):
        if is_running():
            _tracing.emit_instant(self.name, cat="marker")


class Domain:
    def __init__(self, name):
        self.name = name


# reference env_var.md: MXNET_PROFILER_AUTOSTART starts the profiler at
# import; MXNET_PROFILER_MODE selects whether only symbolic/compiled
# execution (0, the reference default) or everything including
# imperative ops (1) is profiled
if os.environ.get("MXNET_PROFILER_AUTOSTART", "0") == "1":
    set_config(profile_all=os.environ.get("MXNET_PROFILER_MODE", "0") == "1")
    set_state("run")
