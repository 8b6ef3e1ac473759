"""What ``run.py``, the runners and the readers share: finding a file by
its name, the compile counter, the benchmark's own spans, the context a
runner gets and the window's profiler trace."""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import itertools
import json
import os
import resource
import shutil
import statistics
import tempfile
import time

from . import trace_reduce

WINDOW_SPAN = "chipbench.trace_window"


def note(msg: str) -> None:
    """A line for the reader of the log; never the last one."""
    print(f"chipbench: {msg}", flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(root: str, group: str, name: str):
    """``<root>/chipbench/<group>/<name>.py``, loaded by path: a later PR
    adds a runner, a traffic kind or a reader as a new file and edits
    none that is there."""
    path = os.path.join(root, "chipbench", group, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"no {group[:-1]} named {name!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{group}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Compiles:
    """Counts backend compiles through jax's own monitoring events (the
    counter of ``chip_smoke.py``)."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


class Spans:
    """The benchmark's own spans, on the host clock and, as
    ``TraceAnnotation``, in the profiler's trace (a no-op while no trace
    is being taken)."""

    def __init__(self):
        self.rows: list = []          # (name, start_s, end_s)

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        with jax.profiler.TraceAnnotation("chipbench." + name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.rows.append((name, t0, time.perf_counter()))

    def durations(self, name: str, lo: float = 0.0,
                  hi: float = float("inf")) -> list:
        return [e - s for n, s, e in self.rows
                if n == name and s >= lo and e <= hi]


class HostWatch:
    """What the host did to a window besides the program's work, for the
    notes: a run whose rate reads far off is told apart by them. Garbage
    collections and their time (``gc.callbacks``), the process's CPU
    seconds and context switches (``getrusage``; many involuntary ones, or
    far fewer CPU seconds than its neighbours, mean the host took the
    cores away) and the machine's load average."""

    def __init__(self):
        self.gc_rows: list = []       # (start_s, seconds, generation)
        self._gc_t = self._open = self._close = None

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t = time.perf_counter()
        else:
            self.gc_rows.append((self._gc_t, time.perf_counter()
                                 - self._gc_t, info["generation"]))

    @staticmethod
    def _usage():
        r = resource.getrusage(resource.RUSAGE_SELF)
        return r.ru_utime + r.ru_stime, r.ru_nvcsw, r.ru_nivcsw

    def __enter__(self):
        gc.callbacks.append(self._on_gc)
        self._open = self._usage()
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on_gc)
        self._close = self._usage()

    def summary(self) -> str:
        cpu, vol, invol = (b - a for a, b in zip(self._open, self._close))
        by_gen = {g: (sum(1 for r in self.gc_rows if r[2] == g),
                      sum(r[1] for r in self.gc_rows if r[2] == g))
                  for g in (0, 1, 2)}
        longest = max((r[1] for r in self.gc_rows), default=0.0)
        try:
            with open("/proc/loadavg") as f:
                load = f.read().split()[0]
        except OSError:
            load = "?"
        return (f"host: {cpu:.2f} CPU s, context switches {vol} voluntary "
                f"{invol} involuntary, load average {load}; gc "
                + ", ".join(f"gen{g} {n} x ({t * 1e3:.0f} ms)"
                            for g, (n, t) in by_gen.items())
                + f", longest {longest * 1e3:.1f} ms")


def step_times(starts: list, end: float) -> list:
    """Seconds from each step's start to the next one's (the last: to
    ``end``)."""
    edges = list(starts) + [end]
    return [b - a for a, b in zip(edges, edges[1:])]


def note_steps(times: list) -> None:
    """The steps of a window for the notes: median, tail, the slowest and
    every one, so that a stalled step shows and a shorter window can be
    reckoned from a longer one's run."""
    if not times:
        return
    med = statistics.median(times)
    slow = sorted(((t, i) for i, t in enumerate(times)
                   if t > 1.5 * med), reverse=True)[:8]
    note(f"steps: median {med * 1e3:.2f} ms, min {min(times) * 1e3:.2f}, "
         f"max {max(times) * 1e3:.2f}; over 1.5 x median: "
         + (", ".join(f"#{i} {t * 1e3:.0f} ms" for t, i in slow) or "none"))
    note("step_ms: " + " ".join(f"{t * 1e3:.1f}" for t in times))


class WindowTrace:
    """The profiler over the last ``seconds`` of a window. ``maybe_start``
    is called from the measuring loop and starts the trace once its time
    has come; ``stop`` ends it and reduces the file. Off (``on=False``)
    both do nothing."""

    def __init__(self, on: bool, seconds: float):
        self.on, self.seconds = on, seconds
        self.started_at = self.stopped_at = None
        self._dir = self._span = None

    def maybe_start(self, now: float, window_end: float) -> None:
        if not self.on or self._dir is not None \
                or now < window_end - self.seconds:
            return
        import jax

        self._dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # no per-call Python events
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        self.started_at = time.perf_counter()

    def stop(self) -> dict | None:
        """The reduced trace, or None (tracing off, never started, or no
        device operation in it — a CPU run)."""
        if self._dir is None:
            return None
        import jax

        self.stopped_at = time.perf_counter()
        self._span.__exit__(None, None, None)
        try:
            jax.profiler.stop_trace()
            path = trace_reduce.find_xplane(self._dir)
            if path is None:
                return None
            note(f"trace: {path}, {os.path.getsize(path)} bytes")
            return trace_reduce.reduce(trace_reduce.read_xplane(path),
                                       WINDOW_SPAN)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)


def raw(nd):
    """The jax array under an mx ndarray (its one pytree leaf)."""
    import jax

    return jax.tree_util.tree_leaves(nd)[0]


def _weights(words, spec, std):
    """Every parameter of ``spec`` in one program: matrices, embeddings,
    positions, biases and LayerNorm offsets normal(0, ``std``), LayerNorm
    gains 1 + normal(0, ``std``) — no parameter at a value (0 or 1) at
    which dropping its term would go unseen by ``correct``. The ``rbg``
    generator keeps the program small enough for the
    persistent cache (the program's own per-leaf ``jit__uniform`` is a
    300 MB executable that the cache refuses, PR 22 and 25)."""
    import jax
    import jax.numpy as jnp

    key = jax.random.wrap_key_data(words, impl="rbg")

    def normal(i, shape):
        return std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                       jnp.float32)

    # the vectors (biases, offsets, gains) are slices of one draw: a draw
    # of its own for each of the 400-odd took GPT-2-large 200 s to compile
    vectors = [(name, shape[0]) for name, shape, _ in spec if len(shape) == 1]
    flat = normal(len(spec), (sum(n for _, n in vectors),))
    ends = list(itertools.accumulate(n for _, n in vectors))
    vector = {name: flat[end - n:end]
              for (name, n), end in zip(vectors, ends)}
    out = {}
    for i, (name, shape, dtype) in enumerate(spec):
        draw = vector[name] if name in vector else normal(i, shape)
        if name.endswith(".gamma"):
            draw = 1.0 + draw
        out[name] = draw.astype(dtype)
    return out


def make_net(model_kw: dict, dtype: str, seed: int, std: float = 0.02,
             grad_req: str | None = None):
    """``gpt_like`` with seeded random weights, made on the device in one
    jitted call and handed over as a checkpoint would be
    (``Parameter.set_data``), in the type they are run in. A net that only
    serves takes ``grad_req="null"`` — the usual setting for inference — so
    that no gradient buffer is kept beside each weight."""
    import jax
    import numpy as onp
    from mxnet_tpu.gluon.model_zoo import bert

    net = bert.gpt_like(dtype=dtype, **model_kw)
    params = net.collect_params()
    spec = tuple((k, tuple(p.shape), str(onp.dtype(p.dtype)))
                 for k, p in params.items())
    words = onp.array([seed & 0xffffffff, seed >> 32, 0x9e3779b9,
                       0x7f4a7c15], onp.uint32)
    made = jax.jit(_weights, static_argnums=(1, 2))(words, spec, std)
    for k, p in params.items():
        if grad_req == "null":
            p.grad_req = "null"
        p.set_data(made[k])
    return net


@dataclasses.dataclass
class Context:
    """What a runner gets."""
    root: str
    cell: dict            # chipbench/workloads/<cell>.json
    config: dict          # the configuration's file
    traffic: dict         # chipbench/traffic/<traffic>.json
    seed: int
    seconds: float
    trace: bool
    devices: list
    compiles: Compiles
    spans: Spans
    t_start: float        # perf_counter at process start

    def mark(self, what: str) -> None:
        """A note of how far set-up has come, in seconds since the start."""
        note(f"set-up: {time.perf_counter() - self.t_start:7.2f} s  {what}")
