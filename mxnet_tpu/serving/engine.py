"""`InferenceEngine` — shape-bucketed, dynamically-batched inference.

The serving front-end the ROADMAP's "heavy traffic" north star needs:
concurrent callers submit arbitrary-size requests; a background
micro-batcher (:mod:`.batcher`) coalesces them; the engine pads the
coalesced batch up to a **power-of-two bucket** and runs ONE warm XLA
executable per bucket, then slices each caller's rows back out. Why
buckets: XLA compiles per shape, so serving raw request sizes means a
cold compile per novel size (tens of seconds for a real model on TPU) —
bucketing folds every size into ``log2(max_batch)`` executables, the
compiled-executable-cache-by-bucketed-shape idea from TVM (PAPERS.md)
applied to the batch axis, and the padding waste is bounded by 2x and
measured (``pad_waste`` histogram, :mod:`.metrics`).

Backend hygiene:
- the padded device batch is **donated** to the executable on
  accelerator backends (input buffer reused for outputs — no double
  allocation at the serving hot loop's rate).
"""
from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as onp

from .. import aot
from ..base import env_float, env_int
from ..ndarray.ndarray import ndarray, _wrap
from ..resilience import chaos
from .admission import (AdmissionQueue, DeadlineExceeded, Request,
                        ServerOverload)
from .batcher import DynamicBatcher
from .metrics import ServingMetrics

__all__ = ["InferenceEngine"]


def _pow2_bucket(n: int, cap: int) -> int:
    """Smallest power of two >= n, capped at ``cap`` (cap itself is
    always a valid bucket even when not a power of two)."""
    b = 1
    while b < n and b < cap:
        b <<= 1
    return min(b, cap)


def _ladder_bucket(n: int, ladder: Tuple[int, ...]) -> int:
    """Smallest explicit bucket >= n (``ladder`` is sorted ascending and
    ends at max_batch_size, so there is always a fit)."""
    for b in ladder:
        if b >= n:
            return b
    return ladder[-1]


class InferenceEngine:
    """Serve a gluon block (or pure jax callable) with dynamic batching.

    Parameters
    ----------
    model : gluon.Block or callable
        A (hybridizable) gluon block — ``functionalize`` extracts its
        pure forward — or a plain ``fn(x) -> y`` over jax arrays.
        :class:`~mxnet_tpu.gluon.block.SymbolBlock` loaded from an
        export works too (its forward wraps the StableHLO artifact).
    example_input : array-like, optional
        Example input (WITH batch axis) used to finalize deferred
        parameter shapes up front. If omitted, parameters are finalized
        lazily on the first served batch.
    max_batch_size : int
        Largest micro-batch (= largest bucket). Default from
        ``MXNET_SERVING_MAX_BATCH`` (32).
    max_delay_ms : float
        Micro-batching window: longest an admitted request waits for
        companions before its batch fires. Default from
        ``MXNET_SERVING_MAX_DELAY_MS`` (2 ms).
    max_queue_size : int
        Admission bound; a full queue raises :class:`ServerOverload`.
    timeout_ms : float, optional
        Default per-request deadline (admission->execution-start). None
        = no deadline.
    donate : bool, optional
        Donate the padded batch buffer to the executable. Default: on
        for accelerator backends, off for CPU (XLA:CPU ignores donation
        and warns).
    jit : bool
        Compile the forward with jax.jit (default). ``jit=False`` runs
        it eagerly — for host-side callables in tests.
    bucket_sizes : list of int, optional
        Explicit bucket ladder instead of the power-of-two default.
        Required when the wrapped model only accepts FIXED batch shapes
        (a :class:`~mxnet_tpu.gluon.block.SymbolBlock` from a StableHLO
        export compiles exactly its export batch: pass
        ``bucket_sizes=[export_batch]`` so every request pads up to it).
        The largest entry becomes ``max_batch_size``.
    tuned : analysis.opt.TunedConfig or str (path), optional
        A persisted autotune verdict (``mx.analysis.opt.autotune``)
        consumed at build time: its ``bucket_sizes`` /
        ``max_delay_ms`` knobs apply where the caller left the
        defaults (explicit arguments always win). A **stale** config —
        jax/jaxlib upgrade or env-knob flip since it was tuned
        (``TunedConfig.is_current``) — warns once and is ignored; the
        engine then serves on defaults rather than a verdict tuned for
        a different world. Provenance surfaces in ``stats()`` and the
        serve_bench row.
    """

    def __init__(self, model, example_input=None, *,
                 max_batch_size: Optional[int] = None,
                 max_delay_ms: Optional[float] = None,
                 max_queue_size: int = 256,
                 timeout_ms: Optional[float] = None,
                 donate: Optional[bool] = None,
                 jit: bool = True,
                 bucket_sizes: Optional[List[int]] = None,
                 metrics: Optional[ServingMetrics] = None,
                 tuned=None):
        self.tuned = None
        if tuned is not None:
            from ..analysis.opt import TunedConfig, load_tuned

            cfg = load_tuned(tuned) if isinstance(tuned, str) else tuned
            if not isinstance(cfg, TunedConfig):
                raise ValueError(f"tuned= expects a TunedConfig or a "
                                 f"path, got {type(tuned).__name__}")
            if not cfg.is_current():
                import warnings

                warnings.warn(
                    f"mxnet_tpu.serving: tuned config {cfg.label!r} "
                    f"({cfg.filename()}) is stale (jax/jaxlib or env-"
                    "knob signature changed since it was tuned) — "
                    "ignoring it; re-run mx.analysis.opt.autotune",
                    RuntimeWarning, stacklevel=2)
            else:
                self.tuned = cfg
                if bucket_sizes is None \
                        and cfg.knobs.get("bucket_sizes"):
                    bucket_sizes = list(cfg.knobs["bucket_sizes"])
                if max_delay_ms is None \
                        and cfg.knobs.get("max_delay_ms") is not None:
                    max_delay_ms = float(cfg.knobs["max_delay_ms"])
        if bucket_sizes is not None:
            if not bucket_sizes or any(int(b) < 1 for b in bucket_sizes):
                raise ValueError(f"bucket_sizes must be a non-empty list "
                                 f"of positive ints, got {bucket_sizes!r}")
            bucket_sizes = tuple(sorted({int(b) for b in bucket_sizes}))
            if max_batch_size is None:
                max_batch_size = bucket_sizes[-1]
            elif max_batch_size != bucket_sizes[-1]:
                raise ValueError(
                    f"max_batch_size {max_batch_size} must equal the "
                    f"largest bucket {bucket_sizes[-1]}")
        self._bucket_ladder = bucket_sizes  # None = pow2 policy
        if max_batch_size is None:
            # env_float (not env_int): a typo'd knob warns instead of
            # silently serving at the default cap
            max_batch_size = int(env_float("MXNET_SERVING_MAX_BATCH", 32))
        if max_delay_ms is None:
            max_delay_ms = env_float("MXNET_SERVING_MAX_DELAY_MS", 2.0)
        if max_batch_size < 1:
            raise ValueError("max_batch_size must be >= 1")
        self.max_batch_size = int(max_batch_size)
        self.max_delay_ms = float(max_delay_ms)
        self._timeout_ms = timeout_ms
        self._jit = jit
        self.metrics = metrics or ServingMetrics()
        self._closed = False
        self._close_lock = threading.Lock()

        if donate is None:
            donate = jax.default_backend() != "cpu"
        self._donate = bool(donate)

        self._model = model
        self._fn = None            # pure fn(params, x) -> out pytree
        self._params = None        # dict of jax arrays (possibly empty)
        # compiled forwards keyed by TRACE ENVIRONMENT (stem-s2d knob +
        # backend): jit's own cache keys only on shapes, and a long-lived
        # serving process must re-trace on env flips, not serve a stale
        # conv lowering — the same hazard the hybridize cache-key fix
        # (ops/nn.py:stem_s2d_cache_key) closes for HybridBlock
        self._execs: Dict[Tuple, Callable] = {}
        self._build_lock = threading.Lock()
        self._warm_lock = threading.Lock()
        self._warm_buckets: set = set()
        # the shape frontier this process compiled — savable and
        # replayable so the NEXT process warms exactly what was served
        # (docs/aot.md); entries carry the AOT store key when the
        # persistent compile cache (MXNET_TPU_AOT_CACHE) is armed
        self._warmup_manifest = aot.WarmupManifest()
        if example_input is not None:
            self._build(example_input)

        self._queue = AdmissionQueue(max_queue_size, self.metrics)
        self._batcher = DynamicBatcher(
            self._queue, self._run_batch, self.max_batch_size,
            self.max_delay_ms, metrics=self.metrics)
        self._batcher.start()
        # external /healthz answers from the same batcher-loop liveness
        # seam the fleet heartbeats gate on (unregistered at close)
        from ..telemetry import exporter as _texporter

        _texporter.register_liveness(
            f"infer:{id(self):x}",
            lambda: {"alive": self.alive, "last_tick": self.last_tick})

    # -- model plumbing ---------------------------------------------------
    def _build(self, example_input) -> None:
        """Extract the pure forward + params (idempotent, thread-safe)."""
        with self._build_lock:
            if self._fn is not None:
                return
            model = self._model
            if callable(model) and not hasattr(model, "collect_params"):
                fn = lambda params, x: model(x)  # noqa: E731
                params = {}
            else:
                x = example_input
                if not isinstance(x, ndarray):
                    x = _wrap(jnp.asarray(onp.asarray(x)))
                bfn, params = model.functionalize(x, training=False)

                def fn(params, x):
                    out, _new_params = bfn(params, x)
                    return out

            # publish order matters: _get_exec reads _fn WITHOUT the
            # lock on its fast path, so params must be visible first
            self._params = params
            self._fn = fn

    def _get_exec(self) -> Callable:
        """The compiled forward for the CURRENT trace environment."""
        if not self._jit:
            return self._fn
        from ..ops.nn import stem_s2d_cache_key

        key = stem_s2d_cache_key()
        ex = self._execs.get(key)
        if ex is None:
            with self._build_lock:
                ex = self._execs.get(key)
                if ex is None:
                    donate = (1,) if self._donate else ()
                    # the AOT seam: consult the persistent compile cache
                    # before compiling, publish after — a plain jax.jit
                    # when no store is armed (aot.get_cache() is None)
                    ex = aot.cached_jit(self._fn, label="serving.forward",
                                        donate_argnums=donate)
                    self._execs[key] = ex
        return ex

    def _bucket(self, n: int) -> int:
        if self._bucket_ladder is not None:
            return _ladder_bucket(n, self._bucket_ladder)
        return _pow2_bucket(n, self.max_batch_size)

    def warmup(self, item_shape: Optional[Tuple[int, ...]] = None,
               dtype="float32", buckets: Optional[List[int]] = None,
               manifest=None) -> List[int]:
        """Pre-compile bucket executables so the first real traffic does
        not pay cold-compile latency. Returns the buckets warmed.

        Two modes:

        - ``item_shape=`` (+ optional ``buckets=``) — warm one item
          signature over the bucket ladder (all of it by default);
        - ``manifest=`` (a :class:`~mxnet_tpu.aot.WarmupManifest` or a
          path to one, recorded by a previous server via
          :meth:`save_warmup_manifest`) — replay exactly the shape
          frontier that server compiled, across every item signature it
          served, instead of guessing.

        With the persistent compile cache armed
        (``MXNET_TPU_AOT_CACHE``), either mode resolves executables from
        the store — warmup cost becomes deserialize + cached backend
        compile, not cold XLA compiles.
        """
        if manifest is not None:
            if item_shape is not None or buckets is not None:
                raise ValueError(
                    "pass either manifest= or item_shape=/buckets=, "
                    "not both")
            if not isinstance(manifest, aot.WarmupManifest):
                manifest = aot.WarmupManifest.load(manifest)
            out, seen = [], set()
            for b, shape, dt in manifest.serving_signatures():
                if b > self.max_batch_size:
                    continue  # recorded by a larger-capped server
                # map through THIS engine's ladder: a recorder with a
                # different bucket_ladder logged sizes our dispatch
                # would never select — warm the bucket b rows would
                # actually land in, not the recorded literal
                b = self._bucket(b)
                sig = (b, tuple(shape), dt)
                if sig in seen:
                    continue
                seen.add(sig)
                x = onp.zeros((b,) + tuple(shape), dt)
                self._execute_padded(x, tuple(shape),
                                     str(onp.dtype(dt)))
                out.append(b)
            return sorted(set(out))
        if item_shape is None:
            raise ValueError("warmup needs item_shape= or manifest=")
        dtype = onp.dtype(dtype)
        if buckets is None and self._bucket_ladder is not None:
            buckets = list(self._bucket_ladder)
        elif buckets is None:
            buckets, b = [], 1
            while b < self.max_batch_size:
                buckets.append(b)
                b <<= 1
            buckets.append(self.max_batch_size)
        out = []
        for b in sorted(set(buckets)):
            x = onp.zeros((b,) + tuple(item_shape), dtype)
            self._execute_padded(x, tuple(item_shape), str(dtype))
            out.append(b)
        return out

    def warmup_manifest(self) -> "aot.WarmupManifest":
        """The live manifest of every bucket signature this engine has
        compiled (shared object — it keeps growing as traffic arrives)."""
        return self._warmup_manifest

    def save_warmup_manifest(self, path: str) -> str:
        """Snapshot the compiled-shape frontier to ``path`` for a future
        process to replay (``engine.warmup(manifest=path)`` or
        ``tools/aot_warmup.py --manifest path``)."""
        return self._warmup_manifest.save(path)

    # -- client surface ---------------------------------------------------
    def infer(self, x, timeout_ms: Optional[float] = "default"):
        """Blocking inference on one request.

        ``x`` must carry a leading batch axis (``n >= 1`` rows, at most
        ``max_batch_size``); rows from concurrent callers are coalesced
        into shared buckets and each caller gets exactly its rows back.
        Raises :class:`ServerOverload` / :class:`DeadlineExceeded` under
        load shedding.
        """
        return self.infer_async(x, timeout_ms=timeout_ms).wait()

    def infer_one(self, x, timeout_ms: Optional[float] = "default"):
        """Single-sample convenience: adds the batch axis on the way in
        and strips it from the result."""
        xs = onp.asarray(x)[None]
        out = self.infer(xs, timeout_ms=timeout_ms)
        return jax.tree_util.tree_map(
            lambda a: a[0], out,
            is_leaf=lambda v: isinstance(v, ndarray))

    def infer_async(self, x, timeout_ms: Optional[float] = "default") -> Request:
        """Submit without blocking; returns the :class:`Request` handle
        (``handle.wait()`` collects the result or re-raises)."""
        if self._closed:
            raise ServerOverload("serving engine is closed")
        # copy, don't alias: the request holds this buffer until its
        # batch fires — a caller refilling its numpy buffer for the next
        # request must not corrupt the queued one (asnumpy() already
        # yields a fresh host buffer for mx/jax arrays)
        host = (x.asnumpy() if isinstance(x, ndarray)
                else onp.array(x, copy=True))
        if host.ndim < 1 or host.shape[0] < 1:
            raise ValueError("request needs a leading batch axis with >= 1 "
                             f"rows, got shape {host.shape}")
        if host.shape[0] > self.max_batch_size:
            raise ValueError(
                f"request batch {host.shape[0]} exceeds max_batch_size "
                f"{self.max_batch_size}; split it client-side")
        if timeout_ms == "default":
            timeout_ms = self._timeout_ms
        deadline = (time.monotonic() + timeout_ms / 1e3
                    if timeout_ms is not None else None)
        sig = (host.shape[1:], str(host.dtype))
        req = Request(host, host.shape[0], sig, deadline)
        self._queue.submit(req)          # may raise ServerOverload
        self.metrics.count("submitted")
        return req

    def stats(self) -> Dict:
        snap = self.metrics.snapshot()
        with self._warm_lock:  # batcher may be add()ing concurrently
            snap["warm_buckets"] = sorted(self._warm_buckets)
        snap["queue_len"] = len(self._queue)
        snap["max_batch_size"] = self.max_batch_size
        snap["max_delay_ms"] = self.max_delay_ms
        snap["aot"] = aot.stats()  # process-wide hit/miss/bytes counters
        snap["tuned"] = self.tuned.provenance() if self.tuned else None
        snap["backend"] = jax.default_backend()
        return snap

    @property
    def alive(self) -> bool:
        """The serving loop is live: batcher thread running, engine not
        closed (the fleet health monitor's liveness probe)."""
        return not self._closed and self._batcher.alive

    @property
    def last_tick(self) -> float:
        """Monotonic stamp of the batcher loop's last iteration."""
        return self._batcher.last_tick

    def close(self, drain: bool = True, timeout_s: float = 60.0) -> None:
        """Shut down: stop admitting, then either finish everything
        queued (``drain=True``) or fail it with :class:`ServerOverload`.
        Idempotent; the batcher thread exits either way."""
        from ..telemetry import exporter as _texporter

        _texporter.unregister_liveness(f"infer:{id(self):x}")
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
            self._queue.close()
            if not drain:
                self._queue.fail_all(
                    lambda: ServerOverload("engine closed without drain"))
            self._batcher.join(timeout_s)

    def __enter__(self) -> "InferenceEngine":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    # -- batcher callback -------------------------------------------------
    def _run_batch(self, batch: List[Request]) -> None:
        # a request can expire between being gathered (take() holds the
        # batch open up to max_delay) and execution starting — the
        # shed-before-compute contract needs one last check here
        now = time.monotonic()
        live = []
        for r in batch:
            if r.expired(now):
                self.metrics.count("shed_deadline")
                r.fail(DeadlineExceeded(
                    f"deadline passed while the batch was forming "
                    f"({r.latency_s * 1e3:.1f} ms since admission) — "
                    "shed before execution"))
            else:
                live.append(r)
        batch = live
        if not batch:
            return
        total = sum(r.n for r in batch)
        bucket = self._bucket(total)
        item_shape = batch[0].signature[0]
        dtype = batch[0].signature[1]
        # host-side staging: one padded buffer, one device transfer
        staged = onp.zeros((bucket,) + tuple(item_shape), dtype=dtype)
        off = 0
        for r in batch:
            staged[off:off + r.n] = r.payload
            off += r.n
        t0 = time.perf_counter()
        # no try here: an execution error propagates to DynamicBatcher's
        # loop, the ONE canonical fail-the-batch path (request fail +
        # failed-counter accounting, first-completion-wins guarded)
        out = self._execute_padded(staged, tuple(item_shape), dtype)
        exec_s = time.perf_counter() - t0
        self.metrics.observe_batch(total, bucket, exec_s)
        off = 0
        for r in batch:
            lo, hi = off, off + r.n
            off = hi
            sliced = jax.tree_util.tree_map(lambda a: _wrap(a[lo:hi]), out)
            r.finish(sliced)
            self.metrics.observe_done(r.latency_s, ok=True, n=1)

    def _execute_padded(self, staged: onp.ndarray,
                        item_shape: Tuple[int, ...], dtype: str):
        """Run one padded bucket through the compiled forward. Returns
        the raw output pytree of jax arrays (leading axis = bucket)."""
        bucket = staged.shape[0]
        key = (bucket, item_shape, dtype)
        # chaos site BEFORE the compute: injected latency here holds the
        # batcher thread (queued requests blow their deadlines — the
        # serving deadline drill), an injected fault fails the batch
        # through the canonical DynamicBatcher fail path
        chaos.site("serving.infer", bucket=bucket)

        if self._fn is None:
            self._build(staged)
        out = self._get_exec()(self._params, jnp.asarray(staged))
        out = jax.tree_util.tree_map(
            lambda a: a.block_until_ready()
            if hasattr(a, "block_until_ready") else a, out)
        with self._warm_lock:
            record = key not in self._warm_buckets
            if record:  # counted on SUCCESS only:
                self.metrics.count("compiles")  # retries don't inflate
                self._warm_buckets.add(key)
        if record:
            # outside _warm_lock: the manifest append re-enters
            # _get_exec and may COMPILE — holding the lock through a
            # compile wedges every concurrent first-bucket request (C002)
            self._record_warmup(bucket, item_shape, dtype, staged)
        return out

    def _record_warmup(self, bucket: int, item_shape: Tuple[int, ...],
                       dtype: str, staged: onp.ndarray) -> None:
        """Append the just-compiled bucket signature to the warmup
        manifest, with the AOT store key when one resolved (observability
        only — must never fail a served batch)."""
        entry = {"label": "serving.bucket", "bucket": int(bucket),
                 "item_shape": list(item_shape), "dtype": str(dtype)}
        try:
            if self._jit:
                ex = self._get_exec()
                key = getattr(ex, "resolved_key", lambda *a: None)(
                    self._params, staged)
                if key:
                    entry["key"] = key
        except Exception:  # noqa: BLE001 — manifest is best-effort
            pass
        self._warmup_manifest.record(**entry)
