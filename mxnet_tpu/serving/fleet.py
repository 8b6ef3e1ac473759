"""``mxnet_tpu.serving.fleet`` — the serving fleet fault domain.

One engine in one process is a single point of failure: a wedged or
dead replica takes every in-flight request and all future traffic with
it. This module is the serving twin of the training-side elastic fault
domain (:mod:`mxnet_tpu.resilience.elastic`): a :class:`Router` over N
engine replicas (:class:`ReplicaPool`) that **detects, contains, and
routes around** failure, so the millions-of-users north star has
something that stays up before it gets an autoscaler.

- **Replica health** — each replica beats a per-replica heartbeat file
  under the fleet root (the ``elastic.Heartbeat`` file discipline),
  gated on a liveness probe of the engine's step loop
  (``engine.alive`` + ``engine.last_tick`` age): a dead scheduler stops
  beating immediately, a *wedged* one (alive but stuck inside a step)
  goes stale on the same clock. Replicas transition
  ``healthy → draining → dead``; a dead replica's in-flight requests
  are failed typed-:class:`~mxnet_tpu.base.TransientError` and
  re-admitted elsewhere **exactly once** (first-completion-wins
  idempotence keys, so a retry never double-delivers).
- **Routing robustness** — least-loaded dispatch off the engines' live
  occupancy/queue/pool gauges; per-request deadline budgets propagated
  end-to-end (the remaining budget rides into the replica, which
  retires expired lanes mid-decode — admission wait + queue +
  execution all draw from ONE budget); **hedged sends** for requests
  past a latency percentile, first-wins with loser cancellation; and a
  per-replica **circuit breaker** (consecutive-failure trip →
  half-open probe → close) so a flapping replica can't absorb the
  hedges.
- **Tenant isolation under failure** — weighted-fair admission layered
  on :mod:`.admission`: per-tenant capacity quotas (KV blocks for LLM
  replicas, queue slots for fixed-shape ones) sized as weight shares
  of the *live* fleet capacity, and deadline-class shed order under
  pressure — a noisy neighbor or a capacity loss degrades the lowest
  class first.
- **Graceful degradation** — :meth:`ReplicaPool.drain` shrinks the
  fleet through a drain path (stop admitting, finish or re-home
  lanes, free pool state); :meth:`ReplicaPool.restart` warms the new
  engine from the previous incarnation's AOT warmup manifest and
  rejoins the rotation.

Chaos site ``serving.fleet.replica`` fires in every replica's step
loop (plus a per-replica ``serving.fleet.replica.<name>`` variant for
targeted drills): an injected fatal kills that replica in place, an
injected delay wedges it, and — for subprocess-backed replicas — a
``kill`` rule is a real ``os._exit(137)``. The tier-1 acceptance drill
chaos-kills 1 of 3 replicas mid-load and pins zero lost requests,
bounded p99 through recovery, and a flight dump naming the dead
replica (``fleet_*`` gauges ride every dump).

See ``docs/serving.md`` (fleet section) for topology and policy.
"""
from __future__ import annotations

import itertools
import json
import logging
import os
import shutil
import hashlib
import subprocess
import sys
import tempfile
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..base import FatalError, TransientError, env_float
from ..resilience import chaos
from ..telemetry import flight as _flight
from ..telemetry import tracing as _tracing
from ..telemetry.registry import get_registry
from .admission import (DeadlineExceeded, Request, RequestCancelled,
                        ServerOverload)

__all__ = [
    "HEALTHY", "DRAINING", "DEAD", "SPARE",
    "ReplicaUnavailable", "TenantConfig", "ModelSpec", "FleetRequest",
    "CircuitBreaker", "Replica", "ReplicaPool", "Router",
]

HEALTHY = "healthy"
DRAINING = "draining"
DEAD = "dead"
#: A pre-warmed replica parked OUT of rotation (the autoscaler's warm
#: pool): engine built, AOT-manifest warmed, heartbeat beating — but
#: never routed to until :meth:`ReplicaPool.activate` flips it healthy.
SPARE = "spare"

log = logging.getLogger(__name__)


# ---------------------------------------------------------------------------
# knobs
# ---------------------------------------------------------------------------

def fleet_replicas_default() -> int:
    """``MXNET_TPU_FLEET_REPLICAS`` (default 2)."""
    return int(env_float("MXNET_TPU_FLEET_REPLICAS", 2))


def fleet_heartbeat_s() -> float:
    """``MXNET_TPU_FLEET_HEARTBEAT_S`` (default 0.25 s)."""
    return env_float("MXNET_TPU_FLEET_HEARTBEAT_S", 0.25)


def fleet_stale_s(period: Optional[float] = None) -> float:
    """``MXNET_TPU_FLEET_STALE_S`` (default ``max(4 x heartbeat, 1 s)``)."""
    v = env_float("MXNET_TPU_FLEET_STALE_S", 0.0)
    if v > 0:
        return v
    return max(4.0 * (period if period is not None else fleet_heartbeat_s()),
               1.0)


def fleet_hedge_ms() -> float:
    """``MXNET_TPU_FLEET_HEDGE_MS`` (default 250; 0 disables hedging)."""
    return env_float("MXNET_TPU_FLEET_HEDGE_MS", 250.0)


def fleet_hedge_pct() -> float:
    """``MXNET_TPU_FLEET_HEDGE_PCT`` (default 95)."""
    return env_float("MXNET_TPU_FLEET_HEDGE_PCT", 95.0)


def fleet_breaker_n() -> int:
    """``MXNET_TPU_FLEET_BREAKER_N`` (default 3 consecutive failures)."""
    return int(env_float("MXNET_TPU_FLEET_BREAKER_N", 3))


def fleet_breaker_cooldown_s() -> float:
    """``MXNET_TPU_FLEET_BREAKER_COOLDOWN_S`` (default 2 s)."""
    return env_float("MXNET_TPU_FLEET_BREAKER_COOLDOWN_S", 2.0)


def fleet_affinity_on() -> bool:
    """``MXNET_TPU_FLEET_AFFINITY`` (default 1 — prefix-affinity
    routing on LLM fleets; 0 = pure least-loaded)."""
    return env_float("MXNET_TPU_FLEET_AFFINITY", 1) != 0


def fleet_affinity_blocks() -> int:
    """``MXNET_TPU_FLEET_AFFINITY_BLOCKS`` (default 4 leading blocks
    hashed into the affinity key)."""
    return int(env_float("MXNET_TPU_FLEET_AFFINITY_BLOCKS", 4))


def fleet_affinity_block_size() -> int:
    """``MXNET_TPU_FLEET_AFFINITY_BLOCK_SIZE`` — MUST match the
    engines' KV block size or affinity keys drift from cache keys
    (default: the engine's, ``llm.DEFAULT_BLOCK_SIZE``, 16)."""
    from .llm import DEFAULT_BLOCK_SIZE

    return int(env_float("MXNET_TPU_FLEET_AFFINITY_BLOCK_SIZE",
                         DEFAULT_BLOCK_SIZE))


def fleet_affinity_max_load() -> float:
    """``MXNET_TPU_FLEET_AFFINITY_MAX_LOAD`` (default 0.85): the
    affinity target's load fraction above which dispatch falls back to
    least-loaded — cache locality must never queue behind a saturated
    replica."""
    return env_float("MXNET_TPU_FLEET_AFFINITY_MAX_LOAD", 0.85)


class ReplicaUnavailable(TransientError):
    """No healthy replica could take (or keep) this request. Transient:
    the fleet may heal (breaker closes, replica restarts, capacity
    returns) — back off and resubmit through the standard
    ``resilience.retry`` loop."""


@dataclass
class ModelSpec:
    """One hosted model family in a multi-model pool: a named factory
    whose engines every replica carries side by side.

    Each replica builds ONE engine per spec, so a model's KV block pool
    is a hard per-model budget — the engine the factory configures
    (``max_running``/``max_context``/``block_size``) IS the model's
    block-pool budget on every replica, and a flood of long prompts on
    one model can never evict another model's KV blocks. The pool keeps
    a per-model AOT warmup-manifest frontier, so spares and restarts
    replay every model's compiled shapes.

    All specs in one pool must build the same engine *kind*
    (:class:`~.llm.LLMEngine` or :class:`~.engine.InferenceEngine`).
    """

    name: str
    factory: Callable[[], Any]


@dataclass
class TenantConfig:
    """One tenant's isolation contract.

    ``weight`` sizes the tenant's fair share of live fleet capacity
    (KV blocks for LLM fleets, queue slots for fixed-shape ones):
    ``quota = weight / sum(weights) * live_capacity``, recomputed as
    replicas die/rejoin *and on every autoscaler scale event* — losing
    a replica throttles every tenant proportionally, activating one
    grows every share, and a noisy neighbor saturates only its own
    share. An explicit ``quota_units`` overrides the weight share.

    ``deadline_class`` orders shedding under pressure (higher = kept
    longer): when fleet free capacity drops below the pressure
    threshold, class 0 (best-effort) is shed first, then class 1, so a
    capacity loss degrades the *right* tenants first.

    ``model`` pins the tenant to one hosted :class:`ModelSpec` in a
    multi-model pool: its requests route to that model's engines and
    its weight-share quota is computed against that MODEL's capacity,
    normalized over the tenants pinned to the same model (unpinned
    tenants share the pool-wide total).
    """

    name: str
    weight: float = 1.0
    deadline_class: int = 1
    quota_units: Optional[int] = None
    model: Optional[str] = None


_req_seq = itertools.count()


class FleetRequest(Request):
    """One fleet-level request: a one-shot completion slot shared by
    every attempt (original, hedges, re-admissions) carrying the same
    idempotence key — first completion wins, so a hedge twin or a
    retry after replica death can never double-deliver."""

    __slots__ = ("tenant", "key", "max_new_tokens", "eos_token",
                 "on_token", "units", "readmits", "hedges", "attempt_n",
                 "trace", "model", "akey")

    def __init__(self, prompt, max_new_tokens: int, tenant: str,
                 deadline: Optional[float], units: int,
                 eos_token: Optional[int], on_token: Optional[Callable],
                 model: Optional[str] = None,
                 akey: Optional[bytes] = None):
        super().__init__(prompt, 1, ("fleet",), deadline)
        self.tenant = tenant
        self.model = model
        self.akey = akey   # prefix-affinity key (kv_hash.prefix_key)
        self.key = f"{tenant}-{next(_req_seq)}"
        # request-scoped distributed trace, minted HERE (the cluster's
        # front door): every attempt — original, hedge twin,
        # re-admission, across the subprocess pipe — carries the same
        # trace id into the serving engine's step spans
        self.trace = _tracing.TraceContext(
            trace_id=_tracing.new_trace_id("req"),
            parent_span="fleet.submit")
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token = eos_token
        self.on_token = on_token
        self.units = int(units)      # capacity units reserved fleet-side
        self.readmits = 0
        self.hedges = 0
        self.attempt_n = 0


class CircuitBreaker:
    """Per-replica circuit breaker: ``trip_after`` consecutive failures
    open it; after ``cooldown_s`` one half-open probe is allowed —
    success closes, failure re-opens (fresh cooldown). Keeps a flapping
    replica from absorbing hedges and retries while it fails them."""

    CLOSED, OPEN, HALF_OPEN = "closed", "open", "half_open"

    def __init__(self, trip_after: Optional[int] = None,
                 cooldown_s: Optional[float] = None):
        self.trip_after = int(trip_after if trip_after is not None
                              else fleet_breaker_n())
        self.cooldown_s = float(cooldown_s if cooldown_s is not None
                                else fleet_breaker_cooldown_s())
        self.state = self.CLOSED
        self.failures = 0
        self.trips = 0
        self._opened_t = 0.0
        self._probing = False
        self._lock = threading.Lock()

    def allow(self) -> bool:
        """May a request be routed here right now?"""
        with self._lock:
            if self.state == self.CLOSED:
                return True
            now = time.monotonic()
            if self.state == self.OPEN:
                if now - self._opened_t < self.cooldown_s:
                    return False
                self.state = self.HALF_OPEN
                self._probing = False
            # half-open: exactly one in-flight probe
            if self._probing:
                return False
            self._probing = True
            return True

    def release_probe(self) -> None:
        """Give back a claimed half-open probe WITHOUT a verdict (the
        chosen replica shed the request before trying — e.g. a full
        queue). The breaker stays half-open; the next ``allow()``
        re-claims the probe."""
        with self._lock:
            self._probing = False

    def record_success(self) -> None:
        with self._lock:
            self.failures = 0
            self._probing = False
            self.state = self.CLOSED

    def record_failure(self) -> None:
        with self._lock:
            self.failures += 1
            if self.state == self.HALF_OPEN:
                # the probe failed: re-open with a fresh cooldown
                self.state = self.OPEN
                self._opened_t = time.monotonic()
                self._probing = False
                self.trips += 1
            elif (self.state == self.CLOSED
                  and self.failures >= self.trip_after):
                self.state = self.OPEN
                self._opened_t = time.monotonic()
                self.trips += 1


# ---------------------------------------------------------------------------
# fleet metrics
# ---------------------------------------------------------------------------

class FleetMetrics:
    """Registry-backed fleet/tenant series (labelled ``fleet=`` so
    several pools expose side by side; everything lands in flight
    dumps — the acceptance drill reads the dead replica's name out of
    ``fleet_replica_healthy``)."""

    def __init__(self, fleet: str):
        reg = get_registry()
        self.fleet = fleet
        self._events = reg.counter(
            "fleet_events_total", "Fleet router lifecycle events",
            ("fleet", "event"))
        self._tenant_events = reg.counter(
            "fleet_tenant_events_total", "Per-tenant router events",
            ("fleet", "tenant", "event"))
        self._replicas = reg.gauge(
            "fleet_replicas", "Replicas by health state",
            ("fleet", "state"))
        self.replica_healthy = reg.gauge(
            "fleet_replica_healthy",
            "1 while the replica is in rotation, 0 once draining/dead",
            ("fleet", "replica"))
        self.breaker_open = reg.gauge(
            "fleet_breaker_open",
            "1 while the replica's circuit breaker is open/half-open",
            ("fleet", "replica"))
        self.capacity_units = reg.gauge(
            "fleet_capacity_units",
            "Live fleet capacity (KV blocks / queue slots) over "
            "healthy replicas", ("fleet",)).labels(fleet=fleet)
        self.free_units = reg.gauge(
            "fleet_free_units", "Free capacity units over healthy "
            "replicas", ("fleet",)).labels(fleet=fleet)
        self.tenant_inflight = reg.gauge(
            "fleet_tenant_inflight_units",
            "Capacity units reserved by the tenant's in-flight "
            "requests", ("fleet", "tenant"))
        self.request_ms = reg.histogram(
            "fleet_request_ms", "End-to-end fleet request latency",
            ("fleet", "tenant"))
        # the hedge threshold's latency window: ONE registry histogram
        # (rolling p50/p95/p99 exported as gauge series) instead of the
        # router's former private deque — the same p99 definition the
        # exposition, the SLO sentinel and fleet_bench read
        self.attempt_ms = reg.histogram(
            "fleet_attempt_ms",
            "Completed fleet request latency across tenants (the "
            "hedge-threshold window)", ("fleet",),
            cap=512).labels(fleet=fleet)

    def count(self, event: str, n: int = 1) -> None:
        self._events.labels(fleet=self.fleet, event=event).inc(n)

    def count_tenant(self, tenant: str, event: str, n: int = 1) -> None:
        self._tenant_events.labels(fleet=self.fleet, tenant=tenant,
                                   event=event).inc(n)

    def set_states(self, counts: Dict[str, int]) -> None:
        for state in (HEALTHY, DRAINING, DEAD, SPARE):
            self._replicas.labels(fleet=self.fleet, state=state).set(
                counts.get(state, 0))

    def value(self, event: str) -> int:
        return int(self._events.labels(fleet=self.fleet,
                                       event=event).value)


# ---------------------------------------------------------------------------
# engine hosts (in-process and subprocess)
# ---------------------------------------------------------------------------

class _LocalHost:
    """In-process engine host: one engine per hosted model family
    (:class:`ModelSpec`), all built by their factories inside this
    replica. The single-model pool is the N=1 case — ``self.engine``
    stays the primary (first) model's engine for back-compat. Every
    ``model=None`` query aggregates across the hosted engines; a named
    model scopes it to that engine (the model's hard KV budget)."""

    def __init__(self, factories: Dict[str, Callable[[], Any]],
                 hook: Callable[[], None]):
        if not factories:
            raise ValueError("at least one model factory is required")
        self._factories = dict(factories)
        self._primary = next(iter(self._factories))
        self._hook = hook
        self.engines: Dict[str, Any] = {}
        self.engine = None               # primary engine (back-compat)
        self.kind = None

    def start(self) -> None:
        from .engine import InferenceEngine
        from .llm import LLMEngine

        for model, factory in self._factories.items():
            eng = factory()
            if isinstance(eng, LLMEngine):
                kind = "llm"
                # the per-replica chaos/liveness hook rides the
                # scheduler tick (respect a hook the factory installed
                # itself)
                if eng._step_hook is None:
                    eng._step_hook = self._hook
            elif isinstance(eng, InferenceEngine):
                kind = "infer"
                # same seam on the batcher loop: the chaos site fires
                # in the REPLICA's thread (a delay wedges it, a fatal
                # kills it), never in the router's or a caller's
                if eng._batcher._step_hook is None:
                    eng._batcher._step_hook = self._hook
            else:
                raise TypeError(
                    f"fleet replica factory must build an LLMEngine or "
                    f"InferenceEngine, got {type(eng).__name__}")
            if self.kind is None:
                self.kind = kind
            elif kind != self.kind:
                eng.close(drain=False, timeout_s=1.0)
                raise TypeError(
                    f"model {model!r} builds a {kind} engine but the "
                    f"pool hosts {self.kind} engines — one kind per "
                    "pool")
            self.engines[model] = eng
        self.engine = self.engines[self._primary]

    def _eng(self, model: Optional[str]):
        if model is None:
            return self.engine
        try:
            return self.engines[model]
        except KeyError:
            raise ValueError(
                f"unknown model {model!r} (hosted: "
                f"{sorted(self.engines)})") from None

    # -- liveness ---------------------------------------------------------
    @property
    def alive(self) -> bool:
        # one dead engine kills the replica: its requests (BOTH
        # models') re-home, and the restart path rebuilds all engines
        return bool(self.engines) and all(
            bool(getattr(e, "alive", False))
            for e in self.engines.values())

    def tick_age(self) -> float:
        if not self.engines:
            return float("inf")
        return max(time.monotonic() - float(e.last_tick)
                   for e in self.engines.values())

    # -- load / capacity --------------------------------------------------
    def _eng_inflight(self, e) -> int:
        if self.kind == "llm":
            return int(e.metrics.lanes_active.get()) + len(e._queue)
        return len(e._queue)

    def inflight(self, model: Optional[str] = None) -> int:
        if model is not None:
            return self._eng_inflight(self._eng(model))
        return sum(self._eng_inflight(e) for e in self.engines.values())

    def _eng_capacity(self, e) -> int:
        if self.kind == "llm":
            return int(e.num_blocks)
        return int(e._queue._max)

    def capacity_units(self, model: Optional[str] = None) -> int:
        if model is not None:
            return self._eng_capacity(self._eng(model))
        return sum(self._eng_capacity(e) for e in self.engines.values())

    def _eng_free(self, e) -> int:
        if self.kind == "llm":
            # free list + evictable prefix-cache residents: a cached
            # block nothing references is reclaimable on the next
            # admission, so it IS free capacity — counting only the
            # free list makes an idle prefix-cache engine look
            # permanently saturated (pressure-shedding every request
            # and pinning the autoscaler's free fraction at 0)
            free = int(e.metrics.pool_free.get())
            ev = getattr(e, "evictable_blocks", None)
            if ev is not None:
                free += int(ev())
            return free
        return max(0, self._eng_capacity(e) - len(e._queue))

    def free_units(self, model: Optional[str] = None) -> int:
        if model is not None:
            return self._eng_free(self._eng(model))
        return sum(self._eng_free(e) for e in self.engines.values())

    def cost_units(self, prompt_len: int, max_new: int,
                   model: Optional[str] = None) -> int:
        if self.kind == "llm":
            e = self._eng(model)
            return -(-(prompt_len + max_new + e._slack) // e.block_size)
        return 1

    # -- dispatch ---------------------------------------------------------
    def submit(self, req: FleetRequest,
               timeout_ms: Optional[float]) -> Request:
        eng = self._eng(req.model)
        if self.kind == "llm":
            return eng.submit(
                req.payload, req.max_new_tokens,
                eos_token=req.eos_token, timeout_ms=timeout_ms,
                on_token=req.on_token, trace_id=req.trace.trace_id)
        return eng.infer_async(req.payload, timeout_ms=timeout_ms)

    # -- lifecycle --------------------------------------------------------
    def snapshot_manifest(self):
        """Per-model AOT warmup frontier: ``{model: manifest}`` (models
        whose engine cannot report one are absent)."""
        out = {}
        for model, e in self.engines.items():
            try:
                out[model] = e.warmup_manifest()
            except Exception:  # noqa: BLE001 — observability only
                pass
        return out or None

    def warm(self, manifest) -> None:
        """Replay AOT warmup manifests: a ``{model: manifest}`` dict
        warms each hosted engine from its model's frontier; a bare
        manifest (pre-multi-model snapshot) warms the primary."""
        if manifest is None:
            return
        per_model = (manifest if isinstance(manifest, dict)
                     else {self._primary: manifest})
        for model, m in per_model.items():
            eng = self.engines.get(model)
            if eng is None or m is None:
                continue
            try:
                if list(m.entries()):
                    eng.warmup(manifest=m)
            except Exception:  # noqa: BLE001 — warmup is an
                pass           # optimization, not a correctness gate

    def close(self, drain: bool, timeout_s: float) -> None:
        for e in self.engines.values():
            try:
                e.close(drain=drain, timeout_s=timeout_s)
            except Exception:  # noqa: BLE001 — close the rest anyway
                pass


class _ProcRequest(Request):
    """Parent-side handle for one subprocess-replica request: its
    ``cancel()`` also rides the wire, so first-wins hedge cancellation
    and submitter cancels retire the WORKER's lane (the in-process
    sweep can't see across the pipe)."""

    __slots__ = ("_on_cancel",)

    def __init__(self, deadline, on_cancel):
        super().__init__(None, 1, ("fleet",), deadline)
        self._on_cancel = on_cancel

    def cancel(self) -> None:
        super().cancel()
        cb = self._on_cancel
        if cb is not None:
            try:
                cb()
            except Exception:  # noqa: BLE001 — dead pipe = dead lane
                pass


class _ProcHost:
    """Subprocess engine host: the replica is a real OS process (its
    own Python, its own engine, its own heartbeat files) speaking a
    JSON-lines protocol over stdin/stdout — so a chaos ``kill`` rule is
    a true ``os._exit(137)`` and health detection exercises the exact
    file discipline a multi-host fleet would.

    ``spec``: ``{"model": "pkg.mod:callable", "model_kwargs": {...},
    "seed": 0, "engine_kwargs": {...}, "env": {...},
    "env_by_index": {"1": {...}}}`` — ``env`` applies to every worker,
    ``env_by_index`` to one, which is how a drill arms
    ``MXNET_TPU_CHAOS`` (e.g. a real ``kill``) in ONE replica's
    process only.
    """

    def __init__(self, spec: Dict, root: str, index: int, name: str,
                 heartbeat_s: float):
        self._spec = dict(spec)
        self._root = root
        self._index = index
        self._name = name
        self._hb_s = heartbeat_s
        self.kind = "llm"
        self.engine = None           # no in-process engine
        self._proc: Optional[subprocess.Popen] = None
        self._pending: Dict[int, Request] = {}
        self._stats = {"load": 0, "free": 0, "cap": 1,
                       "block_size": 16, "slack": 0}
        self._id = itertools.count()
        self._wlock = threading.Lock()
        self._plock = threading.Lock()
        self._ready = threading.Event()
        self._dead = False

    def start(self, start_timeout_s: float = 120.0) -> None:
        env = dict(os.environ)
        env.update({k: str(v)
                    for k, v in self._spec.get("env", {}).items()})
        env.update({k: str(v) for k, v in self._spec.get(
            "env_by_index", {}).get(str(self._index), {}).items()})
        # cluster telemetry identity: with a shared MXNET_TPU_TELEMETRY
        # root armed (inherited from the parent env) each worker
        # exports into its own proc_fleet_replica_r<i>_p<pid> subdir.
        # An explicit spec env wins; the PARENT's inherited role must
        # not (the worker is a replica regardless of who launched it)
        if not any("MXNET_TPU_TELEMETRY_ROLE" in d for d in (
                self._spec.get("env", {}),
                self._spec.get("env_by_index", {}).get(
                    str(self._index), {}))):
            env["MXNET_TPU_TELEMETRY_ROLE"] = \
                f"fleet_replica:{self._index}"
        env["MXT_FLEET_WORKER_SPEC"] = json.dumps({
            **{k: v for k, v in self._spec.items()
               if k not in ("env", "env_by_index")},
            "root": self._root, "index": self._index,
            "name": self._name, "heartbeat_s": self._hb_s,
        })
        self._proc = subprocess.Popen(
            [sys.executable, "-c",
             "from mxnet_tpu.serving.fleet import _worker_main; "
             "_worker_main()"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=None, env=env, text=True, bufsize=1)
        threading.Thread(target=self._read_loop, daemon=True,
                         name=f"fleet-reader:{self._name}").start()
        if not self._ready.wait(start_timeout_s):
            self.close(drain=False, timeout_s=1.0)
            raise ReplicaUnavailable(
                f"fleet replica {self._name!r} subprocess did not come "
                f"up within {start_timeout_s:g}s")

    def _read_loop(self) -> None:
        proc = self._proc
        try:
            for line in proc.stdout:
                try:
                    msg = json.loads(line)
                except ValueError:
                    continue             # stray stdout noise
                op = msg.get("op")
                if op == "ready":
                    self._stats.update(msg.get("stats", {}))
                    self._ready.set()
                elif op == "stats":
                    self._stats.update(msg.get("stats", {}))
                elif op == "done":
                    with self._plock:
                        req = self._pending.pop(msg.get("id"), None)
                    if req is None:
                        continue
                    if msg.get("ok"):
                        import numpy as onp

                        req.finish(onp.asarray(msg["tokens"], onp.int32))
                    else:
                        kind = msg.get("kind")
                        cls = (FatalError if kind == "fatal"
                               else RequestCancelled
                               if kind == "cancelled"
                               else TransientError)
                        req.fail(cls(msg.get("error", "replica error")))
        except Exception:  # noqa: BLE001 — pipe torn by death
            pass
        # EOF: the worker exited (clean close or a real kill) — nobody
        # will ever answer the still-pending requests
        self._dead = True
        with self._plock:
            pending, self._pending = dict(self._pending), {}
        for req in pending.values():
            req.fail(TransientError(
                f"fleet replica {self._name!r} process exited with its "
                "request in flight — re-admit elsewhere"))

    @property
    def alive(self) -> bool:
        return (not self._dead and self._proc is not None
                and self._proc.poll() is None and self._ready.is_set())

    def tick_age(self) -> float:
        from ..resilience.elastic import Heartbeat

        ages = Heartbeat.ages(self._root)
        return ages.get(self._index, float("inf"))

    def inflight(self, model: Optional[str] = None) -> int:
        # the worker's reported load already counts every admitted
        # request; _pending holds the same requests until their reply
        # lands. max() covers the stats lag (just-submitted, not yet in
        # the worker's 0.25 s-cadence stats) without double-counting.
        return max(int(self._stats.get("load", 0)), len(self._pending))

    def capacity_units(self, model: Optional[str] = None) -> int:
        return int(self._stats.get("cap", 1))

    def free_units(self, model: Optional[str] = None) -> int:
        return int(self._stats.get("free", 0))

    def cost_units(self, prompt_len: int, max_new: int,
                   model: Optional[str] = None) -> int:
        bs = int(self._stats.get("block_size", 16))
        return -(-(prompt_len + max_new
                   + int(self._stats.get("slack", 0))) // bs)

    def submit(self, req: FleetRequest,
               timeout_ms: Optional[float]) -> Request:
        if not self.alive:
            raise ReplicaUnavailable(
                f"fleet replica {self._name!r} process is gone")
        if req.model is not None:
            raise ValueError(
                "subprocess replicas host one model (the worker spec) "
                "— model= routing needs in-process multi-model pools")
        if req.on_token is not None:
            raise ValueError("subprocess replicas do not stream "
                             "(on_token=) — use in-process replicas")
        rid = next(self._id)
        handle = _ProcRequest(req.deadline,
                              lambda: self._send({"op": "cancel",
                                                  "id": rid}))
        with self._plock:
            self._pending[rid] = handle
        try:
            self._send({
                "op": "submit", "id": rid,
                "prompt": [int(t) for t in req.payload],
                "max_new": req.max_new_tokens,
                "eos": req.eos_token,
                "timeout_ms": timeout_ms,
                # trace context rides the JSON-lines pipe: the worker's
                # engine stamps it into its step[llm_*] spans, so the
                # merged cluster timeline follows the request across
                # the process boundary
                "trace": req.trace.to_dict(),
            })
        except (OSError, ValueError) as e:
            with self._plock:
                self._pending.pop(rid, None)
            raise ReplicaUnavailable(
                f"fleet replica {self._name!r} pipe is closed: "
                f"{e!r}") from e
        return handle

    def _send(self, msg: Dict) -> None:
        with self._wlock:
            self._proc.stdin.write(json.dumps(msg) + "\n")
            self._proc.stdin.flush()

    def snapshot_manifest(self):
        return None                   # lives (and dies) with the worker

    def warm(self, manifest) -> None:
        pass                          # the worker warms itself at boot

    def close(self, drain: bool, timeout_s: float) -> None:
        proc = self._proc
        if proc is None:
            return
        try:
            with self._wlock:
                proc.stdin.write(json.dumps({"op": "close",
                                             "drain": bool(drain)}) + "\n")
                proc.stdin.flush()
        except (OSError, ValueError):
            pass
        try:
            proc.wait(timeout_s)
        except subprocess.TimeoutExpired:
            proc.kill()
            try:
                proc.wait(5.0)
            except subprocess.TimeoutExpired:
                pass


# ---------------------------------------------------------------------------
# replica + pool
# ---------------------------------------------------------------------------

class Replica:
    """One fleet member: an engine host + health state + heartbeat +
    circuit breaker. State machine ``healthy → draining → dead``:
    draining stops new routing (in-flight lanes finish or re-home),
    dead replicas are out of rotation until :meth:`ReplicaPool.restart`
    warms a fresh engine from the last incarnation's AOT manifest."""

    def __init__(self, name: str, index: int, host, root: str,
                 heartbeat_s: float, stale_s: float):
        from ..resilience.elastic import Heartbeat

        self.name = name
        self.index = int(index)
        self.host = host
        self.state = DEAD            # until start() succeeds
        self.state_reason = "not started"
        self.breaker = CircuitBreaker()
        self.stale_s = float(stale_s)
        self._hb = Heartbeat(root, index, heartbeat_s)
        self._beat_stop = threading.Event()
        self._beater: Optional[threading.Thread] = None
        self._manifest = None        # last incarnation's warmup frontier
        self.generation = 0
        self._restarting = False

    # the per-replica chaos/liveness hook (installed into LLM engines'
    # step loop; fired from submit() for batcher-style engines)
    def _hook(self) -> None:
        chaos.site("serving.fleet.replica", replica=self.name)
        chaos.site(f"serving.fleet.replica.{self.name}")

    def start(self) -> None:
        if isinstance(self.host, _LocalHost):
            self.host._hook = self._hook
        self.host.start()
        if self._manifest is not None:
            self.host.warm(self._manifest)
        for eng in (getattr(self.host, "engines", None)
                    or {}).values():
            try:
                # factory-side warmup holds the scheduler's state lock
                # for seconds (compiles): the loop could not tick, but
                # a just-warmed engine IS live — re-stamp so the first
                # health pass doesn't flag a fresh replica as wedged
                eng.last_tick = time.monotonic()
            except AttributeError:
                pass            # InferenceEngine: batcher-owned stamp
        self.state = HEALTHY
        self.state_reason = "started"
        # the beater is for IN-PROCESS hosts only: a subprocess worker
        # beats its OWN heartbeat file (gated on its engine's liveness)
        # — a parent-side beater on the same file would keep it fresh
        # while the worker is wedged, defeating the whole point
        if not isinstance(self.host, _ProcHost):
            os.makedirs(self._hb.dir, exist_ok=True)
            self.stop_beating()             # join any prior incarnation
            self._beat_stop = threading.Event()
            self._beater = threading.Thread(
                target=self._beat_loop, args=(self._beat_stop,),
                daemon=True, name=f"fleet-beater:{self.name}")
            self._beater.start()

    def _beat_loop(self, stop: threading.Event) -> None:
        """Beat the heartbeat file only while the engine's step loop is
        provably live: host dead OR tick stale ⇒ no beat ⇒ the file
        ages out on the same clock external observers read (the
        ``elastic.Heartbeat`` discipline — liveness is a *claim the
        engine keeps renewing*, not a one-time registration). ``stop``
        is this incarnation's OWN event (a restart hands the next
        beater a fresh one, so set-then-clear can never revive us)."""
        period = self._hb.period
        while not stop.wait(period):
            if self.state == DEAD:
                continue
            try:
                if (self.host.alive
                        and self.host.tick_age() <= max(2 * period, 0.2)):
                    self._hb.beat()
            except Exception:  # noqa: BLE001 — a missed beat, not a crash
                pass

    # -- health probe (pool monitor) --------------------------------------
    def probe(self) -> str:
        """Current health verdict: ``healthy`` / ``wedged`` / ``dead``
        (does not mutate state — the pool owns transitions)."""
        if isinstance(self.host, _ProcHost):
            if not self.host.alive:
                return "dead"
            return ("wedged" if self.host.tick_age() > self.stale_s
                    else "healthy")
        if not self.host.alive:
            return "dead"
        if self.host.tick_age() > self.stale_s:
            return "wedged"
        return "healthy"

    @property
    def routable(self) -> bool:
        return self.state == HEALTHY

    def stop_beating(self) -> None:
        self._beat_stop.set()
        t, self._beater = self._beater, None
        if t is not None and t is not threading.current_thread():
            t.join(2 * self._hb.period + 1.0)

    def snapshot_manifest(self) -> None:
        m = self.host.snapshot_manifest()
        if m is not None:
            self._manifest = m


_pool_seq = itertools.count()


class ReplicaPool:
    """N engine replicas + the health monitor state the router routes
    on.

    Parameters
    ----------
    factory : callable, optional
        Zero-arg builder returning a fresh engine
        (:class:`~.llm.LLMEngine` or
        :class:`~.engine.InferenceEngine`) — one call per in-process
        replica (and per restart). Replicas sharing one model object
        share its compiled programs (the generation-module memoization),
        so an in-process fleet pays ONE compile per program shape.
        Shorthand for ``models=[ModelSpec("default", factory)]``.
    n_replicas : int
        Fleet width. Default ``MXNET_TPU_FLEET_REPLICAS`` (2).
    models : list of ModelSpec, optional
        Multi-model tenancy: EVERY replica hosts one engine per spec
        over the one shared replica set (consolidation — N models on
        one pool, not N dedicated pools), each with its own hard KV
        block-pool budget and AOT manifest frontier. Mutually
        exclusive with ``factory`` and ``subprocess_spec``.
    subprocess_spec : dict, optional
        Build subprocess-backed replicas instead (see
        :class:`_ProcHost`): each replica is a real OS process with its
        own engine and heartbeat files — the full-fidelity chaos-kill
        target. Mutually exclusive with ``factory``.
    root : str, optional
        Fleet coordination root (heartbeat files live under
        ``<root>/heartbeats``). Default: a private temp dir, removed at
        close.
    role : None | "prefill" | "decode"
        Disaggregated-serving replica class (see :mod:`.disagg`): a
        ``"prefill"`` pool's engines run prompt prefill and EXPORT the
        resulting KV block rows; a ``"decode"`` pool's engines
        re-attach shipped rows and decode. The role is the pool's
        identity only — engines must be built with the matching
        ``LLMEngine(role=)`` by the factory (checked at first use by
        :class:`~mxnet_tpu.serving.disagg.DisaggRouter`).
    """

    def __init__(self, factory: Optional[Callable[[], Any]] = None,
                 n_replicas: Optional[int] = None, *,
                 models: Optional[List[ModelSpec]] = None,
                 subprocess_spec: Optional[Dict] = None,
                 root: Optional[str] = None,
                 heartbeat_s: Optional[float] = None,
                 stale_s: Optional[float] = None,
                 name: Optional[str] = None,
                 role: Optional[str] = None):
        if role not in (None, "prefill", "decode"):
            raise ValueError(
                f"role {role!r} not supported (None/'prefill'/'decode')")
        self.role = role
        n_sources = sum(x is not None
                        for x in (factory, models, subprocess_spec))
        if n_sources != 1:
            raise ValueError(
                "pass exactly one of factory= / models= (in-process "
                "replicas) or subprocess_spec= (subprocess-backed "
                "replicas)")
        if factory is not None:
            models = [ModelSpec("default", factory)]
        if models is not None:
            names = [m.name for m in models]
            if len(set(names)) != len(names):
                raise ValueError(f"duplicate model names: {names}")
        if n_replicas is None:
            n_replicas = fleet_replicas_default()
        if n_replicas < 1:
            raise ValueError("n_replicas must be >= 1")
        self.name = name or f"fleet{next(_pool_seq)}"
        self._own_root = root is None
        self.root = os.path.abspath(
            root or tempfile.mkdtemp(prefix="mxt_fleet_"))
        self._hb_s = float(heartbeat_s if heartbeat_s is not None
                           else fleet_heartbeat_s())
        self._stale_s = float(stale_s if stale_s is not None
                              else fleet_stale_s(self._hb_s))
        self.models: List[ModelSpec] = list(models or [])
        self._factories = {m.name: m.factory for m in self.models}
        self._spec = subprocess_spec
        self.metrics = FleetMetrics(self.name)
        self._lock = threading.RLock()
        self._next_index = int(n_replicas)
        # the pool-level AOT warmup frontier, per model: refreshed from
        # live replicas and absorbed from dying ones, so a NEW spare
        # warms by manifest replay instead of cold compile
        self._manifests: Dict[str, Any] = {}
        # scale-event subscribers (router quota rebalance, autoscaler
        # bookkeeping) — called OUTSIDE the pool lock
        self._scale_subs: List[Callable[[str, str], None]] = []
        self.replicas: List[Replica] = []
        for i in range(int(n_replicas)):
            self.replicas.append(self._build(i))
        try:
            for r in self.replicas:
                r.start()
        except BaseException:
            # a later replica failing to boot must not leak the ones
            # already started (real OS subprocesses, beater threads)
            # nor the owned temp root — the caller gets no pool object
            # to close
            self.close()
            raise
        self._publish_states()

    def _build(self, index: int) -> Replica:
        rname = f"{self.name}.r{index}"
        if self._factories:
            host = _LocalHost(self._factories, hook=lambda: None)
        else:
            host = _ProcHost(self._spec, self.root, index, rname,
                             self._hb_s)
        return Replica(rname, index, host, self.root, self._hb_s,
                       self._stale_s)

    # -- views -------------------------------------------------------------
    def healthy(self) -> List[Replica]:
        with self._lock:
            return [r for r in self.replicas if r.routable]

    def get(self, name: str) -> Replica:
        for r in self.replicas:
            if r.name == name or name == f"r{r.index}":
                return r
        raise KeyError(name)

    @property
    def kind(self) -> str:
        return self.replicas[0].host.kind or "llm"

    def capacity_units(self, model: Optional[str] = None) -> int:
        return sum(r.host.capacity_units(model) for r in self.healthy())

    def free_units(self, model: Optional[str] = None) -> int:
        return sum(r.host.free_units(model) for r in self.healthy())

    def cost_units(self, prompt_len: int, max_new: int,
                   model: Optional[str] = None) -> int:
        return self.replicas[0].host.cost_units(prompt_len, max_new,
                                                model)

    def each_engine(self, fn: Callable[[Any], None],
                    healthy_only: bool = False) -> int:
        """Apply ``fn(engine)`` to every engine of every in-process
        replica (subprocess hosts have no reachable engine object and
        are skipped). A raising ``fn`` is contained per engine. Returns
        the number of engines visited — the disagg router's decode-side
        peer-rewiring seam."""
        with self._lock:
            reps = ([r for r in self.replicas if r.routable]
                    if healthy_only else list(self.replicas))
        n = 0
        for r in reps:
            for eng in list(
                    (getattr(r.host, "engines", None) or {}).values()):
                try:
                    fn(eng)
                    n += 1
                except Exception:  # noqa: BLE001 — contained per engine
                    pass
        return n

    def kv_export_endpoints(self) -> List[str]:
        """``host:port`` endpoints of every healthy replica engine's
        serving spill tier (the prefill fleet's handoff export plane —
        what the disagg router wires into decode engines' peer
        lists)."""
        eps: List[str] = []
        for r in self.healthy():
            for eng in (getattr(r.host, "engines", None) or {}).values():
                ep = getattr(eng, "kv_spill_endpoint", None)
                if ep:
                    eps.append(ep)
        return eps

    def _publish_states(self) -> None:
        counts: Dict[str, int] = {}
        for r in self.replicas:
            counts[r.state] = counts.get(r.state, 0) + 1
            self.metrics.replica_healthy.labels(
                fleet=self.name, replica=r.name).set(
                    1 if r.state == HEALTHY else 0)
            self.metrics.breaker_open.labels(
                fleet=self.name, replica=r.name).set(
                    0 if r.breaker.state == CircuitBreaker.CLOSED else 1)
        self.metrics.set_states(counts)
        self.metrics.capacity_units.set(self.capacity_units())
        self.metrics.free_units.set(self.free_units())

    # -- health monitor (driven by the router's control loop) --------------
    def check(self) -> List[Replica]:
        """One health pass. Transitions: a dead engine ⇒ ``dead``
        (immediately); a wedged one ⇒ ``draining`` (out of rotation),
        then ``dead`` if still wedged past another stale window; a
        drained-for-wedge replica whose loop recovers rejoins
        ``healthy``. Returns replicas that became DEAD this pass (their
        in-flight requests need re-homing)."""
        newly_dead: List[Replica] = []
        with self._lock:
            for r in self.replicas:
                if r.state == DEAD:
                    continue
                verdict = r.probe()
                if verdict == "dead":
                    self._mark_dead(r, "engine step loop dead")
                    newly_dead.append(r)
                elif verdict == "wedged":
                    if r.state == HEALTHY:
                        r.state = DRAINING
                        r.state_reason = "wedged"
                        r._wedged_t = time.monotonic()
                        self.metrics.count("replica_wedged")
                    elif (r.state_reason == "wedged"
                          and time.monotonic() - getattr(
                              r, "_wedged_t", 0.0)
                          > max(2 * r.stale_s, 30.0)):
                        # a wedged replica drains (out of rotation)
                        # immediately, but death waits max(2x stale,
                        # 30 s): a legitimate long step — a cold
                        # in-step compile runs tens of seconds on a
                        # real backend — must drain and SURVIVE, not
                        # get its engine closed mid-compile (which
                        # would re-home the request onto the next
                        # replica and serially kill the whole fleet on
                        # one cold shape). Hedging covers the stalled
                        # request meanwhile; drain-at-stale already
                        # stops new traffic, so the only cost of the
                        # generous floor is delayed pool-state cleanup.
                        self._mark_dead(r, "wedged past stale window")
                        newly_dead.append(r)
                elif r.state == DRAINING and r.state_reason == "wedged":
                    r.state = HEALTHY     # recovered straggler rejoins
                    r.state_reason = "recovered"
            self._publish_states()
        # membership edge, outside the lock like every scale event: the
        # router's prefix-affinity map must drop a dead member NOW, not
        # on the next activate/drain
        for r in newly_dead:
            self._notify_scale("dead", r.name)
        return newly_dead

    def _mark_dead(self, r: Replica, reason: str) -> None:
        r.state = DEAD
        r.state_reason = reason
        r.generation += 1
        r.snapshot_manifest()
        self._absorb_manifest(r._manifest)
        self.metrics.count("replica_dead")
        # free pool state best-effort in the background: a wedged
        # engine's close() join must not stall the health loop. The
        # HOST OBJECT is captured now — by the time the reaper runs, a
        # kill-then-restart drill may have swapped r.host for the new
        # incarnation, which must not be the one closed.
        host = r.host
        threading.Thread(
            target=lambda: self._safe_close(host), daemon=True,
            name=f"fleet-reaper:{r.name}").start()
        # the post-mortem names the dead replica; every fleet_* gauge
        # rides the dump (no-op while the recorder is unarmed)
        _flight.try_dump(f"fleet_replica_dead:{r.name}")

    @staticmethod
    def _safe_close(host) -> None:
        try:
            host.close(drain=False, timeout_s=2.0)
        except Exception:  # noqa: BLE001 — already dead
            pass

    # -- drill / lifecycle APIs -------------------------------------------
    def kill(self, name: str) -> Replica:
        """Drill API: abruptly stop a replica (its in-flight requests
        fail typed and re-home through the router; pool state is freed
        by the background reaper)."""
        r = self.get(name)
        killed = False
        with self._lock:
            if r.state != DEAD:
                self._mark_dead(r, "killed (drill)")
                self._publish_states()
                killed = True
        if killed:
            self._notify_scale("dead", r.name)
        return r

    def drain(self, name: str, timeout_s: float = 30.0) -> Replica:
        """Graceful scale-down: stop routing to the replica, let its
        in-flight work finish (bounded), then free its pool state and
        mark it dead. Lanes still running at the deadline are cancelled
        — the router re-homes them like any replica fault."""
        r = self.get(name)
        with self._lock:
            if r.state != HEALTHY:
                return r
            r.state = DRAINING
            r.state_reason = "draining (scale-down)"
            self._publish_states()
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if r.host.inflight() == 0:
                break
            time.sleep(0.01)
        r.snapshot_manifest()
        self._absorb_manifest(r._manifest)
        try:
            r.host.close(drain=False, timeout_s=5.0)
        except Exception:  # noqa: BLE001
            pass
        with self._lock:
            if r.state != DEAD:
                r.state = DEAD
                r.state_reason = "drained"
                r.generation += 1
                self.metrics.count("replica_drained")
            self._publish_states()
        self._notify_scale("drained", r.name)
        return r

    def restart(self, name: str) -> Replica:
        """Bring a dead replica back: fresh engine from the factory,
        warmed from the previous incarnation's AOT warmup manifest
        (with ``MXNET_TPU_AOT_CACHE`` armed the compiles resolve from
        the persistent store — the zero-cold-compile rejoin), breaker
        reset, back in rotation.

        The engine build/warmup (seconds of compiles, or a subprocess
        boot) runs OUTSIDE the pool lock — the rest of the fleet keeps
        routing and relaying while the replica rejoins; the replica
        stays DEAD (skipped by health checks and routing) until
        ``start()`` completes."""
        r = self.get(name)
        with self._lock:
            if r.state != DEAD:
                raise ValueError(f"replica {name!r} is {r.state}, not dead")
            if r._restarting:
                raise ValueError(f"replica {name!r} is already restarting")
            r._restarting = True
        try:
            r.stop_beating()
            if self._factories:
                host = _LocalHost(self._factories, hook=r._hook)
            else:
                host = _ProcHost(self._spec, self.root, r.index,
                                 r.name, self._hb_s)
            with self._lock:
                r.host = host
                r.breaker = CircuitBreaker()
            r.start()                    # build + warm, no pool lock
            self.metrics.count("replica_restarts")
            with self._lock:
                self._publish_states()
        finally:
            r._restarting = False
        return r

    # -- scale events (the autoscaler's actuators) -------------------------
    def on_scale(self, fn: Callable[[str, str], None]) -> None:
        """Subscribe to membership scale events: ``fn(event, replica)``
        fires (outside the pool lock) on ``spare_added`` /
        ``activated`` / ``added`` / ``drained`` / ``dead`` — the router
        rebalances tenant quotas and rebuilds its prefix-affinity map
        on this edge, the autoscaler logs it."""
        self._scale_subs.append(fn)

    def _notify_scale(self, event: str, replica: str) -> None:
        for fn in list(self._scale_subs):
            try:
                fn(event, replica)
            except Exception:  # noqa: BLE001 — a broken subscriber
                pass           # must not stop the scale event

    def _absorb_manifest(self, m) -> None:
        """Merge a replica's per-model manifest snapshot into the
        pool-level frontier (what new spares warm from)."""
        if not isinstance(m, dict):
            return
        with self._lock:
            self._manifests.update(
                {k: v for k, v in m.items() if v is not None})

    def snapshot_manifests(self) -> Dict[str, Any]:
        """Refresh the pool's per-model AOT warmup frontier from the
        first live replica (spares warm from this — manifest replay,
        not cold compile)."""
        for r in self.healthy():
            self._absorb_manifest(r.host.snapshot_manifest())
            break
        with self._lock:
            return dict(self._manifests)

    def spares(self) -> List[Replica]:
        with self._lock:
            return [r for r in self.replicas if r.state == SPARE]

    def add_spare(self) -> Replica:
        """Warm-pool policy: build + start a NEW replica pre-warmed
        from the pool's AOT manifest frontier, parked in ``SPARE``
        state (beating, out of rotation, zero routed traffic) so the
        next scale-up is :meth:`activate` — a state flip, not a
        compile. The build runs outside the pool lock; the rest of the
        fleet keeps serving."""
        with self._lock:
            index = self._next_index
            self._next_index += 1
        manifests = self.snapshot_manifests()
        r = self._build(index)
        r._manifest = manifests or None
        r.start()                        # build + warm, no pool lock
        with self._lock:
            r.state = SPARE
            r.state_reason = "warm spare (pre-warmed, out of rotation)"
            self.replicas.append(r)
            self._publish_states()
        self.metrics.count("spare_added")
        self._notify_scale("spare_added", r.name)
        return r

    def activate(self, name: Optional[str] = None) -> Optional[Replica]:
        """Fast scale-up: flip a pre-warmed ``SPARE`` into rotation
        (the warmed replica starts taking traffic immediately — no
        build, no compile). ``name=None`` activates any spare; returns
        None when there is none to activate (the caller falls back to
        the cold :meth:`add_replica` path)."""
        with self._lock:
            if name is None:
                r = next((x for x in self.replicas
                          if x.state == SPARE), None)
            else:
                r = self.get(name)
            if r is None or r.state != SPARE:
                return None
            r.state = HEALTHY
            r.state_reason = "activated (scale-up)"
            self._publish_states()
        self.metrics.count("replica_activated")
        self._notify_scale("activated", r.name)
        return r

    def add_replica(self) -> Replica:
        """Cold scale-up: build + start a new replica straight into
        rotation. Pays the engine build (and any compile the AOT
        manifest frontier / persistent cache cannot replay) on the
        scale-up critical path — the warm-pool's :meth:`activate` is
        the fast path; this is the fallback when no spare is parked."""
        with self._lock:
            index = self._next_index
            self._next_index += 1
        manifests = self.snapshot_manifests()
        r = self._build(index)
        r._manifest = manifests or None
        r.start()                        # build + warm, no pool lock
        with self._lock:
            self.replicas.append(r)
            self._publish_states()
        self.metrics.count("replica_added")
        self._notify_scale("added", r.name)
        return r

    def close(self) -> None:
        for r in self.replicas:
            r.stop_beating()
            try:
                r.host.close(drain=False, timeout_s=5.0)
            except Exception:  # noqa: BLE001
                pass
            r.state = DEAD
            r.state_reason = "pool closed"
        self._publish_states()
        if self._own_root:
            shutil.rmtree(self.root, ignore_errors=True)


# ---------------------------------------------------------------------------
# router
# ---------------------------------------------------------------------------

class _Attempt:
    __slots__ = ("freq", "replica", "handle", "t0", "is_hedge", "probed")

    def __init__(self, freq: FleetRequest, replica: Replica,
                 handle: Request, is_hedge: bool, probed: bool = False):
        self.freq = freq
        self.replica = replica
        self.handle = handle
        self.t0 = time.monotonic()
        self.is_hedge = is_hedge
        # True when this attempt holds the replica's one half-open
        # breaker probe: any resolution that is neither success nor
        # failure (cancellation, finalize) must release it, or the
        # breaker stays probe-claimed forever and the replica never
        # routes again
        self.probed = probed


class Router:
    """The fleet front door: tenant-fair admission → least-loaded
    dispatch → relay with hedging, re-admission and breaker
    bookkeeping (one control loop, no waiter thread per request).

    Parameters
    ----------
    pool : ReplicaPool
    tenants : list of TenantConfig, optional
        Unknown tenants fall back to an implicit ``default`` config
        (weight 1, class 1).
    hedge_ms / hedge_pct :
        Hedge a request once its oldest attempt is older than
        ``max(hedge_ms, p<hedge_pct> of recent fleet latencies)``.
        ``hedge_ms=0`` disables hedging. Defaults from
        ``MXNET_TPU_FLEET_HEDGE_MS`` / ``_PCT``.
    pressure_free_frac : float
        Below this free-capacity fraction the fleet is under pressure:
        deadline class 0 is shed; below half of it class 1 too (class 2
        is only ever shed by quota/capacity).
    default_timeout_ms : float, optional
        Deadline budget applied when a submit does not carry one.
    """

    def __init__(self, pool: ReplicaPool, tenants: Optional[List[TenantConfig]] = None, *,
                 hedge_ms: Optional[float] = None,
                 hedge_pct: Optional[float] = None,
                 readmit_limit: int = 1, hedge_limit: int = 1,
                 pressure_free_frac: float = 0.25,
                 default_timeout_ms: Optional[float] = None,
                 poll_s: float = 0.002,
                 affinity: Optional[bool] = None,
                 affinity_blocks: Optional[int] = None,
                 affinity_block_size: Optional[int] = None,
                 affinity_max_load: Optional[float] = None):
        self.pool = pool
        self.metrics = pool.metrics
        # prefix-affinity routing (LLM fleets only — fixed-shape
        # engines have no KV to be affine to): requests sharing their
        # leading prompt blocks dispatch to the same replica, so the
        # fleet's prefix caches specialize instead of each holding a
        # diluted copy of every prefix
        self._aff_on = ((bool(affinity) if affinity is not None
                         else fleet_affinity_on())
                        and pool.kind == "llm")
        self._aff_blocks = int(affinity_blocks
                               if affinity_blocks is not None
                               else fleet_affinity_blocks())
        self._aff_bs = int(affinity_block_size
                           if affinity_block_size is not None
                           else fleet_affinity_block_size())
        self._aff_max_load = float(affinity_max_load
                                   if affinity_max_load is not None
                                   else fleet_affinity_max_load())
        self._affinity_members: Tuple[str, ...] = ()
        self._tenants: Dict[str, TenantConfig] = {
            t.name: t for t in (tenants or [])}
        self._tenants.setdefault("default", TenantConfig("default"))
        self._hedge_s = (hedge_ms if hedge_ms is not None
                         else fleet_hedge_ms()) / 1e3
        self._hedge_pct = (hedge_pct if hedge_pct is not None
                           else fleet_hedge_pct())
        self._readmit_limit = int(readmit_limit)
        self._hedge_limit = int(hedge_limit)
        self._pressure = float(pressure_free_frac)
        self._timeout_ms = default_timeout_ms
        self._poll = float(poll_s)
        self._lock = threading.RLock()
        self._inflight: Dict[FleetRequest, List[_Attempt]] = {}
        self._t_inflight: Dict[str, int] = {}
        self._observed_n = 0     # completions THIS router observed
        # idempotence keys already delivered (exactly-once proof);
        # bounded — the one-shot FleetRequest event is the real guard,
        # this set just makes double-delivery *observable*
        self._delivered: set = set()
        self._delivered_order: deque = deque(maxlen=8192)
        self._closed = False
        # health passes run on their own cadence (half the heartbeat
        # period, floored), NOT per relay poll: pool.check() lists/stats
        # heartbeat files and rewrites every gauge — at the 2 ms relay
        # cadence that is thousands of syscalls/s conveying nothing new
        # between beats
        self._health_every = max(pool._hb_s / 2, 0.05)
        self._next_health = 0.0
        self._quota_gauge = get_registry().gauge(
            "fleet_tenant_quota_units",
            "Weighted-fair tenant quota against live capacity "
            "(rebalanced on every scale event)", ("fleet", "tenant"))
        # quota rebalance + affinity-map rebuild on every scale event:
        # _quota() reads LIVE capacity so admission is always current,
        # but the published gauges (what the autoscaler/bench/operator
        # read) and the prefix->replica membership refresh on the
        # membership edge, not lazily on the next submit
        pool.on_scale(self._on_scale_event)
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name=f"fleet-router:{pool.name}")
        self._thread.start()
        self._publish_quotas()
        self._rebuild_affinity()

    # -- admission ---------------------------------------------------------
    def _tenant(self, name: str) -> TenantConfig:
        return self._tenants.get(name) or self._tenants["default"]

    def _quota(self, t: TenantConfig) -> int:
        if t.quota_units is not None:
            return int(t.quota_units)
        # weights normalize within the tenant's capacity group: tenants
        # pinned to the same model share THAT model's capacity;
        # unpinned tenants share the pool-wide total
        group = [c for c in self._tenants.values()
                 if (c.model or None) == (t.model or None)]
        total_w = sum(c.weight for c in group) or 1.0
        return max(1, int(t.weight / total_w
                          * self.pool.capacity_units(t.model)))

    def _publish_quotas(self) -> None:
        """Recompute + publish every tenant's weighted-fair quota (the
        scale-event rebalance edge)."""
        for t, cfg in list(self._tenants.items()):
            self._quota_gauge.labels(
                fleet=self.pool.name, tenant=t).set(self._quota(cfg))
        self.metrics.count("quota_rebalanced")

    def _on_scale_event(self, event: str, replica: str) -> None:
        self._publish_quotas()
        self._rebuild_affinity()

    # -- prefix affinity ---------------------------------------------------
    def _rebuild_affinity(self) -> None:
        """Recompute the consistent prefix->replica membership on a
        scale/death edge. The member set (not an explicit key map) IS
        the routing table: rendezvous hashing over it means a member's
        death remaps only the keys that member owned — every other
        session keeps its replica and its warm KV."""
        members = tuple(sorted(r.name for r in self.pool.healthy()))
        if members != self._affinity_members:
            self._affinity_members = members
            self.metrics.count("affinity_rebuilds")

    def _affinity_target(self, akey: bytes) -> Optional[str]:
        """Rendezvous (highest-random-weight) hash of the affinity key
        over the healthy member set."""
        members = self._affinity_members
        if not members:
            return None
        return max(members, key=lambda name: hashlib.blake2b(
            akey + name.encode(), digest_size=8).digest())

    def _required_class(self) -> int:
        cap = self.pool.capacity_units()
        if cap <= 0:
            return 0
        frac = self.pool.free_units() / cap
        if frac < self._pressure / 2:
            return 2
        if frac < self._pressure:
            return 1
        return 0

    def submit(self, prompt, max_new_tokens: int = 0, *,
               tenant: str = "default", timeout_ms="default",
               eos_token: Optional[int] = None,
               on_token: Optional[Callable[[int], None]] = None,
               model: Optional[str] = None) -> FleetRequest:
        """Admit one request into the fleet. Typed shedding:
        :class:`~.admission.ServerOverload` on tenant quota /
        deadline-class pressure / no capacity,
        :class:`ReplicaUnavailable` when no healthy replica can take
        it. ``model=`` routes to one hosted :class:`ModelSpec`'s
        engines in a multi-model pool (default: the tenant's pinned
        model, else the primary). Streaming requests (``on_token``)
        are pinned to one replica — never hedged or re-admitted (a
        replayed stream would emit duplicate tokens); replica death
        fails them typed-transient for the client's retry loop."""
        if self._closed:
            raise ServerOverload("fleet router is closed")
        import numpy as onp

        cfg = self._tenant(tenant)
        if model is None:
            model = cfg.model
        akey = None
        if self.pool.kind == "llm":
            prompt = onp.asarray(prompt, onp.int32).reshape(-1)
            plen = int(prompt.shape[0])
            units = self.pool.cost_units(plen, int(max_new_tokens),
                                         model)
            if self._aff_on:
                from . import kv_hash

                # the SAME chain-hash discipline the engines' prefix
                # caches key on (the drift guarantee lives in kv_hash)
                akey = kv_hash.prefix_key(prompt, self._aff_bs,
                                          depth=self._aff_blocks)
        else:
            if on_token is not None:
                raise ValueError(
                    "on_token= streams generated tokens — fixed-shape "
                    "(InferenceEngine) fleets have none; the callback "
                    "would silently never fire")
            prompt = onp.asarray(prompt)
            units = 1
        if timeout_ms == "default":
            timeout_ms = self._timeout_ms
        deadline = (time.monotonic() + timeout_ms / 1e3
                    if timeout_ms is not None else None)
        with self._lock:
            # weighted-fair quota: the tenant's share of LIVE capacity
            held = self._t_inflight.get(tenant, 0)
            quota = self._quota(cfg)
            if held + units > quota:
                self.metrics.count("shed_quota")
                self.metrics.count_tenant(tenant, "shed_quota")
                raise ServerOverload(
                    f"tenant {tenant!r} over its capacity quota "
                    f"({held}+{units} > {quota} units) — back off and "
                    "retry")
            # deadline-class shed order under pressure: capacity loss
            # (or a noisy neighbor) degrades the lowest class first
            need = self._required_class()
            if cfg.deadline_class < need:
                self.metrics.count("shed_class")
                self.metrics.count_tenant(tenant, "shed_class")
                raise ServerOverload(
                    f"fleet under pressure (free "
                    f"{self.pool.free_units()}/"
                    f"{self.pool.capacity_units()} units): deadline "
                    f"class {cfg.deadline_class} < required {need} — "
                    "shed, retry with backoff")
            freq = FleetRequest(prompt, max_new_tokens, tenant, deadline,
                                units, eos_token, on_token, model=model,
                                akey=akey)
            self._t_inflight[tenant] = held + units
            self.metrics.tenant_inflight.labels(
                fleet=self.pool.name, tenant=tenant).set(
                    self._t_inflight[tenant])
        # the trace's birth certificate on the router's own timeline
        # (the dispatching process is one lane of the merged trace)
        _tracing.emit_instant(
            "fleet.submit", cat="fleet",
            args={"trace_id": freq.trace.trace_id, "tenant": tenant,
                  "fleet": self.pool.name, "units": units})
        try:
            self._dispatch(freq, exclude=(), is_hedge=False)
        except BaseException:
            self._release_tenant(freq)
            raise
        self.metrics.count("submitted")
        self.metrics.count_tenant(tenant, "submitted")
        return freq

    def generate(self, prompt, max_new_tokens: int, **kw):
        """Blocking convenience: submit + wait."""
        return self.submit(prompt, max_new_tokens, **kw).wait()

    def infer(self, x, **kw):
        """Blocking fixed-shape convenience (infer fleets)."""
        return self.submit(x, 0, **kw).wait()

    # -- dispatch ----------------------------------------------------------
    @staticmethod
    def _load(r: Replica, model: Optional[str] = None) -> float:
        # least-loaded is judged per MODEL in a multi-model pool: the
        # other model's lanes don't contend for this model's KV blocks
        return (r.host.inflight(model)
                / max(1, r.host.capacity_units(model)))

    def _pick(self, exclude: Tuple[str, ...],
              model: Optional[str] = None,
              akey: Optional[bytes] = None
              ) -> Optional[Tuple[Replica, bool]]:
        """Affinity-first / least-loaded-second healthy replica with a
        willing breaker; returns ``(replica, probed)`` — ``probed``
        marks a claimed half-open breaker probe the caller must
        eventually resolve or release.

        ``akey`` (the prompt's leading-block chain hash) prefers the
        rendezvous-hash owner of that prefix — where the KV blocks are
        already hot — unless the owner is excluded, unhealthy, breaker
        open/half-open, or loaded past the affinity ceiling; then the
        pick falls back to least-loaded (counted
        ``affinity_fallback``).

        Recovery probes come first: a tripped replica past its cooldown
        claims exactly ONE live request (``allow()`` is the side-
        effecting claim, so it is only called on candidates we would
        actually choose) — without this, a fleet with any healthy
        replica would never re-test a tripped one and an open breaker
        could never close. A probe failure re-opens the breaker and the
        request re-admits like any replica fault, so at most one
        request per cooldown window is at risk."""
        healthy = [r for r in self.pool.healthy()
                   if r.name not in exclude]

        def load(r: Replica) -> float:
            return self._load(r, model)

        for r in sorted(healthy, key=load):
            if r.breaker.state != CircuitBreaker.CLOSED \
                    and r.breaker.allow():
                return r, True            # this dispatch owns the probe
        closed = [r for r in healthy
                  if r.breaker.state == CircuitBreaker.CLOSED]
        if closed:
            if akey is not None:
                target = self._affinity_target(akey)
                if target is not None:
                    for r in closed:
                        if r.name == target:
                            if load(r) <= self._aff_max_load:
                                self.metrics.count("affinity_hit")
                                return r, False
                            break   # saturated owner: least-loaded
                    self.metrics.count("affinity_fallback")
            return min(closed, key=load), False
        return None

    def _remaining_ms(self, freq: FleetRequest) -> Optional[float]:
        if freq.deadline is None:
            return None
        return max(1.0, (freq.deadline - time.monotonic()) * 1e3)

    def _dispatch(self, freq: FleetRequest, exclude: Tuple[str, ...],
                  is_hedge: bool) -> bool:
        """Place one attempt, walking the healthy set least-loaded
        first; returns whether an attempt was placed (a hedge that
        finds no replica returns False instead of raising). Failure
        taxonomy at the submit seam: a **shed** (``ServerOverload`` —
        full queue, closing engine) skips the replica without a breaker
        verdict; a **replica fault** (any other ``TransientError``,
        e.g. a dead subprocess pipe) counts a breaker failure and tries
        the next replica; a **client error** (``ValueError`` & friends
        — bad request, streaming on a subprocess fleet) propagates
        immediately and must NOT trip breakers or be laundered into a
        retryable error."""
        exclude = tuple(exclude)
        last: Optional[BaseException] = None
        for _ in range(len(self.pool.replicas)):
            picked = self._pick(exclude, freq.model, freq.akey)
            if picked is None:
                break
            r, probed = picked
            try:
                # (the serving.fleet.replica chaos site fires in the
                # REPLICA's own loop — LLM scheduler tick or batcher
                # iteration — never here in the dispatching thread)
                handle = r.host.submit(freq, self._remaining_ms(freq))
            except ServerOverload as e:
                if probed:
                    r.breaker.release_probe()  # a shed is not a verdict
                last = e
                exclude = exclude + (r.name,)
                continue
            except TransientError as e:
                r.breaker.record_failure()  # resolves a claimed probe
                last = e
                exclude = exclude + (r.name,)
                continue
            except BaseException:
                # a client/config error: the replica did nothing wrong
                if probed:
                    r.breaker.release_probe()
                raise
            att = _Attempt(freq, r, handle, is_hedge, probed=probed)
            freq.attempt_n += 1
            with self._lock:
                self._inflight.setdefault(freq, []).append(att)
            return True
        if is_hedge:
            return False                  # a hedge silently waits instead
        if isinstance(last, TransientError):
            raise last
        err = ReplicaUnavailable(
            "no healthy replica with a willing breaker could take the "
            "request — the fleet is degraded, back off and retry")
        if last is not None:
            err.__cause__ = last
        raise err

    # -- control loop ------------------------------------------------------
    def _loop(self) -> None:
        last_warn = 0.0
        while not self._closed or self._inflight:
            try:
                self._tick()
            except Exception:  # noqa: BLE001 — the relay must survive
                # survive, but never SILENTLY: a persistent relay bug
                # would hang every deadline-less wait() with zero
                # diagnostics. Throttled so a hot failure doesn't spam.
                now = time.monotonic()
                if now - last_warn > 5.0:
                    last_warn = now
                    log.exception(
                        "fleet router %s: control-loop tick failed "
                        "(relay continues; in-flight requests may "
                        "stall if this persists)", self.pool.name)
            time.sleep(self._poll)

    def _hedge_threshold(self) -> float:
        if self._hedge_s <= 0:
            return float("inf")
        # the registry histogram IS the latency window (recency
        # reservoir, cap 512): one p-percentile definition shared with
        # the exposition's fleet_attempt_ms_p* gauge series. The
        # warmup gate counts THIS router's own completions — the
        # registry series outlives a closed router, and a fresh
        # incarnation over the same fleet name must not compute its
        # threshold purely from its predecessor's (e.g. death-spike)
        # window before re-observing 20 of its own.
        if self._observed_n < 20:
            return self._hedge_s
        return max(self._hedge_s,
                   self.metrics.attempt_ms.quantile(
                       self._hedge_pct / 100.0) / 1e3)

    def _tick(self) -> None:
        now = time.monotonic()
        dead: set = set()
        if now >= self._next_health:
            self._next_health = now + self._health_every
            dead = {r.name for r in self.pool.check()}
        with self._lock:
            items = [(freq, list(atts))
                     for freq, atts in self._inflight.items()]
        # lazily computed on first need: sorting the latency window
        # every 2 ms tick of an idle fleet is pure overhead
        hedge_after = None
        for freq, atts in items:
            if freq.done:
                self._finalize(freq)
                continue
            # a submitter's cancel() settles here: fail the fleet
            # request typed, cancel every attempt's lane, release quota
            if freq.cancelled:
                if freq.fail(RequestCancelled(
                        "fleet request cancelled by its submitter")):
                    self.metrics.count("cancelled")
                self._finalize(freq)
                continue
            # fleet-level deadline: authoritative even if every replica
            # sits on it (their lane sweeps lag by at most a tick)
            if freq.deadline is not None and now > freq.deadline:
                elapsed = now - freq.enqueue_t
                budget = freq.deadline - freq.enqueue_t
                if freq.fail(DeadlineExceeded(
                        f"fleet deadline passed ({elapsed * 1e3:.1f} ms "
                        f"elapsed vs a {budget * 1e3:.1f} ms budget)",
                        elapsed_s=elapsed, budget_s=budget)):
                    self.metrics.count("shed_deadline")
                self._finalize(freq)
                continue
            pending = []
            for att in atts:
                if att.handle.done:
                    self._on_attempt_done(freq, att, dead)
                    if freq.done:
                        break
                elif att.replica.name in dead \
                        or att.replica.state == DEAD:
                    # the replica died under this attempt and its
                    # engine never got to fail the handle (hard kill):
                    # fail it fleet-side, typed transient
                    att.handle.fail(TransientError(
                        f"fleet replica {att.replica.name!r} died with "
                        "the request in flight"))
                    self._on_attempt_done(freq, att, dead)
                    if freq.done:
                        break
                else:
                    pending.append(att)
            if freq.done:
                self._finalize(freq)
                continue
            if not pending and freq not in self._inflight:
                continue
            if not self._inflight.get(freq):
                # every attempt resolved without completing the fleet
                # request and nothing was re-admitted — fail it typed
                # so no wait() hangs (re-admission budget exhausted)
                if freq.fail(ReplicaUnavailable(
                        "every attempt failed and the re-admission "
                        "budget is spent — back off and retry")):
                    self.metrics.count("failed")
                    self.metrics.count_tenant(freq.tenant, "failed")
                self._finalize(freq)
                continue
            # hedging: oldest live attempt past the latency percentile
            if hedge_after is None and pending \
                    and self._hedge_s > 0:
                hedge_after = self._hedge_threshold()
            if (freq.on_token is None and freq.hedges < self._hedge_limit
                    and pending and hedge_after is not None
                    and now - pending[0].t0 > hedge_after):
                exclude = tuple(a.replica.name
                                for a in self._inflight.get(freq, ()))
                try:
                    placed = self._dispatch(freq, exclude, is_hedge=True)
                except Exception:  # noqa: BLE001 — hedges are optional
                    placed = False
                if placed:
                    # the budget is spent only on a PLACED hedge — a
                    # momentary no-available-replica blip must not
                    # permanently disable hedging for this request
                    freq.hedges += 1
                    self.metrics.count("hedged")

    def _on_attempt_done(self, freq: FleetRequest, att: _Attempt,
                         dead: set) -> None:
        with self._lock:
            atts = self._inflight.get(freq, [])
            if att in atts:
                atts.remove(att)
        err = att.handle.exception()
        if err is None:
            # the replica DID succeed, winner or not — the breaker's
            # verdict (and any half-open probe) resolves on that fact,
            # independent of the first-completion-wins race below
            att.replica.breaker.record_success()
            # success — first completion wins; the idempotence key set
            # proves a hedge/readmit can never double-deliver
            with self._lock:
                duplicate = freq.key in self._delivered
                if not duplicate:
                    if len(self._delivered_order) \
                            == self._delivered_order.maxlen:
                        self._delivered.discard(
                            self._delivered_order.popleft())
                    self._delivered.add(freq.key)
                    self._delivered_order.append(freq.key)
            if duplicate or not freq.finish(att.handle.result()):
                self.metrics.count("hedge_losses")
                return
            self.metrics.attempt_ms.observe(
                (time.monotonic() - freq.enqueue_t) * 1e3)
            self._observed_n += 1
            self.metrics.count("completed")
            self.metrics.count_tenant(freq.tenant, "completed")
            if att.is_hedge:
                self.metrics.count("hedge_wins")
            self.metrics.request_ms.labels(
                fleet=self.pool.name, tenant=freq.tenant).observe(
                    (time.monotonic() - freq.enqueue_t) * 1e3)
            # first-wins cancellation: retire the loser lanes now
            # instead of letting them decode tokens nobody wants
            with self._lock:
                losers = list(self._inflight.get(freq, ()))
            for loser in losers:
                loser.handle.cancel()
            return
        if att.probed:
            # a failed/cancelled probe must not stay claimed: cancelled
            # resolves to release (no verdict), failure re-opens below
            att.replica.breaker.release_probe()
        if freq.done:
            return                        # a sibling already settled it
        if isinstance(err, RequestCancelled):
            return                        # our own first-wins cancel
        replica_fault = (att.replica.name in dead
                         or att.replica.state != HEALTHY
                         or not att.replica.host.alive)
        client_fault = isinstance(err, DeadlineExceeded) or (
            isinstance(err, FatalError) and not replica_fault)
        if client_fault:
            if freq.fail(err):
                self.metrics.count("failed")
                self.metrics.count_tenant(freq.tenant, "failed")
            return
        att.replica.breaker.record_failure()
        with self._lock:
            sibling_live = bool(self._inflight.get(freq))
        if sibling_live:
            # a hedge twin (or the original) is still running: let it
            # settle the request instead of spawning a redundant third
            # attempt and burning the one re-admission this request has
            return
        retryable = isinstance(err, TransientError) or replica_fault
        streaming = freq.on_token is not None
        if retryable and not streaming \
                and freq.readmits < self._readmit_limit:
            freq.readmits += 1
            exclude = (att.replica.name,)
            try:
                self._dispatch(freq, exclude, is_hedge=False)
                self.metrics.count("readmitted")
                self.metrics.count_tenant(freq.tenant, "readmitted")
                return
            except Exception:  # noqa: BLE001 — fall through to fail
                pass
        typed = err if isinstance(err, TransientError) else \
            ReplicaUnavailable(
                f"replica {att.replica.name!r} failed the request and "
                f"it cannot be re-admitted: {err!r}")
        if typed is not err:
            typed.__cause__ = err
        if freq.fail(typed):
            self.metrics.count("failed")
            self.metrics.count_tenant(freq.tenant, "failed")

    def _finalize(self, freq: FleetRequest) -> None:
        """Settle the request's bookkeeping: pop and cancel whatever
        attempts are STILL tracked (the live registry is the single
        source of truth — not any caller-held snapshot), release probe
        claims and the tenant's quota units. Idempotent."""
        with self._lock:
            leftovers = self._inflight.pop(freq, [])
        for att in leftovers:
            att.handle.cancel()
            if att.probed:
                # nobody will relay this attempt again: a claimed
                # half-open probe resolved-by-cancellation releases,
                # or the breaker stays probe-locked forever
                att.replica.breaker.release_probe()
        self._release_tenant(freq)

    def _release_tenant(self, freq: FleetRequest) -> None:
        with self._lock:
            if freq.units <= 0:
                return
            held = self._t_inflight.get(freq.tenant, 0)
            self._t_inflight[freq.tenant] = max(0, held - freq.units)
            self.metrics.tenant_inflight.labels(
                fleet=self.pool.name, tenant=freq.tenant).set(
                    self._t_inflight[freq.tenant])
            freq.units = 0

    # -- observability / lifecycle ----------------------------------------
    def stats(self) -> Dict:
        reps = []
        for r in self.pool.replicas:
            reps.append({
                "name": r.name, "state": r.state,
                "reason": r.state_reason,
                "breaker": r.breaker.state,
                "breaker_trips": r.breaker.trips,
                "generation": r.generation,
                "inflight": (r.host.inflight()
                             if r.state != DEAD else None),
            })
        m = self.metrics
        with self._lock:
            tenants = {t: dict(inflight_units=self._t_inflight.get(t, 0),
                               quota_units=self._quota(cfg),
                               weight=cfg.weight,
                               deadline_class=cfg.deadline_class,
                               model=cfg.model)
                       for t, cfg in self._tenants.items()}
        return {
            "fleet": self.pool.name,
            "kind": self.pool.kind,
            "replicas": reps,
            "models": [s.name for s in self.pool.models] or ["default"],
            "capacity_units": self.pool.capacity_units(),
            "free_units": self.pool.free_units(),
            "tenants": tenants,
            "counters": {e: m.value(e) for e in (
                "submitted", "completed", "failed", "readmitted",
                "hedged", "hedge_wins", "hedge_losses", "shed_quota",
                "shed_class", "shed_deadline", "replica_dead",
                "replica_wedged", "replica_restarts",
                "replica_drained", "replica_activated",
                "replica_added", "spare_added", "quota_rebalanced",
                "affinity_hit", "affinity_fallback",
                "affinity_rebuilds")},
        }

    def close(self, drain: bool = True, timeout_s: float = 60.0) -> None:
        """Stop admitting; let in-flight work settle (bounded), then
        stop the control loop and the pool. Requests still unresolved
        at the deadline are failed typed — never left hanging."""
        if self._closed:
            return
        self._closed = True
        deadline = time.monotonic() + (timeout_s if drain else 0.0)
        while self._inflight and time.monotonic() < deadline:
            time.sleep(0.01)
        with self._lock:
            leftovers = list(self._inflight.keys())
        for freq in leftovers:
            if freq.fail(ServerOverload(
                    "fleet router closed with the request unresolved — "
                    "resubmit elsewhere")):
                self.metrics.count("failed")
            self._finalize(freq)
        self._thread.join(5.0)
        self.pool.close()

    def __enter__(self) -> "Router":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


# ---------------------------------------------------------------------------
# subprocess worker entry point
# ---------------------------------------------------------------------------

def _worker_main() -> None:  # pragma: no cover — subprocess entry
    """The subprocess replica body: build model + engine from the spec
    in ``MXT_FLEET_WORKER_SPEC``, beat heartbeat files under the fleet
    root, serve JSON-line requests from stdin, answer on stdout. A
    chaos ``kill`` rule armed in THIS process's env (the
    ``serving.fleet.replica`` site fires per scheduler tick) is a real
    ``os._exit(137)``."""
    import importlib

    import numpy as onp

    from ..resilience.elastic import Heartbeat

    spec = json.loads(os.environ["MXT_FLEET_WORKER_SPEC"])
    out_lock = threading.Lock()

    def emit(msg: Dict) -> None:
        with out_lock:
            sys.stdout.write(json.dumps(msg) + "\n")
            sys.stdout.flush()

    onp.random.seed(int(spec.get("seed", 0)))
    mod_name, _, attr = spec["model"].partition(":")
    builder = getattr(importlib.import_module(mod_name), attr)
    model = builder(**spec.get("model_kwargs", {}))
    if hasattr(model, "initialize"):
        model.initialize()
    name = spec.get("name", f"r{spec.get('index', 0)}")

    def hook() -> None:
        chaos.site("serving.fleet.replica", replica=name)
        chaos.site(f"serving.fleet.replica.{name}")

    from .llm import LLMEngine

    eng = LLMEngine(model, step_hook=hook,
                    **spec.get("engine_kwargs", {}))
    eng.warmup()

    hb = Heartbeat(spec["root"], int(spec.get("index", 0)),
                   float(spec.get("heartbeat_s", 0.25)))
    os.makedirs(hb.dir, exist_ok=True)
    stop = threading.Event()

    def stats() -> Dict:
        return {
            "load": int(eng.metrics.lanes_active.get()) + len(eng._queue),
            "free": int(eng.metrics.pool_free.get()),
            "cap": int(eng.num_blocks),
            "block_size": int(eng.block_size),
            "slack": int(eng._slack),
        }

    def beat_loop() -> None:
        while not stop.wait(hb.period):
            try:
                if eng.alive and \
                        time.monotonic() - eng.last_tick \
                        <= max(2 * hb.period, 0.2):
                    hb.beat()
                emit({"op": "stats", "stats": stats()})
            except Exception:  # noqa: BLE001
                pass

    hb.beat()
    threading.Thread(target=beat_loop, daemon=True).start()
    emit({"op": "ready", "stats": stats()})

    open_handles: Dict[int, Any] = {}
    handles_lock = threading.Lock()

    def answer(rid: int, handle) -> None:
        try:
            toks = handle.wait()
            emit({"op": "done", "id": rid, "ok": True,
                  "tokens": [int(t) for t in onp.asarray(toks)]})
        except Exception as e:  # noqa: BLE001 — typed over the wire
            from ..resilience.retry import TRANSIENT, classify

            kind = ("cancelled" if isinstance(e, RequestCancelled)
                    else "transient" if classify(e) == TRANSIENT
                    else "fatal")
            emit({"op": "done", "id": rid, "ok": False,
                  "error": repr(e), "kind": kind})
        finally:
            with handles_lock:
                open_handles.pop(rid, None)

    drain = True
    for line in sys.stdin:
        try:
            msg = json.loads(line)
        except ValueError:
            continue
        op = msg.get("op")
        if op == "close":
            drain = bool(msg.get("drain", True))
            break
        if op == "cancel":
            # first-wins hedge cancellation / submitter cancel crossing
            # the pipe: retire the worker-side lane (blocks freed at
            # the engine's next sweep; the done reply routes back as
            # RequestCancelled through the classifier)
            with handles_lock:
                h = open_handles.get(msg.get("id"))
            if h is not None:
                h.cancel()
            continue
        if op != "submit":
            continue
        rid = msg.get("id")
        trace = msg.get("trace") or {}
        try:
            handle = eng.submit(
                onp.asarray(msg["prompt"], onp.int32),
                int(msg["max_new"]),
                eos_token=msg.get("eos"),
                timeout_ms=msg.get("timeout_ms"),
                trace_id=trace.get("trace_id"))
        except Exception as e:  # noqa: BLE001 — typed shed
            from ..resilience.retry import TRANSIENT, classify

            emit({"op": "done", "id": rid, "ok": False, "error": repr(e),
                  "kind": ("transient" if classify(e) == TRANSIENT
                           else "fatal")})
            continue
        with handles_lock:
            open_handles[rid] = handle
        threading.Thread(target=answer, args=(rid, handle),
                         daemon=True).start()
    stop.set()
    eng.close(drain=drain, timeout_s=30.0)


if __name__ == "__main__":  # pragma: no cover
    _worker_main()
