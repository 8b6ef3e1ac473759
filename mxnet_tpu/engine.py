"""Execution-engine contract.

The reference dependency engine (``src/engine/``: ``ThreadedEngine``,
``ThreadedEnginePerDevice``, ``NaiveEngine``) schedules every NDArray
mutation asynchronously with read/write dependencies. On TPU, XLA's runtime
*is* the async engine: jax dispatch enqueues work on per-device streams and
returns immediately; data dependencies order execution; errors surface on
``block_until_ready``. This module keeps the user-facing contract:

- ``waitall()``  — reference ``Engine::WaitForAll`` / ``MXNDArrayWaitAll``
- ``MXNET_ENGINE_TYPE=NaiveEngine`` — synchronous deterministic mode for
  debugging (reference ``src/engine/engine.cc:32`` factory), implemented by
  blocking after every op.
- ``set_bulk_size`` / ``bulk`` — op bulking (reference ``engine.h:315``,
  default ``MXNET_ENGINE_BULK_SIZE``). Inside jit traces XLA fuses
  everything, so the knob governs the EAGER path: bulk size 0 forces a
  block after every dispatched op (same execution as NaiveEngine), any
  positive size keeps XLA's async pipelining. ``bulk(0)`` is therefore a
  scoped synchronous-debug region.
- async exception propagation — tested by
  ``tests/python/unittest/test_exc_handling.py`` in the reference; jax
  raises deferred XLA errors at the next sync point, same contract.
  ``waitall()`` additionally re-raises the FIRST deferred error of any
  eager op whose output was never explicitly waited on (reference
  ``threaded_engine.cc:422-431``: ``WaitForAll`` rethrows accumulated
  exceptions from the global var). Errors already observed at
  ``wait_to_read``/``asnumpy`` are cleared from the pending set, so a
  caught failure does not resurface — matching the reference, where the
  var's ``exception_ptr`` is cleared once thrown.
"""
from __future__ import annotations

import collections
import contextlib
import threading as _threading
import weakref

import jax

from .base import env_int, env_str

__all__ = ["waitall", "is_naive", "set_bulk_size", "bulk"]

import os as _os

try:
    _bulk_size = int(_os.environ.get("MXNET_ENGINE_BULK_SIZE") or 15)
except ValueError:
    _bulk_size = 15  # malformed env must not break `import mxnet_tpu`


def engine_type() -> str:
    return env_str("MXNET_ENGINE_TYPE", "ThreadedEnginePerDevice")


def is_naive() -> bool:
    return engine_type() == "NaiveEngine"


# Output groups of eager ops whose completion nobody has explicitly waited
# on. One entry per op (a tuple of weakrefs to that op's outputs): group
# granularity means observing one failed sibling clears the whole op, like
# the reference clearing the op's exception_ptr, not one var's. Weakrefs:
# tracking must not extend buffer lifetime (the reference engine tracks
# vars, not data). Bounded: an eager loop that never syncs evicts old
# entries instead of growing without bound — matching the reference, whose
# exception store only keeps the first failure per var.
# malformed/negative env must not break `import mxnet_tpu`; 0 disables
# tracking (deque(maxlen=0) drops every append)
_PENDING_CAP = max(0, env_int("MXNET_ENGINE_PENDING_CAP", 512))
_pending: "collections.deque[tuple]" = collections.deque(maxlen=_PENDING_CAP)
_pending_lock = _threading.Lock()


def track(val) -> None:
    """Register eager-op outputs so ``waitall()`` can surface their deferred
    errors even when the caller never waits on them (reference
    ``ThreadedEngine::OnCompleteStatic`` storing the exception_ptr on the
    var, rethrown by ``WaitForAll``, threaded_engine.cc:422-431)."""
    if sync_each_op():
        return  # per-op blocking mode: nothing can be pending
    _track(val)


def _track(val) -> None:
    """track() when the caller already knows per-op sync did not run —
    avoids a second ``sync_each_op`` environ lookup on the eager hot path."""
    vals = val if isinstance(val, (tuple, list)) else (val,)
    group = []
    for v in vals:
        if hasattr(v, "block_until_ready"):
            try:
                group.append(weakref.ref(v))
            except TypeError:
                pass  # tracer or non-weakrefable value
    if group:
        with _pending_lock:
            _pending.append(tuple(group))


def observed(data) -> None:
    """Forget the tracked op whose deferred error was just raised at an
    explicit wait (wait_to_read/asnumpy) — the reference clears the
    exception_ptr once thrown, so waitall must not re-raise it. Clears the
    whole output group: siblings of a multi-output op share the failure."""
    with _pending_lock:
        kept = [g for g in _pending if not any(r() is data for r in g)]
        _pending.clear()
        _pending.extend(kept)


def waitall() -> None:
    """Block until all async device work is done; re-raises the first
    pending deferred error (reference ``Engine::WaitForAll`` /
    ``MXNDArrayWaitAll``, threaded_engine.cc:422-431)."""
    with _pending_lock:
        groups = list(_pending)
        _pending.clear()
    first_exc: Exception | None = None
    for g in groups:
        for r in g:
            v = r()
            if v is None:
                continue
            try:
                v.block_until_ready()
            except Exception as e:  # deferred execution error
                if first_exc is None:
                    first_exc = e
                break  # one failure per op group is the contract
    try:
        jax.effects_barrier()
    except Exception as e:
        if first_exc is None:
            first_exc = e
    for d in jax.devices():
        try:
            jax.device_put(0, d).block_until_ready()
        except Exception:
            pass  # device wedged: the barrier above already surfaced errors
    if first_exc is not None:
        raise first_exc


def sync_each_op() -> bool:
    """True when eager dispatch must block per op: NaiveEngine mode, or a
    ``bulk(0)`` / ``set_bulk_size(0)`` scope. Called on the eager hot
    path, so it is one global compare + one environ dict lookup — no
    helper chain (the env read stays live so the knob can be flipped
    mid-process, which the reference's engine factory cannot)."""
    return (_bulk_size == 0
            or _os.environ.get("MXNET_ENGINE_TYPE") == "NaiveEngine")


def maybe_sync(val) -> bool:
    """Force synchronous execution after one op when the engine mode asks.
    Returns True when it blocked — the caller can then skip ``track``
    (nothing can be pending for a value just waited on)."""
    if not sync_each_op():
        return False
    vals = val if isinstance(val, (tuple, list)) else (val,)
    for v in vals:
        if hasattr(v, "block_until_ready"):
            v.block_until_ready()
    return True


def set_bulk_size(size: int) -> int:
    global _bulk_size
    prev, _bulk_size = _bulk_size, size
    return prev


@contextlib.contextmanager
def bulk(size: int):
    prev = set_bulk_size(size)
    try:
        yield
    finally:
        set_bulk_size(prev)
