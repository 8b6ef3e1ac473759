"""Watchdog timer: convert a hang into a typed :class:`StallDetected`.

A wedged TPU runtime or a wedged XLA compile does not raise — it
blocks forever, which no retry loop can see. ``run_with_watchdog`` runs
the operation in a worker thread and joins with a deadline: on timeout
the CALLER gets :class:`~mxnet_tpu.base.StallDetected` (a
``TransientError``, so ``resilience.retry`` re-attempts it) while the
stuck thread is left to finish or die with the process.

Python cannot kill a thread, so the abandoned attempt may still complete
later — appropriate for idempotent operations (compile, infer, device
probe, checkpoint write-to-tmp). For non-idempotent work use a
subprocess-based guard.
"""
from __future__ import annotations

import threading
from typing import Callable, Optional

from ..base import StallDetected

__all__ = ["StallDetected", "Watchdog", "run_with_watchdog"]

_SENTINEL = object()


def run_with_watchdog(fn: Callable, timeout_s: float, *args,
                      name: Optional[str] = None, **kwargs):
    """Run ``fn(*args, **kwargs)`` with a deadline; raise
    :class:`StallDetected` if it does not finish in ``timeout_s``."""
    box = {"result": _SENTINEL, "error": None}

    def target():
        try:
            box["result"] = fn(*args, **kwargs)
        except BaseException as e:  # noqa: BLE001 — re-raised in caller
            box["error"] = e

    label = name or getattr(fn, "__name__", "operation")
    t = threading.Thread(target=target, daemon=True,
                         name=f"watchdog:{label}")
    t.start()
    t.join(timeout_s)
    if t.is_alive():
        from .retry import _flight_dump

        _flight_dump(f"stall:{label}")
        raise StallDetected(
            f"{label} did not complete within {timeout_s:g}s — backend "
            "hang suspected (the attempt is abandoned; a retry may "
            "succeed on recovered capacity)")
    if box["error"] is not None:
        raise box["error"]
    return box["result"]


class Watchdog:
    """Reusable deadline for a family of operations.

    Guard each stage as a CALL under the deadline — the old example
    (``wd.run(jax.jit(fn).lower(x).compile)``) evaluated ``.lower(x)``,
    the stage that actually hangs on a wedged backend, *before*
    ``wd.run`` ever started the clock:

    >>> wd = Watchdog(timeout_s=30, name="compile")
    >>> lowered = wd.run(jax.jit(fn).lower, x)      # doctest: +SKIP
    >>> exec_ = wd.run(lowered.compile)             # doctest: +SKIP

    The raised :class:`StallDetected` is a ``TransientError``, so the
    ``resilience.retry`` classifier re-attempts a guarded compile or an
    AOT cache deserialize instead of killing the run.
    """

    def __init__(self, timeout_s: float, name: Optional[str] = None):
        if timeout_s <= 0:
            raise ValueError("timeout_s must be > 0")
        self.timeout_s = float(timeout_s)
        self.name = name

    def run(self, fn: Callable, *args, **kwargs):
        return run_with_watchdog(fn, self.timeout_s, *args,
                                 name=self.name, **kwargs)
