"""Device contexts.

Parity with reference ``include/mxnet/base.h:90`` ``struct Context`` and
``python/mxnet/context.py`` (``Context :28``, ``gpu() :229``,
``num_gpus :261``) — extended with a first-class ``tpu`` device type, which
is the whole point of this framework. ``gpu()`` is kept as an alias for
``tpu()`` so reference training scripts run with only a context flag change
(the BASELINE.json north-star requirement).
"""
from __future__ import annotations

import threading
from typing import Any, List, Optional

import jax

from .base import MXNetError

__all__ = [
    "Context",
    "cpu",
    "cpu_pinned",
    "tpu",
    "gpu",
    "num_tpus",
    "num_gpus",
    "current_context",
    "current_device",
    "Device",
    "device",
]


class Context:
    """A device context. ``Context('tpu', 0)`` maps to ``jax.devices()[0]``."""

    # mirrors Context::DeviceType taxonomy (reference base.h:92-96) + kTPU
    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
    devstr2type = {v: k for k, v in devtype2str.items()}
    _default_ctx = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if isinstance(device_type, Context):
            device_type, device_id = device_type.device_type, device_type.device_id
        if device_type not in self.devstr2type:
            raise MXNetError(f"unknown device type {device_type!r}")
        # gpu is an alias for the accelerator so reference scripts port 1:1
        self.device_type = device_type
        self.device_id = device_id

    # -- identity ----------------------------------------------------------
    @property
    def device_typeid(self) -> int:
        return self.devstr2type[self.device_type]

    def __eq__(self, other: Any) -> bool:
        return (
            isinstance(other, Context)
            and self._canonical() == other._canonical()
        )

    def _canonical(self):
        dt = "tpu" if self.device_type == "gpu" else self.device_type
        return (dt, self.device_id)

    def __hash__(self) -> int:
        return hash(self._canonical())

    def __repr__(self) -> str:
        return f"{self.device_type}({self.device_id})"

    __str__ = __repr__

    # -- jax mapping -------------------------------------------------------
    @property
    def jax_device(self):
        """The concrete jax.Device backing this context."""
        kind, idx = self._canonical()
        if kind in ("cpu", "cpu_pinned", "cpu_shared"):
            devs = [d for d in jax.devices() if d.platform == "cpu"]
            if not devs:  # accelerator-only runtime: host staging via cpu backend
                try:
                    devs = jax.devices("cpu")
                except RuntimeError:
                    devs = list(jax.devices())
        else:
            devs = [d for d in jax.devices() if d.platform != "cpu"]
            if not devs:
                # CPU-only rig: tpu(i) maps onto the (virtual) cpu devices.
                # The ~1,400 CPU tests stand on this mapping, so nothing
                # here can tell a missing chip from a test rig —
                # chip_smoke.py is what asserts real placement on the TPU.
                devs = list(jax.devices())
        if idx >= len(devs):
            raise MXNetError(f"context {self} out of range ({len(devs)} devices)")
        return devs[idx]

    # -- scoping -----------------------------------------------------------
    def __enter__(self) -> "Context":
        stack = getattr(Context._default_ctx, "stack", None)
        if stack is None:
            stack = Context._default_ctx.stack = []
        stack.append(self)
        return self

    def __exit__(self, *exc) -> None:
        Context._default_ctx.stack.pop()

    @classmethod
    def default(cls) -> "Context":
        stack = getattr(cls._default_ctx, "stack", None)
        if stack:
            return stack[-1]
        return _default_device()


def _default_device() -> Context:
    """Accelerator if present, else cpu — eager arrays land there."""
    if any(d.platform != "cpu" for d in jax.devices()):
        return Context("tpu", 0)
    return Context("cpu", 0)


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Alias of :func:`tpu` for porting reference scripts unchanged."""
    return Context("gpu", device_id)


def num_tpus() -> int:
    devs = [d for d in jax.devices() if d.platform != "cpu"]
    return len(devs) if devs else len(jax.devices())


def num_gpus() -> int:
    """Parity alias (reference python/mxnet/context.py:261)."""
    devs = [d for d in jax.devices() if d.platform != "cpu"]
    return len(devs)


def current_context() -> Context:
    return Context.default()


# mxnet 2.x renamed Context->Device; keep both names
Device = Context
device = Context
current_device = current_context


def ctx_list(ctx) -> List[Context]:
    if isinstance(ctx, Context):
        return [ctx]
    return list(ctx)
