"""Gluon Parameter (reference ``python/mxnet/gluon/parameter.py``, 1,081
lines: lazy-shape Parameter, sharing, deferred init).

TPU-native notes: a Parameter owns ONE logical array (a jax.Array that may
itself be sharded over the mesh) instead of the reference's per-GPU replica
list — replication is the mesh's job (pjit), not the Parameter's. The
deferred-init contract (shape with 0/-1 entries completed at first forward)
is kept exactly, since Gluon layers rely on it.
"""
from __future__ import annotations

from typing import Any, List, Optional, Tuple

import jax.numpy as jnp
import numpy as onp

from ..base import MXNetError, dtype_from_any
from ..context import Context, current_context
from ..ndarray.ndarray import ndarray, _wrap
from .. import initializer as init_mod

__all__ = ["Parameter", "Constant", "DeferredInitializationError"]

import contextlib
import threading

# thread-local parameter substitution used while tracing: maps
# id(Parameter) -> ndarray (usually wrapping a jax tracer). Other threads
# never observe these values.
_tls = threading.local()


def _trace_state_clean() -> bool:
    """True when no jax trace is active (safe to materialize a concrete
    parameter). Falls back to False (= keep the loud DeferredInit error)
    if the probe is unavailable, never to unsafe self-healing."""
    try:
        from jax._src.core import trace_state_clean
        return bool(trace_state_clean())
    except Exception:  # noqa: BLE001 — private API moved; stay conservative
        return False


def _tls_override(param) -> Optional[ndarray]:
    overrides = getattr(_tls, "overrides", None)
    if not overrides:
        return None
    return overrides.get(id(param))


_MISSING = object()


@contextlib.contextmanager
def substitute_params(pairs):
    """Thread-locally substitute parameter values for the duration of a
    trace. ``pairs`` is an iterable of (Parameter, ndarray). The same
    Parameter may appear multiple times (tied weights collected under two
    names) — only its FIRST pre-existing state is restored on exit."""
    overrides = getattr(_tls, "overrides", None)
    if overrides is None:
        overrides = _tls.overrides = {}
    added = {}
    for p, v in pairs:
        added.setdefault(id(p), overrides.get(id(p), _MISSING))
        overrides[id(p)] = v
    try:
        yield
    finally:
        for key, prev in added.items():
            if prev is _MISSING:
                overrides.pop(key, None)
            else:
                overrides[key] = prev


class DeferredInitializationError(MXNetError):
    """Parameter accessed before its deferred shape/init completed."""


def _shape_known(shape) -> bool:
    return shape is not None and all(int(s) > 0 for s in shape)


class Parameter:
    """A trainable tensor with init/grad/sharding metadata."""

    def __init__(
        self,
        name: str = "weight",
        grad_req: str = "write",
        shape=None,
        dtype="float32",
        lr_mult: float = 1.0,
        wd_mult: float = 1.0,
        init=None,
        allow_deferred_init: bool = False,
        differentiable: bool = True,
        stype: str = "default",
        grad_stype: str = "default",
    ):
        self._name = name
        self._shape = tuple(shape) if shape is not None else None
        self.dtype = dtype_from_any(dtype)
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self.allow_deferred_init = allow_deferred_init
        self.grad_req = grad_req if differentiable else "null"
        self._differentiable = differentiable
        self.stype = stype
        self.grad_stype = grad_stype
        self._data: Optional[ndarray] = None
        self._deferred_init: Optional[tuple] = None  # (init, ctx)
        # sharding annotation for the parallel layer (PartitionSpec-like)
        self.sharding = None

    # -- naming ------------------------------------------------------------
    @property
    def name(self) -> str:
        return self._name

    @name.setter
    def name(self, value):
        self._name = value

    # -- shape (deferred completion) --------------------------------------
    @property
    def shape(self):
        return self._shape

    @shape.setter
    def shape(self, new_shape):
        if self._shape is None:
            self._shape = tuple(new_shape)
            return
        unknown_ok = all(
            s1 == s2 or int(s1) <= 0 for s1, s2 in zip(self._shape, new_shape)
        ) and len(self._shape) == len(new_shape)
        if not unknown_ok:
            raise MXNetError(
                f"cannot update shape of {self.name} from {self._shape} to {new_shape}"
            )
        self._shape = tuple(new_shape)

    @property
    def shape_known(self) -> bool:
        return _shape_known(self._shape)

    # -- initialization ----------------------------------------------------
    def initialize(self, init=None, device=None, ctx=None, default_init=None, force_reinit=False):
        ctx = ctx or device
        if self._data is not None and not force_reinit:
            return
        self._deferred_init = (
            init or self.init or default_init or init_mod.Uniform(0.07),
            ctx,
        )
        if self.shape_known:
            self._finish_deferred_init()

    def _finish_deferred_init(self):
        if self._deferred_init is None:
            return
        if not self.shape_known:
            if not self.allow_deferred_init:
                raise DeferredInitializationError(
                    f"Parameter {self.name} has unknown shape {self._shape} and "
                    "allow_deferred_init=False"
                )
            return
        initializer, ctx = self._deferred_init
        initializer = init_mod.create(initializer) if not isinstance(initializer, init_mod.Initializer) else initializer
        import jax as _jax

        # ensure_compile_time_eval: finalize may run inside an abstract
        # trace (HybridBlock.infer_shape / first traced forward); the
        # parameter array must be CONCRETE or it escapes the trace
        with _jax.ensure_compile_time_eval():
            arr = ndarray(onp.zeros(self._shape, self.dtype), ctx=ctx)
            initializer.init_array(self.name, arr)
        self._data = arr
        self._deferred_init = None
        if self.grad_req != "null":
            self._data.attach_grad(
                self.grad_req,
                stype=self.grad_stype if self.grad_stype != "default" else None)

    def finalize(self):
        """Complete deferred init once shape is known (called by layers)."""
        if self._data is None and self._deferred_init is not None:
            self._finish_deferred_init()

    # -- access ------------------------------------------------------------
    def _check_initialized(self):
        if self._data is None and _tls_override(self) is None:
            if self._deferred_init is not None:
                if self.shape_known and _trace_state_clean():
                    # self-heal: shape became known after initialize()
                    # (e.g. an infer_shape pass that set shapes but died
                    # before finalizing, or user-assigned shape) — the
                    # reference completes deferred init at this point too
                    # (gluon block.py catches DeferredInitializationError
                    # and finalizes once shapes are inferable). Inside an
                    # ACTIVE trace we still raise: finalizing there would
                    # bake the fresh weight into the cached graph as a
                    # constant (it is not in the substitution set).
                    self._finish_deferred_init()
                    return
                raise DeferredInitializationError(
                    f"Parameter {self.name} deferred; run a forward pass or set shape"
                )
            raise MXNetError(
                f"Parameter {self.name} has not been initialized; call .initialize()"
            )

    def data(self, ctx=None) -> ndarray:
        # trace-time substitution is THREAD-LOCAL: a concurrent trace on
        # another thread (hybridize first call, functionalize) must never
        # leak its tracers into this thread's view of the parameter
        # (CachedOpThreadSafe contract, cached_op_threadsafe.h:82)
        override = _tls_override(self)
        if override is not None:
            return override
        self._check_initialized()
        return self._data

    def list_data(self) -> List[ndarray]:
        return [self.data()]

    def set_data(self, data):
        if isinstance(data, ndarray):
            data = data._data
        if self._data is None:
            self._shape = tuple(data.shape)
            self._data = _wrap(jnp.asarray(data, self.dtype))
            if self.grad_req != "null":
                self._data.attach_grad(
                    self.grad_req,
                    stype=self.grad_stype if self.grad_stype != "default" else None)
        else:
            if tuple(data.shape) != tuple(self._shape):
                raise MXNetError(
                    f"shape mismatch setting {self.name}: {data.shape} vs {self._shape}"
                )
            self._data._set_data(jnp.asarray(data, self.dtype))

    def grad(self, ctx=None) -> ndarray:
        self._check_initialized()
        if self._data._grad is None:
            raise MXNetError(f"Parameter {self.name} has grad_req='null'")
        return self._data._grad

    def list_grad(self):
        return [self.grad()]

    def zero_grad(self):
        if self._data is not None and self._data._grad is not None:
            g = self._data._grad
            from ..ndarray.sparse import RowSparseNDArray

            if isinstance(g, RowSparseNDArray):
                g._values = g._values[:0]
                g._indices = g._indices[:0]
            else:
                g._set_data(jnp.zeros(g.shape, g.dtype))

    def reset_ctx(self, ctx):
        if self._data is not None:
            self._data = self._data.as_in_ctx(ctx if isinstance(ctx, Context) else ctx[0])

    reset_device = reset_ctx

    def list_ctx(self):
        self._check_initialized()
        return [self._data.ctx]

    list_device = list_ctx

    def cast(self, dtype):
        self.dtype = dtype_from_any(dtype)
        if self._data is not None:
            had_grad = self._data._grad is not None
            self._data = self._data.astype(self.dtype)
            if had_grad:
                self._data.attach_grad(self.grad_req)

    def var(self):
        raise NotImplementedError("symbol var() not supported; use hybridize tracing")

    def __repr__(self):
        return f"Parameter {self.name} (shape={self._shape}, dtype={onp.dtype(self.dtype).name})"


class Constant(Parameter):
    """Non-trainable constant parameter (reference gluon/parameter.py Constant)."""

    def __init__(self, value, name="const"):
        if not isinstance(value, ndarray):
            value = ndarray(value)
        super().__init__(
            name=name,
            grad_req="null",
            shape=value.shape,
            dtype=value.dtype,
            differentiable=False,
        )
        self._value = value
        self.init = init_mod.Constant(value)

    def initialize(self, *a, **kw):
        self._data = self._value.copy()
