"""The universal array value type, backed by ``jax.Array``.

Re-design of the reference NDArray (``include/mxnet/ndarray.h:82``,
``src/ndarray/``): a ref-counted device buffer plus an engine variable that
serializes readers/writers and an autograd entry. On TPU the XLA runtime
already provides async dispatch and buffer lifetime management, so this
class keeps the *contract* — ``wait_to_read``/``wait_to_write`` block until
pending async work (and surface async exceptions, the
``threaded_engine.cc:422`` behavior), ``ctx``/``copyto`` move data between
devices, in-place ops serialize — while the mechanism is jax.

Mutation model: jax arrays are immutable, so in-place ops rebind the
underlying buffer (functional update via ``.at[].set``). A version counter
detects stale autograd references, mirroring the reference's var
versioning (``threaded_engine.h:104 VersionedVarBlock``).
"""
from __future__ import annotations

import operator
from typing import Any, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as onp

from ..base import MXNetError, dtype_from_any, bfloat16
from ..context import Context, current_context
from ..ops.dispatch import apply_op, autograd_state, is_recording

__all__ = ["ndarray", "NDArray", "array", "_wrap", "_unwrap"]

# tpulint runtime sentinel seam (analysis.sentinel): called with the
# ndarray on every device->host transfer. item()/float()/int()/bool()/
# __array__ all funnel through asnumpy, so one tap covers every implicit
# sync; a module-global None-check is the entire cost when off.
_transfer_observer = None


def _unwrap(x: Any):
    if isinstance(x, ndarray):
        return x._data
    return x


def _wrap(val) -> "ndarray":
    out = ndarray.__new__(ndarray)
    out._data = val
    out._grad = None
    out._grad_req = "null"
    out._fresh_grad_node = None
    out._version = 0
    return out


class ndarray:
    """Dense n-dimensional array on a device (reference NDArray / mx.np.ndarray)."""

    __slots__ = (
        "_data",
        "_grad",
        "_grad_req",
        "_fresh_grad_node",
        "_version",
        "__weakref__",
    )

    def __init__(self, data, ctx: Optional[Context] = None, dtype=None):
        if isinstance(data, ndarray):
            data = data._data
        dt = dtype_from_any(dtype) if dtype is not None else None
        if not isinstance(data, (jax.Array,)):
            data = onp.asarray(data, dtype=dt)
            # mx.np default-dtype semantics: float64 host data becomes
            # float32 unless the caller asked for float64 explicitly
            if dt is None and data.dtype == onp.float64:
                data = data.astype(onp.float32)
        val = jnp.asarray(data, dtype=dt)
        if ctx is not None:
            val = jax.device_put(val, ctx.jax_device)
        self._data = val
        self._grad: Optional[ndarray] = None
        self._grad_req = "null"
        self._fresh_grad_node = None
        self._version = 0

    # ------------------------------------------------------------------
    # metadata
    # ------------------------------------------------------------------
    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return onp.dtype(self._data.dtype)

    @property
    def ndim(self) -> int:
        return self._data.ndim

    @property
    def size(self) -> int:
        return int(onp.prod(self.shape)) if self.shape else 1

    @property
    def ctx(self) -> Context:
        try:
            dev = self._data.devices().pop()
        except Exception:  # tracer inside jit — context is abstract
            return current_context()
        if dev.platform == "cpu":
            cpu_devs = [d for d in jax.devices() if d.platform == "cpu"]
            try:
                idx = cpu_devs.index(dev)
            except ValueError:
                idx = 0
            # on the virtual-device CPU test rig, cpu devices double as tpus
            if all(d.platform == "cpu" for d in jax.devices()):
                return Context("tpu", idx) if idx else Context("cpu", 0)
            return Context("cpu", idx)
        accel = [d for d in jax.devices() if d.platform != "cpu"]
        return Context("tpu", accel.index(dev))

    context = ctx
    device = ctx

    @property
    def T(self) -> "ndarray":
        return self.transpose()

    @property
    def real(self) -> "ndarray":
        from ..ops.dispatch import apply_op

        return apply_op(lambda v: v.real, [self], name="real")

    @property
    def imag(self) -> "ndarray":
        from ..ops.dispatch import apply_op

        return apply_op(lambda v: v.imag, [self], name="imag")

    def conj(self) -> "ndarray":
        from ..ops.dispatch import apply_op

        return apply_op(lambda v: v.conj(), [self], name="conj")

    conjugate = conj

    @property
    def grad(self) -> Optional["ndarray"]:
        return self._grad

    # ------------------------------------------------------------------
    # engine contract: async wait + exception surfacing
    # ------------------------------------------------------------------
    def wait_to_read(self) -> None:
        """Block until async work producing this array completes; raises any
        deferred exception (reference ndarray.h:374 + threaded_engine.cc:422)."""
        try:
            self._data.block_until_ready()
        except AttributeError:
            pass  # tracer
        except Exception:
            # error observed here → clear from the engine's pending set so
            # waitall() does not rethrow it (reference clears the var's
            # exception_ptr once thrown)
            from .. import engine as _engine

            _engine.observed(self._data)
            raise

    def wait_to_write(self) -> None:
        self.wait_to_read()

    # ------------------------------------------------------------------
    # host transfer / conversion
    # ------------------------------------------------------------------
    def asnumpy(self) -> onp.ndarray:
        self.wait_to_read()
        if _transfer_observer is not None:
            _transfer_observer(self)
        return onp.asarray(self._data)

    def item(self):
        return self.asnumpy().item()

    def asscalar(self):
        return self.item()

    def __float__(self):
        return float(self.item())

    def __int__(self):
        return int(self.item())

    def __bool__(self):
        if self.size != 1:
            raise MXNetError(
                "The truth value of an ndarray with multiple elements is ambiguous."
            )
        return bool(self.item())

    def __len__(self) -> int:
        if not self.shape:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __array__(self, dtype=None):
        arr = self.asnumpy()
        return arr.astype(dtype) if dtype is not None else arr

    # jax interop: our arrays flow straight into jnp/pytree code
    def __jax_array__(self):
        return self._data

    def astype(self, dtype, copy: bool = True) -> "ndarray":
        dt = dtype_from_any(dtype)
        if not copy and self.dtype == dt:
            return self
        return apply_op(lambda x: x.astype(dt), (self,), name="astype")

    def copy(self) -> "ndarray":
        return apply_op(lambda x: x + 0, (self,), name="copy")

    def copyto(self, other: Union["ndarray", Context]) -> "ndarray":
        """Cross-device copy (reference src/ndarray/ndarray.cc CopyFromTo)."""
        from ..resilience import chaos

        chaos.site("device.put")
        if isinstance(other, Context):
            out = _wrap(jax.device_put(self._data, other.jax_device))
            return out
        other._set_data(
            jax.device_put(self._data.astype(other.dtype), other.ctx.jax_device)
        )
        return other

    def as_in_ctx(self, ctx: Context) -> "ndarray":
        if ctx == self.ctx:
            return self
        return self.copyto(ctx)

    as_in_context = as_in_ctx
    to_device = as_in_ctx

    def tolist(self):
        return self.asnumpy().tolist()

    # ------------------------------------------------------------------
    # autograd surface (reference python/mxnet/ndarray/ndarray.py attach_grad)
    # ------------------------------------------------------------------
    def attach_grad(self, grad_req: str = "write", stype=None) -> None:
        if grad_req not in ("write", "add", "null"):
            raise MXNetError(f"invalid grad_req {grad_req!r}")
        self._grad_req = grad_req
        if grad_req == "null":
            self._grad = None
        elif stype == "row_sparse":
            # sparse gradient storage (reference attach_grad stype arg →
            # kRowSparseStorage grad, ndarray.py:2747): starts empty; the
            # backward pass fills only the touched rows
            from .sparse import RowSparseNDArray

            self._grad = RowSparseNDArray(
                jnp.zeros((0,) + self.shape[1:], self.dtype),
                jnp.zeros((0,), jnp.int32), self.shape)
        else:
            self._grad = _wrap(jnp.zeros(self.shape, self.dtype))

    def detach(self) -> "ndarray":
        out = _wrap(self._data)
        return out

    @property
    def stype(self) -> str:
        """Storage type (reference ``NDArray.stype``): dense arrays are
        ``"default"``; RowSparseNDArray/CSRNDArray override."""
        return "default"

    def check_format(self, full_check: bool = True) -> None:
        """Validate storage-format integrity (reference
        ``NDArray.check_format`` / ``MXNDArraySyncCheckFormat``). Dense
        arrays are always well-formed; the sparse classes override with
        real index checks."""

    def backward(self, out_grad=None, retain_graph=False, train_mode=True) -> None:
        from ..ops import dispatch

        dispatch.backward(
            [self],
            [out_grad] if out_grad is not None else None,
            retain_graph=retain_graph,
            train_mode=train_mode,
        )

    # ------------------------------------------------------------------
    # mutation (rebind + version bump)
    # ------------------------------------------------------------------
    def _set_data(self, val) -> None:
        self._data = val
        self._version += 1

    def __setitem__(self, key, value) -> None:
        if is_recording() and self._grad_req != "null":
            raise MXNetError(
                "in-place assignment to an array that requires grad while recording"
            )
        val = _unwrap(value)
        if key is None or (isinstance(key, slice) and key == slice(None)):
            if not onp.isscalar(val) and getattr(val, "shape", ()) != self.shape:
                val = jnp.broadcast_to(jnp.asarray(val, self.dtype), self.shape)
            self._set_data(jnp.asarray(val, self.dtype) * jnp.ones(self.shape, self.dtype) if onp.isscalar(val) else jnp.asarray(val, self.dtype))
            return
        self._check_int_index(key)  # jnp scatter silently drops OOB writes
        key = _unwrap_index(key)
        self._set_data(self._data.at[key].set(jnp.asarray(val, self.dtype) if not onp.isscalar(val) else val))

    @staticmethod
    def _is_plain_int(k) -> bool:
        return isinstance(k, (int, onp.integer)) and not isinstance(
            k, (bool, onp.bool_))

    def _check_int_index(self, key) -> None:
        """numpy contract: out-of-range integer indexing raises IndexError
        (jnp clamps gathers / drops scatters, which would also make the
        legacy __getitem__ iteration protocol loop forever). bool is an
        int subclass but means mask/newaxis indexing — excluded; array
        keys are not checked (a bounds check would force a device sync)."""
        if not hasattr(self._data, "ndim"):
            return  # tuple-valued results (control-flow ops) index as-is

        def check(k, axis):
            if self._is_plain_int(k):
                if axis >= self.ndim:
                    raise IndexError(
                        f"too many indices for {self.ndim}-d array")
                n = self.shape[axis]
                if not -n <= k < n:
                    raise IndexError(f"index {k} is out of bounds for "
                                     f"axis {axis} with size {n}")

        if isinstance(key, tuple):
            entries = [k for k in key if k is not None]
            if any(getattr(k, "ndim", 0) > 0 for k in entries):
                return  # advanced indexing: axis mapping is nontrivial
            if Ellipsis in [k for k in entries if not hasattr(k, "shape")]:
                i = next(j for j, k in enumerate(entries) if k is Ellipsis)
                before, after = entries[:i], entries[i + 1:]
            else:
                before, after = entries, []
            for ax, k in enumerate(before):
                check(k, ax)
            for j, k in enumerate(after):
                check(k, self.ndim - len(after) + j)
        else:
            check(key, 0)

    def __getitem__(self, key) -> "ndarray":
        self._check_int_index(key)
        key = _unwrap_index(key)
        return apply_op(lambda x: x[key], (self,), name="getitem")

    def __iter__(self):
        if self.ndim == 0:
            raise TypeError("iteration over a 0-d array")
        return (self[i] for i in range(self.shape[0]))

    # ------------------------------------------------------------------
    # shape ops
    # ------------------------------------------------------------------
    def reshape(self, *shape) -> "ndarray":
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        shape = tuple(-1 if s in (-1, 0) and s == -1 else s for s in shape)
        return apply_op(lambda x: x.reshape(shape), (self,), name="reshape")

    def transpose(self, *axes) -> "ndarray":
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        ax = axes if axes else None
        return apply_op(lambda x: jnp.transpose(x, ax), (self,), name="transpose")

    def flatten(self) -> "ndarray":
        return self.reshape(-1)

    def squeeze(self, axis=None) -> "ndarray":
        return apply_op(lambda x: jnp.squeeze(x, axis), (self,), name="squeeze")

    def expand_dims(self, axis) -> "ndarray":
        return apply_op(lambda x: jnp.expand_dims(x, axis), (self,), name="expand_dims")

    def broadcast_to(self, shape) -> "ndarray":
        return apply_op(lambda x: jnp.broadcast_to(x, tuple(shape)), (self,), name="broadcast_to")

    def swapaxes(self, a1, a2) -> "ndarray":
        return apply_op(lambda x: jnp.swapaxes(x, a1, a2), (self,), name="swapaxes")

    # ------------------------------------------------------------------
    # reductions / common methods
    # ------------------------------------------------------------------
    def sum(self, axis=None, dtype=None, keepdims=False) -> "ndarray":
        dt = dtype_from_any(dtype) if dtype is not None else None
        return apply_op(lambda x: jnp.sum(x, axis=axis, dtype=dt, keepdims=keepdims), (self,), name="sum")

    def mean(self, axis=None, dtype=None, keepdims=False) -> "ndarray":
        dt = dtype_from_any(dtype) if dtype is not None else None
        return apply_op(lambda x: jnp.mean(x, axis=axis, dtype=dt, keepdims=keepdims), (self,), name="mean")

    def max(self, axis=None, keepdims=False) -> "ndarray":
        return apply_op(lambda x: jnp.max(x, axis=axis, keepdims=keepdims), (self,), name="max")

    def min(self, axis=None, keepdims=False) -> "ndarray":
        return apply_op(lambda x: jnp.min(x, axis=axis, keepdims=keepdims), (self,), name="min")

    def prod(self, axis=None, dtype=None, keepdims=False) -> "ndarray":
        dt = dtype_from_any(dtype) if dtype is not None else None
        return apply_op(lambda x: jnp.prod(x, axis=axis, dtype=dt, keepdims=keepdims), (self,), name="prod")

    def all(self, axis=None, keepdims=False) -> "ndarray":
        return apply_op(lambda x: jnp.all(x, axis=axis, keepdims=keepdims), (self,), name="all")

    def any(self, axis=None, keepdims=False) -> "ndarray":
        return apply_op(lambda x: jnp.any(x, axis=axis, keepdims=keepdims), (self,), name="any")

    def argmax(self, axis=None) -> "ndarray":
        return apply_op(lambda x: jnp.argmax(x, axis=axis), (self,), name="argmax")

    def argmin(self, axis=None) -> "ndarray":
        return apply_op(lambda x: jnp.argmin(x, axis=axis), (self,), name="argmin")

    def clip(self, a_min=None, a_max=None) -> "ndarray":
        return apply_op(lambda x: jnp.clip(x, a_min, a_max), (self,), name="clip")

    def dot(self, other) -> "ndarray":
        return apply_op(lambda a, b: jnp.dot(a, b), (self, other), name="dot")

    def abs(self) -> "ndarray":
        return apply_op(jnp.abs, (self,), name="abs")

    def round(self) -> "ndarray":
        return apply_op(jnp.round, (self,), name="round")

    def cumsum(self, axis=None) -> "ndarray":
        return apply_op(lambda x: jnp.cumsum(x, axis=axis), (self,), name="cumsum")

    def take(self, indices, axis=None) -> "ndarray":
        return apply_op(
            lambda x, i: jnp.take(x, i.astype(jnp.int32) if hasattr(i, "astype") else i, axis=axis),
            (self, indices),
            name="take",
        )

    def item_size(self):
        return self.dtype.itemsize

    def __repr__(self) -> str:
        try:
            body = str(self.asnumpy())
        except Exception:
            body = f"<abstract {self.shape} {self.dtype}>"
        return f"{body}\n<ndarray {self.shape} @{self.ctx} {self.dtype}>"

    # pickle support (DataLoader workers, block export): device buffers
    # travel as host numpy and are re-uploaded on unpickle
    def __getstate__(self):
        return {"data": self.asnumpy(), "grad_req": self._grad_req}

    def __setstate__(self, state):
        self._data = jnp.asarray(state["data"])
        self._grad = None
        self._grad_req = "null"
        self._fresh_grad_node = None
        self._version = 0
        if state.get("grad_req", "null") != "null":
            self.attach_grad(state["grad_req"])

    def __reduce__(self):
        return (_rebuild_ndarray, (self.__getstate__(),))

    # ------------------------------------------------------------------
    # arithmetic operators
    # ------------------------------------------------------------------
    def _binop(self, other, fn, name, reverse=False):
        if isinstance(other, (list, tuple, onp.ndarray)):
            other = _wrap(jnp.asarray(other))
        args = (other, self) if reverse else (self, other)
        return apply_op(fn, args, name=name)

    def __add__(self, o):
        return self._binop(o, operator.add, "add")

    def __radd__(self, o):
        return self._binop(o, operator.add, "add", reverse=True)

    def __sub__(self, o):
        return self._binop(o, operator.sub, "sub")

    def __rsub__(self, o):
        return self._binop(o, operator.sub, "sub", reverse=True)

    def __mul__(self, o):
        return self._binop(o, operator.mul, "mul")

    def __rmul__(self, o):
        return self._binop(o, operator.mul, "mul", reverse=True)

    def __truediv__(self, o):
        return self._binop(o, operator.truediv, "div")

    def __rtruediv__(self, o):
        return self._binop(o, operator.truediv, "div", reverse=True)

    def __mod__(self, o):
        return self._binop(o, operator.mod, "mod")

    def __rmod__(self, o):
        return self._binop(o, operator.mod, "mod", reverse=True)

    def __floordiv__(self, o):
        return self._binop(o, operator.floordiv, "floordiv")

    def __pow__(self, o):
        return self._binop(o, operator.pow, "pow")

    def __rpow__(self, o):
        return self._binop(o, operator.pow, "pow", reverse=True)

    def __matmul__(self, o):
        return self._binop(o, operator.matmul, "matmul")

    def __neg__(self):
        return apply_op(operator.neg, (self,), name="neg")

    def __abs__(self):
        return self.abs()

    # in-place operators rebind (engine write-dependency analog)
    def __iadd__(self, o):
        self._set_data(self._data + _unwrap(o))
        return self

    def __isub__(self, o):
        self._set_data(self._data - _unwrap(o))
        return self

    def __imul__(self, o):
        self._set_data(self._data * _unwrap(o))
        return self

    def __itruediv__(self, o):
        self._set_data(self._data / _unwrap(o))
        return self

    # comparisons (non-differentiable)
    def _cmp(self, other, fn, name):
        return apply_op(fn, (self, _coerce(other)), name=name)

    def __eq__(self, o):
        return self._cmp(o, lambda a, b: a == b, "eq")

    def __ne__(self, o):
        return self._cmp(o, lambda a, b: a != b, "ne")

    def __lt__(self, o):
        return self._cmp(o, lambda a, b: a < b, "lt")

    def __le__(self, o):
        return self._cmp(o, lambda a, b: a <= b, "le")

    def __gt__(self, o):
        return self._cmp(o, lambda a, b: a > b, "gt")

    def __ge__(self, o):
        return self._cmp(o, lambda a, b: a >= b, "ge")

    __hash__ = object.__hash__


def _coerce(x):
    if isinstance(x, (list, tuple, onp.ndarray)):
        return _wrap(jnp.asarray(x))
    return x


def _unwrap_index(key):
    if isinstance(key, ndarray):
        return key._data
    if isinstance(key, tuple):
        return tuple(_unwrap_index(k) for k in key)
    return key


def _rebuild_ndarray(state):
    out = ndarray.__new__(ndarray)
    out.__setstate__(state)
    return out


NDArray = ndarray


# -- fluent methods (reference numpy/multiarray.py) -------------------------
# The reference ndarray keeps a small set of REAL fluent delegations
# (multiarray.py:1733 sort, :1749 argsort, std/var/repeat/tile/nonzero,
# reshape_view, slice_assign*) and deliberately raises AttributeError for
# the legacy nd fluent surface (exp/log/relu/...) — absence here matches
# that contract exactly.

def _fluent(op_name):
    def method(self, *args, **kwargs):
        from .. import numpy as _np

        return getattr(_np, op_name)(self, *args, **kwargs)

    method.__name__ = op_name
    method.__doc__ = (f"Convenience fluent method for mx.np.{op_name} "
                      f"with this array as the first argument.")
    return method


for _name in ("sort", "argsort", "std", "var", "repeat", "tile", "nonzero"):
    setattr(ndarray, _name, _fluent(_name))


def _as_np_ndarray(self):
    return self


def _reshape_view(self, *shape):
    if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
        shape = tuple(shape[0])
    return self.reshape(shape)


def _slice_assign(self, rhs, begin, end, step=None):
    """Eager in-place region assign, returns self. Like ``__setitem__``
    (which it delegates to), this mutates and is therefore REJECTED on a
    grad-attached array inside ``autograd.record()`` — use
    ``npx.index_update`` for a functional, differentiable update."""
    step = step or [1] * len(begin)
    key = tuple(slice(b, e, s) for b, e, s in zip(begin, end, step))
    self[key] = rhs
    return self


ndarray.as_np_ndarray = _as_np_ndarray
ndarray.as_nd_ndarray = _as_np_ndarray  # unified array type on TPU
ndarray.reshape_view = _reshape_view
ndarray.slice_assign = _slice_assign
ndarray.slice_assign_scalar = _slice_assign


def array(obj, dtype=None, ctx=None, device=None) -> ndarray:
    return ndarray(obj, ctx=ctx or device, dtype=dtype)


# register as a pytree leaf container so jax.tree_util flattens through it
jax.tree_util.register_pytree_node(
    ndarray,
    lambda a: ((a._data,), None),
    lambda aux, children: _wrap(children[0]),
)
