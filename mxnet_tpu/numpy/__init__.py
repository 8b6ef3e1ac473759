"""``mx.np`` — the NumPy-compatible array API (the 2.0-native surface).

Parity target: reference ``python/mxnet/numpy/`` + the C++ kernels in
``src/operator/numpy/`` (~40k lines of CUDA/C++). On TPU every one of these
functions lowers to XLA through jax.numpy; autograd recording happens in
:func:`mxnet_tpu.ops.dispatch.apply_op`, so each call is differentiable and
trace-transparent (usable inside hybridized blocks).
"""
from __future__ import annotations

import builtins
from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as onp

from ..base import dtype_from_any, bfloat16, MXNetError
from ..context import Context, current_context
from ..ndarray.ndarray import ndarray, _wrap, _unwrap
from ..ops.dispatch import apply_op

from . import random  # noqa: E402  (submodule)
from . import linalg  # noqa: E402

newaxis = None
pi = onp.pi
e = onp.e
inf = onp.inf
nan = onp.nan
euler_gamma = onp.euler_gamma

float16 = onp.float16
float32 = onp.float32
float64 = onp.float64
int8 = onp.int8
int16 = onp.int16
int32 = onp.int32
int64 = onp.int64
uint8 = onp.uint8
uint16 = onp.uint16
uint32 = onp.uint32
uint64 = onp.uint64
bool_ = onp.bool_
dtype = onp.dtype
_np = onp


def _call(jfn, args, kwargs=None, name=None, n_out=1):
    kwargs = kwargs or {}
    args = list(args)
    arr_pos = [i for i, a in enumerate(args) if isinstance(a, ndarray)]
    arrays = [args[i] for i in arr_pos]

    def fn(*vals):
        full = list(args)
        for i, v in builtins.zip(arr_pos, vals):
            full[i] = v
        return jfn(*full, **kwargs)

    fn.__name__ = name or getattr(jfn, "__name__", "op")
    return apply_op(fn, arrays, name=fn.__name__, n_out=n_out)


def _seq_call(jfn, seq, kwargs=None, name=None):
    """Ops taking a sequence of arrays (concatenate/stack/...)."""
    kwargs = kwargs or {}
    seq = list(seq)

    def fn(*vals):
        return jfn(list(vals), **kwargs)

    fn.__name__ = name or getattr(jfn, "__name__", "op")
    return apply_op(fn, seq, name=fn.__name__)


# ---------------------------------------------------------------------------
# creation
# ---------------------------------------------------------------------------
def array(obj, dtype=None, ctx=None, device=None, copy=True):
    return ndarray(obj, ctx=ctx or device, dtype=dtype)


def _create(val, ctx=None):
    out = _wrap(val)
    if ctx is not None:
        out._data = jax.device_put(out._data, ctx.jax_device)
    return out


def zeros(shape, dtype=float32, ctx=None, device=None, order="C"):
    if isinstance(shape, int):
        shape = (shape,)
    return _create(jnp.zeros(shape, dtype_from_any(dtype)), ctx or device)


def ones(shape, dtype=float32, ctx=None, device=None, order="C"):
    if isinstance(shape, int):
        shape = (shape,)
    return _create(jnp.ones(shape, dtype_from_any(dtype)), ctx or device)


def empty(shape, dtype=float32, ctx=None, device=None, order="C"):
    return zeros(shape, dtype, ctx or device)


def full(shape, fill_value, dtype=None, ctx=None, device=None):
    if isinstance(shape, int):
        shape = (shape,)
    if isinstance(fill_value, ndarray):
        return _call(lambda f: jnp.full(shape, f, dtype and dtype_from_any(dtype)), (fill_value,), name="full")
    return _create(jnp.full(shape, fill_value, dtype and dtype_from_any(dtype)), ctx or device)


def zeros_like(a, dtype=None):
    return _call(lambda x: jnp.zeros_like(x, dtype and dtype_from_any(dtype)), (a,), name="zeros_like")


def ones_like(a, dtype=None):
    return _call(lambda x: jnp.ones_like(x, dtype and dtype_from_any(dtype)), (a,), name="ones_like")


def full_like(a, fill_value, dtype=None):
    return _call(lambda x: jnp.full_like(x, fill_value, dtype and dtype_from_any(dtype)), (a,), name="full_like")


def arange(start, stop=None, step=1, dtype=None, ctx=None, device=None):
    return _create(jnp.arange(start, stop, step, dtype and dtype_from_any(dtype)), ctx or device)


def linspace(start, stop, num=50, endpoint=True, retstep=False, dtype=None, axis=0, ctx=None):
    out = jnp.linspace(start, stop, num, endpoint=endpoint, retstep=retstep, dtype=dtype and dtype_from_any(dtype), axis=axis)
    if retstep:
        return _create(out[0], ctx), out[1]
    return _create(out, ctx)


def logspace(start, stop, num=50, endpoint=True, base=10.0, dtype=None, ctx=None):
    return _create(jnp.logspace(start, stop, num, endpoint, base, dtype and dtype_from_any(dtype)), ctx)


def eye(N, M=None, k=0, dtype=float32, ctx=None):
    return _create(jnp.eye(N, M, k, dtype_from_any(dtype)), ctx)


def identity(n, dtype=float32, ctx=None):
    return eye(n, dtype=dtype, ctx=ctx)


def meshgrid(*xi, indexing="xy"):
    outs = jnp.meshgrid(*[_unwrap(x) for x in xi], indexing=indexing)
    return [_wrap(o) for o in outs]


def copy(a):
    return _call(lambda x: x + 0 if onp.issubdtype(onp.dtype(x.dtype), onp.number) else jnp.array(x), (a,), name="copy")


def ascontiguousarray(a, dtype=None):
    return asarray(a, dtype)


def asarray(a, dtype=None, ctx=None):
    if isinstance(a, ndarray):
        return a.astype(dtype, copy=False) if dtype is not None else a
    return ndarray(a, ctx=ctx, dtype=dtype)


def frombuffer(buffer, dtype=float32, count=-1, offset=0):
    return _create(jnp.asarray(
        onp.frombuffer(buffer, onp.dtype(dtype), count, offset)))


def tril(m, k=0):
    return _call(lambda x: jnp.tril(x, k), (m,), name="tril")


def triu(m, k=0):
    return _call(lambda x: jnp.triu(x, k), (m,), name="triu")


def diag(v, k=0):
    return _call(lambda x: jnp.diag(x, k), (v,), name="diag")


def diagonal(a, offset=0, axis1=0, axis2=1):
    return _call(lambda x: jnp.diagonal(x, offset, axis1, axis2), (a,), name="diagonal")


def tri(N, M=None, k=0, dtype=float32, ctx=None):
    return _create(jnp.tri(N, M, k, dtype_from_any(dtype)), ctx)


# ---------------------------------------------------------------------------
# elementwise unary — generated
# ---------------------------------------------------------------------------
def _unary(jfn, pyname):
    def op(x, out=None, **kw):
        res = _call(jfn, (x,), kw, name=pyname)
        if out is not None:
            out._set_data(res._data)
            return out
        return res

    op.__name__ = pyname
    return op


_UNARY = [
    "abs", "absolute", "exp", "expm1", "log", "log2", "log10", "log1p",
    "sqrt", "cbrt", "square", "sin", "cos", "tan", "arcsin", "arccos",
    "arctan", "sinh", "cosh", "tanh", "arcsinh", "arccosh", "arctanh",
    "sign", "floor", "ceil", "trunc", "rint", "reciprocal", "negative",
    "positive", "logical_not", "isnan", "isinf", "isfinite", "isneginf",
    "isposinf", "invert", "degrees", "radians", "deg2rad", "rad2deg",
    "conj", "conjugate", "real", "imag", "angle", "exp2", "signbit",
    "nan_to_num",
]
for _n in _UNARY:
    globals()[_n] = _unary(getattr(jnp, _n), _n)

fix = _unary(jnp.trunc, "fix")

fabs = globals()["abs"]


def round(x, decimals=0):
    return _call(lambda v: jnp.round(v, decimals), (x,), name="round")


around = round
round_ = round


def erf(x):
    return _call(jax.scipy.special.erf, (x,), name="erf")


def erfinv(x):
    return _call(jax.scipy.special.erfinv, (x,), name="erfinv")


def gamma_fn(x):
    return _call(jax.scipy.special.gamma, (x,), name="gamma")


def gammaln(x):
    return _call(jax.scipy.special.gammaln, (x,), name="gammaln")


def sigmoid(x):
    return _call(jax.nn.sigmoid, (x,), name="sigmoid")


def relu(x):
    return _call(jax.nn.relu, (x,), name="relu")


# ---------------------------------------------------------------------------
# elementwise binary — generated
# ---------------------------------------------------------------------------
def _binary(jfn, pyname):
    def op(a, b, out=None, **kw):
        res = _call(jfn, (_c(a), _c(b)), kw, name=pyname)
        if out is not None:
            out._set_data(res._data)
            return out
        return res

    op.__name__ = pyname
    return op


def _c(x):
    if isinstance(x, (list, tuple, onp.ndarray)):
        return _wrap(jnp.asarray(x))
    return x


_BINARY = [
    "add", "subtract", "multiply", "divide", "true_divide", "floor_divide",
    "mod", "remainder", "fmod", "power", "float_power", "maximum", "minimum",
    "fmax", "fmin", "arctan2", "hypot", "copysign", "logaddexp", "logaddexp2",
    "logical_and", "logical_or", "logical_xor", "bitwise_and", "bitwise_or",
    "bitwise_xor", "left_shift", "right_shift", "equal", "not_equal", "less",
    "less_equal", "greater", "greater_equal", "gcd", "lcm", "heaviside",
    "ldexp", "nextafter",
]
for _n in _BINARY:
    globals()[_n] = _binary(getattr(jnp, _n), _n)

bitwise_not = globals()["invert"]
bitwise_left_shift = globals()["left_shift"]
bitwise_right_shift = globals()["right_shift"]
pow = globals()["power"]


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------
def _reduction(jfn, pyname):
    def op(a, axis=None, dtype=None, keepdims=False, out=None, **kw):
        kwargs = dict(axis=axis, keepdims=keepdims, **kw)
        if dtype is not None:
            kwargs["dtype"] = dtype_from_any(dtype)
        res = _call(lambda x: jfn(x, **kwargs), (a,), name=pyname)
        if out is not None:
            out._set_data(res._data)
            return out
        return res

    op.__name__ = pyname
    return op


for _n in ["sum", "prod", "nansum", "nanprod"]:
    globals()[_n] = _reduction(getattr(jnp, _n), _n)


def _reduction_nodtype(jfn, pyname):
    def op(a, axis=None, keepdims=False, out=None, **kw):
        res = _call(lambda x: jfn(x, axis=axis, keepdims=keepdims, **kw), (a,), name=pyname)
        if out is not None:
            out._set_data(res._data)
            return out
        return res

    op.__name__ = pyname
    return op


for _n in ["mean", "max", "min", "amax", "amin", "nanmax", "nanmin", "nanmean", "median", "all", "any"]:
    globals()[_n] = _reduction_nodtype(getattr(jnp, _n), _n)


def std(a, axis=None, dtype=None, ddof=0, keepdims=False):
    return _call(lambda x: jnp.std(x, axis=axis, ddof=ddof, keepdims=keepdims), (a,), name="std")


def var(a, axis=None, dtype=None, ddof=0, keepdims=False):
    return _call(lambda x: jnp.var(x, axis=axis, ddof=ddof, keepdims=keepdims), (a,), name="var")


def average(a, axis=None, weights=None, returned=False):
    if weights is None:
        return globals()["mean"](a, axis=axis)
    return _call(lambda x, w: jnp.average(x, axis=axis, weights=w), (a, _c(weights)), name="average")


def ptp(a, axis=None, keepdims=False):
    return _call(lambda x: jnp.ptp(x, axis=axis, keepdims=keepdims), (a,), name="ptp")


def argmax(a, axis=None):
    return _call(lambda x: jnp.argmax(x, axis=axis), (a,), name="argmax")


def argmin(a, axis=None):
    return _call(lambda x: jnp.argmin(x, axis=axis), (a,), name="argmin")


def nanargmax(a, axis=None):
    return _call(lambda x: jnp.nanargmax(x, axis=axis), (a,), name="nanargmax")


def nanargmin(a, axis=None):
    return _call(lambda x: jnp.nanargmin(x, axis=axis), (a,), name="nanargmin")


def cumsum(a, axis=None, dtype=None):
    return _call(lambda x: jnp.cumsum(x, axis=axis, dtype=dtype and dtype_from_any(dtype)), (a,), name="cumsum")


def cumprod(a, axis=None, dtype=None):
    return _call(lambda x: jnp.cumprod(x, axis=axis, dtype=dtype and dtype_from_any(dtype)), (a,), name="cumprod")


def count_nonzero(a, axis=None):
    return _call(lambda x: jnp.count_nonzero(x, axis=axis), (a,), name="count_nonzero")


def percentile(a, q, axis=None, interpolation="linear", keepdims=False):
    return _call(lambda x: jnp.percentile(x, q, axis=axis, method=interpolation, keepdims=keepdims), (a,), name="percentile")


def quantile(a, q, axis=None, interpolation="linear", keepdims=False):
    return _call(lambda x: jnp.quantile(x, q, axis=axis, method=interpolation, keepdims=keepdims), (a,), name="quantile")


def bincount(x, weights=None, minlength=0):
    if weights is None:
        return _call(lambda v: jnp.bincount(v, minlength=minlength), (x,), name="bincount")
    return _call(lambda v, w: jnp.bincount(v, w, minlength=minlength), (x, _c(weights)), name="bincount")


def histogram(a, bins=10, range=None, weights=None, density=None):
    h, edges = onp.histogram(_to_np(a), bins=_to_np(bins) if isinstance(bins, ndarray) else bins, range=range, weights=_to_np(weights) if weights is not None else None, density=density)
    return _wrap(jnp.asarray(h)), _wrap(jnp.asarray(edges))


def _to_np(a):
    return a.asnumpy() if isinstance(a, ndarray) else onp.asarray(a)


# ---------------------------------------------------------------------------
# linear algebra (top-level)
# ---------------------------------------------------------------------------
def dot(a, b, out=None):
    res = _call(jnp.dot, (_c(a), _c(b)), name="dot")
    if out is not None:
        out._set_data(res._data)
        return out
    return res


def matmul(a, b):
    return _call(jnp.matmul, (_c(a), _c(b)), name="matmul")


def inner(a, b):
    return _call(jnp.inner, (_c(a), _c(b)), name="inner")


def outer(a, b):
    return _call(jnp.outer, (_c(a), _c(b)), name="outer")


def vdot(a, b):
    return _call(jnp.vdot, (_c(a), _c(b)), name="vdot")


def cross(a, b, axis=-1):
    return _call(lambda x, y: jnp.cross(x, y, axis=axis), (_c(a), _c(b)), name="cross")


def kron(a, b):
    return _call(jnp.kron, (_c(a), _c(b)), name="kron")


def tensordot(a, b, axes=2):
    return _call(lambda x, y: jnp.tensordot(x, y, axes=axes), (_c(a), _c(b)), name="tensordot")


def einsum(subscripts, *operands, **kwargs):
    return _call(lambda *ops: jnp.einsum(subscripts, *ops), [_c(o) for o in operands], name="einsum")


def trace(a, offset=0, axis1=0, axis2=1):
    return _call(lambda x: jnp.trace(x, offset, axis1, axis2), (a,), name="trace")


def interp(x, xp, fp, left=None, right=None):
    return _call(lambda a, b, c: jnp.interp(a, b, c, left=left, right=right), (_c(x), _c(xp), _c(fp)), name="interp")


def convolve(a, v, mode="full"):
    return _call(lambda x, y: jnp.convolve(x, y, mode=mode), (_c(a), _c(v)), name="convolve")


def astype(a, dtype):
    """Functional dtype cast (array-API style; ndarray.astype's twin)."""
    dt = dtype_from_any(dtype)
    return _call(lambda x: x.astype(dt), (a,), name="astype")


def clip(a, a_min=None, a_max=None, out=None):
    res = _call(lambda x: jnp.clip(x, a_min, a_max), (a,), name="clip")
    if out is not None:
        out._set_data(res._data)
        return out
    return res


# ---------------------------------------------------------------------------
# shape manipulation
# ---------------------------------------------------------------------------
def reshape(a, newshape, order="C"):
    return _call(lambda x: jnp.reshape(x, newshape), (a,), name="reshape")


def transpose(a, axes=None):
    return _call(lambda x: jnp.transpose(x, axes), (a,), name="transpose")


def permute_dims(a, axes=None):
    return transpose(a, axes)


def swapaxes(a, axis1, axis2):
    return _call(lambda x: jnp.swapaxes(x, axis1, axis2), (a,), name="swapaxes")


def moveaxis(a, source, destination):
    return _call(lambda x: jnp.moveaxis(x, source, destination), (a,), name="moveaxis")


def rollaxis(a, axis, start=0):
    return _call(lambda x: jnp.rollaxis(x, axis, start), (a,), name="rollaxis")


def expand_dims(a, axis):
    return _call(lambda x: jnp.expand_dims(x, axis), (a,), name="expand_dims")


def squeeze(a, axis=None):
    return _call(lambda x: jnp.squeeze(x, axis), (a,), name="squeeze")


def ravel(a, order="C"):
    return _call(jnp.ravel, (a,), name="ravel")


def flatten(a):
    return ravel(a)


def broadcast_to(a, shape):
    return _call(lambda x: jnp.broadcast_to(x, tuple(shape)), (a,), name="broadcast_to")


def broadcast_arrays(*args):
    outs = jnp.broadcast_arrays(*[_unwrap(_c(a)) for a in args])
    return [_wrap(o) for o in outs]


def atleast_1d(*arys):
    outs = [_call(jnp.atleast_1d, (_c(a),), name="atleast_1d") for a in arys]
    return outs[0] if len(outs) == 1 else outs


def atleast_2d(*arys):
    outs = [_call(jnp.atleast_2d, (_c(a),), name="atleast_2d") for a in arys]
    return outs[0] if len(outs) == 1 else outs


def atleast_3d(*arys):
    outs = [_call(jnp.atleast_3d, (_c(a),), name="atleast_3d") for a in arys]
    return outs[0] if len(outs) == 1 else outs


def concatenate(seq, axis=0, out=None):
    res = _seq_call(lambda vs: jnp.concatenate(vs, axis=axis), [_c(s) for s in seq], name="concatenate")
    if out is not None:
        out._set_data(res._data)
        return out
    return res


concat = concatenate


def stack(seq, axis=0, out=None):
    res = _seq_call(lambda vs: jnp.stack(vs, axis=axis), [_c(s) for s in seq], name="stack")
    if out is not None:
        out._set_data(res._data)
        return out
    return res


def vstack(seq):
    return _seq_call(jnp.vstack, [_c(s) for s in seq], name="vstack")


def hstack(seq):
    return _seq_call(jnp.hstack, [_c(s) for s in seq], name="hstack")


def dstack(seq):
    return _seq_call(jnp.dstack, [_c(s) for s in seq], name="dstack")


def column_stack(seq):
    return _seq_call(jnp.column_stack, [_c(s) for s in seq], name="column_stack")


def append(arr, values, axis=None):
    return _call(lambda a, v: jnp.append(a, v, axis=axis), (_c(arr), _c(values)), name="append")


def split(a, indices_or_sections, axis=0):
    a = _c(a)
    vals = jnp.split(_unwrap(a), indices_or_sections, axis=axis)
    n = len(vals)

    def fn(x):
        return tuple(jnp.split(x, indices_or_sections, axis=axis))

    return list(apply_op(fn, (a,), n_out=n, name="split"))


def array_split(a, indices_or_sections, axis=0):
    a = _c(a)
    vals = jnp.array_split(_unwrap(a), indices_or_sections, axis=axis)
    n = len(vals)

    def fn(x):
        return tuple(jnp.array_split(x, indices_or_sections, axis=axis))

    return list(apply_op(fn, (a,), n_out=n, name="array_split"))


def hsplit(a, i):
    return split(a, i, axis=1 if _c(a).ndim > 1 else 0)


def vsplit(a, i):
    return split(a, i, axis=0)


def dsplit(a, i):
    return split(a, i, axis=2)


def tile(a, reps):
    return _call(lambda x: jnp.tile(x, reps), (_c(a),), name="tile")


def repeat(a, repeats, axis=None):
    return _call(lambda x: jnp.repeat(x, repeats, axis=axis), (_c(a),), name="repeat")


def flip(a, axis=None):
    return _call(lambda x: jnp.flip(x, axis), (a,), name="flip")


def fliplr(a):
    return _call(jnp.fliplr, (a,), name="fliplr")


def flipud(a):
    return _call(jnp.flipud, (a,), name="flipud")


def roll(a, shift, axis=None):
    return _call(lambda x: jnp.roll(x, shift, axis), (a,), name="roll")


def rot90(a, k=1, axes=(0, 1)):
    return _call(lambda x: jnp.rot90(x, k, axes), (a,), name="rot90")


def pad(a, pad_width, mode="constant", **kwargs):
    return _call(lambda x: jnp.pad(x, pad_width, mode=mode, **kwargs), (a,), name="pad")


def resize(a, new_shape):
    return _call(lambda x: jnp.resize(x, new_shape), (a,), name="resize")


def delete(arr, obj, axis=None):
    return _call(lambda x: jnp.delete(x, obj, axis=axis), (arr,), name="delete")


def insert(arr, obj, values, axis=None):
    return _call(lambda x, v: jnp.insert(x, obj, v, axis=axis), (arr, _c(values)), name="insert")


def trim_zeros(filt, trim="fb"):
    return _wrap(jnp.asarray(onp.trim_zeros(_to_np(filt), trim)))


# ---------------------------------------------------------------------------
# indexing / searching / sorting
# ---------------------------------------------------------------------------
def where(condition, x=None, y=None):
    if x is None and y is None:
        return nonzero(condition)
    return _call(jnp.where, (_c(condition), _c(x), _c(y)), name="where")


def nonzero(a):
    vals = jnp.nonzero(_unwrap(_c(a)))
    return tuple(_wrap(v) for v in vals)


def flatnonzero(a):
    return _wrap(jnp.flatnonzero(_unwrap(_c(a))))


def take(a, indices, axis=None, mode="clip"):
    return _call(
        lambda x, i: jnp.take(x, i, axis=axis, mode="clip" if mode == "clip" else "wrap"),
        (_c(a), _c(indices)),
        name="take",
    )


def take_along_axis(a, indices, axis):
    return _call(lambda x, i: jnp.take_along_axis(x, i, axis=axis), (_c(a), _c(indices)), name="take_along_axis")


def put_along_axis(a, indices, values, axis):
    res = _call(
        lambda x, i, v: jnp.put_along_axis(x, i, v, axis=axis, inplace=False),
        (_c(a), _c(indices), _c(values)),
        name="put_along_axis",
    )
    a._set_data(res._data)
    return a


def compress(condition, a, axis=None):
    return _wrap(jnp.compress(_unwrap(_c(condition)), _unwrap(_c(a)), axis=axis))


def extract(condition, arr):
    return _wrap(jnp.extract(_unwrap(_c(condition)), _unwrap(_c(arr))))


def sort(a, axis=-1, kind=None, order=None):
    return _call(lambda x: jnp.sort(x, axis=axis), (a,), name="sort")


def argsort(a, axis=-1, kind=None, order=None):
    return _call(lambda x: jnp.argsort(x, axis=axis), (a,), name="argsort")


def lexsort(keys, axis=-1):
    return _wrap(jnp.lexsort([_unwrap(_c(k)) for k in keys], axis=axis))


def partition(a, kth, axis=-1):
    return _call(lambda x: jnp.partition(x, kth, axis=axis), (a,), name="partition")


def argpartition(a, kth, axis=-1):
    return _call(lambda x: jnp.argpartition(x, kth, axis=axis), (a,), name="argpartition")


def searchsorted(a, v, side="left", sorter=None):
    return _call(lambda x, q: jnp.searchsorted(x, q, side=side), (_c(a), _c(v)), name="searchsorted")


def unique(ar, return_index=False, return_inverse=False, return_counts=False, axis=None):
    out = onp.unique(_to_np(ar), return_index=return_index, return_inverse=return_inverse, return_counts=return_counts, axis=axis)
    if isinstance(out, tuple):
        return tuple(_wrap(jnp.asarray(o)) for o in out)
    return _wrap(jnp.asarray(out))


def digitize(x, bins, right=False):
    return _wrap(jnp.digitize(_unwrap(_c(x)), _unwrap(_c(bins)), right=right))


def indices(dimensions, dtype=int32, ctx=None):
    return _create(jnp.indices(dimensions, dtype_from_any(dtype)), ctx)


def unravel_index(indices_, shape):
    outs = jnp.unravel_index(_unwrap(_c(indices_)), shape)
    return tuple(_wrap(o) for o in outs)


def ravel_multi_index(multi_index, dims, mode="clip"):
    return _wrap(jnp.ravel_multi_index(tuple(_unwrap(_c(i)) for i in multi_index), dims, mode="clip"))


def diff(a, n=1, axis=-1):
    return _call(lambda x: jnp.diff(x, n=n, axis=axis), (a,), name="diff")


def ediff1d(ary, to_end=None, to_begin=None):
    return _call(lambda x: jnp.ediff1d(x, to_end=to_end, to_begin=to_begin), (_c(ary),), name="ediff1d")


def gradient(f, *varargs, axis=None):
    outs = jnp.gradient(_unwrap(_c(f)), *varargs, axis=axis)
    if isinstance(outs, (list, tuple)):
        return [_wrap(o) for o in outs]
    return _wrap(outs)


def trapz(y, x=None, dx=1.0, axis=-1):
    if x is not None:
        return _call(lambda a, b: jnp.trapezoid(a, b, axis=axis), (_c(y), _c(x)), name="trapz")
    return _call(lambda a: jnp.trapezoid(a, dx=dx, axis=axis), (_c(y),), name="trapz")


# ---------------------------------------------------------------------------
# logic
# ---------------------------------------------------------------------------
def isclose(a, b, rtol=1e-05, atol=1e-08, equal_nan=False):
    return _call(lambda x, y: jnp.isclose(x, y, rtol, atol, equal_nan), (_c(a), _c(b)), name="isclose")


def allclose(a, b, rtol=1e-05, atol=1e-08, equal_nan=False):
    return builtins.bool(jnp.allclose(_unwrap(_c(a)), _unwrap(_c(b)), rtol, atol, equal_nan))


def array_equal(a1, a2, equal_nan=False):
    return builtins.bool(jnp.array_equal(_unwrap(_c(a1)), _unwrap(_c(a2)), equal_nan))


def array_equiv(a1, a2):
    return builtins.bool(jnp.array_equiv(_unwrap(_c(a1)), _unwrap(_c(a2))))


def isscalar(x):
    return onp.isscalar(x)


def iscomplexobj(x):
    return onp.iscomplexobj(_to_np(x) if isinstance(x, ndarray) else x)


def isrealobj(x):
    return not iscomplexobj(x)


def result_type(*arrays_and_dtypes):
    args = [a.dtype if isinstance(a, ndarray) else a for a in arrays_and_dtypes]
    return jnp.result_type(*args)


def promote_types(t1, t2):
    return jnp.promote_types(t1, t2)


def can_cast(from_, to):
    return onp.can_cast(from_, to)


def shape(a):
    return _c(a).shape if isinstance(_c(a), ndarray) else onp.shape(a)


def ndim(a):
    return _c(a).ndim if isinstance(_c(a), ndarray) else onp.ndim(a)


def size(a, axis=None):
    if isinstance(a, ndarray):
        return a.size if axis is None else a.shape[axis]
    return onp.size(a, axis)


def may_share_memory(a, b):
    return False  # functional arrays never alias


def shares_memory(a, b):
    return False


def get_include():
    return onp.get_include()


# ---------------------------------------------------------------------------
# numpy parity tail: statistics, set ops, index builders, polynomials
# (reference src/operator/numpy/ covers these via dedicated kernels; here
# they lower through jnp/XLA like everything else)
# ---------------------------------------------------------------------------
def cov(m, y=None, rowvar=True, bias=False, ddof=None):
    if y is None:
        return _call(lambda a: jnp.cov(a, rowvar=rowvar, bias=bias,
                                       ddof=ddof), (_c(m),), name="cov")
    return _call(lambda a, b: jnp.cov(a, b, rowvar=rowvar, bias=bias,
                                      ddof=ddof), (_c(m), _c(y)), name="cov")


def corrcoef(x, y=None, rowvar=True):
    if y is None:
        return _call(lambda a: jnp.corrcoef(a, rowvar=rowvar), (_c(x),),
                     name="corrcoef")
    return _call(lambda a, b: jnp.corrcoef(a, b, rowvar=rowvar),
                 (_c(x), _c(y)), name="corrcoef")


def isin(element, test_elements, invert=False):
    return _call(lambda a, b: jnp.isin(a, b, invert=invert),
                 (_c(element), _c(test_elements)), name="isin")


def in1d(ar1, ar2, assume_unique=False, invert=False):
    # assume_unique accepted for numpy signature compat (no-op here)
    return isin(_c(ar1), _c(ar2), invert=invert).reshape(-1)


def union1d(ar1, ar2):
    """EAGER-ONLY (data-dependent output size, like the reference's
    dynamic-shape set kernels)."""
    return _wrap(jnp.asarray(onp.union1d(
        onp.asarray(_unwrap(_c(ar1))), onp.asarray(_unwrap(_c(ar2))))))


def intersect1d(ar1, ar2, assume_unique=False, return_indices=False):
    """EAGER-ONLY (data-dependent output size)."""
    res = onp.intersect1d(onp.asarray(_unwrap(_c(ar1))),
                          onp.asarray(_unwrap(_c(ar2))),
                          assume_unique=assume_unique,
                          return_indices=return_indices)
    if return_indices:
        return tuple(_wrap(jnp.asarray(r)) for r in res)
    return _wrap(jnp.asarray(res))


def setdiff1d(ar1, ar2, assume_unique=False):
    """EAGER-ONLY (data-dependent output size)."""
    return _wrap(jnp.asarray(onp.setdiff1d(
        onp.asarray(_unwrap(_c(ar1))), onp.asarray(_unwrap(_c(ar2))),
        assume_unique=assume_unique)))


def select(condlist, choicelist, default=0):
    n = len(condlist)

    def fn(*vals):
        return jnp.select(list(vals[:n]), list(vals[n:]), default)

    fn.__name__ = "select"
    return apply_op(fn, [_c(x) for x in condlist]
                    + [_c(x) for x in choicelist], name="select")


def piecewise(x, condlist, funclist):
    def fn(xv, *conds):
        return jnp.piecewise(xv, list(conds), funclist)

    fn.__name__ = "piecewise"
    return apply_op(fn, [_c(x)] + [_c(ci) for ci in condlist],
                    name="piecewise")


def polyval(p, x):
    return _call(lambda pp, xx: jnp.polyval(pp, xx), (_c(p), _c(x)),
                 name="polyval")


def polyfit(x, y, deg):
    return _call(lambda a, b: jnp.polyfit(a, b, deg), (_c(x), _c(y)),
                 name="polyfit")


def vander(x, N=None, increasing=False):
    return _call(lambda v: jnp.vander(v, N=N, increasing=increasing),
                 (_c(x),), name="vander")


def row_stack(tup):
    return vstack(tup)


def tril_indices(n, k=0, m=None):
    r, c = onp.tril_indices(n, k=k, m=m)
    return _wrap(jnp.asarray(r)), _wrap(jnp.asarray(c))


def triu_indices(n, k=0, m=None):
    r, c = onp.triu_indices(n, k=k, m=m)
    return _wrap(jnp.asarray(r)), _wrap(jnp.asarray(c))


def tril_indices_from(arr, k=0):
    return tril_indices(arr.shape[-2], k=k, m=arr.shape[-1])


def triu_indices_from(arr, k=0):
    return triu_indices(arr.shape[-2], k=k, m=arr.shape[-1])


def ix_(*args):
    return tuple(_wrap(jnp.asarray(g))
                 for g in onp.ix_(*[onp.asarray(_unwrap(_c(a)))
                                    for a in args]))


def fromfunction(function, shape, dtype=float, **kwargs):
    grids = onp.indices(shape).astype(dtype)
    return _wrap(jnp.asarray(function(*grids, **kwargs)))


def empty_like(prototype, dtype=None, order="K", device=None):
    p = _c(prototype)
    return _wrap(jnp.zeros(p.shape, dtype or p.dtype))


def apply_along_axis(func1d, axis, arr, *args, **kwargs):
    return _call(
        lambda a: jnp.apply_along_axis(func1d, axis, a, *args, **kwargs),
        (_c(arr),), name="apply_along_axis")


from . import fft  # noqa: E402  (needs _call, so imported last)


# ---------------------------------------------------------------------------
# numpy parity: generated delegations (aliases, windows, nan-reductions,
# polynomials, dtype taxonomy, printing). Differentiable ops go through
# _call (tape-recorded); meta/dtype utilities pass straight to numpy.
# ---------------------------------------------------------------------------
_SIMPLE_UNARY_TAIL = [
    "sinc", "i0", "unwrap", "diagflat", "argwhere", "iscomplex", "isreal",
    "nancumprod", "nancumsum", "nanmedian", "nanstd", "nanvar",
    "sort_complex", "matrix_transpose", "spacing",
]
for _n in _SIMPLE_UNARY_TAIL:
    def _mk_tail(name):
        jfn = getattr(jnp, name)

        def op(a, *args, **kwargs):
            return _call(lambda x: jfn(x, *args, **kwargs), (_c(a),),
                         name=name)

        op.__name__ = name
        return op
    globals()[_n] = _mk_tail(_n)

# trig aliases (array-api names)
acos, acosh, asin = globals()["arccos"], globals()["arccosh"], globals()["arcsin"]
asinh, atan, atanh = globals()["arcsinh"], globals()["arctan"], globals()["arctanh"]
atan2 = globals()["arctan2"] if "arctan2" in globals() else None
bitwise_invert = globals()["invert"]


def vecdot(x1, x2, axis=-1):
    return _call(lambda a, b: jnp.vecdot(a, b, axis=axis), (_c(x1), _c(x2)),
                 name="vecdot")


def correlate(a, v, mode="valid"):
    return _call(lambda x, y: jnp.correlate(x, y, mode=mode),
                 (_c(a), _c(v)), name="correlate")


def nanpercentile(a, q, axis=None, keepdims=False):
    return _call(lambda x: jnp.nanpercentile(x, q, axis=axis,
                                             keepdims=keepdims), (_c(a),),
                 name="nanpercentile")


def nanquantile(a, q, axis=None, keepdims=False):
    return _call(lambda x: jnp.nanquantile(x, q, axis=axis,
                                           keepdims=keepdims), (_c(a),),
                 name="nanquantile")


trapezoid = trapz  # array-api name for the pre-existing trapz


def divmod(x1, x2):  # noqa: A001
    return _call(lambda a, b: jnp.divmod(a, b), (_c(x1), _c(x2)),
                 name="divmod", n_out=2)


def modf(x):
    return _call(lambda a: jnp.modf(a), (_c(x),), name="modf", n_out=2)


def frexp(x):
    return _call(lambda a: jnp.frexp(a), (_c(x),), name="frexp", n_out=2)


def geomspace(start, stop, num=50, endpoint=True, dtype=None, ctx=None):
    return _create(jnp.geomspace(start, stop, num, endpoint=endpoint,
                                 dtype=dtype and dtype_from_any(dtype)), ctx)


# window functions
for _n in ("bartlett", "blackman", "hamming", "hanning", "kaiser"):
    def _mk_window(name):
        jfn = getattr(jnp, name)

        def op(*args):
            return _wrap(jfn(*args))

        op.__name__ = name
        return op
    globals()[_n] = _mk_window(_n)


# polynomial family (differentiable where coefficient arrays flow through)
def polyadd(a1, a2):
    return _call(lambda a, b: jnp.polyadd(a, b), (_c(a1), _c(a2)),
                 name="polyadd")


def polysub(a1, a2):
    return _call(lambda a, b: jnp.polysub(a, b), (_c(a1), _c(a2)),
                 name="polysub")


def polymul(a1, a2):
    return _call(lambda a, b: jnp.polymul(a, b), (_c(a1), _c(a2)),
                 name="polymul")


def polyder(p, m=1):
    return _call(lambda a: jnp.polyder(a, m), (_c(p),), name="polyder")


def polyint(p, m=1, k=None):
    return _call(lambda a: jnp.polyint(a, m, k), (_c(p),), name="polyint")


def polydiv(u, v):
    return _call(lambda a, b: jnp.polydiv(a, b), (_c(u), _c(v)),
                 name="polydiv", n_out=2)


def poly(seq_of_zeros):
    return _call(lambda a: jnp.poly(a), (_c(seq_of_zeros),), name="poly")


def roots(p):
    """EAGER-ONLY (leading-zero stripping is data-dependent)."""
    return _wrap(jnp.roots(_unwrap(_c(p)), strip_zeros=True))


def block(arrays):
    """Assemble an array from nested lists of blocks — differentiable:
    the leaf arrays are tape inputs, the nesting is static structure."""
    leaves = []

    def template(a):
        if isinstance(a, list):
            return [template(x) for x in a]
        leaves.append(_c(a))
        return len(leaves) - 1

    tmpl = template(arrays)

    def fn(*vals):
        def rebuild(t):
            if isinstance(t, list):
                return [rebuild(x) for x in t]
            return vals[t]

        return jnp.block(rebuild(tmpl))

    return apply_op(fn, leaves, name="block")


def choose(a, choices, mode="raise"):
    """numpy-default mode='raise' validates indices (works eagerly; use
    mode='clip'/'wrap' inside traced code)."""
    seq_leaves = [_c(c) for c in choices]

    def fn(idx, *cs):
        return jnp.choose(idx, list(cs), mode=mode)

    return apply_op(fn, [_c(a)] + seq_leaves, name="choose")


def fill_diagonal(a, val, wrap=False):
    return _call(lambda x: jnp.fill_diagonal(x, val, wrap=wrap,
                                             inplace=False), (_c(a),),
                 name="fill_diagonal")


def setxor1d(ar1, ar2, assume_unique=False):
    """EAGER-ONLY (data-dependent output size)."""
    return _wrap(jnp.asarray(onp.setxor1d(
        onp.asarray(_unwrap(_c(ar1))), onp.asarray(_unwrap(_c(ar2))),
        assume_unique=assume_unique)))


def histogram2d(x, y, bins=10, range=None, weights=None, density=None):
    h, ex, ey = jnp.histogram2d(_unwrap(_c(x)), _unwrap(_c(y)), bins=bins,
                                range=range, density=density,
                                weights=None if weights is None
                                else _unwrap(_c(weights)))
    return _wrap(h), _wrap(ex), _wrap(ey)


def histogram_bin_edges(a, bins=10, range=None, weights=None):
    return _wrap(jnp.histogram_bin_edges(_unwrap(_c(a)), bins=bins,
                                         range=range))


def diag_indices(n, ndim=2):
    return tuple(_wrap(g) for g in jnp.diag_indices(n, ndim))


def diag_indices_from(arr):
    return diag_indices(arr.shape[0], arr.ndim)


def mask_indices(n, mask_func, k=0):
    r, c = onp.mask_indices(n, mask_func, k)
    return _wrap(jnp.asarray(r)), _wrap(jnp.asarray(c))


def unique_values(x):
    """EAGER-ONLY (data-dependent output size)."""
    return _wrap(jnp.asarray(onp.unique(onp.asarray(_unwrap(_c(x))))))


def unique_counts(x):
    v, c = onp.unique(onp.asarray(_unwrap(_c(x))), return_counts=True)
    return _wrap(jnp.asarray(v)), _wrap(jnp.asarray(c))


def unique_inverse(x):
    v, i = onp.unique(onp.asarray(_unwrap(_c(x))), return_inverse=True)
    return _wrap(jnp.asarray(v)), _wrap(jnp.asarray(i))


def unique_all(x):
    v, idx, inv, cnt = onp.unique(onp.asarray(_unwrap(_c(x))),
                                  return_index=True, return_inverse=True,
                                  return_counts=True)
    return tuple(_wrap(jnp.asarray(t)) for t in (v, idx, inv, cnt))


def broadcast_shapes(*shapes):
    return onp.broadcast_shapes(*shapes)


def einsum_path(*operands, optimize="greedy"):
    ops = [_unwrap(_c(o)) if not isinstance(o, str) else o for o in operands]
    return jnp.einsum_path(*ops, optimize=optimize)


def vectorize(pyfunc, excluded=None, signature=None):
    vf = jnp.vectorize(pyfunc, excluded=excluded or frozenset(),
                       signature=signature)

    def wrapped(*args):
        return _call(lambda *vals: vf(*vals),
                     tuple(_c(a) for a in args), name="vectorize")

    return wrapped


# dtype taxonomy / inspection — straight numpy re-exports
finfo = onp.finfo
iinfo = onp.iinfo
issubdtype = onp.issubdtype
isdtype = jnp.isdtype
iterable = onp.iterable
complex64 = onp.complex64
complex128 = onp.complex128
csingle = onp.csingle
cdouble = onp.cdouble
single = onp.float32
double = onp.float64
int_ = onp.int64
uint = onp.uint64
floating = onp.floating
integer = onp.integer
signedinteger = onp.signedinteger
unsignedinteger = onp.unsignedinteger
inexact = onp.inexact
complexfloating = onp.complexfloating
number = onp.number
generic = onp.generic
character = onp.character
flexible = onp.flexible
object_ = onp.object_
ufunc = onp.ufunc

# printing / repr passthroughs
set_printoptions = onp.set_printoptions
get_printoptions = onp.get_printoptions
printoptions = onp.printoptions


def array_repr(arr, *args, **kwargs):
    return onp.array_repr(onp.asarray(_unwrap(_c(arr))), *args, **kwargs)


def array_str(arr, *args, **kwargs):
    return onp.array_str(onp.asarray(_unwrap(_c(arr))), *args, **kwargs)


# host IO (onp-backed; mx-level durable formats live in mx.serialization)
def save(file, arr):
    onp.save(file, onp.asarray(_unwrap(_c(arr))))


def savez(file, *args, **kwargs):
    onp.savez(file,
              *[onp.asarray(_unwrap(_c(a))) for a in args],
              **{k: onp.asarray(_unwrap(_c(v))) for k, v in kwargs.items()})


def load(file, **kwargs):
    out = onp.load(file, **kwargs)
    if isinstance(out, onp.ndarray):
        return _wrap(jnp.asarray(out))
    return out  # npz archive: lazy dict of numpy arrays


def fromfile(file, dtype=float32, count=-1, sep=""):
    return _wrap(jnp.asarray(onp.fromfile(file, dtype, count, sep)))


def genfromtxt(*args, **kwargs):
    """numpy.genfromtxt onto a device array (reference numpy/io.py:28;
    the ctx kwarg is accepted for API parity)."""
    kwargs.pop("ctx", None)
    return _wrap(jnp.asarray(onp.genfromtxt(*args, **kwargs)))


def fromiter(iterable, dtype, count=-1):
    return _wrap(jnp.asarray(onp.fromiter(iterable, dtype, count)))


def fromstring(string, dtype=float32, count=-1, sep=" "):
    return _wrap(jnp.asarray(onp.fromstring(string, dtype, count, sep=sep)))


def from_dlpack(x):
    return _wrap(jnp.from_dlpack(x))


def packbits(a, axis=None, bitorder="big"):
    """numpy.packbits (jnp has it; non-differentiable int op)."""
    return _call(lambda x: jnp.packbits(x, axis=axis, bitorder=bitorder),
                 (_c(a),), name="packbits")


def unpackbits(a, axis=None, count=None, bitorder="big"):
    return _call(
        lambda x: jnp.unpackbits(x, axis=axis, count=count,
                                 bitorder=bitorder),
        (_c(a),), name="unpackbits")
