"""``mx.np.random`` — stateful-looking RNG over jax's functional PRNG.

Parity: reference ``python/mxnet/numpy/random.py`` + sampler kernels in
``src/operator/random/`` (sampler infra ``random/sampler.h``). The reference
keeps per-device Philox state in the resource manager
(``include/mxnet/resource.h:43 kRandom``); here a module-global key is split
per call, which preserves the user-visible contract (global ``seed()``,
reproducible streams) while every sample is a pure XLA op.
"""
from __future__ import annotations

import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as onp

from ..base import dtype_from_any
from ..ndarray.ndarray import ndarray, _wrap, _unwrap

__all__ = [
    "seed", "uniform", "normal", "randn", "rand", "randint", "choice",
    "shuffle", "permutation", "beta", "gamma", "exponential", "chisquare",
    "laplace", "logistic", "gumbel", "multinomial", "multivariate_normal",
    "lognormal", "pareto", "power", "rayleigh", "weibull", "bernoulli",
    "binomial", "poisson", "geometric", "negative_binomial", "f", "standard_normal",
]


class _RNG(threading.local):
    def __init__(self):
        # LAZY: creating a PRNGKey initializes the XLA backend, and module
        # import must not — jax.distributed.initialize() (multi-process
        # bootstrap, parallel/dist.py) has to run before any backend init
        self.key = None

    def next_key(self):
        if self.key is None:
            self.key = jax.random.PRNGKey(0)
        self.key, sub = jax.random.split(self.key)
        return sub


_rng = _RNG()


def seed(seed_state: Optional[int] = None):
    if seed_state is None:
        seed_state = int.from_bytes(onp.random.bytes(4), "little")
    _rng.key = jax.random.PRNGKey(int(seed_state))


def new_key():
    """Expose key-splitting for internal consumers (initializers, dropout)."""
    return _rng.next_key()


def _shape(size):
    if size is None:
        return ()
    if isinstance(size, int):
        return (size,)
    return tuple(size)


def _sample(fn, dtype="float32"):
    val = fn(_rng.next_key())
    if dtype is not None:
        val = val.astype(dtype_from_any(dtype))
    return _wrap(val)


def uniform(low=0.0, high=1.0, size=None, dtype="float32", ctx=None, device=None, out=None):
    low_v = _unwrap(low) if isinstance(low, ndarray) else low
    high_v = _unwrap(high) if isinstance(high, ndarray) else high
    shp = _shape(size) if size is not None else jnp.broadcast_shapes(jnp.shape(low_v), jnp.shape(high_v))
    res = _sample(lambda k: jax.random.uniform(k, shp, jnp.float32) * (high_v - low_v) + low_v, dtype)
    if out is not None:
        out._set_data(res._data)
        return out
    return res


def normal(loc=0.0, scale=1.0, size=None, dtype="float32", ctx=None, device=None, out=None):
    loc_v = _unwrap(loc) if isinstance(loc, ndarray) else loc
    scale_v = _unwrap(scale) if isinstance(scale, ndarray) else scale
    shp = _shape(size) if size is not None else jnp.broadcast_shapes(jnp.shape(loc_v), jnp.shape(scale_v))
    res = _sample(lambda k: jax.random.normal(k, shp, jnp.float32) * scale_v + loc_v, dtype)
    if out is not None:
        out._set_data(res._data)
        return out
    return res


def standard_normal(size=None, dtype="float32"):
    return normal(0.0, 1.0, size, dtype)


def randn(*shape):
    return normal(0.0, 1.0, shape if shape else None)


def rand(*shape):
    return uniform(0.0, 1.0, shape if shape else None)


def lognormal(mean=0.0, sigma=1.0, size=None, dtype="float32"):
    res = normal(mean, sigma, size, dtype)
    return _wrap(jnp.exp(res._data))


def randint(low, high=None, size=None, dtype="int64", ctx=None, device=None, out=None):
    if high is None:
        low, high = 0, low
    res = _sample(lambda k: jax.random.randint(k, _shape(size), low, high), dtype)
    if out is not None:
        out._set_data(res._data)
        return out
    return res


def choice(a, size=None, replace=True, p=None, ctx=None, out=None):
    a_v = _unwrap(a) if isinstance(a, ndarray) else (jnp.arange(a) if isinstance(a, int) else jnp.asarray(a))
    p_v = _unwrap(p) if isinstance(p, ndarray) else (None if p is None else jnp.asarray(p))
    res = _sample(lambda k: jax.random.choice(k, a_v, _shape(size), replace=replace, p=p_v), None)
    if out is not None:
        out._set_data(res._data)
        return out
    return res


def permutation(x):
    if isinstance(x, int):
        return _sample(lambda k: jax.random.permutation(k, x), None)
    return _sample(lambda k: jax.random.permutation(k, _unwrap(x)), None)


def shuffle(x: ndarray):
    x._set_data(jax.random.permutation(_rng.next_key(), x._data))


def beta(a, b, size=None, dtype="float32"):
    a_v, b_v = _unwrap(a) if isinstance(a, ndarray) else a, _unwrap(b) if isinstance(b, ndarray) else b
    return _sample(lambda k: jax.random.beta(k, a_v, b_v, _shape(size) if size is not None else None), dtype)


def gamma(shape, scale=1.0, size=None, dtype="float32", ctx=None, out=None):
    sh_v = _unwrap(shape) if isinstance(shape, ndarray) else shape
    sc_v = _unwrap(scale) if isinstance(scale, ndarray) else scale
    res = _sample(lambda k: jax.random.gamma(k, sh_v, _shape(size) if size is not None else None) * sc_v, dtype)
    if out is not None:
        out._set_data(res._data)
        return out
    return res


def exponential(scale=1.0, size=None, dtype="float32"):
    sc = _unwrap(scale) if isinstance(scale, ndarray) else scale
    return _sample(lambda k: jax.random.exponential(k, _shape(size)) * sc, dtype)


def chisquare(df, size=None, dtype="float32"):
    df_v = _unwrap(df) if isinstance(df, ndarray) else df
    return _sample(lambda k: jax.random.chisquare(k, df_v, shape=_shape(size) if size is not None else None), dtype)


def laplace(loc=0.0, scale=1.0, size=None, dtype="float32"):
    return _sample(lambda k: jax.random.laplace(k, _shape(size)) * scale + loc, dtype)


def logistic(loc=0.0, scale=1.0, size=None, dtype="float32"):
    return _sample(lambda k: jax.random.logistic(k, _shape(size)) * scale + loc, dtype)


def gumbel(loc=0.0, scale=1.0, size=None, dtype="float32"):
    return _sample(lambda k: jax.random.gumbel(k, _shape(size)) * scale + loc, dtype)


def pareto(a, size=None, dtype="float32"):
    # numpy convention (Lomax, support [0, inf)): jax.random.pareto
    # returns the classical Pareto on [1, inf) — shift down by 1
    a_v = _unwrap(a) if isinstance(a, ndarray) else a
    return _sample(lambda k: jax.random.pareto(
        k, a_v, shape=_shape(size) if size is not None else None) - 1.0,
        dtype)


def power(a, size=None, dtype="float32"):
    a_v = _unwrap(a) if isinstance(a, ndarray) else a
    return _sample(lambda k: jax.random.uniform(k, _shape(size)) ** (1.0 / a_v), dtype)


def rayleigh(scale=1.0, size=None, dtype="float32"):
    return _sample(lambda k: scale * jnp.sqrt(-2.0 * jnp.log(jax.random.uniform(k, _shape(size), minval=1e-20))), dtype)


def weibull(a, size=None, dtype="float32"):
    a_v = _unwrap(a) if isinstance(a, ndarray) else a
    return _sample(lambda k: jax.random.weibull_min(k, 1.0, a_v, _shape(size) if size is not None else None), dtype)


def bernoulli(prob=0.5, size=None, dtype="float32"):
    p = _unwrap(prob) if isinstance(prob, ndarray) else prob
    shp = _shape(size) if size is not None else jnp.shape(p)
    return _sample(lambda k: jax.random.bernoulli(k, p, shp), dtype)


def binomial(n, p, size=None, dtype="float32"):
    return _sample(lambda k: jax.random.binomial(k, n, p, shape=_shape(size) if size is not None else None), dtype)


def poisson(lam=1.0, size=None, dtype="float32"):
    lam_v = _unwrap(lam) if isinstance(lam, ndarray) else lam
    return _sample(lambda k: jax.random.poisson(k, lam_v, shape=_shape(size) if size is not None else None), dtype)


def geometric(p, size=None, dtype="int64"):
    return _sample(lambda k: jax.random.geometric(k, p, shape=_shape(size)), dtype)


def negative_binomial(n, p, size=None, dtype="int64"):
    def fn(k):
        k1, k2 = jax.random.split(k)
        g = jax.random.gamma(k1, n, _shape(size)) * (1 - p) / p
        return jax.random.poisson(k2, g)

    return _sample(fn, dtype)


def f(dfnum, dfden, size=None, dtype="float32"):
    def fn(k):
        k1, k2 = jax.random.split(k)
        x1 = jax.random.chisquare(k1, dfnum, shape=_shape(size))
        x2 = jax.random.chisquare(k2, dfden, shape=_shape(size))
        return (x1 / dfnum) / (x2 / dfden)

    return _sample(fn, dtype)


def multinomial(n, pvals, size=None):
    pv = _unwrap(pvals) if isinstance(pvals, ndarray) else jnp.asarray(pvals)
    shp = _shape(size) + pv.shape if size is not None else pv.shape
    return _sample(lambda k: jax.random.multinomial(k, n, pv, shape=shp), None)


def multivariate_normal(mean, cov, size=None, check_valid=None, tol=None):
    m = _unwrap(mean) if isinstance(mean, ndarray) else jnp.asarray(mean)
    c = _unwrap(cov) if isinstance(cov, ndarray) else jnp.asarray(cov)
    return _sample(lambda k: jax.random.multivariate_normal(k, m, c, shape=_shape(size) if size is not None else None), None)
