"""``mx.np.fft`` — Fourier transforms.

The reference shipped FFT only as a contrib GPU op pair
(``src/operator/contrib/fft.cc`` cuFFT wrappers); here the full numpy fft
namespace lowers through jnp.fft onto XLA's FFT HLO (TPU-native), and every
transform is differentiable + trace-transparent like any other op.
"""
from __future__ import annotations

import jax.numpy as jnp

from . import _call

__all__ = ["fft", "ifft", "rfft", "irfft", "fft2", "ifft2", "rfft2",
           "irfft2", "fftn", "ifftn", "hfft", "ihfft", "fftfreq",
           "rfftfreq", "fftshift", "ifftshift"]

def _make1(name):
    jfn = getattr(jnp.fft, name)

    def op(a, n=None, axis=-1, norm=None):
        return _call(lambda x: jfn(x, n=n, axis=axis, norm=norm), (a,),
                     name=f"fft.{name}")

    op.__name__ = name
    return op


def _make2(name):
    jfn = getattr(jnp.fft, name)

    def op(a, s=None, axes=(-2, -1), norm=None):
        return _call(lambda x: jfn(x, s=s, axes=axes, norm=norm), (a,),
                     name=f"fft.{name}")

    op.__name__ = name
    return op


def _maken(name):
    jfn = getattr(jnp.fft, name)

    def op(a, s=None, axes=None, norm=None):
        return _call(lambda x: jfn(x, s=s, axes=axes, norm=norm), (a,),
                     name=f"fft.{name}")

    op.__name__ = name
    return op


fft = _make1("fft")
ifft = _make1("ifft")
rfft = _make1("rfft")
irfft = _make1("irfft")
hfft = _make1("hfft")
ihfft = _make1("ihfft")
fft2 = _make2("fft2")
ifft2 = _make2("ifft2")
rfft2 = _make2("rfft2")
irfft2 = _make2("irfft2")
fftn = _maken("fftn")
ifftn = _maken("ifftn")


def fftfreq(n, d=1.0):
    from ..ndarray.ndarray import _wrap

    return _wrap(jnp.fft.fftfreq(n, d))


def rfftfreq(n, d=1.0):
    from ..ndarray.ndarray import _wrap

    return _wrap(jnp.fft.rfftfreq(n, d))


def fftshift(x, axes=None):
    return _call(lambda v: jnp.fft.fftshift(v, axes=axes), (x,),
                 name="fft.fftshift")


def ifftshift(x, axes=None):
    return _call(lambda v: jnp.fft.ifftshift(v, axes=axes), (x,),
                 name="fft.ifftshift")
