"""Pallas fused LayerNorm / RMSNorm — one of SURVEY §7's named Pallas
targets (attention → flash_attention.py, norm → here; the softmax
family's row reduction is XLA's since PR 32: ops/nn.py
``softmax_cross_entropy``).

The reference computes LayerNorm as a multi-kernel sequence
(src/operator/nn/layer_norm.cc: mean reduce, variance reduce, then the
normalize map). XLA fuses most of that already; what it cannot fuse away
on TPU is re-reading the row from HBM for each reduction. Here a row
block is loaded into VMEM ONCE: mean, variance, normalize and the
gamma/beta affine all happen in-register, fp32 accumulation regardless
of input dtype (bf16-safe), one HBM read + one write per element.

Rows live on the leading axis: inputs are (N, D) with D the normalized
axis. Whole rows are kept in VMEM (D ≤ ~8k fp32 at block_n 128), which
covers every transformer width this framework ships; wider rows fall
back to the jnp path in ops/nn.py.

Backward is ``jax.custom_vjp`` from saved (x, mean, rstd) — the standard
analytic LN gradient, one fused XLA pass, no recompute of the
reductions. ``interpret=None`` auto-selects: compiled Mosaic on TPU, the
Pallas interpreter elsewhere (CPU tests exercise the same kernel).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


def _ln_kernel(x_ref, g_ref, b_ref, o_ref, m_ref, r_ref, *, eps, d):
    import jax.experimental.pallas as pl  # noqa: F401 — interpret parity

    x = x_ref[...].astype(jnp.float32)                    # (bn, Dp)
    mask = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) < d
    xm = jnp.where(mask, x, 0.0)
    mean = xm.sum(axis=-1, keepdims=True) / d             # (bn, 1)
    cent = jnp.where(mask, x - mean, 0.0)
    var = (cent * cent).sum(axis=-1, keepdims=True) / d
    rstd = jax.lax.rsqrt(var + eps)
    y = cent * rstd
    g = g_ref[...].astype(jnp.float32)                    # (1, Dp)
    b = b_ref[...].astype(jnp.float32)
    o_ref[...] = (y * g + b).astype(o_ref.dtype)
    m_ref[...] = jnp.broadcast_to(mean, m_ref.shape)
    r_ref[...] = jnp.broadcast_to(rstd, r_ref.shape)


def _rms_kernel(x_ref, g_ref, o_ref, r_ref, *, eps, d):
    import jax.experimental.pallas as pl  # noqa: F401

    x = x_ref[...].astype(jnp.float32)
    mask = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) < d
    xm = jnp.where(mask, x, 0.0)
    ms = (xm * xm).sum(axis=-1, keepdims=True) / d
    rstd = jax.lax.rsqrt(ms + eps)
    g = g_ref[...].astype(jnp.float32)
    o_ref[...] = (x * rstd * g).astype(o_ref.dtype)
    r_ref[...] = jnp.broadcast_to(rstd, r_ref.shape)


def _pad_rows(x, bn):
    n = x.shape[0]
    n_n = -(-n // bn)
    pad = n_n * bn - n
    return (jnp.pad(x, ((0, pad), (0, 0))) if pad else x), n_n


def _pad_cols(x, dp):
    pad = dp - x.shape[-1]
    return jnp.pad(x, ((0, 0), (0, pad))) if pad else x


# jitted: a bare ``pallas_call`` builds a new jit wrapper on every call,
# so an eager caller would compile the kernel again each time
@functools.partial(jax.jit, static_argnums=(0, 3, 4, 5, 6))
def _run_norm(kernel, x, scales, n_extra_outs, eps, block_n, interpret):
    """Shared pallas_call plumbing for the two norm kernels. The output
    dtype follows jnp promotion over (x, *scales) so the kernel path is
    dtype-identical to the jnp path under mixed precision."""
    import jax.experimental.pallas as pl

    n, d = x.shape
    dp = -(-d // 128) * 128
    # row blocks rounded up to the 8-row fp32 tile Mosaic expects
    bn = min(block_n, -(-max(8, n) // 8) * 8)
    xp, n_n = _pad_rows(_pad_cols(x, dp), bn)
    scales_p = [_pad_cols(s.reshape(1, -1), dp) for s in scales]
    out_dtype = jnp.result_type(x.dtype, *(s.dtype for s in scales))
    outs = pl.pallas_call(
        functools.partial(kernel, eps=eps, d=d),
        grid=(n_n,),
        in_specs=[pl.BlockSpec((bn, dp), lambda i: (i, jnp.int32(0)))]
        + [pl.BlockSpec((1, dp), lambda i: (jnp.int32(0), jnp.int32(0)))
           for _ in scales],
        out_specs=[pl.BlockSpec((bn, dp), lambda i: (i, jnp.int32(0)))]
        + [pl.BlockSpec((bn, 128), lambda i: (i, jnp.int32(0)))
           for _ in range(n_extra_outs)],
        out_shape=[jax.ShapeDtypeStruct((n_n * bn, dp), out_dtype)]
        + [jax.ShapeDtypeStruct((n_n * bn, 128), jnp.float32)
           for _ in range(n_extra_outs)],
        interpret=interpret,
    )(xp, *scales_p)
    out = outs[0][:n, :d]
    stats = [o[:n, 0] for o in outs[1:]]
    return out, stats


def _auto_interpret(interpret):
    return jax.default_backend() != "tpu" if interpret is None else interpret


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_layer_norm(x, gamma, beta, eps: float = 1e-5,
                     interpret: Optional[bool] = None):
    """LayerNorm over the last axis of (N, D) in one fused kernel."""
    out, _ = _ln_fwd(x, gamma, beta, eps, interpret)
    return out


def _ln_fwd(x, gamma, beta, eps, interpret):
    out, (mean, rstd) = _run_norm(
        _ln_kernel, x, [gamma, beta], 2, eps,
        128, _auto_interpret(interpret))
    return out, (x, gamma, beta, mean, rstd)


def _ln_bwd(eps, interpret, res, g):
    x, gamma, beta, mean, rstd = res
    beta_dtype = beta.dtype
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    xhat = (xf - mean[:, None]) * rstd[:, None]
    dy = gf * gamma.astype(jnp.float32)[None, :]
    m1 = dy.mean(axis=-1, keepdims=True)
    m2 = (dy * xhat).mean(axis=-1, keepdims=True)
    dx = ((dy - m1 - xhat * m2) * rstd[:, None]).astype(x.dtype)
    dgamma = (gf * xhat).sum(axis=0).astype(gamma.dtype)
    dbeta = gf.sum(axis=0).astype(beta_dtype)
    return dx, dgamma, dbeta


fused_layer_norm.defvjp(_ln_fwd, _ln_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def fused_rms_norm(x, gamma, eps: float = 1e-6,
                   interpret: Optional[bool] = None):
    """RMSNorm over the last axis of (N, D) in one fused kernel."""
    out, _ = _rms_fwd(x, gamma, eps, interpret)
    return out


def _rms_fwd(x, gamma, eps, interpret):
    out, (rstd,) = _run_norm(
        _rms_kernel, x, [gamma], 1, eps,
        128, _auto_interpret(interpret))
    return out, (x, gamma, rstd)


def _rms_bwd(eps, interpret, res, g):
    x, gamma, rstd = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    d = x.shape[-1]
    xhat = xf * rstd[:, None]
    dy = gf * gamma.astype(jnp.float32)[None, :]
    m2 = (dy * xhat).mean(axis=-1, keepdims=True)
    dx = ((dy - xhat * m2) * rstd[:, None]).astype(x.dtype)
    dgamma = (gf * xhat).sum(axis=0).astype(gamma.dtype)
    return dx, dgamma


fused_rms_norm.defvjp(_rms_fwd, _rms_bwd)
