"""Pallas fused softmax cross-entropy for big-vocab LM heads.

The reference computes ``softmax_cross_entropy`` by materializing the full
softmax in a workspace and then row-choosing it
(``src/operator/loss_binary_op-inl.h:44-57 SoftmaxCrossEntropyForward``:
``mshadow::Softmax(temp1, mdata)`` over a (N, V) temp). XLA's stock
``logsumexp`` lowering is two HBM passes over the logits (a max reduce,
then an exp-sum reduce). For an LM head the logits are the biggest live
tensor in the step (batch*seq × 32-50k vocab, hundreds of MB), so this
kernel does the whole reduction in ONE streaming pass: V-blocks of the
logits go HBM→VMEM once, an online (max, sumexp) accumulator pair lives
in VMEM across the sequential V grid axis (same trick as the flash
attention kernel next door), and only the (N,) lse ever leaves.

Backward is analytic from the saved lse — ``dlogits = (exp(x - lse) -
onehot(label)) * g`` — one fused elementwise pass, no recompute of the
reduction and no fp32 (N, V) log-softmax intermediate at all.

``interpret=None`` auto-selects the compiled Mosaic kernel on TPU and the
Pallas interpreter elsewhere, so CPU tests run the same kernel logic.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as onp

_NEG_INF = -1e30


def _lse_kernel(x_ref, o_ref, m_ref, l_ref, *, n_v, v_total, block_v):
    import jax.experimental.pallas as pl

    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, jnp.float32(_NEG_INF))
        l_ref[:] = jnp.zeros_like(l_ref)

    x = x_ref[...].astype(jnp.float32)                     # (bn, bv)
    v_pos = vi * block_v + jax.lax.broadcasted_iota(
        jnp.int32, x.shape, 1)
    x = jnp.where(v_pos < v_total, x, jnp.float32(_NEG_INF))

    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_new = jnp.maximum(m_prev, x.max(axis=-1, keepdims=True))
    l_new = l_prev * jnp.exp(m_prev - m_new) + \
        jnp.exp(x - m_new).sum(axis=-1, keepdims=True)
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(vi == n_v - 1)
    def _finalize():
        l = l_ref[:, :1]
        lse = m_ref[:, :1] + jnp.log(jnp.where(l == 0.0, 1.0, l))
        o_ref[...] = jnp.broadcast_to(lse, o_ref.shape)


def fused_lse(x, block_n: int = 256, block_v: int = 2048,
              interpret: Optional[bool] = None):
    """Row-wise logsumexp of a 2-D array in one HBM pass. Returns (N,) f32."""
    if x.ndim != 2:
        raise ValueError(f"expected (N, V), got {x.shape}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _fused_lse(x, block_n, block_v, interpret)


# jitted: a bare ``pallas_call`` builds a new jit wrapper on every call,
# so an eager caller (the loss outside a hybridized block) would compile
# the kernel again at every step
@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _fused_lse(x, block_n, block_v, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, v = x.shape
    # round blocks to Mosaic fp32 tile multiples (8 sublanes × 128 lanes):
    # an unaligned bn/bv (e.g. N=100 or V=1000) is a hard Mosaic reject on
    # TPU. The jnp.pad below already supplies the extra rows/cols and the
    # v_pos mask neutralizes padded columns.
    bn = min(block_n, max(8, n))
    bn = -(-bn // 8) * 8
    bv = min(block_v, max(128, v))
    bv = -(-bv // 128) * 128
    n_n = -(-n // bn)
    n_v = -(-v // bv)
    pad_n = n_n * bn - n
    pad_v = n_v * bv - v
    xp = jnp.pad(x, ((0, pad_n), (0, pad_v))) if (pad_n or pad_v) else x

    kernel = functools.partial(_lse_kernel, n_v=n_v, v_total=v, block_v=bv)
    out = pl.pallas_call(
        kernel,
        grid=(n_n, n_v),
        in_specs=[pl.BlockSpec((bn, bv), lambda ri, vi: (ri, vi))],
        out_specs=pl.BlockSpec((bn, 128), lambda ri, vi: (ri, jnp.int32(0))),
        out_shape=jax.ShapeDtypeStruct((n_n * bn, 128), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bn, 128), jnp.float32),
            pltpu.VMEM((bn, 128), jnp.float32),
        ],
        interpret=interpret,
    )(xp)
    return out[:n, 0]


@jax.custom_vjp
def cross_entropy_with_logits(logits, labels):
    """Per-row sparse-label NLL: ``lse(logits) - logits[i, labels[i]]``.

    logits: (N, V) any float dtype; labels: (N,) integer. Returns (N,) f32.
    Rows with a negative label get loss 0 (ignore-index semantics).
    """
    nll, _ = _ce_fwd(logits, labels)
    return nll


def _ce_fwd(logits, labels):
    lse = fused_lse(logits)
    label_logit = jnp.take_along_axis(
        logits, labels[:, None].astype(jnp.int32), axis=-1)[:, 0]
    nll = lse - label_logit.astype(jnp.float32)
    nll = jnp.where(labels >= 0, nll, 0.0)
    return nll, (logits, labels, lse)


def _ce_bwd(res, g):
    logits, labels, lse = res
    # one fused elementwise pass: softmax from the saved lse minus onehot
    p = jnp.exp(logits.astype(jnp.float32) - lse[:, None])
    onehot = (jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
              == labels[:, None].astype(jnp.int32))
    gr = jnp.where(labels >= 0, g, 0.0)
    dlogits = ((p - onehot.astype(jnp.float32)) * gr[:, None]).astype(
        logits.dtype)
    return dlogits, onp.zeros(labels.shape, jax.dtypes.float0)


cross_entropy_with_logits.defvjp(_ce_fwd, _ce_bwd)
