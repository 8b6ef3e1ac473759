"""Neural-net ops as pure jax functions.

TPU-native re-design of the reference kernel library ``src/operator/nn/``
(Convolution ``convolution.cc:402``, FullyConnected, BatchNorm, LayerNorm,
Pooling, Softmax, Dropout, ...). Each function here is pure and
trace-transparent: it is wrapped once by ``apply_op`` for the eager/autograd
path (mxnet_tpu.numpy_extension) and reused verbatim inside jit traces (the
hybridize path), so there is exactly one implementation per op — the
reference needs 3 (CPU, cuDNN, MKLDNN); XLA is all three here.

Layouts: the API default is NCHW for parity with the reference, but the
convolution lowers through ``lax.conv_general_dilated`` with explicit
dimension_numbers so XLA is free to pick MXU-friendly internal layouts.
"""
from __future__ import annotations

import functools
import os
import threading
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as onp
from jax import lax

IntOrTuple = Union[int, Tuple[int, ...]]


def _tuple(v: IntOrTuple, n: int) -> Tuple[int, ...]:
    if isinstance(v, int):
        return (v,) * n
    t = tuple(v)
    return t if len(t) == n else t + (t[-1],) * (n - len(t))


# ---------------------------------------------------------------------------
# MXU tile-pad helpers (the J001 rewrite's primitives, and a public
# surface for model authors who want to pad feature dims at model edges
# once instead of paying tile padding per op — docs/auto_opt.md)
# ---------------------------------------------------------------------------
def mxu_pad_amount(dim: int, tile: int) -> int:
    """Zeros needed to round ``dim`` up to a multiple of ``tile``
    (the float32 MXU register tiles are sublane=8 / lane=128)."""
    return (-int(dim)) % int(tile)


def pad_to_tile(x, axis_tiles):
    """Zero-pad ``x`` so each ``axis -> tile`` in ``axis_tiles`` becomes
    a tile multiple. Padding with zeros is exact for every contraction
    (zero taps contribute zero) and for feature dims that are sliced
    back afterwards (:func:`unpad_slice`). Differentiable: the vjp of a
    zero-pad is the matching slice, so gradients flow to the original
    (unpadded) operand untouched. A no-op (same ``x``) when every listed
    axis is already aligned — safe to call unconditionally."""
    pads = [(0, 0, 0)] * x.ndim
    any_pad = False
    for axis, tile in dict(axis_tiles).items():
        amount = mxu_pad_amount(x.shape[axis], tile)
        if amount:
            pads[axis] = (0, amount, 0)
            any_pad = True
    if not any_pad:
        return x
    return lax.pad(x, jnp.zeros((), x.dtype), pads)


def unpad_slice(x, shape):
    """Slice a tile-padded result back to its logical ``shape`` (the
    inverse of :func:`pad_to_tile` on the output side)."""
    shape = tuple(int(d) for d in shape)
    if tuple(x.shape) == shape:
        return x
    return lax.slice(x, (0,) * x.ndim, shape)


# ---------------------------------------------------------------------------
# dense / matmul
# ---------------------------------------------------------------------------
def fully_connected(x, weight, bias=None, num_hidden=None, flatten=True, no_bias=False):
    """y = x @ W^T + b (reference src/operator/nn/fully_connected.cc)."""
    if flatten and x.ndim > 2:
        x = x.reshape(x.shape[0], -1)
    y = jnp.matmul(x, weight.T)
    if bias is not None and not no_bias:
        y = y + bias
    return y


# ---------------------------------------------------------------------------
# convolution
# ---------------------------------------------------------------------------
def _s2d_axis_plan(K, S, P):
    """Per-spatial-dim tap algebra for the space-to-depth stem rewrite.

    A stride-S conv tap reads position S*i + (u - P); splitting u - P into
    S*du + a (a in [0, S)) maps it onto phase-a of the space-to-depth
    tensor at spatial offset du. Returns (K2, pad_l, pad_r, lo): kernel
    length in s2d space, the zero-padding that embeds the original kernel
    into the (K2*S)-long phase-major layout, and the left lax-conv padding
    of the rewritten stride-1 conv.
    """
    du_min = -((P + S - 1) // S)               # floor((0-P)/S)
    du_max = (K - 1 - P) // S
    K2 = du_max - du_min + 1
    t = P + S * du_min                          # <= 0
    pad_l, pad_r = -t, K2 * S - K + t
    lo = -du_min
    return K2, pad_l, pad_r, lo


def _stem_space_to_depth(x, weight, stride, pad, out_sizes):
    """MXU-friendly lowering of a lane-starved stem conv (NCHW, groups=1,
    no dilation): the 7x7/s2 (ResNet), 11x11/s4 (AlexNet) and 3x3/s2
    (Inception) first convs read 3 input channels, which occupy 3 of the
    MXU's 128 contraction lanes. Folding each SxS spatial block into
    channels (space-to-depth) multiplies the contraction depth by S*S and
    turns the conv into an equivalent stride-1 conv whose weight is a pure
    zero-pad + reshape + transpose of the original — numerically identical
    taps, autodiff flows through the rearrangement. The standard TPU
    ResNet trick (reference convs: src/operator/nn/convolution.cc:402
    always lower the direct form; CUDNN picks algos instead).
    """
    N, C, H, W = x.shape
    O = weight.shape[0]
    (Sh, Sw), (Ph, Pw) = stride, pad
    Kh, Kw = weight.shape[2], weight.shape[3]
    K2h, plh, prh, loh = _s2d_axis_plan(Kh, Sh, Ph)
    K2w, plw, prw, low = _s2d_axis_plan(Kw, Sw, Pw)
    Hp, Wp = -(-H // Sh) * Sh, -(-W // Sw) * Sw
    if Hp != H or Wp != W:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, Hp - H), (0, Wp - W)))
    # x2: (N, C*Sh*Sw, Hp/Sh, Wp/Sw), channel order (c, row-phase, col-phase)
    x2 = x.reshape(N, C, Hp // Sh, Sh, Wp // Sw, Sw)
    x2 = x2.transpose(0, 1, 3, 5, 2, 4).reshape(N, C * Sh * Sw,
                                                Hp // Sh, Wp // Sw)
    # w2: embed taps into phase-major layout with the same channel order
    w2 = jnp.pad(weight, ((0, 0), (0, 0), (plh, prh), (plw, prw)))
    w2 = w2.reshape(O, C, K2h, Sh, K2w, Sw)
    w2 = w2.transpose(0, 1, 3, 5, 2, 4).reshape(O, C * Sh * Sw, K2h, K2w)
    hi_h = out_sizes[0] - 1 + K2h - loh - Hp // Sh
    hi_w = out_sizes[1] - 1 + K2w - low - Wp // Sw
    dn = lax.conv_dimension_numbers(x2.shape, w2.shape,
                                    ("NCHW", "OIHW", "NCHW"))
    return lax.conv_general_dilated(
        x2, w2, window_strides=(1, 1),
        padding=[(loh, hi_h), (low, hi_w)],
        dimension_numbers=dn)


def stem_s2d_cache_key():
    """The trace-environment component of any jit-cache key whose graph
    may contain a convolution: ``_stem_s2d_wanted`` reads the
    ``MXNET_TPU_STEM_S2D`` knob and the active backend at TRACE time, so
    a cached executable is only valid while both still hold. Long-lived
    serving processes make mid-process knob flips (equivalence tests) a
    real hazard rather than a cosmetic one — cache keys must include
    this."""
    return (os.environ.get("MXNET_TPU_STEM_S2D", "1"),
            jax.default_backend())


def _stem_s2d_wanted(x, weight, ndim, stride, dilate, num_group, layout):
    """Gate for the stem rewrite: 2D NCHW float conv, no groups/dilation,
    <=4 input channels, strided — and a TPU backend (or forced via
    MXNET_TPU_STEM_S2D=force for CPU equivalence tests; =0 disables)."""
    knob = os.environ.get("MXNET_TPU_STEM_S2D", "1")
    if knob == "0":
        return False
    if not (ndim == 2 and layout == "NCHW" and num_group == 1):
        return False
    if any(d != 1 for d in dilate) or max(stride) < 2:
        return False
    if weight.shape[1] > 4 or not jnp.issubdtype(x.dtype, jnp.floating):
        return False
    # rewrite only pays when the kernel spans multiple strides in some dim
    if weight.shape[2] <= stride[0] and weight.shape[3] <= stride[1]:
        return False
    return knob == "force" or jax.default_backend() == "tpu"


def convolution(
    x,
    weight,
    bias=None,
    kernel=None,
    stride=1,
    dilate=1,
    pad=0,
    num_group=1,
    layout="NCHW",
):
    """N-D convolution (reference src/operator/nn/convolution.cc:402).

    weight layout: OIHW (out_ch, in_ch/groups, *kernel) for NCHW input —
    the reference's native layout; lax handles the MXU mapping.
    """
    ndim = x.ndim - 2
    stride = _tuple(stride, ndim)
    dilate = _tuple(dilate, ndim)
    pad = _tuple(pad, ndim)
    if layout in ("NCHW", "NCW", "NCDHW"):
        spatial = "".join("WHD"[i] for i in range(ndim))[::-1] if ndim > 1 else "W"
        spec = ("NC" + spatial, "OI" + spatial, "NC" + spatial)
    elif layout in ("NHWC", "NWC", "NDHWC"):
        spatial = {1: "W", 2: "HW", 3: "DHW"}[ndim]
        spec = ("N" + spatial + "C", "O" + spatial + "I", "N" + spatial + "C")
    else:
        raise ValueError(f"unsupported layout {layout}")
    if _stem_s2d_wanted(x, weight, ndim, stride, dilate, num_group, layout):
        out_sizes = tuple(
            (x.shape[2 + i] + 2 * pad[i] - weight.shape[2 + i]) // stride[i]
            + 1 for i in range(2))
        y = _stem_space_to_depth(x, weight, stride, pad, out_sizes)
    else:
        dn = lax.conv_dimension_numbers(x.shape, weight.shape, spec)
        # no preferred_element_type: the MXU accumulates bf16 convs in fp32
        # internally and rounds at the final store, so bf16-out == fp32-out +
        # downcast — and requesting fp32 out breaks the conv transpose rule
        # (jax's vjp feeds the fp32 cotangent into a bf16-weight grad conv)
        y = lax.conv_general_dilated(
            x,
            weight,
            window_strides=stride,
            padding=[(p, p) for p in pad],
            rhs_dilation=dilate,
            dimension_numbers=dn,
            feature_group_count=num_group,
        )
    if bias is not None:
        if layout.startswith("NC"):
            y = y + bias.reshape((1, -1) + (1,) * ndim)
        else:
            y = y + bias
    return y


def deconvolution(
    x, weight, bias=None, stride=1, dilate=1, pad=0, adj=0, num_group=1, layout="NCHW"
):
    """Transposed convolution (reference src/operator/nn/deconvolution.cc).
    weight layout IOHW (in_ch, out_ch/groups, *kernel) like the reference."""
    ndim = x.ndim - 2
    stride = _tuple(stride, ndim)
    pad = _tuple(pad, ndim)
    adj = _tuple(adj, ndim)
    dilate = _tuple(dilate, ndim)
    if num_group != 1:
        xs = jnp.split(x, num_group, axis=1)
        ws = jnp.split(weight, num_group, axis=0)
        outs = [
            deconvolution(xg, wg, None, stride, dilate, pad, adj, 1, layout)
            for xg, wg in zip(xs, ws)
        ]
        y = jnp.concatenate(outs, axis=1)
    else:
        kernel = weight.shape[2:]
        spatial = {1: "W", 2: "HW", 3: "DHW"}[ndim]
        dn = lax.conv_dimension_numbers(
            x.shape, (weight.shape[1], weight.shape[0]) + kernel,
            ("NC" + spatial, "OI" + spatial, "NC" + spatial))
        # padding for transpose conv: effective = k - 1 - pad
        pads = [
            (d * (k - 1) - p, d * (k - 1) - p + a)
            for k, p, a, d in zip(kernel, pad, adj, dilate)
        ]
        # deconv = grad-of-conv: I/O-swapped, spatially-flipped kernel with
        # lhs_dilation=stride (conv_general_dilated has no transpose_kernel
        # arg; the flip must be explicit)
        w = jnp.swapaxes(weight, 0, 1)
        w = w[(slice(None), slice(None)) + (slice(None, None, -1),) * ndim]
        y = lax.conv_general_dilated(
            x,
            w,
            window_strides=(1,) * ndim,
            padding=pads,
            lhs_dilation=stride,
            rhs_dilation=dilate,
            dimension_numbers=dn,
        )
    if bias is not None:
        y = y + bias.reshape((1, -1) + (1,) * ndim)
    return y


# ---------------------------------------------------------------------------
# pooling
# ---------------------------------------------------------------------------
def pooling(
    x,
    kernel=1,
    pool_type="max",
    stride=None,
    pad=0,
    global_pool=False,
    count_include_pad=True,
    layout="NCHW",
    ceil_mode=False,
):
    """Pooling (reference src/operator/nn/pooling.cc).

    Deliberately avoids lax.reduce_window: its reverse-mode rule does not
    lower under jit on this TPU backend. Two differentiable lowerings:
    - non-overlapping windows (stride==kernel, no pad, divisible): a
      reshape + reduce — the cheapest possible XLA program;
    - general: patch extraction (conv_general_dilated_patches) + reduce
      over the window axis. The patch conv is pinned to HIGHEST
      precision: it is a one-hot selection, not arithmetic, and under
      the ambient one-pass bf16 default it would (a) quantize every
      pooled fp32 value to bf16 and (b) turn the fp32 finfo.min padding
      into -inf (|f32 min| exceeds bf16 max), whose 0-tap products are
      0 * -inf = NaN — every padded max-pool window NaNs. Found on the
      real chip 2026-08-02 after the round-4 precision un-pin; the
      oracle suite pins 'highest' so only default-precision use hit it
      (regression test: tests/test_layer_smoke.py
      test_padded_pool_exact_under_default_precision).
    """
    ndim = x.ndim - 2
    channels_last = not layout.startswith("NC")
    if channels_last:
        x = jnp.moveaxis(x, -1, 1)
    sp_axes = tuple(range(2, 2 + ndim))
    if global_pool:
        if pool_type == "max":
            out = jnp.max(x, axis=sp_axes, keepdims=True)
        elif pool_type == "lp":
            out = jnp.sqrt(jnp.sum(jnp.square(jnp.abs(x)), axis=sp_axes, keepdims=True))
        else:
            out = jnp.mean(x, axis=sp_axes, keepdims=True)
        return jnp.moveaxis(out, 1, -1) if channels_last else out

    kernel = _tuple(kernel, ndim)
    stride = _tuple(stride if stride is not None else kernel, ndim)
    pad = _tuple(pad, ndim)
    spatial = x.shape[2:]
    n, c = x.shape[0], x.shape[1]

    # ceil_mode ('full' pooling convention): extend the high side so the
    # last partial window is kept instead of dropped
    extra = (0,) * ndim
    if ceil_mode:
        extra = tuple(
            max(0, (-(-(S + 2 * p - k) // st)) * st + k - (S + 2 * p))
            for S, k, st, p in zip(spatial, kernel, stride, pad)
        )

    non_overlap = (
        stride == kernel
        and all(p == 0 for p in pad)
        and all(e == 0 for e in extra)
        and all(s % k == 0 for s, k in zip(spatial, kernel))
    )
    if non_overlap:
        # reshape (N,C,H,W) -> (N,C,H/k,k,W/k,k) and reduce the k axes
        new_shape = [n, c]
        red_axes = []
        for i, (s, k) in enumerate(zip(spatial, kernel)):
            new_shape += [s // k, k]
            red_axes.append(3 + 2 * i)
        xr = x.reshape(new_shape)
        if pool_type == "max":
            out = jnp.max(xr, axis=tuple(red_axes))
        elif pool_type == "sum":
            out = jnp.sum(xr, axis=tuple(red_axes))
        elif pool_type == "lp":
            out = jnp.sqrt(jnp.sum(jnp.square(jnp.abs(xr)), axis=tuple(red_axes)))
        else:
            out = jnp.mean(xr, axis=tuple(red_axes))
        return jnp.moveaxis(out, 1, -1) if channels_last else out

    # general path: extract windows as patches, reduce over the window axis
    if pool_type == "max":
        pad_val = (
            jnp.finfo(x.dtype).min
            if jnp.issubdtype(x.dtype, jnp.floating)
            else jnp.iinfo(x.dtype).min
        )
    else:
        pad_val = 0
    xp = jnp.pad(
        x,
        ((0, 0), (0, 0)) + tuple((p, p + e) for p, e in zip(pad, extra)),
        constant_values=pad_val,
    )
    patches = lax.conv_general_dilated_patches(
        xp,
        kernel,
        stride,
        "VALID",
        dimension_numbers=lax.conv_dimension_numbers(
            xp.shape, (1, 1) + kernel, _patch_spec(ndim)
        ),
        precision=lax.Precision.HIGHEST,
    )
    ksize = functools.reduce(lambda a, b: a * b, kernel)
    out_spatial = patches.shape[2:]
    pk = patches.reshape((n, c, ksize) + out_spatial)
    if pool_type == "max":
        out = jnp.max(pk, axis=2)
    elif pool_type == "sum":
        out = jnp.sum(pk, axis=2)
    elif pool_type == "lp":
        out = jnp.sqrt(jnp.sum(jnp.square(jnp.abs(pk)), axis=2))
    elif pool_type == "avg":
        if count_include_pad:
            out = jnp.sum(pk, axis=2) / jnp.asarray(ksize, x.dtype)
        else:
            ones = jnp.pad(
                jnp.ones_like(x),
                ((0, 0), (0, 0)) + tuple((p, p + e) for p, e in zip(pad, extra)),
                constant_values=0,
            )
            cpatches = lax.conv_general_dilated_patches(
                ones,
                kernel,
                stride,
                "VALID",
                dimension_numbers=lax.conv_dimension_numbers(
                    ones.shape, (1, 1) + kernel, _patch_spec(ndim)
                ),
                precision=lax.Precision.HIGHEST,
            )
            counts = cpatches.reshape((n, c, ksize) + out_spatial).sum(axis=2)
            out = jnp.sum(pk, axis=2) / counts
    else:
        raise ValueError(f"unknown pool_type {pool_type}")
    return jnp.moveaxis(out, 1, -1) if channels_last else out


def _patch_spec(ndim):
    sp = {1: "W", 2: "HW", 3: "DHW"}[ndim]
    return ("NC" + sp, "OI" + sp, "NC" + sp)


def adaptive_avg_pool2d(x, output_size):
    """reference src/operator/contrib/adaptive_avg_pooling.cc"""
    if isinstance(output_size, int):
        output_size = (output_size, output_size)
    n, c, h, w = x.shape
    oh, ow = output_size
    x = x.reshape(n, c, oh, h // oh, ow, w // ow)
    return x.mean(axis=(3, 5))


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------
def batch_norm(
    x,
    gamma,
    beta,
    moving_mean,
    moving_var,
    eps=1e-5,
    momentum=0.9,
    fix_gamma=False,
    use_global_stats=False,
    training=True,
    axis=1,
):
    """BatchNorm (reference src/operator/nn/batch_norm.cc). Returns
    (out, new_moving_mean, new_moving_var); the caller owns running-stat
    state (functional design — no hidden mutation inside the op)."""
    axis = axis % x.ndim
    red_axes = tuple(i for i in range(x.ndim) if i != axis)
    bshape = [1] * x.ndim
    bshape[axis] = x.shape[axis]
    if fix_gamma:
        gamma = jnp.ones_like(gamma)
    if training and not use_global_stats:
        mean = jnp.mean(x.astype(jnp.float32), axis=red_axes)
        var = jnp.var(x.astype(jnp.float32), axis=red_axes)
        new_mean = momentum * moving_mean + (1 - momentum) * mean
        new_var = momentum * moving_var + (1 - momentum) * var
    else:
        mean, var = moving_mean, moving_var
        new_mean, new_var = moving_mean, moving_var
    inv = lax.rsqrt(var + eps).astype(x.dtype)
    out = (x - mean.reshape(bshape).astype(x.dtype)) * inv.reshape(bshape)
    out = out * gamma.reshape(bshape).astype(x.dtype) + beta.reshape(bshape).astype(x.dtype)
    return out, new_mean, new_var


class _PallasDisabled(threading.local):
    def __init__(self):
        self.depth = 0


_pallas_disabled = _PallasDisabled()  # per-thread depth; see no_pallas()


class no_pallas:
    """Disable every Pallas fused-kernel dispatch inside the context
    (norms, fused CE, flash attention) so tracing produces a
    backend-portable jaxpr of plain lax ops. Used by the ONNX exporter:
    ``pallas_call`` has no ONNX translation, while the jnp fallback
    paths these sites already maintain translate cleanly. Re-entrant,
    and thread-LOCAL: an export in one thread must not knock another
    thread's training step off the fused kernels."""

    def __enter__(self):
        _pallas_disabled.depth += 1
        return self

    def __exit__(self, *exc):
        _pallas_disabled.depth -= 1
        return False


def _in_mesh_scope() -> bool:
    from ..parallel.mesh import current_mesh

    return current_mesh() is not None


def _tpu_kernels_selected() -> bool:
    """The one selection rule for the compiled Pallas kernels: the TPU
    backend, outside :class:`no_pallas`, and outside a mesh scope. A
    program traced inside ``parallel.use_mesh`` (``Trainer.shard``,
    ``LLMEngine(mesh=)``) is partitioned by GSPMD, and the chip's
    compiler refuses it with a kernel inside ("Mosaic kernels cannot be
    automatically partitioned"); there the XLA-op paths are taken, which
    the partitioner can split. (A ``shard_map`` over the head axis would
    keep the attention kernels under ``tp``: ROADMAP S3.)"""
    return (not _pallas_disabled.depth and jax.default_backend() == "tpu"
            and not _in_mesh_scope())


def layer_norm(x, gamma, beta, axis=-1, eps=1e-5):
    """LayerNorm (reference src/operator/nn/layer_norm.cc).

    Last-axis rows ≤8k on TPU run the fused Pallas kernel
    (ops/pallas/layer_norm.py): one HBM read per element instead of
    re-reading the row for each reduction. Other axes/widths: jnp."""
    ax = axis if axis >= 0 else x.ndim + axis
    if (ax == x.ndim - 1 and x.shape[-1] <= 8192
            and gamma.ndim == 1 and gamma.shape[0] == x.shape[-1]
            and beta.ndim == 1 and beta.shape[0] == x.shape[-1]
            and _tpu_kernels_selected()):
        from .pallas.layer_norm import fused_layer_norm
        shp = x.shape
        return fused_layer_norm(
            x.reshape(-1, shp[-1]), gamma, beta, float(eps)).reshape(shp)
    mean = jnp.mean(x, axis=axis, keepdims=True)
    var = jnp.var(x, axis=axis, keepdims=True)
    out = (x - mean) * lax.rsqrt(var + eps)
    bshape = [1] * x.ndim
    bshape[axis] = x.shape[axis]
    return out * gamma.reshape(bshape) + beta.reshape(bshape)


def group_norm(x, gamma, beta, num_groups=1, eps=1e-5):
    """GroupNorm over NCHW (reference src/operator/nn/group_norm.cc)."""
    n, c = x.shape[:2]
    orig = x.shape
    xg = x.reshape((n, num_groups, c // num_groups) + x.shape[2:])
    axes = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.var(xg, axis=axes, keepdims=True)
    xg = (xg - mean) * lax.rsqrt(var + eps)
    x = xg.reshape(orig)
    bshape = (1, c) + (1,) * (x.ndim - 2)
    return x * gamma.reshape(bshape) + beta.reshape(bshape)


def instance_norm(x, gamma, beta, eps=1e-5):
    """InstanceNorm (reference src/operator/instance_norm.cc)."""
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * lax.rsqrt(var + eps)
    bshape = (1, x.shape[1]) + (1,) * (x.ndim - 2)
    return out * gamma.reshape(bshape) + beta.reshape(bshape)


def rms_norm(x, gamma, axis=-1, eps=1e-6):
    """RMSNorm — modern-transformer extension (no reference counterpart).
    Fused Pallas kernel on TPU for last-axis rows ≤8k (see layer_norm)."""
    ax = axis if axis >= 0 else x.ndim + axis
    if (ax == x.ndim - 1 and x.shape[-1] <= 8192
            and getattr(gamma, "ndim", 0) == 1
            and gamma.shape[0] == x.shape[-1]
            and _tpu_kernels_selected()):
        from .pallas.layer_norm import fused_rms_norm
        shp = x.shape
        return fused_rms_norm(
            x.reshape(-1, shp[-1]), gamma, float(eps)).reshape(shp)
    ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=axis, keepdims=True)
    out = x * lax.rsqrt(ms + eps).astype(x.dtype)
    return out * gamma


def l2_normalization(x, eps=1e-10, mode="instance"):
    if mode == "instance":
        axes = tuple(range(1, x.ndim))
    elif mode == "channel":
        axes = (1,)
    else:  # spatial
        axes = tuple(range(2, x.ndim))
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes, keepdims=True) + eps)
    return x / norm


# ---------------------------------------------------------------------------
# activations / softmax
# ---------------------------------------------------------------------------
def activation(x, act_type="relu"):
    """reference src/operator/nn/activation.cc"""
    if act_type == "relu":
        return jax.nn.relu(x)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(x)
    if act_type == "tanh":
        return jnp.tanh(x)
    if act_type == "softrelu":
        return jax.nn.softplus(x)
    if act_type == "softsign":
        return jax.nn.soft_sign(x)
    if act_type == "log_sigmoid":
        return jax.nn.log_sigmoid(x)
    if act_type == "mish":
        return x * jnp.tanh(jax.nn.softplus(x))
    if act_type in ("silu", "swish"):
        return jax.nn.silu(x)
    if act_type == "gelu":
        return jax.nn.gelu(x, approximate=False)
    if act_type == "gelu_tanh":
        return jax.nn.gelu(x, approximate=True)
    raise ValueError(f"unknown activation {act_type}")


def leaky_relu(x, gamma=None, act_type="leaky", slope=0.25, lower_bound=0.125, upper_bound=0.334, key=None, training=True):
    """reference src/operator/leaky_relu.cc (leaky/prelu/elu/selu/gelu/rrelu)."""
    if act_type == "leaky":
        return jnp.where(x >= 0, x, slope * x)
    if act_type == "prelu":
        g = gamma
        if g.ndim < x.ndim and g.ndim == 1:
            g = g.reshape((1, -1) + (1,) * (x.ndim - 2))
        return jnp.where(x >= 0, x, g * x)
    if act_type == "elu":
        return jnp.where(x >= 0, x, slope * (jnp.exp(x) - 1))
    if act_type == "selu":
        alpha, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(x >= 0, x, alpha * (jnp.exp(x) - 1))
    if act_type == "gelu":
        return jax.nn.gelu(x, approximate=False)
    if act_type == "rrelu":
        if training and key is not None:
            u = jax.random.uniform(key, x.shape, jnp.float32, lower_bound, upper_bound).astype(x.dtype)
        else:
            u = jnp.asarray((lower_bound + upper_bound) / 2.0, x.dtype)
        return jnp.where(x >= 0, x, u * x)
    raise ValueError(f"unknown leaky_relu type {act_type}")


def softmax(x, axis=-1, temperature=None, length=None):
    """reference src/operator/nn/softmax.cc (with optional length masking)."""
    if temperature is not None and temperature != 1.0:
        x = x / temperature
    if length is not None:
        mask = jnp.arange(x.shape[axis]) < jnp.expand_dims(length, -1)
        shape = [1] * x.ndim
        shape[0] = x.shape[0]
        shape[axis] = x.shape[axis]
        mask = mask.reshape(shape)
        x = jnp.where(mask, x, -jnp.inf)
        out = jax.nn.softmax(x, axis=axis)
        return jnp.where(mask, out, 0.0)
    return jax.nn.softmax(x, axis=axis)


def log_softmax(x, axis=-1, temperature=None):
    if temperature is not None and temperature != 1.0:
        x = x / temperature
    return jax.nn.log_softmax(x, axis=axis)


def softmax_cross_entropy(data, label, per_example=False):
    """Sparse-label softmax cross entropy (reference
    src/operator/loss_binary_op.cc:30 ``softmax_cross_entropy``).

    ``data`` (N, V) logits, ``label`` (N,) class indices. The default
    matches the reference contract: a shape-(1,) SUM over rows of
    ``-log(max(softmax(data)[i, label[i]], 1e-8))``
    (loss_binary_op-inl.h:44-57). ``per_example=True`` returns the
    unclamped per-row NLL instead (the gluon-loss building block). Rows
    with a negative label contribute 0 (ignore-index).

    Two programs whoever calls: one jitted forward ``(data, label) ->
    (loss, lse)`` and one jitted pullback ``(data, label, lse, g) ->
    dlogits``, the rules of one ``custom_vjp``. An eager caller
    (``apply_op`` jits nothing) launches exactly these, and the only new
    (N, V) array is ``dlogits``; inside a hybridized block or under a
    mesh they are inlined into the caller's program.
    """
    if data.ndim != 2 or label.ndim != 1:
        raise ValueError(
            f"softmax_cross_entropy expects (N, V) data and (N,) label, "
            f"got {data.shape} / {label.shape}")
    return _softmax_ce(data, label.astype(jnp.int32), bool(per_example))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _softmax_ce(data, lab, per_example):
    return _ce_forward(data, lab, per_example)[0]


@functools.partial(jax.jit, static_argnums=(2,))
def _ce_forward(data, lab, per_example):
    """The forward program: ``(loss, lse)`` in two passes over ``data``
    and nothing else shaped (N, V). The row reduction is XLA's
    ``logsumexp`` (a max pass, then an exp-sum pass); the label's logit is
    a masked row sum, which fuses into the second pass, needs no gather
    and splits over a sharded vocabulary. No Pallas kernel: the chip keeps
    an (8192, 50257) array column-major, a kernel would first copy all of
    it into rows, and XLA's two passes over the array as it lies are
    faster than that copy (PERF.md section 6, PR 32)."""
    x = data.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(x, axis=-1)
    hit = lax.broadcasted_iota(jnp.int32, x.shape, 1) == lab[:, None]
    nll = jnp.where(lab >= 0, lse - jnp.sum(jnp.where(hit, x, 0.0), axis=-1),
                    0.0)
    if per_example:
        return nll, lse  # f32: per-row NLL keeps full precision
    # the reference's 1e-8 floor, in the value only (see _ce_backward); a
    # masked label (prob exactly 0, nll=+inf) reads the finite cap
    nll = jnp.minimum(nll, -jnp.log(jnp.float32(1e-8)))
    return jnp.sum(nll, keepdims=True).astype(data.dtype), lse


@jax.jit
def _ce_backward(data, lab, lse, g):
    """The pullback program: ``(softmax - onehot) * g`` from the saved
    lse, which XLA fuses into one pass that reads ``data`` and writes
    ``dlogits`` in its dtype; the iota, the comparison and the float32
    softmax never reach HBM. ``g`` is (1,) for the sum and (N,) per row.

    The reference backward (loss_binary_op-inl.h:85-106) is
    softmax-onehot UNCONDITIONALLY: the forward's 1e-8 floor is the
    identity here and must not zero dlogits on confidently-wrong rows."""
    p = jnp.exp(data.astype(jnp.float32) - lse[:, None])
    hit = lax.broadcasted_iota(jnp.int32, data.shape, 1) == lab[:, None]
    gr = jnp.where(lab >= 0, g.astype(jnp.float32), 0.0)
    return ((p - hit.astype(jnp.float32)) * gr[:, None]).astype(data.dtype)


def _softmax_ce_fwd(data, lab, per_example):
    out, lse = _ce_forward(data, lab, per_example)
    return out, (data, lab, lse)


def _softmax_ce_bwd(per_example, res, g):
    data, lab, lse = res
    return (_ce_backward(data, lab, lse, g),
            onp.zeros(lab.shape, jax.dtypes.float0))


_softmax_ce.defvjp(_softmax_ce_fwd, _softmax_ce_bwd)


def masked_softmax(x, mask, axis=-1, temperature=1.0):
    x = x / temperature
    neg = jnp.asarray(jnp.finfo(x.dtype if jnp.issubdtype(x.dtype, jnp.floating) else jnp.float32).min, x.dtype)
    masked = jnp.where(mask, x, neg)
    out = jax.nn.softmax(masked, axis=axis)
    return jnp.where(mask, out, 0.0)


def masked_log_softmax(x, mask, axis=-1, temperature=1.0):
    x = x / temperature
    neg = jnp.asarray(jnp.finfo(jnp.float32).min, x.dtype)
    masked = jnp.where(mask, x, neg)
    out = jax.nn.log_softmax(masked, axis=axis)
    return jnp.where(mask, out, -jnp.inf)


def softmin(x, axis=-1):
    return jax.nn.softmax(-x, axis=axis)


# ---------------------------------------------------------------------------
# dropout
# ---------------------------------------------------------------------------
def dropout(x, p=0.5, key=None, training=True, axes=None, mode="training"):
    """reference src/operator/nn/dropout.cc"""
    if not training or p <= 0 or key is None:
        return x
    shape = list(x.shape)
    if axes:
        for ax in range(len(shape)):
            if ax not in axes:
                shape[ax] = 1
    keep = 1.0 - p
    # a Python-float threshold would make bernoulli draw its uniform in
    # float64 under jax_enable_x64 (tpulint J002) — pin the draw to f32
    mask = jax.random.bernoulli(key, jnp.float32(keep), tuple(shape))
    return jnp.where(mask, x / keep, jnp.zeros_like(x))


# ---------------------------------------------------------------------------
# embedding / indexing ops
# ---------------------------------------------------------------------------
def embedding(indices, weight, sparse_grad=False):
    """reference src/operator/tensor/indexing_op.cc (Embedding)."""
    return jnp.take(weight, indices.astype(jnp.int32), axis=0)


def one_hot(indices, depth, on_value=1.0, off_value=0.0, dtype="float32"):
    return jax.nn.one_hot(indices.astype(jnp.int32), depth, dtype=jnp.dtype(dtype)) * (on_value - off_value) + off_value


def pick(data, index, axis=-1, keepdims=False, mode="clip"):
    """reference src/operator/tensor/broadcast_reduce_op_index.cc pick"""
    idx = jnp.clip(index.astype(jnp.int32), 0, data.shape[axis] - 1)
    out = jnp.take_along_axis(data, jnp.expand_dims(idx, axis), axis=axis)
    if not keepdims:
        out = jnp.squeeze(out, axis=axis)
    return out


def topk(data, k=1, axis=-1, ret_typ="indices", is_ascend=False, dtype="float32"):
    """reference src/operator/tensor/ordering_op.cc: k LARGEST entries by
    default, k smallest with ``is_ascend=True``."""
    moved = jnp.moveaxis(data, axis, -1)
    if is_ascend:
        idxs = jnp.argsort(moved, axis=-1)[..., :k]
        vals = jnp.take_along_axis(moved, idxs, axis=-1)
    else:
        vals, idxs = lax.top_k(moved, k)
    vals = jnp.moveaxis(vals, -1, axis)
    idxs = jnp.moveaxis(idxs, -1, axis)
    if ret_typ == "indices":
        return idxs.astype(jnp.dtype(dtype))
    if ret_typ == "value":
        return vals
    if ret_typ == "both":
        return vals, idxs.astype(jnp.dtype(dtype))
    if ret_typ == "mask":
        mask = jnp.zeros(jnp.moveaxis(data, axis, -1).shape, jnp.int32)
        mask = mask.at[..., :1].set(0)  # placeholder; mask built below
        oh = jax.nn.one_hot(jnp.moveaxis(idxs, axis, -1), data.shape[axis], dtype=jnp.int32).sum(-2)
        return jnp.moveaxis(oh, -1, axis)
    raise ValueError(ret_typ)


def gather_nd(data, indices):
    """reference src/operator/tensor/indexing_op.cc gather_nd"""
    idx = tuple(indices[i].astype(jnp.int32) for i in range(indices.shape[0]))
    return data[idx]


def scatter_nd(data, indices, shape):
    idx = tuple(indices[i].astype(jnp.int32) for i in range(indices.shape[0]))
    out = jnp.zeros(shape, data.dtype)
    return out.at[idx].add(data)


# ---------------------------------------------------------------------------
# sequence ops (reference src/operator/sequence_*.cc)
# ---------------------------------------------------------------------------
def sequence_mask(data, sequence_length=None, use_sequence_length=False, value=0.0, axis=0):
    if not use_sequence_length or sequence_length is None:
        return data
    maxlen = data.shape[axis]
    steps = jnp.arange(maxlen)
    if axis == 0:
        mask = steps[:, None] < sequence_length[None, :]
        mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
    else:  # axis == 1
        mask = steps[None, :] < sequence_length[:, None]
        mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
    return jnp.where(mask, data, jnp.asarray(value, data.dtype))


def sequence_last(data, sequence_length=None, use_sequence_length=False, axis=0):
    if not use_sequence_length or sequence_length is None:
        return jnp.take(data, -1, axis=axis)
    idx = (sequence_length - 1).astype(jnp.int32)
    if axis == 0:
        batch = jnp.arange(data.shape[1])
        return data[idx, batch]
    batch = jnp.arange(data.shape[0])
    return data[batch, idx]


def sequence_reverse(data, sequence_length=None, use_sequence_length=False, axis=0):
    if not use_sequence_length or sequence_length is None:
        return jnp.flip(data, axis=axis)
    if axis != 0:
        # masked path is written for TNC (time on axis 0); transpose around
        data = jnp.swapaxes(data, 0, axis)
        out = sequence_reverse(data, sequence_length, True, axis=0)
        return jnp.swapaxes(out, 0, axis)
    maxlen = data.shape[axis]
    steps = jnp.arange(maxlen)
    # reverse only the first seq_len elements per batch (axis=0 layout TNC)
    rev_idx = jnp.where(
        steps[:, None] < sequence_length[None, :],
        sequence_length[None, :] - 1 - steps[:, None],
        steps[:, None],
    ).astype(jnp.int32)
    batch = jnp.arange(data.shape[1])[None, :]
    return data[rev_idx, batch]


# ---------------------------------------------------------------------------
# transformer attention primitives (reference src/operator/contrib/
# transformer.cc:650 interleaved_matmul_selfatt_qk, :693 *_valatt, and the
# encdec variants) — layout (seq, batch, heads * 3 * head_dim) with Q/K/V
# interleaved per head, exactly the reference's memory layout so ported
# code and weights work unchanged.
# ---------------------------------------------------------------------------
def _split_selfatt(qkv, heads):
    l, b, hidden = qkv.shape
    d = hidden // (3 * heads)
    x = qkv.reshape(l, b, heads, 3, d)
    return x[..., 0, :], x[..., 1, :], x[..., 2, :]  # (L, B, H, D) each


def interleaved_matmul_selfatt_qk(queries_keys_values, heads):
    """Scores (B*H, Lq, Lk) from interleaved QKV, scaled by 1/sqrt(D)."""
    q, k, _ = _split_selfatt(queries_keys_values, heads)
    l, b, h, d = q.shape
    scale = 1.0 / (d ** 0.5)
    s = jnp.einsum("qbhd,kbhd->bhqk", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    return s.reshape(b * h, l, l).astype(queries_keys_values.dtype)


def interleaved_matmul_selfatt_valatt(queries_keys_values, attention, heads):
    """(Lq, B, H*D) = attention @ V from interleaved QKV."""
    _, _, v = _split_selfatt(queries_keys_values, heads)
    l, b, h, d = v.shape
    att = attention.reshape(b, h, l, l).astype(jnp.float32)
    out = jnp.einsum("bhqk,kbhd->qbhd", att, v.astype(jnp.float32))
    return out.reshape(l, b, h * d).astype(queries_keys_values.dtype)


def interleaved_matmul_encdec_qk(queries, keys_values, heads):
    """Scores (B*H, Lq, Lk): q (Lq, B, H*D); kv interleaved (Lk, B, H*2*D)."""
    lq, b, hidden = queries.shape
    d = hidden // heads
    q = queries.reshape(lq, b, heads, d)
    lk = keys_values.shape[0]
    kv = keys_values.reshape(lk, b, heads, 2, d)
    k = kv[..., 0, :]
    scale = 1.0 / (d ** 0.5)
    s = jnp.einsum("qbhd,kbhd->bhqk", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    return s.reshape(b * heads, lq, lk).astype(queries.dtype)


def interleaved_matmul_encdec_valatt(keys_values, attention, heads):
    lk, b, hidden = keys_values.shape
    d = hidden // (2 * heads)
    kv = keys_values.reshape(lk, b, heads, 2, d)
    v = kv[..., 1, :]
    lq = attention.shape[1]
    att = attention.reshape(b, heads, lq, lk).astype(jnp.float32)
    out = jnp.einsum("bhqk,kbhd->qbhd", att, v.astype(jnp.float32))
    return out.reshape(lq, b, heads * d).astype(keys_values.dtype)


# --- paged KV-cache attention (the serving.llm decode path) ----------------
# Decode is HBM-bandwidth bound: every generated token re-reads the whole
# cache. int8 storage halves those bytes vs bf16 (4x vs f32). Layout trick:
# the per-(batch, head, position) f32 scale is bitcast into 4 extra int8
# bytes on the feature axis — the cache stays ONE int8 array, so every
# consumer (lax.scan carries, block-pool gathers, donation) works
# unchanged. Granularity: one scale per token per head — the standard
# KV-quant setting; round-trip error ~0.4% rms. (Canonical home of the
# helpers ``gluon.nn.transformer`` re-exports.)
_KV_SCALE_BYTES = 4


# The scale's four bytes (little-endian, the f32's own bit pattern) are
# taken apart and put together with 32-bit integer ops, not with a
# 4 x int8 <-> f32 bitcast: Mosaic has no such bitcast, and these two
# functions are the ONE definition of the layout — the Pallas kernel
# (ops/pallas/paged_attention.py) calls them inside the kernel body.
def kv_cache_quantize(t):
    """(..., D) float -> (..., D+4) int8 [values | f32 scale bytes]."""
    t = t.astype(jnp.float32)
    amax = jnp.max(jnp.abs(t), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    parts = [jnp.clip(jnp.round(t / scale), -127, 127).astype(jnp.int32)]
    bits = jax.lax.bitcast_convert_type(scale, jnp.int32)     # (..., 1)
    for i in range(_KV_SCALE_BYTES):
        byte = (bits >> (8 * i)) & 0xFF
        parts.append(byte - ((byte & 0x80) << 1))             # as signed
    return jnp.concatenate(parts, axis=-1).astype(jnp.int8)


def kv_cache_dequantize(c, dtype):
    """(..., D+4) int8 -> (..., D) ``dtype``."""
    d = c.shape[-1] - _KV_SCALE_BYTES
    w = c.astype(jnp.int32)
    b = w[..., d:] & 0xFF                                     # (..., 4)
    bits = (b[..., 0:1] | (b[..., 1:2] << 8) | (b[..., 2:3] << 16)
            | (b[..., 3:4] << 24))
    scale = jax.lax.bitcast_convert_type(bits, jnp.float32)   # (..., 1)
    return (w[..., :d].astype(jnp.float32) * scale).astype(dtype)


# --- the ONE KV-pool layout -------------------------------------------------
# A pool is ``(L, NB, bs, H*D')``: layer, block, slot, then one ROW per
# token with all heads side by side (head ``h`` in columns
# ``[h*D', (h+1)*D')``; ``D' = D`` for float pools, ``D + 4`` for int8).
# Why: three parties touch a pool inside the decode program — the XLA
# scatter that stores the step's rows, the Pallas kernel that reads
# blocks, the compiler's layout for the donated parameter and result. On
# ``(L, NB, H, bs, D)`` each chose its own (a 64-wide minor dimension
# wastes half of the 128 lanes) and every layer converted a whole pool
# twice: 72% of the decode step (PERF.md, PR 27). Rows of ``H*D`` = 768
# or 1,280 are whole multiples of 128 lanes: all three agree on plain
# row-major and the donated pool is updated in place. These two functions
# alone turn per-head tensors into rows and back; everyone else indexes
# blocks (axis 1) and slots (axis 2).
def kv_pool_rows(t):
    """``(..., H, D')`` per-head tensors -> ``(..., H*D')`` pool rows."""
    return t.reshape(*t.shape[:-2], t.shape[-2] * t.shape[-1])


def kv_pool_heads(rows, heads):
    """``(..., H*D')`` pool rows -> ``(..., H, D')`` per-head tensors."""
    return rows.reshape(*rows.shape[:-1], heads, rows.shape[-1] // heads)


def _gather_lanes(pool, layer, block_table, heads, dtype):
    """Each lane's blocks of one layer as a dense view:
    ``(L, NB, bs, H*D')`` -> ``(R, H, MB*bs, D)`` in ``dtype`` (int8 rows
    dequantized after the gather)."""
    r, mb = block_table.shape
    rows = pool[layer, block_table]             # (R, MB, bs, H*D')
    c = kv_pool_heads(rows.reshape(r, mb * rows.shape[2], -1), heads)
    c = c.transpose(0, 2, 1, 3)                 # (R, H, MB*bs, D')
    if pool.dtype == jnp.int8:                  # int8 rides HBM; math in q's
        c = kv_cache_dequantize(c, dtype)
    return c


def paged_attention(q, k_pool, v_pool, block_table, lengths, layer=0,
                    use_kernel=None):
    """Single-token decode attention through a paged KV block pool.

    The continuous-batching decode core (``serving.llm``): each lane's
    KV history lives in fixed-size blocks scattered across a shared pool
    and is gathered through its block table INSIDE the compiled step —
    the pool shape is static, so admission/retirement/sequence growth
    never retrace.

    Parameters
    ----------
    q : (R, H, D) — one query token per decode lane.
    k_pool, v_pool : (L, NB, bs, H*D') — the WHOLE block pools of every
        layer, in the one pool layout (:func:`kv_pool_rows`: a row per
        token, heads side by side, so that a row is a whole multiple of
        128 lanes at GPT-2 widths and the scatter, the kernel and the
        donated buffer agree on row-major); ``D' = D`` for float pools,
        ``D + 4`` for int8 pools (:func:`kv_cache_quantize` layout,
        dequantized per gather). No per-layer slice is ever taken: a
        slice of a pool is a copy of a pool.
    block_table : (R, MB) int32 — lane -> pool-block indices, logical
        position ``p`` lives in ``block_table[r, p // bs]`` slot
        ``p % bs``. Entries past a lane's context may point anywhere
        live (a trash block): they are masked by ``lengths``.
    lengths : (R,) int32 — valid positions per lane (current token
        included, written by the caller before attending).
    layer : int or () int32 — which layer's blocks to read.
    use_kernel : None | bool — None auto-selects the Pallas TPU kernel
        on the TPU backend for float AND int8 pools (int8 — the engine
        default — dequantizes the bitcast-scale layout inside the
        kernel after the block DMA); the jnp gather path (exactly the
        dense ``forward_step`` arithmetic, so greedy decode is
        token-identical to the dense cache) everywhere else.

    Returns (R, H, D) in the pool's value dtype (float pools) or ``q``'s
    dtype (int8 pools).
    """
    _, h, d = q.shape
    bs = k_pool.shape[2]
    mb = block_table.shape[1]
    if use_kernel is None:
        use_kernel = _tpu_kernels_selected()
    if use_kernel:
        from .pallas.paged_attention import paged_attention_kernel

        return paged_attention_kernel(q, k_pool, v_pool, block_table,
                                      lengths, layer)
    if k_pool.dtype != jnp.int8 and k_pool.shape[-1] != h * d:
        return _paged_attention_grouped(q, k_pool, v_pool, block_table,
                                        lengths, layer)
    keys = _gather_lanes(k_pool, layer, block_table, h, q.dtype)
    vals = _gather_lanes(v_pool, layer, block_table, h, q.dtype)
    # the dense MultiHeadAttention.forward_step arithmetic with T=1 and
    # the causal row-mask replaced by the per-lane length mask — kept
    # operation-for-operation identical so paged greedy decode emits the
    # same tokens as the dense cache path
    scores = jnp.einsum("rhd,rhld->rhl", q, keys).astype(jnp.float32)
    scores = scores / onp.sqrt(d).astype(onp.float32)
    pos = jnp.arange(mb * bs)[None, :]
    live = pos < lengths[:, None].astype(jnp.int32)
    scores = jnp.where(live[:, None, :], scores, -jnp.inf)
    attn = jax.nn.softmax(scores, axis=-1).astype(vals.dtype)
    return jnp.einsum("rhl,rhld->rhd", attn, vals)


def _paged_attention_grouped(q, k_pool, v_pool, block_table, lengths,
                             layer):
    """The jnp path of :func:`paged_attention` for grouped K/V heads:
    pool rows hold ``Hkv = row / D`` heads and query head ``i`` reads K/V
    head ``i // (H / Hkv)``. Scores and sums in float32, the weights
    cast to the values' dtype as in the ungrouped path."""
    r, h, d = q.shape
    hkv = k_pool.shape[-1] // d
    if hkv < 1 or h % hkv or hkv * d != k_pool.shape[-1]:
        raise ValueError(f"{h} query heads of {d} do not divide over pool "
                         f"rows of {k_pool.shape[-1]}")
    keys = _gather_lanes(k_pool, layer, block_table, hkv, q.dtype)
    vals = _gather_lanes(v_pool, layer, block_table, hkv, q.dtype)
    qg = q.reshape(r, hkv, h // hkv, d)
    scores = jnp.einsum("rjgd,rjld->rjgl", qg, keys,
                        preferred_element_type=jnp.float32)
    scores = scores / onp.sqrt(d).astype(onp.float32)
    live = jnp.arange(keys.shape[2])[None, :] \
        < lengths[:, None].astype(jnp.int32)
    scores = jnp.where(live[:, None, None, :], scores, -jnp.inf)
    attn = jax.nn.softmax(scores, axis=-1).astype(vals.dtype)
    out = jnp.einsum("rjgl,rjld->rjgd", attn, vals,
                     preferred_element_type=jnp.float32)
    return out.reshape(r, h, d).astype(vals.dtype)


def paged_attention_multi(q, k_pool, v_pool, block_table, positions,
                          layer=0, use_kernel=None):
    """Multi-token paged decode attention: ``q`` is (R, T, H, D), lane
    ``r``'s query ``t`` at absolute position ``positions[r] + t``; pools
    and ``layer`` as in :func:`paged_attention` (the whole
    ``(L, NB, bs, H*D')`` pools, never a layer's slice).

    The speculative-verify / suffix-prefill hot path. The point over
    calling :func:`paged_attention` on R*T virtual lanes is the READ
    amortization: each lane's blocks are gathered (and int8-dequantized)
    ONCE, and all T queries attend against that one dense view with
    per-(lane, t) length masks — the length mask IS the causal mask.
    Single-token decode re-reads the whole cache per token; a verify
    chunk reads it once per K+1 tokens, which is the roofline win the
    ISSUE 11 tentpole banks (HBM bytes on TPU, gather+dequant cost on
    CPU). On TPU the scalar-prefetch Pallas kernel path is used instead
    (block DMAs from HBM, no dense per-lane cache materialized).

    Row arithmetic is operation-for-operation :func:`paged_attention`'s,
    so greedy verify stays token-identical to single-token decode.

    Returns (R, T, H, D) in the pool's value dtype (float pools) or
    ``q``'s dtype (int8 pools).
    """
    r, t, h, d = q.shape
    bs = k_pool.shape[2]
    mb = block_table.shape[1]
    pos = positions.astype(jnp.int32)
    abs_pos = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
    if use_kernel is None:
        use_kernel = _tpu_kernels_selected()
    if use_kernel:
        from .pallas.paged_attention import paged_attention_kernel

        out = paged_attention_kernel(
            q.reshape(r * t, h, d), k_pool, v_pool,
            jnp.repeat(block_table, t, axis=0),
            (abs_pos + 1).reshape(-1), layer)
        return out.reshape(r, t, h, d)
    keys = _gather_lanes(k_pool, layer, block_table, h, q.dtype)   # ONCE
    vals = _gather_lanes(v_pool, layer, block_table, h, q.dtype)
    scores = jnp.einsum("rthd,rhld->rthl", q, keys).astype(jnp.float32)
    scores = scores / onp.sqrt(d).astype(onp.float32)
    live = jnp.arange(mb * bs)[None, None, :] < (abs_pos + 1)[:, :, None]
    scores = jnp.where(live[:, :, None, :], scores, -jnp.inf)
    attn = jax.nn.softmax(scores, axis=-1).astype(vals.dtype)
    return jnp.einsum("rthl,rhld->rthd", attn, vals)


def attend(q, k, v, heads, causal=False, mask=None, dropout=0.0, key=None,
           training=False):
    """Pure multi-head attention over (B, L, H*D) projections — the single
    attention core behind nn.MultiHeadAttention and npx.multi_head_attention.

    No mask and no dropout: the Pallas flash kernel (TPU; interpreter on
    CPU). Otherwise: the masked jnp path with fp32 softmax (the flash
    kernel takes only causal + length masks)."""
    b, lq, hidden = q.shape
    d = hidden // heads
    qh = q.reshape(b, lq, heads, d).transpose(0, 2, 1, 3)
    kh = k.reshape(b, k.shape[1], heads, d).transpose(0, 2, 1, 3)
    vh = v.reshape(b, v.shape[1], heads, d).transpose(0, 2, 1, 3)
    # the flash kernel: compiled on the TPU (not inside a mesh scope, see
    # _tpu_kernels_selected), interpreted — plain XLA ops — elsewhere
    if mask is None and not (dropout and training) \
            and not _pallas_disabled.depth \
            and not (jax.default_backend() == "tpu" and _in_mesh_scope()):
        from .pallas.flash_attention import flash_attention

        out = flash_attention(qh, kh, vh, causal=causal)
    else:
        scale = d ** -0.5
        s = jnp.einsum("bhqd,bhkd->bhqk", qh.astype(jnp.float32),
                       kh.astype(jnp.float32)) * scale
        if causal:
            cm = jnp.tril(jnp.ones((lq, kh.shape[2]), dtype=bool),
                          k=kh.shape[2] - lq)
            s = jnp.where(cm, s, -1e30)
        if mask is not None:
            if mask.dtype == jnp.bool_:
                s = jnp.where(mask, s, -1e30)
            else:
                s = s + mask.astype(jnp.float32)
        p = jax.nn.softmax(s, axis=-1)
        if dropout and training:
            keep = jax.random.bernoulli(key, 1.0 - dropout, p.shape)
            p = jnp.where(keep, p / (1.0 - dropout), 0.0)
        out = jnp.einsum("bhqk,bhkd->bhqd", p, vh.astype(jnp.float32))
        out = out.astype(q.dtype)
    return out.transpose(0, 2, 1, 3).reshape(b, lq, hidden)
