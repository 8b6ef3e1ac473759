"""``mx.npx`` — operators beyond the NumPy standard (nn ops, control, util).

Parity: reference ``python/mxnet/numpy_extension/`` which exposes the
``src/operator/nn`` and indexing/sequence kernels to the np API. Every op
dispatches through apply_op (autograd-recorded, trace-transparent) onto the
pure jax implementations in :mod:`mxnet_tpu.ops.nn`.
"""
from __future__ import annotations

import contextlib
import threading

import jax
import jax.numpy as jnp
import numpy as onp

from ..base import dtype_from_any
from ..ndarray.ndarray import ndarray, _wrap, _unwrap
from ..ops import nn as _nn
from ..ops.dispatch import apply_op, is_training
from ..util import is_np_array, set_np, reset_np, use_np  # noqa: F401
from ..context import cpu, gpu, tpu, num_gpus, num_tpus, current_context  # noqa: F401


# ---------------------------------------------------------------------------
# RNG plumbing: eager ops draw fresh keys; traced (hybridized) code gets keys
# from the enclosing trace scope so dropout is reproducible & functional.
# ---------------------------------------------------------------------------
class _KeyScope(threading.local):
    def __init__(self):
        self.supplier = None


_key_scope = _KeyScope()


@contextlib.contextmanager
def rng_scope(supplier):
    """Install a key supplier (callable -> PRNGKey) for the duration of a
    trace; used by HybridBlock's cached-op tracing."""
    prev = _key_scope.supplier
    _key_scope.supplier = supplier
    try:
        yield
    finally:
        _key_scope.supplier = prev


def _next_key():
    if _key_scope.supplier is not None:
        return _key_scope.supplier()
    from ..numpy import random as _random

    return _random.new_key()


@contextlib.contextmanager
def functional_mode(key, training: bool):
    """Run the body as a pure function of ``key``: autograd recording off,
    the training flag pinned, and all RNG draws split deterministically
    from ``key``. The shared preamble of every functionalization seam
    (HybridBlock cached-op tracing, ``functionalize``, symbol executors).
    """
    from ..ops.dispatch import autograd_state as _st

    key_state = {"key": key}

    def supplier():
        key_state["key"], sub = jax.random.split(key_state["key"])
        return sub

    prev = (_st.recording, _st.training)
    _st.recording, _st.training = False, training
    try:
        with rng_scope(supplier):
            yield
    finally:
        _st.recording, _st.training = prev


def _call(fn, arrays, static=None, name=None, n_out=1):
    return apply_op(fn, arrays, static=static, n_out=n_out, name=name)


# ---------------------------------------------------------------------------
# nn ops
# ---------------------------------------------------------------------------
def fully_connected(x, weight, bias=None, num_hidden=None, no_bias=False, flatten=True):
    args = (x, weight) if bias is None or no_bias else (x, weight, bias)
    return _call(
        lambda *a: _nn.fully_connected(*a, flatten=flatten),
        args,
        name="FullyConnected",
    )


def convolution(x, weight, bias=None, kernel=None, stride=1, dilate=1, pad=0,
                num_filter=0, num_group=1, no_bias=False, layout="NCHW"):
    static = dict(stride=stride, dilate=dilate, pad=pad, num_group=num_group, layout=layout)
    if bias is None or no_bias:
        return _call(lambda x_, w_: _nn.convolution(x_, w_, None, **static), (x, weight), name="Convolution")
    return _call(lambda x_, w_, b_: _nn.convolution(x_, w_, b_, **static), (x, weight, bias), name="Convolution")


def deconvolution(x, weight, bias=None, stride=1, dilate=1, pad=0, adj=0,
                  num_filter=0, num_group=1, no_bias=False, layout="NCHW"):
    static = dict(stride=stride, dilate=dilate, pad=pad, adj=adj, num_group=num_group, layout=layout)
    if bias is None or no_bias:
        return _call(lambda x_, w_: _nn.deconvolution(x_, w_, None, **static), (x, weight), name="Deconvolution")
    return _call(lambda x_, w_, b_: _nn.deconvolution(x_, w_, b_, **static), (x, weight, bias), name="Deconvolution")


def pooling(x, kernel=1, pool_type="max", stride=None, pad=0, global_pool=False,
            count_include_pad=True, layout="NCHW", pooling_convention="valid"):
    ceil_mode = pooling_convention == "full"
    return _call(
        lambda v: _nn.pooling(v, kernel, pool_type, stride, pad, global_pool,
                              count_include_pad, layout, ceil_mode),
        (x,),
        name="Pooling",
    )


def batch_norm(x, gamma, beta, running_mean, running_var, eps=1e-5, momentum=0.9,
               fix_gamma=False, use_global_stats=False, output_mean_var=False, axis=1):
    """Functional batch_norm; updates running stats in-place on the passed
    ndarrays when training (matching the reference's aux-state mutation)."""
    training = is_training()
    out, new_mean, new_var = _call(
        lambda x_, g_, b_, m_, v_: _nn.batch_norm(
            x_, g_, b_, m_, v_, eps=eps, momentum=momentum, fix_gamma=fix_gamma,
            use_global_stats=use_global_stats, training=training, axis=axis,
        ),
        (x, gamma, beta, running_mean, running_var),
        name="BatchNorm",
        n_out=3,
    )
    if training and not use_global_stats:
        running_mean._set_data(_unwrap(new_mean))
        running_var._set_data(_unwrap(new_var))
    if output_mean_var:
        return out, new_mean, new_var
    return out


def layer_norm(x, gamma, beta, axis=-1, eps=1e-5):
    return _call(lambda x_, g_, b_: _nn.layer_norm(x_, g_, b_, axis=axis, eps=eps), (x, gamma, beta), name="LayerNorm")


def group_norm(x, gamma, beta, num_groups=1, eps=1e-5):
    return _call(lambda x_, g_, b_: _nn.group_norm(x_, g_, b_, num_groups=num_groups, eps=eps), (x, gamma, beta), name="GroupNorm")


def instance_norm(x, gamma, beta, eps=1e-5):
    return _call(lambda x_, g_, b_: _nn.instance_norm(x_, g_, b_, eps=eps), (x, gamma, beta), name="InstanceNorm")


def rms_norm(x, gamma, axis=-1, eps=1e-6):
    return _call(lambda x_, g_: _nn.rms_norm(x_, g_, axis=axis, eps=eps), (x, gamma), name="RMSNorm")


def l2_normalization(x, eps=1e-10, mode="instance"):
    return _call(lambda v: _nn.l2_normalization(v, eps=eps, mode=mode), (x,), name="L2Normalization")


def activation(x, act_type="relu"):
    return _call(lambda v: _nn.activation(v, act_type), (x,), name="Activation")


def leaky_relu(x, gamma=None, act_type="leaky", slope=0.25, lower_bound=0.125, upper_bound=0.334):
    key = _next_key() if act_type == "rrelu" and is_training() else None
    training = is_training()
    if act_type == "prelu":
        return _call(
            lambda v, g: _nn.leaky_relu(v, g, act_type=act_type, slope=slope),
            (x, gamma),
            name="LeakyReLU",
        )
    return _call(
        lambda v: _nn.leaky_relu(v, None, act_type=act_type, slope=slope,
                                 lower_bound=lower_bound, upper_bound=upper_bound,
                                 key=key, training=training),
        (x,),
        name="LeakyReLU",
    )


def softmax(x, axis=-1, temperature=None, length=None):
    if length is not None:
        return _call(lambda v, l: _nn.softmax(v, axis=axis, temperature=temperature, length=l), (x, length), name="softmax")
    return _call(lambda v: _nn.softmax(v, axis=axis, temperature=temperature), (x,), name="softmax")


def log_softmax(x, axis=-1, temperature=None):
    return _call(lambda v: _nn.log_softmax(v, axis=axis, temperature=temperature), (x,), name="log_softmax")


def softmax_cross_entropy(data, label, per_example=False):
    """Sparse-label CE over (N, V) logits: one jitted forward and one
    jitted pullback (ops/nn.py); reference loss_binary_op.cc contract."""
    return _call(
        lambda d, l: _nn.softmax_cross_entropy(d, l, per_example=per_example),
        (data, label), name="softmax_cross_entropy")


def masked_softmax(x, mask, axis=-1, temperature=1.0):
    return _call(lambda v, m: _nn.masked_softmax(v, m, axis=axis, temperature=temperature), (x, mask), name="masked_softmax")


def masked_log_softmax(x, mask, axis=-1, temperature=1.0):
    return _call(lambda v, m: _nn.masked_log_softmax(v, m, axis=axis, temperature=temperature), (x, mask), name="masked_log_softmax")


def dropout(x, p=0.5, axes=None, mode="training"):
    training = is_training() or mode == "always"
    if not training or p <= 0:
        return x
    key = _next_key()
    return _call(lambda v: _nn.dropout(v, p=p, key=key, training=True, axes=axes), (x,), name="Dropout")


def embedding(data, weight, input_dim=None, output_dim=None, dtype=None, sparse_grad=False):
    """reference src/operator/tensor/indexing_op.cc Embedding.

    With ``sparse_grad=True`` the weight cotangent is emitted as a
    row_sparse array holding only the looked-up rows (reference
    EmbeddingOpBackward's kRowSparseStorage output) — on TPU that means
    the backward touches nnz rows of HBM instead of the whole vocab, and
    lazy optimizers update just those rows. Applies on the eager tape
    only; under jit tracing the dense scatter-add path is used (XLA fuses
    it) exactly like the reference's symbolic mode.
    """
    if sparse_grad:
        import jax
        import jax.numpy as jnp

        from ..ndarray.ndarray import ndarray as _ndarr, _unwrap, _wrap
        from ..ndarray.sparse import RowSparseNDArray
        from ..ops.dispatch import TapeNode, _tracks_grad, autograd_state

        state = autograd_state
        ids_val = _unwrap(data)
        w_val = _unwrap(weight)
        traced = isinstance(ids_val, jax.core.Tracer) or isinstance(
            w_val, jax.core.Tracer)
        # the sparse cotangent can only be routed to a grad LEAF — a
        # tape-produced weight would feed the RowSparse ct into an
        # upstream jax.vjp pullback that only understands dense arrays
        if (state.recording and state.tape is not None and not traced
                and isinstance(weight, _ndarr)
                and id(weight) not in state.tape.producer
                and getattr(weight, "_grad_req", "null") != "null"
                and weight._grad is not None):
            ids32 = ids_val.astype(jnp.int32)
            out = _wrap(jnp.take(w_val, ids32, axis=0))
            ids_flat = ids32.reshape(-1)

            def vjp_fn(ct):
                vals = jnp.reshape(ct, (-1,) + tuple(w_val.shape[1:]))
                return (RowSparseNDArray(vals, ids_flat, w_val.shape),)

            node = TapeNode(vjp_fn, [weight], 1, "Embedding",
                            out_avals=[(out.shape, out.dtype)])
            state.tape.add(node, (out,))
            return out
    return _call(lambda i, w: _nn.embedding(i, w), (data, weight), name="Embedding")


def one_hot(data, depth, on_value=1.0, off_value=0.0, dtype="float32"):
    return _call(lambda i: _nn.one_hot(i, depth, on_value, off_value, dtype), (data,), name="one_hot")


def pick(data, index, axis=-1, keepdims=False, mode="clip"):
    return _call(lambda d, i: _nn.pick(d, i, axis=axis, keepdims=keepdims), (data, index), name="pick")


def topk(data, k=1, axis=-1, ret_typ="indices", is_ascend=False, dtype="float32"):
    n_out = 2 if ret_typ == "both" else 1
    return _call(
        lambda d: _nn.topk(d, k=k, axis=axis, ret_typ=ret_typ, is_ascend=is_ascend, dtype=dtype),
        (data,),
        name="topk",
        n_out=n_out,
    )


def gather_nd(data, indices):
    return _call(lambda d, i: _nn.gather_nd(d, i), (data, indices), name="gather_nd")


def scatter_nd(data, indices, shape):
    return _call(lambda d, i: _nn.scatter_nd(d, i, shape), (data, indices), name="scatter_nd")


def sequence_mask(data, sequence_length=None, use_sequence_length=False, value=0.0, axis=0):
    if sequence_length is None:
        return _call(lambda d: _nn.sequence_mask(d, None, use_sequence_length, value, axis), (data,), name="SequenceMask")
    return _call(
        lambda d, sl: _nn.sequence_mask(d, sl, use_sequence_length, value, axis),
        (data, sequence_length),
        name="SequenceMask",
    )


def sequence_last(data, sequence_length=None, use_sequence_length=False, axis=0):
    if sequence_length is None:
        return _call(lambda d: _nn.sequence_last(d, None, use_sequence_length, axis), (data,), name="SequenceLast")
    return _call(lambda d, sl: _nn.sequence_last(d, sl, use_sequence_length, axis), (data, sequence_length), name="SequenceLast")


def sequence_reverse(data, sequence_length=None, use_sequence_length=False, axis=0):
    if sequence_length is None:
        return _call(lambda d: _nn.sequence_reverse(d, None, use_sequence_length, axis), (data,), name="SequenceReverse")
    return _call(lambda d, sl: _nn.sequence_reverse(d, sl, use_sequence_length, axis), (data, sequence_length), name="SequenceReverse")


# ---------------------------------------------------------------------------
# misc util ops
# ---------------------------------------------------------------------------
def arange_like(data, start=0.0, step=1.0, repeat=1, axis=None):
    def fn(d):
        if axis is None:
            n = 1
            for s in d.shape:
                n *= s
            return (jnp.arange(n) * step + start).reshape(d.shape)
        n = d.shape[axis]
        return jnp.arange(n, dtype=jnp.float32) * step + start

    return _call(fn, (data,), name="arange_like")


def broadcast_like(lhs, rhs, lhs_axes=None, rhs_axes=None):
    return _call(lambda a, b: jnp.broadcast_to(a, b.shape), (lhs, rhs), name="broadcast_like")


def slice_like(data, shape_like, axes=()):
    import builtins

    def fn(d, s):
        # builtins.slice: the module-level `slice` is the npx op below
        slices = [builtins.slice(None)] * d.ndim
        use = axes if axes else range(d.ndim)
        for ax in use:
            slices[ax] = builtins.slice(0, s.shape[ax])
        return d[tuple(slices)]

    return _call(fn, (data, shape_like), name="slice_like")


def reshape_like(lhs, rhs):
    return _call(lambda a, b: a.reshape(b.shape), (lhs, rhs), name="reshape_like")


def batch_flatten(data):
    """Collapse all non-batch dims (reference npx.batch_flatten)."""
    return _call(lambda x: x.reshape(x.shape[0], -1), (data,),
                 name="batch_flatten")


def slice(data, begin, end, step=None):  # noqa: A001 - reference op name
    """Strided crop (reference npx.slice / src/operator/tensor/slice).
    ``begin``/``end`` entries may be None meaning from-start / to-end."""
    import builtins

    step = step or [1] * len(begin)
    idx = tuple(builtins.slice(b, e, s)
                for b, e, s in zip(begin, end, step))
    return _call(lambda x: x[idx], (data,), name="slice")


def shape_array(data):
    return _wrap(jnp.asarray(onp.asarray(data.shape, onp.int64)))


def waitall():
    from .. import engine

    engine.waitall()


def load(fname):
    from ..serialization import load as _load

    return _load(fname)


def save(fname, data):
    from ..serialization import save as _save

    return _save(fname, data)


def sigmoid(x):
    return _call(jax.nn.sigmoid, (x,), name="sigmoid")


def relu(x):
    return _call(jax.nn.relu, (x,), name="relu")


def gelu(x, approximate=True):
    return _call(lambda v: jax.nn.gelu(v, approximate=approximate), (x,), name="gelu")


def erf(x):
    return _call(jax.scipy.special.erf, (x,), name="erf")


def erfinv(x):
    return _call(jax.scipy.special.erfinv, (x,), name="erfinv")


def gamma(x):
    return _call(jax.scipy.special.gamma, (x,), name="gamma")


def gammaln(x):
    return _call(jax.scipy.special.gammaln, (x,), name="gammaln")


def index_add(data, indices, values):
    # int64 indices: int32 overflows beyond 2^31 elements (the reference's
    # USE_INT64_TENSOR_SIZE large-tensor support; jax_enable_x64 is on)
    return _call(lambda d, i, v: d.at[tuple(i.astype(jnp.int64))].add(v), (data, indices, values), name="index_add")


def index_update(data, indices, values):
    return _call(lambda d, i, v: d.at[tuple(i.astype(jnp.int64))].set(v), (data, indices, values), name="index_update")


# control-flow ops (reference src/operator/control_flow.cc foreach/while_loop/cond)
from .control_flow import foreach, while_loop, cond  # noqa: E402,F401

from . import random  # noqa: E402,F401


def interleaved_matmul_selfatt_qk(queries_keys_values, heads):
    return _call(lambda x: _nn.interleaved_matmul_selfatt_qk(x, heads),
                 (queries_keys_values,), name="interleaved_matmul_selfatt_qk")


def interleaved_matmul_selfatt_valatt(queries_keys_values, attention, heads):
    return _call(lambda x, a: _nn.interleaved_matmul_selfatt_valatt(x, a, heads),
                 (queries_keys_values, attention),
                 name="interleaved_matmul_selfatt_valatt")


def interleaved_matmul_encdec_qk(queries, keys_values, heads):
    return _call(lambda q, kv: _nn.interleaved_matmul_encdec_qk(q, kv, heads),
                 (queries, keys_values), name="interleaved_matmul_encdec_qk")


def interleaved_matmul_encdec_valatt(keys_values, attention, heads):
    return _call(lambda kv, a: _nn.interleaved_matmul_encdec_valatt(kv, a, heads),
                 (keys_values, attention), name="interleaved_matmul_encdec_valatt")


def multi_head_attention(query, key, value, heads, causal=False):
    """Fused multi-head attention over (B, L, H*D) projections — the Pallas
    flash kernel on TPU (ops/pallas/flash_attention.py), the interpreter
    elsewhere. Shares its core with nn.MultiHeadAttention (ops/nn.py:attend)."""
    return _call(lambda q, k, v: _nn.attend(q, k, v, heads, causal=causal),
                 (query, key, value), name="multi_head_attention")


# ---------------------------------------------------------------------------
# contrib op family (reference src/operator/contrib/; impls in ops/contrib.py)
# ---------------------------------------------------------------------------
from ..ops import contrib as _contrib  # noqa: E402


def roi_pooling(data, rois, pooled_size, spatial_scale=1.0):
    return _call(lambda d, r: _contrib.roi_pooling(
        d, r, pooled_size, spatial_scale), (data, rois), name="roi_pooling")


def roi_align(data, rois, pooled_size, spatial_scale=1.0, sample_ratio=2,
              aligned=False):
    return _call(lambda d, r: _contrib.roi_align(
        d, r, pooled_size, spatial_scale, sample_ratio, aligned),
        (data, rois), name="roi_align")


def boolean_mask(data, index, axis=0):
    """EAGER-ONLY: output shape depends on the mask values."""
    return _call(lambda d, i: _contrib.boolean_mask(d, i, axis),
                 (data, index), name="boolean_mask")


def count_sketch(data, h, s, out_dim):
    return _call(lambda d, hh, ss: _contrib.count_sketch(d, hh, ss, out_dim),
                 (data, h, s), name="count_sketch")


def adaptive_avg_pool2d(data, output_size):
    return _call(lambda d: _contrib.adaptive_avg_pool2d(d, output_size),
                 (data,), name="adaptive_avg_pool2d")


def sync_batch_norm(x, gamma, beta, moving_mean, moving_var, eps=1e-3,
                    momentum=0.9, axis_name=None):
    """Cross-device batch norm; inside shard_map pass the mesh axis name.

    Training mode (``autograd.record(train_mode=True)``) normalizes with
    mesh-global batch stats and updates ``moving_mean``/``moving_var`` in
    place (the reference's aux-state mutation); inference mode normalizes
    with the moving stats. Returns (out, mean_used, var_used)."""
    training = is_training()
    out, mean, var, new_mm, new_mv = _call(
        lambda xx, g, b, mm, mv: _contrib.sync_batch_norm(
            xx, g, b, mm, mv, eps=eps, momentum=momentum,
            axis_name=axis_name, training=training),
        (x, gamma, beta, moving_mean, moving_var),
        name="sync_batch_norm", n_out=5)
    if training:
        moving_mean._set_data(_unwrap(new_mm))
        moving_var._set_data(_unwrap(new_mv))
    return out, mean, var


def box_iou(lhs, rhs, fmt="corner"):
    return _call(lambda a, b: _contrib.box_iou(a, b, fmt), (lhs, rhs),
                 name="box_iou")


def box_nms(data, overlap_thresh=0.5, valid_thresh=0.0, topk=-1,
            score_index=1, coord_start=2):
    return _call(lambda d: _contrib.box_nms(
        d, overlap_thresh, valid_thresh, topk, score_index, coord_start),
        (data,), name="box_nms")


def bipartite_matching(score, threshold=1e-12, topk=-1, is_ascend=False):
    return _call(lambda s: _contrib.bipartite_matching(
        s, threshold, topk, is_ascend), (score,),
        name="bipartite_matching", n_out=2)


def allclose(a, b, rtol=1e-5, atol=1e-8, equal_nan=False):
    return _call(lambda x, y: _contrib.allclose(x, y, rtol, atol, equal_nan),
                 (a, b), name="allclose")


def index_array(data, axes=None):
    return _call(lambda d: _contrib.index_array(d, axes), (data,),
                 name="index_array")


def multibox_prior(data, sizes=(1.0,), ratios=(1.0,), steps=(-1.0, -1.0),
                   offsets=(0.5, 0.5), clip=False):
    return _call(lambda d: _contrib.multibox_prior(
        d, sizes, ratios, steps, offsets, clip), (data,),
        name="multibox_prior")


def deformable_convolution(data, offset, weight, bias=None, kernel=None,
                           stride=1, dilate=1, pad=0, num_filter=None,
                           num_group=1, num_deformable_group=1, no_bias=False):
    args = ((data, offset, weight) if bias is None or no_bias
            else (data, offset, weight, bias))
    return _call(
        lambda d, o, w, *b: _contrib.deformable_convolution(
            d, o, w, b[0] if b else None, kernel=kernel, stride=stride,
            dilate=dilate, pad=pad, num_filter=num_filter,
            num_group=num_group, num_deformable_group=num_deformable_group,
            no_bias=no_bias),
        args, name="deformable_convolution")


def modulated_deformable_convolution(data, offset, mask, weight, bias=None,
                                     kernel=None, stride=1, dilate=1, pad=0,
                                     num_filter=None, num_group=1,
                                     num_deformable_group=1, no_bias=False):
    args = ((data, offset, mask, weight) if bias is None or no_bias
            else (data, offset, mask, weight, bias))
    return _call(
        lambda d, o, m, w, *b: _contrib.deformable_convolution(
            d, o, w, b[0] if b else None, mask=m, kernel=kernel,
            stride=stride, dilate=dilate, pad=pad, num_filter=num_filter,
            num_group=num_group, num_deformable_group=num_deformable_group,
            no_bias=no_bias),
        args, name="modulated_deformable_convolution")


def hawkes_ll(mu, alpha, beta, state, lags, marks, valid_length, max_time):
    return _call(
        _contrib.hawkes_ll,
        (mu, alpha, beta, state, lags, marks, valid_length, max_time),
        name="hawkes_ll", n_out=2)


def round_ste(data):
    """round fwd, straight-through grad (reference contrib/stes_op.cc)."""
    return _call(_contrib.round_ste, (data,), name="round_ste")


def sign_ste(data):
    """sign fwd, straight-through grad (reference contrib/stes_op.cc)."""
    return _call(_contrib.sign_ste, (data,), name="sign_ste")


def khatri_rao(*matrices):
    """Column-wise Kronecker product (reference contrib/krprod.cc)."""
    return _call(_contrib.khatri_rao, matrices, name="khatri_rao")


def quadratic(data, a=0.0, b=0.0, c=0.0):
    """a*x^2+b*x+c (reference contrib/quadratic_op.cc)."""
    return _call(lambda x: _contrib.quadratic(x, a=a, b=b, c=c), (data,),
                 name="quadratic")


def all_finite(data, init_output=True):
    """AMP overflow probe, shape (1,) (reference contrib/all_finite.cc)."""
    return _call(_contrib.all_finite, (data,), name="all_finite")


def multi_all_finite(*arrays, num_arrays=None):
    return _call(_contrib.multi_all_finite, arrays, name="multi_all_finite")


def multi_sum_sq(*arrays, num_arrays=None):
    """Per-array sum of squares (reference contrib/multi_sum_sq.cc)."""
    return _call(_contrib.multi_sum_sq, arrays, name="multi_sum_sq")


def nnz(data):
    """Count of non-zero entries (reference contrib/nnz.cc getnnz).
    CSR input answers from stored-value metadata like the reference,
    without densifying."""
    from ..ndarray.sparse import CSRNDArray
    if isinstance(data, CSRNDArray):
        from ..numpy import array as _np_array
        return _np_array(int(data.nnz))
    return _call(_contrib.nnz, (data,), name="nnz")


def bilinear_resize_2d(data, height=None, width=None, scale_height=None,
                       scale_width=None, align_corners=True):
    """NCHW bilinear resize (reference contrib/bilinear_resize.cc)."""
    return _call(
        lambda x: _contrib.bilinear_resize_2d(
            x, height=height, width=width, scale_height=scale_height,
            scale_width=scale_width, align_corners=align_corners),
        (data,), name="bilinear_resize_2d")


def psroi_pooling(data, rois, output_dim, pooled_size, spatial_scale=1.0,
                  group_size=None):
    """Position-sensitive ROI pooling (reference contrib/psroi_pooling.cc)."""
    return _call(
        lambda d, r: _contrib.psroi_pooling(
            d, r, output_dim=output_dim, pooled_size=pooled_size,
            spatial_scale=spatial_scale, group_size=group_size),
        (data, rois), name="psroi_pooling")


# ---------------------------------------------------------------------------
# activation / math tail (reference src/operator: *_activation, special fns)
# ---------------------------------------------------------------------------
def rsqrt(x):
    return _call(lambda v: jax.lax.rsqrt(v), (x,), name="rsqrt")


def rcbrt(x):
    return _call(lambda v: 1.0 / jnp.cbrt(v), (x,), name="rcbrt")


def digamma(x):
    return _call(jax.scipy.special.digamma, (x,), name="digamma")


def log_sigmoid(x):
    return _call(jax.nn.log_sigmoid, (x,), name="log_sigmoid")


def hard_sigmoid(x, alpha=0.2, beta=0.5):
    return _call(lambda v: jnp.clip(alpha * v + beta, 0.0, 1.0), (x,),
                 name="hard_sigmoid")


def silu(x):
    return _call(jax.nn.silu, (x,), name="silu")


swish = silu


def mish(x):
    return _call(lambda v: v * jnp.tanh(jax.nn.softplus(v)), (x,),
                 name="mish")


def softplus(x):
    return _call(jax.nn.softplus, (x,), name="softplus")


def smooth_l1(data, scalar=1.0):
    """reference src/operator/tensor/elemwise_binary_scalar_op_extended.cc
    smooth_l1: 0.5(sx)^2 if |x|<1/s^2 else |x|-0.5/s^2."""
    s2 = scalar * scalar

    def fn(x):
        absx = jnp.abs(x)
        return jnp.where(absx < 1.0 / s2, 0.5 * s2 * x * x,
                         absx - 0.5 / s2)

    return _call(fn, (data,), name="smooth_l1")


def reshape(data, newshape, reverse=False):
    """MXNet reshape with the legacy magic codes (reference
    src/operator/tensor/matrix_op.cc Reshape):
      0   copy this dimension from the input
      -1  infer from remaining elements (at most one)
      -2  copy ALL remaining input dimensions
      -3  merge two consecutive input dimensions
      -4  split one input dimension by the next two values (one may be -1)
    ``reverse=True`` applies the codes right-to-left.
    """
    in_shape = list(data.shape)
    spec = list(newshape)
    if reverse:
        in_shape = in_shape[::-1]
        spec = spec[::-1]
    out, i = [], 0  # i: input dim cursor
    j = 0
    while j < len(spec):
        s = spec[j]
        if s == 0:
            out.append(in_shape[i])
            i += 1
        elif s == -1:
            out.append(-1)
            i += 1
        elif s == -2:
            out.extend(in_shape[i:])
            i = len(in_shape)
        elif s == -3:
            out.append(in_shape[i] * in_shape[i + 1])
            i += 2
        elif s == -4:
            d1, d2 = spec[j + 1], spec[j + 2]
            if d1 == -1:
                d1 = in_shape[i] // d2
            if d2 == -1:
                d2 = in_shape[i] // d1
            out.extend([d1, d2])
            i += 1
            j += 2
        else:
            out.append(int(s))
            i += 1
        j += 1
    if reverse:
        out = out[::-1]
    return _call(lambda x: x.reshape(tuple(out)), (data,), name="reshape")


def ctc_loss(data, label, data_lengths=None, label_lengths=None,
             blank_label="first"):
    """Connectionist Temporal Classification loss (reference
    src/operator/nn/ctc_loss.cc; data (T, B, C) activations, label (B, L)
    int classes with 1-based classes when blank is 'first').

    TPU-native: the alpha recursion runs in log space under ``lax.scan``
    over time — one compiled program, no per-step host work. Returns (B,)
    losses. Simplification vs the warp-ctc kernel: blank index is 0
    ('first'); 'last' maps labels accordingly.
    """
    def fn(d, lab, dlen, llen):
        t_max, b, c = d.shape
        logp = jax.nn.log_softmax(d.astype(jnp.float32), axis=-1)
        lab = lab.astype(jnp.int32)
        l_max = lab.shape[1]
        if blank_label == "first":
            blank = 0
        else:
            blank = c - 1
        s_max = 2 * l_max + 1
        # extended label sequence: blank, l1, blank, l2, ..., blank
        ext = jnp.full((b, s_max), blank, jnp.int32)
        ext = ext.at[:, 1::2].set(lab)
        neg_inf = -1e30
        # allow alpha(s-2) only when ext[s] != blank and ext[s] != ext[s-2]
        ext_prev2 = jnp.concatenate(
            [jnp.full((b, 2), -1, jnp.int32), ext[:, :-2]], axis=1)
        can_skip = (ext != blank) & (ext != ext_prev2)

        alpha0 = jnp.full((b, s_max), neg_inf)
        alpha0 = alpha0.at[:, 0].set(logp[0, :, blank])
        alpha0 = alpha0.at[:, 1].set(
            jnp.take_along_axis(logp[0], ext[:, 1:2], axis=-1)[:, 0])

        tl = (jnp.full((b,), t_max, jnp.int32) if dlen is None
              else dlen.astype(jnp.int32))
        ll = (jnp.full((b,), l_max, jnp.int32) if llen is None
              else llen.astype(jnp.int32))

        # O(B*S) memory: carry a running "alpha at t = tl-1" selection
        # instead of stacking the full (T, B, S) alpha history
        saved0 = jnp.where((tl == 1)[:, None], alpha0, neg_inf)

        def step(carry, inp):
            alpha, saved = carry
            t, logp_t = inp
            a1 = jnp.concatenate(
                [jnp.full((b, 1), neg_inf), alpha[:, :-1]], axis=1)
            a2 = jnp.concatenate(
                [jnp.full((b, 2), neg_inf), alpha[:, :-2]], axis=1)
            a2 = jnp.where(can_skip, a2, neg_inf)
            merged = jnp.logaddexp(jnp.logaddexp(alpha, a1), a2)
            emit = jnp.take_along_axis(logp_t, ext, axis=-1)
            new_alpha = merged + emit
            saved = jnp.where((t == tl - 1)[:, None], new_alpha, saved)
            return (new_alpha, saved), None

        (_, alpha_T), _ = jax.lax.scan(
            step, (alpha0, saved0),
            (jnp.arange(1, t_max), logp[1:]))
        end1 = jnp.take_along_axis(alpha_T, (2 * ll)[:, None], axis=1)[:, 0]
        # empty target (ll == 0): only the all-blank path at s=0 counts;
        # 2*ll-1 would wrap to -1 and add a spurious alignment
        end2_ix = jnp.maximum(2 * ll - 1, 0)[:, None]
        end2 = jnp.take_along_axis(alpha_T, end2_ix, axis=1)[:, 0]
        end2 = jnp.where(ll > 0, end2, neg_inf)
        return -jnp.logaddexp(end1, end2)

    arrays = [data, label]
    if data_lengths is None and label_lengths is None:
        return _call(lambda d, l: fn(d, l, None, None), arrays,
                     name="ctc_loss")
    extra = [a for a in (data_lengths, label_lengths) if a is not None]

    def dispatch(*vals):
        d, l = vals[0], vals[1]
        rest = list(vals[2:])
        dl = rest.pop(0) if data_lengths is not None else None
        ll_ = rest.pop(0) if label_lengths is not None else None
        return fn(d, l, dl, ll_)

    return _call(dispatch, arrays + extra, name="ctc_loss")


def index_copy(old_tensor, index_vector, new_tensor):
    return _call(_contrib.index_copy, (old_tensor, index_vector, new_tensor),
                 name="index_copy")


def gradientmultiplier(data, scalar=1.0):
    return _call(lambda d: _contrib.gradientmultiplier(d, scalar), (data,),
                 name="gradientmultiplier")


def multibox_target(anchor, label, cls_pred, overlap_threshold=0.5,
                    ignore_label=-1.0, negative_mining_ratio=-1.0,
                    negative_mining_thresh=0.5, minimum_negative_samples=0,
                    variances=(0.1, 0.1, 0.2, 0.2)):
    """Eager host-side SSD target assignment — not traceable (greedy
    matching + sorting, reference multibox_target.cc CPU kernel)."""
    out = _contrib.multibox_target(
        _unwrap(anchor) if isinstance(anchor, ndarray) else anchor,
        _unwrap(label) if isinstance(label, ndarray) else label,
        _unwrap(cls_pred) if isinstance(cls_pred, ndarray) else cls_pred,
        overlap_threshold, ignore_label, negative_mining_ratio,
        negative_mining_thresh, minimum_negative_samples, variances)
    return tuple(_wrap(o) for o in out)


def multibox_detection(cls_prob, loc_pred, anchor, threshold=0.01,
                       clip=True, variances=(0.1, 0.1, 0.2, 0.2),
                       nms_threshold=0.5, force_suppress=False, nms_topk=-1):
    """Eager host-side SSD decode + NMS (reference
    multibox_detection.cc CPU kernel)."""
    out = _contrib.multibox_detection(
        _unwrap(cls_prob) if isinstance(cls_prob, ndarray) else cls_prob,
        _unwrap(loc_pred) if isinstance(loc_pred, ndarray) else loc_pred,
        _unwrap(anchor) if isinstance(anchor, ndarray) else anchor,
        threshold, clip, variances, nms_threshold, force_suppress, nms_topk)
    return _wrap(out)


# ---- npx tail: seed alias, npx-only samplers, DLPack interop, nonzero,
# constraint_check (reference numpy_extension/random.py + np_nonzero_op.cc
# + np_constraint_check.cc + to/from_dlpack in c_api) ----
from .random import seed, bernoulli, uniform_n, normal_n  # noqa: F401,E402


def nonzero(x):
    """Indices of nonzero elements as an (N, ndim) int64 array — the npx
    layout, transposed vs np.nonzero's tuple (reference
    np_nonzero_op.cc:115 _npx_nonzero). Eager-only: the output shape is
    data-dependent, which XLA tracing cannot express (the reference
    likewise restricts it to FComputeEx)."""
    arr = _unwrap(x) if isinstance(x, ndarray) else jnp.asarray(x)
    idx = onp.argwhere(onp.asarray(arr))
    return _wrap(jnp.asarray(idx, jnp.int64))


def constraint_check(x, msg="Constraint violated."):
    """All-reduce a bool tensor; raise ``msg`` when any element is False
    (reference np_constraint_check.cc:59 — the runtime guard behind the
    distributions' parameter validation). Returns the scalar bool under
    tracing, where a data-dependent raise cannot exist."""
    arr = _unwrap(x) if isinstance(x, ndarray) else jnp.asarray(x)
    ok = jnp.all(arr)
    if not isinstance(ok, jax.core.Tracer) and not bool(ok):
        from ..base import MXNetError
        raise MXNetError(msg)
    return _wrap(ok)


def to_dlpack_for_read(data):
    """DLPack capsule sharing the array's device buffer (reference
    c_api.cc MXNDArrayToDLPack; jax arrays are immutable so read/write
    variants coincide)."""
    return _unwrap(data).__dlpack__()


def to_dlpack_for_write(data):
    """Alias of :func:`to_dlpack_for_read` — XLA buffers are immutable;
    consumers mutate a copy (documented divergence from the reference's
    in-place write contract)."""
    return to_dlpack_for_read(data)


def from_dlpack(dlpack):
    """Wrap a DLPack capsule (or any object with ``__dlpack__``) as an
    mx ndarray, zero-copy where the producer's device allows."""
    return _wrap(jnp.asarray(jax.dlpack.from_dlpack(dlpack)))
