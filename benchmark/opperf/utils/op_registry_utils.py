"""Auto-enumeration of the full op registry with default input rules
(reference ``benchmark/opperf/utils/op_registry_utils.py``: walks every
registered op and synthesizes default inputs per category).

Here the registry is the public callable surface of ``mx.np`` /
``mx.npx`` / ``mx.np.random`` / ``mx.np.linalg`` / ``mx.np.fft``. Each
op gets its inputs from either a SPECIAL rule (ops with structural
arguments: convolution, attention, creation ops, ...) or the generic
candidate chain (unary → binary → list → index → shape → ...), exactly
the reference's "default inputs by category" idea without a hand-rule
per op.

Measurement is EAGER per-op latency with a blocking fetch — the honest
analog of the reference timing engine-pushed kernels one at a time
(MXNET_ENGINE_TYPE=NaiveEngine); dispatch overhead is part of the
number, as it was there.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as onp

# ops that are utilities / contexts / control-flow drivers, not compute
# kernels; excluded with a reason instead of "error"
SKIP = {
    "np": {"ndarray", "save", "load", "set_np", "reset_np", "use_np",
           "is_np_array", "get_include", "seterr", "geterr", "errstate",
           "printoptions", "set_printoptions", "get_printoptions",
           "asnumpy", "may_share_memory", "shares_memory",
           # not ops: dispatch chokepoint, typing re-exports, io, planning
           "apply_op", "List", "Optional", "Sequence", "current_context",
           "einsum_path", "from_dlpack", "fromfile", "fromstring",
           "savez", "savez_compressed"},
    "np.random": {"Optional", "new_key", "apply_op"},
    "np.linalg": {"apply_op"},
    "np.fft": {"apply_op"},
    "npx": {"apply_op", "cpu", "gpu", "tpu", "current_context",
            "is_np_array", "is_training", "set_np", "reset_np", "use_np",
            "functional_mode", "rng_scope", "waitall", "load", "save",
            "ndarray", "dtype_from_any", "num_gpus", "num_tpus",
            "cond", "foreach", "while_loop", "allclose"},
}


def _mx():
    import mxnet_tpu as mx

    return mx


def list_all_ops() -> Dict[str, Callable]:
    """qualified name -> callable, across the public op namespaces."""
    mx = _mx()
    out: Dict[str, Callable] = {}
    spaces = [("np", mx.np), ("npx", mx.npx),
              ("np.random", mx.np.random), ("np.linalg", mx.np.linalg),
              ("np.fft", mx.np.fft)]
    for prefix, mod in spaces:
        skip = SKIP.get(prefix, set())
        for n in dir(mod):
            if n.startswith("_") or n in skip:
                continue
            fn = getattr(mod, n)
            if callable(fn) and not isinstance(fn, type):
                out[f"{prefix}.{n}"] = fn
    return out


_CACHE: dict = {}


def _inputs():
    if "inputs" in _CACHE:
        return _CACHE["inputs"]
    mx = _mx()
    rng = onp.random.RandomState(0)
    x = mx.np.array(rng.uniform(0.1, 0.9, (64, 64)).astype(onp.float32))
    y = mx.np.array(rng.uniform(0.1, 0.9, (64, 64)).astype(onp.float32))
    v = mx.np.array(rng.uniform(0.1, 0.9, (64,)).astype(onp.float32))
    iv = mx.np.array(rng.randint(0, 32, (64,)).astype(onp.int32))
    bm = mx.np.array((rng.uniform(size=(64, 64)) > 0.5))
    _CACHE["inputs"] = {"x": x, "y": y, "v": v, "iv": iv, "bm": bm}
    return _CACHE["inputs"]


def _special_rules() -> Dict[str, Callable]:
    """name -> zero-arg builder returning (call_args, call_kwargs, diff).

    Only ops whose signatures the generic chain cannot satisfy.
    Memoized: the dict (and its closures) is built once per process.
    """
    if "specials" in _CACHE:
        return _CACHE["specials"]
    mx = _mx()
    np, npx = mx.np, mx.npx
    rng = onp.random.RandomState(1)

    def t(shape, dtype=onp.float32, lo=0.1, hi=0.9):
        return mx.np.array(rng.uniform(lo, hi, shape).astype(dtype))

    def it(shape, hi=8):
        return mx.np.array(rng.randint(0, hi, shape).astype(onp.int32))

    nchw = (8, 8, 16, 16)
    w_oihw = (16, 8, 3, 3)
    posdef = None

    def _posdef():
        nonlocal posdef
        if posdef is None:
            a = rng.randn(16, 16).astype(onp.float32)
            posdef = mx.np.array(a @ a.T + 16 * onp.eye(16, dtype=onp.float32))
        return posdef

    R = {
        # --- npx structural ops ---
        "npx.activation": lambda: ((t((64, 64)),), {"act_type": "relu"}, True),
        "npx.leaky_relu": lambda: ((t((64, 64)),), {"act_type": "leaky"}, True),
        "npx.convolution": lambda: ((t(nchw), t(w_oihw)), {
            "kernel": (3, 3), "num_filter": 16, "pad": (1, 1),
            "no_bias": True}, True),
        "npx.deconvolution": lambda: ((t(nchw), t((8, 16, 3, 3))), {
            "num_filter": 16, "pad": 1, "no_bias": True}, True),
        "npx.pooling": lambda: ((t(nchw),), {
            "kernel": (2, 2), "pool_type": "max", "stride": (2, 2)}, True),
        "npx.fully_connected": lambda: ((t((32, 64)), t((128, 64))), {
            "num_hidden": 128, "no_bias": True}, True),
        "npx.batch_norm": lambda: ((t(nchw), t((8,)), t((8,)),
                                    t((8,)), t((8,), lo=0.5, hi=1.5)),
                                   {}, True),
        "npx.layer_norm": lambda: ((t((32, 64)), t((64,)), t((64,))),
                                   {}, True),
        "npx.group_norm": lambda: ((t(nchw), t((8,)), t((8,))),
                                   {"num_groups": 2}, True),
        "npx.instance_norm": lambda: ((t(nchw), t((8,)), t((8,))),
                                      {}, True),
        "npx.rms_norm": lambda: ((t((32, 64)), t((64,))), {}, True),
        "npx.l2_normalization": lambda: ((t((32, 64)),), {}, True),
        "npx.dropout": lambda: ((t((64, 64)),), {"p": 0.5}, True),
        "npx.embedding": lambda: ((it((32, 16), hi=100), t((100, 32))), {
            "input_dim": 100, "output_dim": 32}, False),
        "npx.one_hot": lambda: ((it((64,), hi=16),), {"depth": 16}, False),
        "npx.pick": lambda: ((t((64, 8)), it((64,), hi=8)), {}, False),
        "npx.topk": lambda: ((t((32, 64)),), {"k": 5}, False),
        "npx.softmax": lambda: ((t((64, 64)),), {}, True),
        "npx.log_softmax": lambda: ((t((64, 64)),), {}, True),
        "npx.masked_softmax": lambda: (
            (t((64, 64)), mx.np.array(rng.uniform(size=(64, 64)) > 0.2)),
            {}, False),
        "npx.masked_log_softmax": lambda: (
            (t((64, 64)), mx.np.array(rng.uniform(size=(64, 64)) > 0.2)),
            {}, False),
        "npx.softmax_cross_entropy": lambda: (
            (t((64, 16)), it((64,), hi=16)), {}, False),
        "npx.ctc_loss": lambda: ((t((20, 4, 10)), it((4, 5), hi=9)),
                                 {}, False),
        "npx.sequence_mask": lambda: ((t((10, 4, 8)), t((4,), lo=1, hi=9)),
                                      {"use_sequence_length": True}, False),
        "npx.sequence_last": lambda: ((t((10, 4, 8)), t((4,), lo=1, hi=9)),
                                      {"use_sequence_length": True}, False),
        "npx.sequence_reverse": lambda: ((t((10, 4, 8)),), {}, False),
        "npx.gather_nd": lambda: ((t((16, 16)), it((2, 8), hi=16)),
                                  {}, False),
        "npx.scatter_nd": lambda: ((t((8,)), it((2, 8), hi=4), (4, 4)),
                                   {}, False),
        "npx.index_add": lambda: ((t((16, 16)), it((1, 4), hi=16),
                                   t((4, 16))), {}, False),
        "npx.index_update": lambda: ((t((16, 16)), it((1, 4), hi=16),
                                      t((4, 16))), {}, False),
        "npx.index_copy": lambda: ((t((16, 16)), it((4,), hi=16),
                                    t((4, 16))), {}, False),
        "npx.index_array": lambda: ((t((8, 8)),), {}, False),
        "npx.boolean_mask": lambda: (
            (t((64, 8)), mx.np.array(rng.uniform(size=(64,)) > 0.5)),
            {}, False),
        "npx.slice": lambda: ((t((64, 64)),), {
            "begin": (0, 0), "end": (32, 32)}, False),
        "npx.slice_like": lambda: ((t((64, 64)), t((32, 32))), {}, False),
        "npx.reshape": lambda: ((t((64, 64)), (4096,)), {}, False),
        "npx.reshape_like": lambda: ((t((64, 64)), t((4096,))), {}, False),
        "npx.broadcast_like": lambda: ((t((1, 64)), t((64, 64))), {}, False),
        "npx.arange_like": lambda: ((t((64, 64)),), {}, False),
        "npx.shape_array": lambda: ((t((64, 64)),), {}, False),
        "npx.batch_flatten": lambda: ((t(nchw),), {}, False),
        "npx.smooth_l1": lambda: ((t((64, 64)),), {}, True),
        "npx.roi_align": lambda: ((t((4, 16, 32, 32)),
                                   mx.np.array(onp.array(
                                       [[0, 1, 1, 20, 20]] * 8,
                                       onp.float32))), {
            "pooled_size": (7, 7), "spatial_scale": 0.5}, False),
        "npx.roi_pooling": lambda: ((t((4, 16, 32, 32)),
                                     mx.np.array(onp.array(
                                         [[0, 1, 1, 20, 20]] * 8,
                                         onp.float32))), {
            "pooled_size": (7, 7), "spatial_scale": 0.5}, False),
        # C must equal output_dim * group_size^2 (2 * 7^2 = 98)
        "npx.psroi_pooling": lambda: ((t((4, 98, 32, 32)),
                                       mx.np.array(onp.array(
                                           [[0, 1, 1, 20, 20]] * 8,
                                           onp.float32))), {
            "output_dim": 2, "pooled_size": 7, "spatial_scale": 0.5,
            "group_size": 7}, False),
        "npx.bilinear_resize_2d": lambda: ((t(nchw),), {
            "height": 48, "width": 48}, False),
        "npx.box_iou": lambda: ((t((64, 4)), t((64, 4))), {}, False),
        "npx.box_nms": lambda: (
            (mx.np.array(onp.concatenate([
                onp.zeros((64, 1), onp.float32),                # class id
                rng.uniform(0.1, 0.9, (64, 1)).astype(onp.float32),
                rng.uniform(0, 0.4, (64, 2)).astype(onp.float32),   # x1 y1
                rng.uniform(0.5, 0.9, (64, 2)).astype(onp.float32),  # x2 y2
            ], axis=1)),), {"overlap_thresh": 0.5}, False),
        "npx.bipartite_matching": lambda: ((t((16, 16)),), {
            "threshold": 0.1}, False),
        "npx.multibox_prior": lambda: ((t(nchw),), {
            "sizes": (0.5,), "ratios": (1.0,)}, False),
        "npx.multibox_detection": lambda: (
            (t((1, 3, 16), lo=0.01, hi=0.99), t((1, 64)),
             t((1, 16, 4), lo=0.1, hi=0.4)), {}, False),
        "npx.multibox_target": lambda: (
            (t((1, 16, 4)), t((1, 4, 5)), t((1, 4, 16))), {}, False),
        "npx.count_sketch": lambda: (
            (t((32, 64)),
             mx.np.array((onp.arange(64) % 16).astype(onp.float32)),
             mx.np.array(onp.where(onp.arange(64) % 2 == 0, 1.0, -1.0)
                         .astype(onp.float32))), {"out_dim": 16}, False),
        "npx.hawkes_ll": lambda: (
            (t((2, 4), lo=0.5, hi=1.5), t((4,), lo=0.1, hi=0.5),
             t((4,), lo=0.5, hi=2.0), t((2, 4), lo=0.0, hi=1.0),
             t((2, 8), lo=0.1, hi=0.6), it((2, 8), hi=4),
             t((2,), lo=7.0, hi=8.0), t((2,), lo=4.0, hi=5.0)),
            {}, False),
        "npx.interleaved_matmul_selfatt_qk": lambda: (
            (t((16, 2, 3 * 64)),), {"heads": 4}, True),
        "npx.interleaved_matmul_selfatt_valatt": lambda: (
            (t((16, 2, 3 * 64)), t((8, 16, 16))), {"heads": 4}, True),
        "npx.interleaved_matmul_encdec_qk": lambda: (
            (t((16, 2, 64)), t((16, 2, 2 * 64))), {"heads": 4}, True),
        "npx.interleaved_matmul_encdec_valatt": lambda: (
            (t((16, 2, 2 * 64)), t((8, 16, 16))), {"heads": 4}, True),
        "npx.multi_head_attention": lambda: (
            (t((2, 16, 64)), t((2, 16, 64)), t((2, 16, 64)), 4),
            {}, False),
        "npx.adaptive_avg_pool2d": lambda: ((t(nchw),), {
            "output_size": (4, 4)}, True),
        "npx.deformable_convolution": lambda: (
            (t((2, 8, 16, 16)), t((2, 18, 16, 16)), t((16, 8, 3, 3))), {
                "kernel": (3, 3), "num_filter": 16, "pad": (1, 1),
                "no_bias": True}, False),
        "npx.modulated_deformable_convolution": lambda: (
            (t((2, 8, 16, 16)), t((2, 18, 16, 16)), t((2, 9, 16, 16)),
             t((16, 8, 3, 3))), {
                "kernel": (3, 3), "num_filter": 16, "pad": (1, 1),
                "no_bias": True}, False),
        "npx.sync_batch_norm": lambda: ((t(nchw), t((8,)), t((8,)),
                                         t((8,)), t((8,), lo=0.5, hi=1.5)),
                                        {}, False),
        "npx.gradientmultiplier": lambda: ((t((64, 64)),), {
            "scalar": 0.5}, True),
        # --- np structural ---
        "np.where": lambda: ((mx.np.array(
            rng.uniform(size=(64, 64)) > 0.5), t((64, 64)), t((64, 64))),
            {}, False),
        "np.take": lambda: ((t((64, 64)), it((16,), hi=64)), {}, False),
        "np.take_along_axis": lambda: ((t((64, 64)),
                                        it((64, 1), hi=64)), {"axis": 1},
                                       False),
        "np.one_hot": lambda: ((it((64,), hi=16),), {"depth": 16}, False),
        "np.arange": lambda: ((64,), {}, False),
        "np.eye": lambda: ((64,), {}, False),
        "np.identity": lambda: ((64,), {}, False),
        "np.linspace": lambda: ((0.0, 1.0, 64), {}, False),
        "np.logspace": lambda: ((0.0, 1.0, 64), {}, False),
        "np.full": lambda: (((64, 64), 3.0), {}, False),
        "np.tri": lambda: ((64,), {}, False),
        "np.tril_indices": lambda: ((8,), {}, False),
        "np.indices": lambda: (((8, 8),), {}, False),
        "np.histogram": lambda: ((t((256,)),), {"bins": 10,
                                                "range": (0.0, 1.0)}, False),
        "np.pad": lambda: ((t((32, 32)), ((2, 2), (2, 2))), {}, False),
        "np.roll": lambda: ((t((64, 64)), 3), {}, False),
        "np.rot90": lambda: ((t((64, 64)),), {}, False),
        "np.tile": lambda: ((t((16, 16)), (2, 2)), {}, False),
        "np.repeat": lambda: ((t((16, 16)), 4), {}, False),
        "np.split": lambda: ((t((64, 64)), 4), {}, False),
        "np.array_split": lambda: ((t((64, 64)), 4), {}, False),
        "np.hsplit": lambda: ((t((64, 64)), 4), {}, False),
        "np.vsplit": lambda: ((t((64, 64)), 4), {}, False),
        "np.dsplit": lambda: ((t((4, 4, 8)), 4), {}, False),
        "np.insert": lambda: ((t((64,)), 2, 5.0), {}, False),
        "np.delete": lambda: ((t((64,)), 2), {}, False),
        "np.unravel_index": lambda: ((it((16,), hi=60), (8, 8)), {}, False),
        "np.ravel_multi_index": lambda: (
            ((it((8,), hi=7), it((8,), hi=7)), (8, 8)), {}, False),
        "np.diag_indices_from": lambda: ((t((16, 16)),), {}, False),
        "np.fill_diagonal": lambda: ((t((16, 16)), 1.0), {}, False),
        "np.interp": lambda: ((t((32,)), t((16,)).sort(), t((16,))),
                              {}, False),
        "np.cross": lambda: ((t((16, 3)), t((16, 3))), {}, True),
        "np.einsum": lambda: (("ij,jk->ik", t((32, 32)), t((32, 32))),
                              {}, True),
        "np.tensordot": lambda: ((t((16, 16)), t((16, 16))), {}, True),
        "np.kron": lambda: ((t((8, 8)), t((8, 8))), {}, True),
        "np.searchsorted": lambda: ((t((64,)).sort(), t((16,))), {}, False),
        "np.digitize": lambda: ((t((64,)),
                                 mx.np.array(onp.array([0.2, 0.5, 0.8],
                                                       onp.float32))),
                                {}, False),
        "np.bincount": lambda: ((it((64,), hi=16),), {}, False),
        "np.clip": lambda: ((t((64, 64)), 0.2, 0.8), {}, True),
        "np.isclose": lambda: ((t((64, 64)), t((64, 64))), {}, False),
        "np.allclose": lambda: ((t((64, 64)), t((64, 64))), {}, False),
        "np.array_equal": lambda: ((t((64, 64)), t((64, 64))), {}, False),
        "np.result_type": lambda: ((t((4,)), t((4,))), {}, False),
        "np.can_cast": lambda: (("float32", "float64"), {}, False),
        "np.promote_types": lambda: (("float32", "float64"), {}, False),
        "np.shape": lambda: ((t((8, 8)),), {}, False),
        "np.ndim": lambda: ((t((8, 8)),), {}, False),
        "np.size": lambda: ((t((8, 8)),), {}, False),
        "np.expand_dims": lambda: ((t((64, 64)), 0), {}, False),
        "np.swapaxes": lambda: ((t((16, 16)), 0, 1), {}, False),
        "np.moveaxis": lambda: ((t((16, 16)), 0, 1), {}, False),
        "np.rollaxis": lambda: ((t((16, 16)), 1), {}, False),
        "np.apply_along_axis": lambda: (
            (lambda a: a.sum(), 0, t((16, 16))), {}, False),
        "np.apply_over_axes": lambda: (
            (lambda a, ax: a.sum(axis=ax, keepdims=True), t((16, 16)),
             (0,)), {}, False),
        "np.piecewise": lambda: (
            (t((64,)), [t((64,)) < 0.5, t((64,)) >= 0.5],
             [lambda a: a * 2, lambda a: a]), {}, False),
        "np.diff": lambda: ((t((64, 64)),), {}, True),
        "np.ediff1d": lambda: ((t((64,)),), {}, True),
        "np.gradient": lambda: ((t((64, 64)),), {}, False),
        "np.trapz": lambda: ((t((64,)),), {}, False),
        "np.meshgrid": lambda: ((t((16,)), t((16,))), {}, False),
        "np.ix_": lambda: ((it((4,), hi=8), it((4,), hi=8)), {}, False),
        "np.atleast_1d": lambda: ((t((8,)),), {}, False),
        "np.atleast_2d": lambda: ((t((8,)),), {}, False),
        "np.atleast_3d": lambda: ((t((8,)),), {}, False),
        "np.triu_indices": lambda: ((8,), {}, False),
        "np.triu_indices_from": lambda: ((t((8, 8)),), {}, False),
        "np.tril": lambda: ((t((64, 64)),), {}, True),
        "np.triu": lambda: ((t((64, 64)),), {}, True),
        "np.vander": lambda: ((t((16,)),), {}, False),
        "np.diag": lambda: ((t((64,)),), {}, True),
        "np.diagflat": lambda: ((t((16,)),), {}, False),
        "np.diagonal": lambda: ((t((16, 16)),), {}, True),
        "np.trace": lambda: ((t((64, 64)),), {}, True),
        "np.average": lambda: ((t((64, 64)),), {}, True),
        "np.cov": lambda: ((t((8, 64)),), {}, False),
        "np.corrcoef": lambda: ((t((8, 64)),), {}, False),
        "np.correlate": lambda: ((t((64,)), t((16,))), {}, False),
        "np.convolve": lambda: ((t((64,)), t((16,))), {}, False),
        "np.percentile": lambda: ((t((64, 64)), 50.0), {}, False),
        "np.quantile": lambda: ((t((64, 64)), 0.5), {}, False),
        "np.nanpercentile": lambda: ((t((64, 64)), 50.0), {}, False),
        "np.nanquantile": lambda: ((t((64, 64)), 0.5), {}, False),
        "np.unique": lambda: ((it((64,), hi=16),), {}, False),
        "np.in1d": lambda: ((it((64,), hi=16), it((8,), hi=16)), {}, False),
        "np.isin": lambda: ((it((64,), hi=16), it((8,), hi=16)), {}, False),
        "np.union1d": lambda: ((it((32,), hi=16), it((32,), hi=16)),
                               {}, False),
        "np.intersect1d": lambda: ((it((32,), hi=16), it((32,), hi=16)),
                                   {}, False),
        "np.setdiff1d": lambda: ((it((32,), hi=16), it((32,), hi=16)),
                                 {}, False),
        "np.setxor1d": lambda: ((it((32,), hi=16), it((32,), hi=16)),
                                {}, False),
        "np.sort_complex": lambda: ((t((32,)),), {}, False),
        "np.partition": lambda: ((t((64, 64)), 10), {}, False),
        "np.argpartition": lambda: ((t((64, 64)), 10), {}, False),
        "np.polyval": lambda: ((t((4,)), t((64,))), {}, False),
        "np.polyfit": lambda: ((t((32,)), t((32,)), 2), {}, False),
        "np.poly": lambda: ((t((4,)),), {}, False),
        "np.roots": lambda: ((t((4,)),), {}, False),
        "np.select": lambda: (
            ([t((64,)) < 0.3, t((64,)) > 0.6], [t((64,)), t((64,))]),
            {}, False),
        "np.choose": lambda: ((it((16,), hi=2), [t((16,)), t((16,))]),
                              {}, False),
        "np.compress": lambda: (
            (mx.np.array(rng.uniform(size=(64,)) > 0.5), t((64, 64))),
            {"axis": 0}, False),
        "np.extract": lambda: (
            (mx.np.array(rng.uniform(size=(64,)) > 0.5), t((64,))),
            {}, False),
        "np.place": lambda: ((t((64,)),
                              mx.np.array(rng.uniform(size=(64,)) > 0.5),
                              t((8,))), {}, False),
        "np.put_along_axis": lambda: ((t((16, 16)), it((16, 1), hi=16),
                                       t((16, 1)), 1), {}, False),
        "np.copyto": lambda: ((t((64,)), t((64,))), {}, False),
        "np.putmask": lambda: ((t((64,)),
                                mx.np.array(rng.uniform(size=(64,)) > 0.5),
                                t((64,))), {}, False),
        "np.broadcast_to": lambda: ((t((1, 64)), (64, 64)), {}, False),
        "np.broadcast_shapes": lambda: (((64, 64), (64, 1)), {}, False),
        "np.broadcast_arrays": lambda: ((t((1, 64)), t((64, 1))), {}, False),
        "np.full_like": lambda: ((t((64, 64)), 2.0), {}, False),
        "np.require": lambda: ((t((16, 16)),), {}, False),
        "np.asfarray": lambda: ((it((16,), hi=4),), {}, False),
        "np.fromfunction": lambda: (
            (lambda i, j: i + j, (8, 8)), {}, False),
        "np.fromiter": lambda: ((range(16), "float32"), {}, False),
        "np.frombuffer": lambda: (
            (onp.arange(16, dtype=onp.float32).tobytes(), "float32"),
            {}, False),
        # the stall/timeout class: an array reaching a shape-typed slot
        # (zeros(x) iterates the array as dims) must never happen — give
        # every shape-consuming / sequence-consuming op an explicit rule
        "np.zeros": lambda: (((64, 64),), {}, False),
        "np.ones": lambda: (((64, 64),), {}, False),
        "np.empty": lambda: (((64, 64),), {}, False),
        "np.reshape": lambda: ((t((64, 64)), (4096,)), {}, False),
        "np.concatenate": lambda: (([t((64, 64)), t((64, 64))],),
                                   {}, False),
        "np.concat": lambda: (([t((64, 64)), t((64, 64))],), {}, False),
        "np.stack": lambda: (([t((64, 64)), t((64, 64))],), {}, False),
        "np.vstack": lambda: (([t((64, 64)), t((64, 64))],), {}, False),
        "np.hstack": lambda: (([t((64, 64)), t((64, 64))],), {}, False),
        "np.dstack": lambda: (([t((64, 64)), t((64, 64))],), {}, False),
        "np.column_stack": lambda: (([t((64,)), t((64,))],), {}, False),
        "np.row_stack": lambda: (([t((64, 64)), t((64, 64))],), {}, False),
        "np.lexsort": lambda: (((t((64,)), t((64,))),), {}, False),
        "np.random.standard_normal": lambda: (((64, 64),), {}, False),
        "np.kaiser": lambda: ((64, 8.6), {}, False),
        "np.histogram2d": lambda: ((t((256,)), t((256,))), {"bins": 8},
                                   False),
        "np.polymul": lambda: ((t((4,)), t((4,))), {}, False),
        "np.polydiv": lambda: ((t((6,)), t((3,))), {}, False),
        "np.mask_indices": lambda: ((8, _mx().np.triu), {}, False),
        "np.unpackbits": lambda: (
            (_mx().np.array(onp.arange(16, dtype=onp.uint8)),), {}, False),
        "np.packbits": lambda: (
            (_mx().np.array((onp.arange(32) % 2).astype(bool)),),
            {}, False),
        "np.squeeze": lambda: ((t((1, 64, 1)),), {}, False),
        # --- random: shape kwarg ---
        "np.random.uniform": lambda: ((0.0, 1.0, (64, 64)), {}, False),
        "np.random.normal": lambda: ((0.0, 1.0, (64, 64)), {}, False),
        "np.random.randn": lambda: ((64, 64), {}, False),
        "np.random.rand": lambda: ((64, 64), {}, False),
        "np.random.randint": lambda: ((0, 10, (64, 64)), {}, False),
        "np.random.choice": lambda: ((64, (16,)), {}, False),
        "np.random.permutation": lambda: ((64,), {}, False),
        "np.random.shuffle": lambda: ((t((64,)),), {}, False),
        "np.random.gamma": lambda: ((2.0, 1.0, (64, 64)), {}, False),
        "np.random.beta": lambda: ((2.0, 3.0, (64, 64)), {}, False),
        "np.random.chisquare": lambda: ((2.0, (64, 64)), {}, False),
        "np.random.exponential": lambda: ((1.0, (64, 64)), {}, False),
        "np.random.f": lambda: ((2.0, 3.0, (64, 64)), {}, False),
        "np.random.geometric": lambda: ((0.5, (64, 64)), {}, False),
        "np.random.gumbel": lambda: ((0.0, 1.0, (64, 64)), {}, False),
        "np.random.laplace": lambda: ((0.0, 1.0, (64, 64)), {}, False),
        "np.random.logistic": lambda: ((0.0, 1.0, (64, 64)), {}, False),
        "np.random.lognormal": lambda: ((0.0, 1.0, (64, 64)), {}, False),
        "np.random.multinomial": lambda: (
            (32, onp.full(8, 1 / 8)), {"size": (16,)}, False),
        "np.random.multivariate_normal": lambda: (
            (mx.np.zeros((4,)), mx.np.array(onp.eye(4, dtype=onp.float32))),
            {"size": (16,)}, False),
        "np.random.negative_binomial": lambda: ((4, 0.5, (64, 64)),
                                                {}, False),
        "np.random.pareto": lambda: ((2.0, (64, 64)), {}, False),
        "np.random.poisson": lambda: ((2.0, (64, 64)), {}, False),
        "np.random.power": lambda: ((2.0, (64, 64)), {}, False),
        "np.random.rayleigh": lambda: ((1.0, (64, 64)), {}, False),
        "np.random.weibull": lambda: ((2.0, (64, 64)), {}, False),
        "np.random.binomial": lambda: ((8, 0.5, (64, 64)), {}, False),
        "np.random.bernoulli": lambda: ((0.5,), {"size": (64, 64)}, False),
        "np.random.triangular": lambda: ((0.0, 0.5, 1.0, (64, 64)),
                                         {}, False),
        "np.random.seed": lambda: ((0,), {}, False),
        "np.random.get_state": lambda: ((), {}, False),
        # --- linalg: well-conditioned inputs ---
        "np.linalg.cholesky": lambda: ((_posdef(),), {}, False),
        "np.linalg.inv": lambda: ((_posdef(),), {}, False),
        "np.linalg.pinv": lambda: ((t((16, 8)),), {}, False),
        "np.linalg.solve": lambda: ((_posdef(), t((16, 4))), {}, False),
        "np.linalg.lstsq": lambda: ((t((16, 8)), t((16, 2))), {
            "rcond": None}, False),
        "np.linalg.det": lambda: ((_posdef(),), {}, False),
        "np.linalg.slogdet": lambda: ((_posdef(),), {}, False),
        "np.linalg.eig": lambda: ((_posdef(),), {}, False),
        "np.linalg.eigh": lambda: ((_posdef(),), {}, False),
        "np.linalg.eigvals": lambda: ((_posdef(),), {}, False),
        "np.linalg.eigvalsh": lambda: ((_posdef(),), {}, False),
        "np.linalg.svd": lambda: ((t((16, 8)),), {}, False),
        "np.linalg.qr": lambda: ((t((16, 8)),), {}, False),
        "np.linalg.norm": lambda: ((t((64, 64)),), {}, True),
        "np.linalg.cond": lambda: ((_posdef(),), {}, False),
        "np.linalg.matrix_rank": lambda: ((t((16, 8)),), {}, False),
        "np.linalg.matrix_power": lambda: ((_posdef(), 3), {}, False),
        "np.linalg.multi_dot": lambda: (
            ([t((16, 16)), t((16, 16)), t((16, 16))],), {}, False),
        "np.linalg.tensorsolve": lambda: (
            (mx.np.array(rng.randn(4, 4, 4, 4).astype(onp.float32)
                         + 4 * onp.eye(16).reshape(4, 4, 4, 4)),
             t((4, 4))), {}, False),
        "np.linalg.tensorinv": lambda: (
            (mx.np.array(rng.randn(4, 4, 4, 4).astype(onp.float32)
                         + 4 * onp.eye(16).reshape(4, 4, 4, 4)),),
            {}, False),
        # --- fft ---
        "np.fft.fftfreq": lambda: ((64,), {}, False),
        "np.fft.rfftfreq": lambda: ((64,), {}, False),
        "np.fft.fftshift": lambda: ((t((64,)),), {}, False),
        "np.fft.ifftshift": lambda: ((t((64,)),), {}, False),
        "np.fft.irfft": lambda: ((np.fft.rfft(t((64, 64))),), {}, False),
        "np.fft.ifft": lambda: ((np.fft.fft(t((64, 64))),), {}, False),
        "np.fft.ihfft": lambda: ((t((64,)),), {}, False),
    }
    _CACHE["specials"] = R
    return R


def build_call(name: str, fn: Callable) -> Optional[Tuple[tuple, dict, bool]]:
    """Resolve inputs for an op: special rule first, then the generic
    candidate chain. Returns (args, kwargs, differentiable) or None."""
    mx = _mx()
    specials = _special_rules()
    if name in specials:
        try:
            return specials[name]()
        except TimeoutError:
            raise  # the per-op alarm is spent: never retry blind
        except Exception:  # noqa: BLE001 — fall through to generic
            pass
    I = _inputs()
    candidates = [
        ((I["x"],), {}, True),                  # unary float
        ((I["x"], I["y"]), {}, True),           # binary float
        (([I["x"], I["y"]],), {}, True),        # list of arrays
        ((I["v"],), {}, True),                  # vector
        ((I["x"], I["iv"]), {}, False),         # float + int index
        ((I["iv"],), {}, False),                # int vector
        ((I["bm"],), {}, False),                # bool mask
        (((64, 64),), {}, False),               # shape tuple (creation)
        ((64,), {}, False),                     # scalar size
        ((I["x"], 2), {}, False),               # float + small int
        ((I["x"], 0.5), {}, False),             # float + scalar
        ((I["iv"], I["iv"]), {}, False),        # int binary (gcd, shifts)
        ((I["v"], I["v"]), {}, False),          # vector binary (poly ops)
    ]
    for args, kwargs, diff in candidates:
        try:
            out = fn(*args, **kwargs)
            _materialize(out)
            return args, kwargs, diff
        except TimeoutError:
            raise  # alarm spent — a later candidate could hang unguarded
        except Exception:  # noqa: BLE001 — try the next shape rule
            continue
    return None


def fetch_with_timeout(a, seconds: float = 45.0):
    """Device->host fetch of one element, bounded by a worker-thread
    timeout. A SIGALRM cannot interrupt a fetch blocked in native code
    (observed: a backend that died mid-sweep left the process wedged for
    minutes past the per-op alarm), so the fetch runs on a daemon thread
    and a TimeoutError is raised from the caller's thread instead."""
    import queue
    import threading

    # plain daemon thread, NOT a ThreadPoolExecutor: concurrent.futures
    # registers an atexit join of its (non-daemon) workers, so a fetch
    # wedged in native code would still block interpreter exit
    box: "queue.Queue" = queue.Queue(maxsize=1)

    def _fetch():
        try:
            box.put((True, onp.asarray(
                a.ravel()[0] if getattr(a, "ndim", 0) else a)))
        except BaseException as e:  # noqa: BLE001 — relayed to caller
            box.put((False, e))

    threading.Thread(target=_fetch, daemon=True).start()
    try:
        ok, val = box.get(timeout=seconds)
    except queue.Empty:
        raise TimeoutError(f"device fetch exceeded {seconds}s")
    if not ok:
        raise val
    return val


def _materialize(out) -> None:
    """Block until every array in a (possibly nested) result is real."""
    import jax

    from mxnet_tpu.ndarray.ndarray import ndarray

    leaves = []

    def walk(o):
        if isinstance(o, ndarray):
            leaves.append(o._data)
        elif isinstance(o, (list, tuple)):
            for e in o:
                walk(e)

    walk(out)
    if leaves:
        jax.block_until_ready(leaves)
        # async errors surface only at fetch time: a one-element
        # device->host fetch — the device executes in order, so fetching
        # from the LAST leaf covers the whole loop
        last = leaves[-1]
        if getattr(last, "size", 0):
            fetch_with_timeout(last)


def bench_registry_op(name: str, fn: Callable, args, kwargs, diff,
                      warmup: int, runs: int) -> dict:
    """Eager per-op latency; optionally the autograd round trip."""
    mx = _mx()

    for _ in range(max(warmup, 1)):
        out = fn(*args, **kwargs)
    _materialize(out)
    t0 = time.perf_counter()
    for _ in range(runs):
        out = fn(*args, **kwargs)
    _materialize(out)
    fwd_ms = (time.perf_counter() - t0) / runs * 1e3

    def _shape(a):
        return list(a.shape) if hasattr(a, "shape") else repr(a)[:24]

    rec = {f"avg_time_forward_{name.split('.')[-1]}": round(fwd_ms, 4),
           "inputs": {f"arg{i}": _shape(a) for i, a in enumerate(args)}}

    if diff:
        from mxnet_tpu import autograd
        from mxnet_tpu.ndarray.ndarray import ndarray

        grads_ok = True
        arr_args = [a for a in args if isinstance(a, ndarray)]
        try:
            for a in arr_args:
                a.attach_grad()

            def fwd_bwd():
                with autograd.record():
                    o = fn(*args, **kwargs)
                    if isinstance(o, (list, tuple)):
                        o = o[0]
                    loss = o.sum()
                loss.backward()
                return loss

            loss = fwd_bwd()
            _materialize(loss)
        except TimeoutError:
            raise
        except Exception:  # noqa: BLE001 — op not differentiable here
            grads_ok = False
        if grads_ok:
            for _ in range(max(warmup, 1)):
                loss = fwd_bwd()
            _materialize(loss)
            t0 = time.perf_counter()
            for _ in range(runs):
                loss = fwd_bwd()
            _materialize(loss)
            rec[f"avg_time_forward_backward_{name.split('.')[-1]}"] = round(
                (time.perf_counter() - t0) / runs * 1e3, 4)
    return rec
