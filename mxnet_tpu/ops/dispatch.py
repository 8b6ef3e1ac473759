"""Imperative op dispatch + autograd tape.

This is the TPU-native re-design of the reference imperative runtime
(``src/imperative/imperative.cc``: ``Imperative::Invoke :98``,
``RecordOp :204``, ``Backward :376``) re-thought for XLA:

- Every eager op is a *pure jax function* ``fn(*arrays, **static)``.
  Dispatch unwraps ``ndarray`` inputs, calls the function (XLA executes it
  asynchronously — jax's dispatch gives us the reference engine's
  "frontend thread never blocks" contract for free), and wraps outputs.
- Under ``autograd.record()`` we additionally compute ``jax.vjp`` at call
  time, so the tape stores a ready-made pullback per node; ``Backward``
  is then a single reverse sweep with no graph re-execution (the reference
  builds a backward nnvm graph and re-runs it through the engine; on TPU
  the pullback closure holding XLA residual buffers is the better design).
- Ops stay trace-transparent: ``ndarray`` can hold jax tracers, so the same
  eager op implementations are reused when a HybridBlock is jit-traced
  (the CachedOp path) — one op library, two execution modes, exactly the
  imperative/symbolic duality of the reference.
"""
from __future__ import annotations

import functools
import threading
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax

from ..base import MXNetError
from ..telemetry import tracing as _tracing

__all__ = ["apply_op", "Tape", "autograd_state", "is_recording", "is_training"]


class _AutogradState(threading.local):
    """Per-thread recording/training flags (Imperative::set_is_recording /
    set_is_training, reference include/mxnet/imperative.h:150-170)."""

    def __init__(self) -> None:
        self.recording = False
        self.training = False
        self.tape: Optional["Tape"] = None


autograd_state = _AutogradState()

# set by mxnet_tpu.amp.init(): an AMPPolicy whose cast_inputs(name, vals)
# applies the mixed-precision cast rule at this single dispatch chokepoint
amp_policy = None



def is_recording() -> bool:
    return autograd_state.recording


def is_training() -> bool:
    return autograd_state.training


class TapeNode:
    """One recorded op: pullback + graph edges (reference AGInfo,
    include/mxnet/imperative.h:54)."""

    __slots__ = (
        "vjp_fn",
        "replay_fn",
        "inputs",
        "n_out",
        "out_ids",
        "out_refs",
        "out_avals",
        "name",
        "req_grad",
        "residual_bytes",
    )

    def __init__(self, vjp_fn, inputs, n_out, name, out_avals=(), replay_fn=None,
                 residual_bytes=0):
        self.vjp_fn = vjp_fn
        self.replay_fn = replay_fn  # pure fn(*input_vals) for higher-order replay
        self.inputs = inputs  # list of ndarray refs (keeps leaves alive)
        self.n_out = n_out
        self.out_ids: List[int] = []
        # strong refs: producer-map keys are id()s, so output objects must
        # stay alive for the tape's lifetime or ids could be recycled
        self.out_refs: List[Any] = []
        self.out_avals = out_avals  # [(shape, dtype)] for zero cotangents
        self.name = name
        self.req_grad = True
        # bytes a compiled forward handed this node's pullback (a
        # hybridized block's recorded call); 0 for an eager op
        self.residual_bytes = residual_bytes


class Tape:
    """The dynamic autograd graph built by recording (the RecordOp tape)."""

    def __init__(self) -> None:
        self.nodes: List[TapeNode] = []
        # id(ndarray) -> (node_index, output_slot)
        self.producer: dict = {}

    def add(self, node: TapeNode, outputs: Sequence[Any]) -> None:
        idx = len(self.nodes)
        self.nodes.append(node)
        for slot, out in enumerate(outputs):
            node.out_ids.append(id(out))
            node.out_refs.append(out)
            self.producer[id(out)] = (idx, slot)
            out._fresh_grad_node = (idx, slot)

    def alias(self, original: Any, replacement: Any) -> None:
        """Register ``replacement`` as another handle for ``original``'s
        tape slot (re-wrapped cached-op outputs)."""
        entry = self.producer.get(id(original))
        if entry is None:
            return
        idx, slot = entry
        self.producer[id(replacement)] = entry
        self.nodes[idx].out_refs.append(replacement)
        replacement._fresh_grad_node = entry


def _differentiable(arr) -> bool:
    """Float and complex arrays participate in grad flow (XLA vjp
    requirement; complex supports spectral losses through np.fft)."""
    import numpy as onp

    dt = onp.dtype(arr.dtype)
    return (onp.issubdtype(dt, onp.floating)
            or onp.issubdtype(dt, onp.complexfloating)
            or str(arr.dtype) == "bfloat16")


def apply_op(
    fn: Callable,
    arrays: Sequence[Any],
    static: Optional[dict] = None,
    n_out: int = 1,
    name: Optional[str] = None,
):
    """Invoke one eager op (the Imperative::Invoke equivalent).

    ``arrays`` are ndarray/array-like positional inputs; ``static`` are
    non-differentiable keyword attributes (the op's dmlc::Parameter set).
    """
    from .. import profiler as _profiler

    if _profiler.is_running():
        import time as _time

        _t0 = _time.perf_counter()
        try:
            return _apply_op(fn, arrays, static, n_out, name)
        finally:
            _profiler.record_op(
                name or getattr(fn, "__name__", "op"), _time.perf_counter() - _t0
            )
    return _apply_op(fn, arrays, static, n_out, name)


def _apply_op(
    fn: Callable,
    arrays: Sequence[Any],
    static: Optional[dict] = None,
    n_out: int = 1,
    name: Optional[str] = None,
):
    from ..ndarray.ndarray import ndarray, _wrap, _unwrap

    vals = [_unwrap(a) for a in arrays]
    if amp_policy is not None and name is not None:
        vals = amp_policy.cast_inputs(name, vals)
    call = functools.partial(fn, **static) if static else fn

    state = autograd_state
    record = state.recording and state.tape is not None
    if record:
        grad_inputs = [
            i
            for i, a in enumerate(arrays)
            if isinstance(a, ndarray) and _differentiable(a) and _tracks_grad(a, state.tape)
        ]
        record = bool(grad_inputs)

    from .. import engine as _engine

    if not record:
        out_vals = call(*vals)
        # MXNET_ENGINE_TYPE=NaiveEngine or bulk(0): block per op (live
        # knobs — the reference engine factory reads them per push);
        # otherwise register for deferred-error surfacing at waitall()
        if not _engine.maybe_sync(out_vals):
            _engine._track(out_vals)
        if n_out == 1:
            return _wrap(out_vals)
        return tuple(_wrap(v) for v in out_vals)

    # recording: single forward via jax.vjp; pullback closes over residuals
    def fwd(*diff_vals):
        full = list(vals)
        for i, v in zip(grad_inputs, diff_vals):
            full[i] = v
        return call(*full)

    out_vals, vjp_fn = jax.vjp(fwd, *[vals[i] for i in grad_inputs])
    # per-op sync applies when recording too; async outputs are tracked
    if not _engine.maybe_sync(out_vals):
        _engine._track(out_vals)
    outs = (
        (_wrap(out_vals),) if n_out == 1 else tuple(_wrap(v) for v in out_vals)
    )
    node = TapeNode(
        vjp_fn,
        [arrays[i] for i in grad_inputs],
        n_out,
        name or getattr(fn, "__name__", "op"),
        out_avals=[(o.shape, o.dtype) for o in outs],
        replay_fn=fwd,
    )
    state.tape.add(node, outs)
    return outs[0] if n_out == 1 else outs


def _tracks_grad(arr, tape: Tape) -> bool:
    """True if ``arr`` is a grad leaf or was produced on the current tape."""
    if getattr(arr, "_grad_req", "null") != "null" and arr._grad is not None:
        return True
    return id(arr) in tape.producer


def backward(
    heads: Sequence[Any],
    head_grads: Optional[Sequence[Any]] = None,
    retain_graph: bool = False,
    train_mode: bool = True,
):
    """Reverse sweep over the tape (Imperative::Backward,
    reference src/imperative/imperative.cc:376).

    Accumulates into each leaf's ``.grad`` honoring its ``grad_req``
    (write/add/null — reference OpReqType, include/mxnet/op_attr_types.h).
    """
    tape = autograd_state.tape
    if tape is None:
        raise MXNetError("backward called outside autograd.record scope with no tape")
    with _tracing.span("autograd.backward", cpu=True,
                       args={"nodes": len(tape.nodes)}) as sp:
        _backward(tape, heads, head_grads, retain_graph, sp)


def _backward(tape: Tape, heads, head_grads, retain_graph: bool, sp) -> None:
    """The sweep of :func:`backward` inside its ``autograd.backward``
    span ``sp``. Each pullback runs under an ``autograd.node:<op>``
    annotation (no ring row: an un-hybridized net has thousands a step);
    the ring gets their sums as ``sp.args["by_op"]``, and as
    ``sp.args["residual_bytes"]`` what compiled forwards handed the nodes
    that ran (0: no hybridized block's call was recorded)."""
    import jax.numpy as jnp

    from .. import engine as _engine
    from ..ndarray.ndarray import ndarray, _unwrap

    # cotangent storage per (node_idx, slot)
    cots: dict = {}
    leaf_grads: dict = {}  # id(leaf ndarray) -> accumulated cotangent

    from ..ndarray.sparse import RowSparseNDArray

    def _acc(prev, ct):
        if prev is None:
            return ct
        if isinstance(ct, RowSparseNDArray):
            return ct + prev  # sparse+sparse concat; sparse+dense -> dense
        if isinstance(prev, RowSparseNDArray):
            return prev + ct
        return prev + ct

    def _route(arr, ct):
        key = id(arr)
        if key in tape.producer:
            cots_key = tape.producer[key]
            cots[cots_key] = _acc(cots.get(cots_key), ct)
        if getattr(arr, "_grad_req", "null") != "null" and arr._grad is not None:
            leaf_grads[key] = _acc(leaf_grads.get(key), ct)
            leaf_grads.setdefault(("arr", key), arr)

    if head_grads is None:
        head_grads = [None] * len(heads)
    pending_nodes = set()
    for h, hg in zip(heads, head_grads):
        if id(h) not in tape.producer and getattr(h, "_grad_req", "null") == "null":
            raise MXNetError("cannot differentiate a head not on the tape")
        ct = jnp.ones(h.shape, h.dtype) if hg is None else _unwrap(hg)
        _route(h, ct)
        if id(h) in tape.producer:
            pending_nodes.add(tape.producer[id(h)][0])

    # reverse topological sweep — tape order is already topological
    by_op: dict = {}    # op name -> [wall s, pullbacks called]
    residual_bytes = 0
    for idx in range(len(tape.nodes) - 1, -1, -1):
        node = tape.nodes[idx]
        slots = [cots.get((idx, s)) for s in range(node.n_out)]
        if all(s is None for s in slots):
            continue
        def _slot_ct(i, s):
            if s is None:
                return jnp.zeros(node.out_avals[i][0], node.out_avals[i][1])
            # a downstream op may produce its input-cotangent in a wider
            # dtype than this node's output (e.g. AMP: a bf16 matmul
            # feeding an fp32-list reduction) — jax.vjp is strict about
            # cotangent dtypes, so cast to the recorded output aval
            want = node.out_avals[i][1]
            if not isinstance(s, RowSparseNDArray) and \
                    getattr(s, "dtype", want) != want:
                s = s.astype(want)
            return s

        full = tuple(_slot_ct(i, s) for i, s in enumerate(slots))
        with _tracing.span("autograd.node:" + node.name, ring=False) as nsp:
            in_cts = node.vjp_fn(full[0] if node.n_out == 1 else full)
        acc = by_op.get(node.name)
        if acc is None:
            acc = by_op[node.name] = [0.0, 0]
        acc[0] += nsp.dur_s
        acc[1] += 1
        residual_bytes += node.residual_bytes
        for arr, ct in zip(node.inputs, in_cts):
            _route(arr, ct)
        if not retain_graph:
            node.vjp_fn = None  # free residuals eagerly
            node.replay_fn = None

    sp.args["ran"] = sum(acc[1] for acc in by_op.values())
    sp.args["residual_bytes"] = residual_bytes
    sp.args["by_op"] = {
        name: [round(acc[0] * 1e3, 3), acc[1]]
        for name, acc in sorted(by_op.items(),
                                key=lambda kv: -kv[1][0])[:8]}

    # write leaf grads honoring grad_req (and grad storage type)
    for key, ct in list(leaf_grads.items()):
        if isinstance(key, tuple):
            continue
        arr = leaf_grads[("arr", key)]
        g = arr._grad
        if isinstance(g, RowSparseNDArray):
            # sparse grad storage: keep only touched rows
            if not isinstance(ct, RowSparseNDArray):
                # dense cotangent into a sparse slot (e.g. tied weights used
                # densely elsewhere): represent as all-rows sparse
                ct = RowSparseNDArray(
                    ct, jnp.arange(ct.shape[0], dtype=jnp.int32), g.shape)
            if arr._grad_req == "add" and g.nnz:
                ct = g + ct
            ct = ct.consolidate()
            g._values = ct._values.astype(g._values.dtype)
            g._indices = ct._indices
        else:
            if isinstance(ct, RowSparseNDArray):
                ct = ct.todense_val()
            if arr._grad_req == "add":
                g._data = g._data + ct.astype(g.dtype)
            else:  # write
                g._data = ct.astype(g.dtype)
        # backward runs async too: in per-op sync mode block on the written
        # grad (NaiveEngine debug must not swallow vjp failures); otherwise
        # register it so waitall() surfaces a deferred vjp failure nobody
        # reads (the reference routes backward ops through the same engine
        # exception store)
        gval = g._values if isinstance(g, RowSparseNDArray) else g._data
        if not _engine.maybe_sync(gval):
            _engine._track(gval)

    if not retain_graph:
        tape.nodes.clear()
        tape.producer.clear()
