"""Backend helpers for the C ABI (``src/c_api/c_api.cc``).

The reference exposes 262 ``MXNET_DLL`` functions whose bodies live in C++
(``src/c_api/``); here the runtime is Python/JAX, so the stable C surface
is a thin layer over these helpers (called via the CPython API from
``libmxtpu_capi.so``). Other-language frontends (layer 11) link against
the .so and never see Python.

Every function takes/returns only simple types (bytes, tuples, ints,
opaque object refs) so the C side stays mechanical.
"""
from __future__ import annotations

import json

import numpy as onp

__version_number__ = 20000  # 2.0.0 — MXGetVersion parity

_DTYPE_TO_CODE = {"float32": 0, "float64": 1, "int32": 4, "int64": 5,
                  "uint8": 6, "bool": 7}
_CODE_TO_DTYPE = {v: k for k, v in _DTYPE_TO_CODE.items()}


def version() -> int:
    return __version_number__


def from_buffer(raw: bytes, shape: tuple, dtype_code: int):
    from . import numpy as mxnp

    arr = onp.frombuffer(raw, dtype=_CODE_TO_DTYPE[dtype_code]).reshape(shape)
    return mxnp.array(arr)


def to_bytes(arr) -> bytes:
    return onp.ascontiguousarray(arr.asnumpy()).tobytes()


def shape(arr) -> tuple:
    return tuple(int(s) for s in arr.shape)


def dtype_code(arr) -> int:
    return _DTYPE_TO_CODE[str(onp.dtype(arr.dtype))]


def invoke(op_name: str, inputs: tuple, kwargs_json: str) -> tuple:
    """Invoke an eager op by qualified name ("np.add", "npx.relu", or a
    bare name searched in npx then np) — MXImperativeInvokeEx parity."""
    from . import numpy as mxnp
    from . import numpy_extension as npx
    from .base import MXNetError
    from .ndarray.ndarray import ndarray

    if op_name.startswith("np."):
        fn = getattr(mxnp, op_name[3:], None)
    elif op_name.startswith("npx."):
        fn = getattr(npx, op_name[4:], None)
    else:
        fn = getattr(npx, op_name, None) or getattr(mxnp, op_name, None)
    if fn is None:
        raise MXNetError(f"unknown operator {op_name!r}")
    kwargs = json.loads(kwargs_json) if kwargs_json else {}
    out = fn(*inputs, **kwargs)
    if isinstance(out, tuple):
        return out
    return (out,)


def waitall() -> None:
    from . import engine

    engine.waitall()


def attach_grad(arr) -> None:
    arr.attach_grad()


def autograd_record(on: int) -> None:
    from . import autograd
    from .ops.dispatch import autograd_state, Tape

    if on:
        autograd_state.recording = True
        autograd_state.training = True
        if autograd_state.tape is None:
            autograd_state.tape = Tape()
    else:
        autograd_state.recording = False
        autograd_state.training = False


def backward(loss) -> None:
    from .ops.dispatch import backward as _backward

    _backward([loss])


def grad(arr):
    g = arr.grad
    if g is None:
        raise ValueError("array has no gradient (attach_grad not called?)")
    return g


def autograd_is_recording() -> int:
    from .ops.dispatch import autograd_state

    return int(autograd_state.recording)


def random_seed(seed: int) -> None:
    from .numpy import random as mxrandom

    mxrandom.seed(seed)


def device_info() -> tuple:
    """(platform, device_count) of the default backend."""
    import jax

    devs = jax.devices()
    return devs[0].platform, len(devs)


def ndarray_context(arr) -> str:
    return str(getattr(arr, "ctx", "cpu(0)"))


def list_ops() -> tuple:
    """All invokable op names, 'np.'-/'npx.'-qualified
    (MXListAllOpNames parity)."""
    from . import numpy as mxnp
    from . import numpy_extension as npx

    names = []
    for mod, prefix in ((mxnp, "np."), (npx, "npx.")):
        for n in dir(mod):
            if not n.startswith("_") and callable(getattr(mod, n, None)):
                names.append(prefix + n)
    return tuple(sorted(names))


# ---- NDArray save/load (MXNDArraySave/Load; reference ndarray.cc) ---------

def save_ndarrays(fname: str, names, arrays) -> None:
    from . import serialization

    if names:
        serialization.save(fname, dict(zip(names, arrays)))
    else:
        serialization.save(fname, list(arrays))


def load_ndarrays(fname: str) -> tuple:
    """-> (names tuple (empty strings for list-saved), arrays tuple)."""
    from . import serialization

    out = serialization.load(fname)
    if isinstance(out, dict):
        return tuple(out.keys()), tuple(out.values())
    return tuple("" for _ in out), tuple(out)


# ---- Symbol (MXSymbol*; reference c_api_symbolic.cc) ----------------------

def symbol_load(fname: str):
    from .symbol import symbol as _sym

    return _sym.load(fname)


def symbol_fromjson(text: str):
    from .symbol.symbol import Symbol

    return Symbol.fromjson(text)


def symbol_tojson(sym) -> str:
    return sym.tojson()


def symbol_save(sym, fname: str) -> None:
    sym.save(fname)


def symbol_arguments(sym) -> tuple:
    return tuple(sym.list_arguments())


def symbol_outputs(sym) -> tuple:
    return tuple(sym.list_outputs())


def symbol_infer_shape(sym, shapes_json: str) -> str:
    """JSON {name: [dims...]} -> JSON {"arg_shapes": {...},
    "out_shapes": [...]} (MXSymbolInferShape with a mechanical wire
    format instead of the reference's pointer-array triple)."""
    shapes = {k: tuple(v) for k, v in json.loads(shapes_json).items()}
    arg_shapes, out_shapes, _aux = sym.infer_shape(**shapes)
    return json.dumps({
        "arg_shapes": {n: list(s) for n, s in
                       zip(sym.list_arguments(), arg_shapes)},
        "out_shapes": [list(s) for s in out_shapes],
    })


# ---- CachedOp over durable exports (MXCachedOp*; c_api_ndarray.cc) --------

def cachedop_create(symbol_file: str, param_file):
    """Load an exported model (StableHLO envelope + .params) as a
    callable — the C-side CachedOp: reference MXCreateCachedOp over a
    loaded symbol. Returns the SymbolBlock."""
    from .gluon.block import SymbolBlock

    return SymbolBlock.imports(symbol_file, param_file=param_file or None)


def cachedop_invoke(block, inputs: tuple) -> tuple:
    out = block(*inputs)
    if isinstance(out, (list, tuple)):
        return tuple(out)
    return (out,)


# ---- Predictor (c_predict_api.cc-shaped convenience layer) ----------------

class _Predictor:
    """Inference session over an exported model: set inputs by key or
    position, forward once, read outputs — the reference's
    MXPred* workflow (src/c_api/c_predict_api.cc) without a Python
    caller."""

    def __init__(self, symbol_file: str, param_file):
        from .gluon.block import SymbolBlock

        self.block = SymbolBlock.imports(symbol_file,
                                         param_file=param_file or None)
        self.meta = self.block._meta
        self.in_specs = self.meta["inputs"]
        self.inputs = [None] * len(self.in_specs)
        self.outputs = None

    def input_index(self, key: str) -> int:
        if key in ("", "data") or not key:
            return 0
        if key.startswith("data") and key[4:].isdigit():
            return int(key[4:])
        raise ValueError(
            f"unknown input key {key!r} (exports have positional inputs; "
            f"use 'data' or 'dataN')")

    def set_input(self, index: int, raw: bytes) -> None:
        from . import numpy as mxnp

        spec = self.in_specs[index]
        # the C predict surface traffics in float32 buffers (reference
        # mx_float); cast to the export's declared input dtype
        arr = onp.frombuffer(raw, dtype="float32").astype(
            spec["dtype"]).reshape(spec["shape"])
        self.inputs[index] = mxnp.array(arr)

    def forward(self) -> None:
        missing = [i for i, v in enumerate(self.inputs) if v is None]
        if missing:
            raise ValueError(f"inputs not set: {missing}")
        out = self.block(*self.inputs)
        if not isinstance(out, (list, tuple)):
            out = (out,)
        # the C predict ABI hands host float32 buffers to the caller —
        # this sync IS the contract (astype(copy=False) avoids the old
        # double conversion when the output is already f32)
        self.outputs = [
            o.asnumpy().astype(onp.float32, copy=False)  # tpulint: disable=A001
            for o in out]

    def output_shape(self, index: int) -> tuple:
        if self.outputs is not None:
            return tuple(self.outputs[index].shape)
        avals = self.block._exported.out_avals
        leaf = jax_tree_leaves(avals)[index]
        return tuple(leaf.shape)

    def get_output(self, index: int) -> bytes:
        if self.outputs is None:
            raise ValueError("call forward() before get_output()")
        return onp.ascontiguousarray(self.outputs[index]).tobytes()


def jax_tree_leaves(tree):
    import jax

    return jax.tree_util.tree_leaves(tree)


def pred_create(symbol_file: str, param_file: str):
    return _Predictor(symbol_file, param_file)


def pred_set_input(pred, key: str, raw: bytes) -> None:
    pred.set_input(pred.input_index(key), raw)


def pred_forward(pred) -> None:
    pred.forward()


def pred_output_shape(pred, index: int) -> tuple:
    return pred.output_shape(index)


def pred_get_output(pred, index: int) -> bytes:
    return pred.get_output(index)


# --------------------------------------------------------------------------
# Round-3 widening #2: KVStore, Executor, NDArray manipulation, autograd
# breadth, runtime control (reference c_api.h MXKVStore*/MXExecutor*/
# MXNDArraySlice/At/Reshape, MXAutogradMarkVariables, MXSetProfilerState,
# MXLoadLib, MXLibInfoFeatures).
# --------------------------------------------------------------------------

def kv_create(type_str: str):
    from . import kvstore

    return kvstore.create(type_str or "local")


def _kv_keys(keys: tuple):
    return [int(k) for k in keys]


def kv_init(store, keys: tuple, vals: tuple) -> None:
    store.init(_kv_keys(keys), list(vals))


def kv_push(store, keys: tuple, vals: tuple, priority: int) -> None:
    store.push(_kv_keys(keys), list(vals), priority=priority)


def kv_pull(store, keys: tuple, priority: int) -> tuple:
    from . import numpy as mxnp

    keys = _kv_keys(keys)
    # placeholders must mirror the stored dtype: pull casts into the
    # out array's dtype, so a fixed-float32 placeholder would silently
    # downcast int64/float64 values on the way to the C caller. Sizing
    # them needs the stored arrays, which only the local-family stores
    # expose — plugin KVStoreBase backends get a clean refusal instead
    # of an AttributeError deep inside.
    from .base import MXNetError

    backing = getattr(store, "_store", None)
    if backing is None:
        raise MXNetError(
            f"MXKVStorePull: store type {type(store).__name__!r} does not "
            "expose stored values for C-side output allocation; pull this "
            "store from Python instead")
    outs = []
    for k in keys:
        stored = backing.get(k)
        if stored is None:
            raise KeyError(f"kv_pull: key {k} was never init'ed")
        outs.append(mxnp.zeros(stored.shape, dtype=stored.dtype))
    store.pull(keys, out=outs, priority=priority)
    return tuple(outs)


def kv_pushpull(store, keys: tuple, vals: tuple, priority: int) -> tuple:
    keys = _kv_keys(keys)
    vals = list(vals)
    outs = [v.copy() for v in vals]
    store.pushpull(keys, vals, out=outs, priority=priority)
    return tuple(outs)


def kv_broadcast(store, keys: tuple, vals: tuple, priority: int) -> tuple:
    keys = _kv_keys(keys)
    vals = list(vals)
    outs = [v.copy() for v in vals]
    store.broadcast(keys, vals, outs, priority=priority)
    return tuple(outs)


def kv_type(store) -> str:
    return store.type


def kv_rank(store) -> int:
    return int(store.rank)


def kv_num_workers(store) -> int:
    return int(store.num_workers)


def kv_set_updater(store, trampoline) -> None:
    """``trampoline(key:int, recv, local)`` is the C-side callback
    (a PyCFunction wrapping the caller's function pointer); the store's
    updater contract is updater(key, recv, local) mutating local."""
    store.set_updater(lambda key, recv, local: trampoline(int(key), recv,
                                                          local))


# ---- Executor (MXExecutorSimpleBind / Forward / Backward / Outputs) ----

def executor_simple_bind(sym, shapes_json: str, grad_req: str):
    shapes = {k: tuple(v) for k, v in json.loads(shapes_json).items()}
    return sym.simple_bind(grad_req=grad_req, **shapes)


def executor_forward(ex, is_train: int, names: tuple, arrays: tuple) -> int:
    kwargs = dict(zip(names, arrays))
    outs = ex.forward(is_train=bool(is_train), **kwargs)
    return len(outs)


def executor_outputs(ex) -> tuple:
    return tuple(ex.outputs)


def executor_backward(ex, out_grads: tuple) -> None:
    ex.backward(list(out_grads) if out_grads else None)


def executor_arg_grad(ex, name: str):
    g = ex.grad_dict.get(name)
    if g is None:
        raise KeyError(f"no gradient for argument {name!r} "
                       f"(grad_req null or unknown name)")
    return g


# ---- NDArray manipulation (MXNDArrayReshape / Slice / At / CopyFrom) ----

def nd_reshape(arr, shape: tuple):
    return arr.reshape(tuple(int(s) for s in shape))


def nd_slice(arr, begin: int, end: int):
    return arr[int(begin):int(end)]


def nd_at(arr, idx: int):
    return arr[int(idx)]


def nd_copy_from_bytes(arr, raw: bytes) -> None:
    """In-place overwrite from host memory (MXNDArraySyncCopyFromCPU):
    the handle keeps identity, so views/graph references see new data."""
    src = onp.frombuffer(raw, dtype=str(onp.dtype(arr.dtype)))
    arr[...] = src.reshape(arr.shape)


def nd_astype(arr, dtype_code: int):
    return arr.astype(_CODE_TO_DTYPE[dtype_code])


# ---- autograd breadth ----

def autograd_set_training(on: int) -> int:
    from . import autograd

    return int(autograd.set_training(bool(on)))


def autograd_is_training() -> int:
    from . import autograd

    return int(autograd.is_training())


def autograd_mark_variables(arrays: tuple, grad_reqs: tuple) -> None:
    for arr, req in zip(arrays, grad_reqs):
        arr.attach_grad(grad_req=req)


def autograd_backward_ex(heads: tuple, head_grads, retain_graph: int,
                         train_mode: int) -> None:
    from . import autograd

    grads = None
    if head_grads is not None:
        # per-head None entries mean "ones" (reference per-head nullptr)
        grads = list(head_grads)
        if all(g is None for g in grads):
            grads = None
    autograd.backward(list(heads), head_grads=grads,
                      retain_graph=bool(retain_graph),
                      train_mode=bool(train_mode))


# ---- runtime control ----

def load_lib(path: str) -> None:
    from . import library

    library.load(path)


def profiler_set_state(state: int) -> None:
    from . import profiler

    profiler.set_state("run" if state else "stop")


def profiler_dump(finished: int) -> None:
    from . import profiler

    profiler.dump(bool(finished))


def libinfo_features() -> tuple:
    from .runtime import Features

    return tuple(f"{name}={int(feat.enabled)}"
                 for name, feat in Features().items())


def symbol_aux_states(sym) -> tuple:
    return tuple(sym.list_auxiliary_states())


def engine_set_bulk_size(size: int) -> int:
    from . import engine

    prev = engine.set_bulk_size(int(size))
    return int(prev)


# ---- Symbol composition from C (MXSymbolCreateVariable /
#      CreateAtomicSymbol / Compose / Group / attrs / GetAtomicSymbolInfo;
#      reference c_api_symbolic.cc: MXSymbolCreateAtomicSymbol,
#      MXSymbolCompose mutate-in-place contract) ----

def _parse_param(text: str):
    """Reference atomic-symbol params arrive as strings ("64", "True",
    "(2,)", "None"); decode to python values where the literal parses
    (json first for "true"/"[2, 2]", then python literals for tuples,
    None and friends), else keep the raw string."""
    import ast

    try:
        return json.loads(text)
    except Exception:
        try:
            return ast.literal_eval(text.strip())
        except Exception:
            return text


def symbol_variable(name: str):
    from .symbol import symbol as _sym

    return _sym.var(name)


def symbol_create_atomic(op_name: str, keys: tuple, vals: tuple, name: str):
    """An atomic symbol is op + params with inputs still unbound; the
    reference keeps it legal to pass around before MXSymbolCompose binds
    inputs in place. Modeled as an empty-headed Symbol carrying the
    pending call."""
    from .symbol import symbol as _sym

    if op_name not in _sym._registry():
        raise KeyError(f"unknown op {op_name!r} "
                       "(MXListAllOpNames lists the registry)")
    s = _sym.Symbol([])
    s._pending = (op_name,
                  {k: _parse_param(v) for k, v in zip(keys, vals)},
                  name or None)
    s._pending_attrs = {}
    return s


def _pending_of(s):
    return getattr(s, "_pending", None)


def _require_composed(s, what: str):
    if _pending_of(s) is not None:
        raise ValueError(
            f"{what}: atomic symbol {s._pending[0]!r} has unbound inputs "
            "— call MXSymbolCompose first")


def symbol_compose(s, name: str, keys: tuple, args: tuple) -> None:
    """Mutates ``s`` in place (the reference contract: the handle passed
    to MXSymbolCompose IS the composed symbol afterwards).

    Two modes, as in the reference:
      - atomic symbol: bind the op's inputs (positional when keys empty,
        by parameter name otherwise);
      - composed symbol: substitute free variables by name (keys
        required); ``name`` renames the composite head.
    """
    from .symbol import symbol as _sym

    pending = _pending_of(s)
    if pending is not None:
        op_name, params, at_name = pending
        pos, kw = (), {}
        if keys:
            kw = dict(zip(keys, args))
        else:
            pos = tuple(args)
        final = name or at_name
        if final:
            params = dict(params, name=final)
        composed = _sym._sym_op(op_name, *pos, **kw, **params)
        attrs = getattr(s, "_pending_attrs", None)
        if attrs:
            composed._set_attr(**attrs)
        s._heads = composed._heads
        del s._pending
        if attrs is not None:
            del s._pending_attrs
    else:
        if not keys:
            raise ValueError(
                "composing a non-atomic symbol substitutes variables: "
                "keys (variable names) are required")
        composed = s(**dict(zip(keys, args)))
        if name and len(composed._heads) == 1:
            # rename the composite head (reference MXSymbolCompose name
            # argument); clone so an unchanged shared node isn't renamed
            # out from under other symbols
            node, slot = composed._heads[0]
            renamed = _sym._Node(node.op, name, list(node.pos_spec),
                                 dict(node.kwargs), dict(node.kw_sym),
                                 list(node.inputs), node.n_out,
                                 dict(node.attrs))
            composed = _sym.Symbol([(renamed, slot)])
        s._heads = composed._heads


def symbol_copy(s):
    """Independent deep copy via the JSON wire format (reference
    __deepcopy__ -> MXSymbolCopy)."""
    from .symbol import symbol as _sym

    if _pending_of(s) is not None:
        c = _sym.Symbol([])
        op, params, nm = s._pending
        c._pending = (op, dict(params), nm)
        c._pending_attrs = dict(getattr(s, "_pending_attrs", {}))
        return c
    return _sym.fromjson(s.tojson())


def symbol_get_name(s) -> str:
    pending = _pending_of(s)
    if pending is not None:
        op_name, _, at_name = pending
        return at_name or op_name.split(".")[-1]
    return s.name


def symbol_get_attr(s, key: str) -> tuple:
    if _pending_of(s) is not None:
        val = getattr(s, "_pending_attrs", {}).get(key)
    else:
        val = s.attr(key)
    return (0, "") if val is None else (1, str(val))


def symbol_set_attr(s, key: str, val: str) -> None:
    if _pending_of(s) is not None:
        # legal before compose in the reference; applied to the node at
        # compose time
        s._pending_attrs[key] = val
        return
    s._set_attr(**{key: val})


def symbol_list_attr(s) -> str:
    if _pending_of(s) is not None:
        attrs = getattr(s, "_pending_attrs", {})
        return json.dumps(
            {symbol_get_name(s): dict(attrs)} if attrs else {})
    return json.dumps(s.attr_dict())


def symbol_group(syms: tuple):
    from .symbol import symbol as _sym

    for m in syms:
        _require_composed(m, "MXSymbolCreateGroup")
    return _sym.Group(list(syms))


def symbol_get_internals(s):
    _require_composed(s, "MXSymbolGetInternals")
    return s.get_internals()


def symbol_num_outputs(s) -> int:
    _require_composed(s, "MXSymbolGetNumOutputs")
    return len(s)


def symbol_get_output(s, index: int):
    _require_composed(s, "MXSymbolGetOutput")
    return s[int(index)]


def atomic_symbol_info(op_name: str) -> str:
    """JSON {name, description, args: [{name, default}]} from the live
    registry (the reference's MXSymbolGetAtomicSymbolInfo doc tuple,
    sourced from dmlc parameter registration; here the op signature IS
    the parameter registration)."""
    import inspect

    from .symbol import symbol as _sym

    reg = _sym._registry()
    if op_name not in reg:
        raise KeyError(f"unknown op {op_name!r}")
    fn = reg[op_name]
    doc = inspect.getdoc(fn) or ""
    args = []
    try:
        for p in inspect.signature(fn).parameters.values():
            if p.kind in (p.VAR_POSITIONAL, p.VAR_KEYWORD):
                continue
            entry = {"name": p.name}
            if p.default is not p.empty:
                entry["default"] = repr(p.default)
            args.append(entry)
    except (TypeError, ValueError):
        pass
    return json.dumps({"name": op_name, "description": doc, "args": args})


def nd_wait_to_read(arr) -> None:
    arr.wait_to_read()


def nd_wait_to_write(arr) -> None:
    # write-wait = read-wait in the XLA model (no pending writers beyond
    # the async dispatch the read already drains)
    arr.wait_to_read()


def symbol_infer_type(sym, dtypes_json: str) -> str:
    dtypes = json.loads(dtypes_json) if dtypes_json else {}
    arg_types, out_types, aux_types = sym.infer_type(**dtypes)
    return json.dumps({
        "arg_types": [str(t) for t in arg_types],
        "out_types": [str(t) for t in out_types],
        "aux_types": [str(t) for t in aux_types],
    })


def symbol_get_children(sym):
    kids = sym.get_children()
    if kids is None:
        raise ValueError("variable symbol has no children")
    return kids
