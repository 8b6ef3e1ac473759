"""jaxpr-level TPU anti-pattern rules (the IR half of tpulint).

Traces a callable / gluon block with ``jax.make_jaxpr`` and walks the
resulting IR — the same statically-visible features (operand padding
against the MXU tiles, dtype traffic, reduction shapes) a learned TPU
cost model consumes, surfaced as findings before anything runs.

Rules (catalog in :mod:`.findings`):

- **J001 tpu-dot-align** — ``dot_general``/``conv_general_dilated``
  operand dims that pad badly against the float32 (sublane=8, lane=128)
  register tiling. Flagged when the padded tile wastes ≥ 25% of its
  footprint (1000→1024 is fine at 2.3%; 130→256 is 49% waste and flags).
- **J002 tpu-f64-leak** — any float64 value inside the traced program.
  TPUs have no f64 ALU; XLA emulates it at >10× cost, and one weak-typed
  host scalar can upcast a whole subgraph.
- **J003 tpu-convert-churn** — a value converted to another dtype and
  straight back (``convert_element_type`` round-trip), the signature of
  mixed-precision boundaries drawn one op too narrow.
- **J004 tpu-scalar-reduce** — a full reduction to a rank-0 *program
  output*: the canonical host-sync magnet (``float(loss)`` right after).
- **J005 tpu-donation-miss** — an argument whose buffers are all
  reproduced in the outputs (an in-place update) but is absent from
  ``donate_argnums``: the step pays double HBM for every such buffer.
  Cross-checked against the live ``gluon.Trainer`` fused step via
  :func:`lint_trainer`.
"""
from __future__ import annotations

from collections import Counter
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as onp

from .findings import Finding

TILE_SUBLANE = 8
TILE_LANE = 128
WASTE_THRESHOLD = 0.25

_REDUCE_PRIMS = {
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_and", "reduce_or", "argmax", "argmin",
}
# shape/dtype plumbing a reduction result may flow through on its way to
# becoming a program output
_PASSTHROUGH_PRIMS = {
    "convert_element_type", "copy", "squeeze", "reshape", "stop_gradient",
    "device_put",
}


def _waste(dim: int, tile: int) -> float:
    padded = -(-dim // tile) * tile
    return (padded - dim) / padded


def _pad_note(dim: int, tile: int) -> str:
    padded = -(-dim // tile) * tile
    return f"{dim}->{padded} ({100 * _waste(dim, tile):.0f}% pad waste)"


def _misaligned(dim: int, tile: int) -> bool:
    return dim > 1 and _waste(dim, tile) >= WASTE_THRESHOLD


def _aval(var):
    return getattr(var, "aval", None)


def _sub_jaxprs(params: dict):
    """Yield nested (Closed)Jaxprs out of an eqn's params (pjit bodies,
    cond branches, scan/while carcasses, custom_vjp closures)."""
    for v in params.values():
        for item in (v if isinstance(v, (tuple, list)) else (v,)):
            inner = getattr(item, "jaxpr", item)
            if hasattr(inner, "eqns") and hasattr(inner, "outvars"):
                yield inner


def lint_jaxpr(closed, scope: str = "jaxpr") -> List[Finding]:
    """Walk a (Closed)Jaxpr recursively and emit J001–J004 findings."""
    jaxpr = getattr(closed, "jaxpr", closed)
    findings: List[Finding] = []
    seen: set = set()

    def emit(rule, message, detail, hint=""):
        if (rule, detail) in seen:
            return
        seen.add((rule, detail))
        findings.append(Finding(rule, message, scope=scope, detail=detail,
                                hint=hint))

    def check_f64(var, prim):
        aval = _aval(var)
        if aval is not None and str(getattr(aval, "dtype", "")) == "float64":
            emit("J002",
                 f"float64 value produced by `{prim}` — TPUs emulate f64 "
                 "in software",
                 f"{prim}:float64",
                 hint="keep the computation in float32/bfloat16; audit "
                      "host scalars and np.float64 inputs for weak-type "
                      "upcasts")

    def check_dot(eqn):
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        lhs, rhs = _aval(eqn.invars[0]), _aval(eqn.invars[1])
        if lhs is None or rhs is None:
            return
        m = [d for i, d in enumerate(lhs.shape) if i not in lc and i not in lb]
        k = [lhs.shape[i] for i in lc]
        n = [d for i, d in enumerate(rhs.shape) if i not in rc and i not in rb]
        bad = ([("M", d, TILE_SUBLANE) for d in m if _misaligned(d, TILE_SUBLANE)]
               + [("K", d, TILE_LANE) for d in k if _misaligned(d, TILE_LANE)]
               + [("N", d, TILE_LANE) for d in n if _misaligned(d, TILE_LANE)])
        if bad:
            note = ", ".join(f"{ax}={_pad_note(d, t)}" for ax, d, t in bad)
            detail = "dot_general " + ",".join(
                f"{ax}{d}" for ax, d, _ in bad)
            emit("J001",
                 f"dot_general operands pad badly on the MXU: {note} "
                 f"(lhs{tuple(lhs.shape)} @ rhs{tuple(rhs.shape)})",
                 detail,
                 hint="round matmul dims to multiples of (8, 128) — pad "
                      "features/vocab once at model edges instead of "
                      "paying tile padding on every step")

    def check_conv(eqn):
        dn = eqn.params["dimension_numbers"]
        lhs, rhs = _aval(eqn.invars[0]), _aval(eqn.invars[1])
        if lhs is None or rhs is None:
            return
        c_in = lhs.shape[dn.lhs_spec[1]]
        c_out = rhs.shape[dn.rhs_spec[0]]
        bad = []
        if _misaligned(c_in, TILE_SUBLANE):
            bad.append(f"C_in={_pad_note(c_in, TILE_SUBLANE)}")
        if _misaligned(c_out, TILE_LANE):
            bad.append(f"C_out={_pad_note(c_out, TILE_LANE)}")
        if bad:
            emit("J001",
                 "conv feature dims pad badly on the MXU: "
                 + ", ".join(bad),
                 f"conv C{c_in}->{c_out}",
                 hint="prefer channel counts that are multiples of "
                      "(8, 128); for <=4-channel image stems enable the "
                      "space-to-depth rewrite (MXNET_TPU_STEM_S2D)")

    def walk(jx):
        produced_by: Dict[Any, Any] = {}
        for eqn in jx.eqns:
            prim = eqn.primitive.name
            for ov in eqn.outvars:
                check_f64(ov, prim)
                produced_by[ov] = eqn
            if prim == "dot_general":
                check_dot(eqn)
            elif prim == "conv_general_dilated":
                check_conv(eqn)
            elif prim == "convert_element_type":
                src = eqn.invars[0]
                src_eqn = produced_by.get(src)
                if (src_eqn is not None
                        and src_eqn.primitive.name == "convert_element_type"):
                    origin = _aval(src_eqn.invars[0])
                    out = _aval(eqn.outvars[0])
                    if (origin is not None and out is not None
                            and origin.dtype == out.dtype):
                        emit("J003",
                             f"dtype round-trip {origin.dtype}->"
                             f"{_aval(src).dtype}->{out.dtype} "
                             "(convert_element_type churn)",
                             f"churn:{origin.dtype}->{_aval(src).dtype}",
                             hint="hoist the precision boundary so the "
                                  "value is converted once, or keep the "
                                  "intermediate op in the narrow dtype")
            for sub in _sub_jaxprs(eqn.params):
                walk(sub)
        return produced_by

    produced_by = walk(jaxpr)

    # J004: only reductions whose scalar ESCAPES the program are flagged —
    # an internal scalar (epsilon guard, norm denominator) is free.
    for ov in jaxpr.outvars:
        var, hops = ov, 0
        while hops < 8:
            eqn = produced_by.get(var)
            if eqn is None:
                break
            prim = eqn.primitive.name
            if prim in _PASSTHROUGH_PRIMS:
                var, hops = eqn.invars[0], hops + 1
                continue
            aval = _aval(ov)
            if (prim in _REDUCE_PRIMS and aval is not None
                    and tuple(getattr(aval, "shape", (1,))) == ()):
                findings.append(Finding(
                    "J004",
                    f"`{prim}` reduces to a scalar program output — the "
                    "caller will almost certainly sync it to host per step",
                    scope=scope, detail=f"scalar:{prim}",
                    hint="keep running statistics on device and fetch "
                         "once per epoch/log-interval, or batch scalars "
                         "into one array before transferring"))
            break
    return findings


def lint_callable(fn, *example_args, scope: str = "callable",
                  enable_x64: bool = False,
                  static_argnums: Sequence[int] = ()) -> List[Finding]:
    """Trace ``fn`` with ``jax.make_jaxpr`` and lint the IR."""
    import jax

    if enable_x64:
        with jax.enable_x64(True):
            closed = jax.make_jaxpr(
                fn, static_argnums=tuple(static_argnums))(*example_args)
    else:
        closed = jax.make_jaxpr(
            fn, static_argnums=tuple(static_argnums))(*example_args)
    return lint_jaxpr(closed, scope=scope)


def lint_block(block, *example_inputs, scope: Optional[str] = None,
               training: bool = False) -> List[Finding]:
    """Trace a gluon block (or exported SymbolBlock) and lint its jaxpr.

    ``example_inputs`` may be mx ndarrays, numpy arrays, or anything
    ``jnp.asarray`` accepts. Parameters are initialized on demand.
    """
    import jax
    import jax.numpy as jnp

    from ..ndarray.ndarray import ndarray as _nd, _unwrap, _wrap

    inputs = tuple(x if isinstance(x, _nd) else _wrap(jnp.asarray(x))
                   for x in example_inputs)
    if any(p._data is None for p in block.collect_params().values()):
        try:
            block.initialize()
        except Exception:  # noqa: BLE001 — already-initialized / deferred
            pass
    scope = scope or type(block).__name__

    if hasattr(block, "functionalize"):
        fn, params0 = block.functionalize(*inputs, training=training)

        def user_outputs(params, *ivals):
            out, _new_params = fn(params, *ivals)
            return out

        closed = jax.make_jaxpr(user_outputs)(
            params0, *[_unwrap(x) for x in inputs])
        return lint_jaxpr(closed, scope=scope)

    # plain Block (e.g. Sequential container): trace __call__ directly
    # with params baked as constants — every aval the rules care about
    # (operand dims, dtypes, reductions) is still in the IR
    from .. import numpy_extension as npx
    from ..numpy import random as _random

    def fwd(key, *ivals):
        wrapped = tuple(_wrap(v) for v in ivals)
        with npx.functional_mode(key, training):
            out = block(*wrapped)
        return jax.tree_util.tree_map(
            lambda v: v._data if isinstance(v, _nd) else v, out,
            is_leaf=lambda v: isinstance(v, _nd))

    # hybridized children draw from the thread-local global RNG inside
    # the trace, which would leave a tracer in _rng.key — restore it
    saved_key = _random._rng.key
    try:
        closed = jax.make_jaxpr(fwd)(
            jax.random.PRNGKey(0), *[_unwrap(x) for x in inputs])
    finally:
        _random._rng.key = saved_key
    return lint_jaxpr(closed, scope=scope)


def find_donation_misses(fn, example_args: Sequence[Any],
                         donate_argnums: Sequence[int] = (),
                         scope: str = "jit") -> List[Finding]:
    """J005: arguments whose buffers are all reproduced in the outputs
    (in-place updates in functional clothing) but are not donated.

    Matching is a greedy multiset walk over (shape, dtype) avals in
    argument order, so of weights/grads/states with identical shapes only
    the args that can still claim output buffers count as update-like —
    the XLA aliasing rule donation itself uses. Scalar-only args
    (lr, step counters) are skipped.
    """
    import jax

    donate = set(donate_argnums if isinstance(donate_argnums, (tuple, list,
                                                               set, frozenset))
                 else (donate_argnums,))
    out = jax.eval_shape(fn, *example_args)
    pool = Counter((tuple(l.shape), str(l.dtype))
                   for l in jax.tree_util.tree_leaves(out))
    findings: List[Finding] = []
    # donated args claim their output slots FIRST (declared intent), so a
    # shape-twin like grads can't steal the states' slots and fire a
    # false J005 on the real Trainer step
    order = sorted(range(len(example_args)),
                   key=lambda i: (i not in donate, i))
    for i in order:
        arg = example_args[i]
        leaves = jax.tree_util.tree_leaves(arg)
        avals = [(tuple(l.shape), str(l.dtype)) for l in leaves]
        if not avals or all(int(onp.prod(s)) <= 1 for s, _ in avals):
            continue
        need = Counter(avals)
        if any(pool[k] < n for k, n in need.items()):
            continue  # not update-like: outputs don't cover this arg
        pool.subtract(need)
        if i not in donate:
            nbytes = sum(
                int(onp.prod(s)) * onp.dtype(d).itemsize for s, d in avals)
            findings.append(Finding(
                "J005",
                f"argument {i} is fully reproduced in the outputs "
                f"(~{nbytes / 1e6:.2f} MB of update-in-place buffers) but "
                "is not donated",
                scope=scope, detail=f"arg{i}",
                hint=f"pass donate_argnums=({i},) (plus the other updated "
                     "args) to jax.jit so XLA aliases the buffers instead "
                     "of double-allocating"))
    return findings


def lint_trainer(trainer, scope: str = "gluon.Trainer._build_jit_step"
                 ) -> List[Finding]:
    """Cross-check the live Trainer fused-update donation contract.

    Rebuilds the exact pure function + donate tuple the Trainer jits
    (``Trainer._fused_update_fn``) and runs :func:`find_donation_misses`
    over it with the real parameter/state avals.
    """
    idxs = [i for i, p in enumerate(trainer._params)
            if p.grad_req != "null" and p._data is not None]
    if not idxs or not getattr(trainer, "_jit_safe", True):
        return []
    if not trainer._states_ready:
        trainer._init_states()
    fused, donate = trainer._fused_update_fn(idxs)
    # the aval construction lives on the Trainer (also the prewarm()
    # path) so the linted signature can never drift from the jitted one
    args = trainer._fused_update_avals(idxs)
    return find_donation_misses(fused, args, donate, scope=scope)
