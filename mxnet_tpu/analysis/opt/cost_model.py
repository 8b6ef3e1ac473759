"""Analytic TPU cost model over jaxprs (the gating brain of ``opt``).

"A Learned Performance Model for TPUs" (arXiv:2008.01040) showed that
the features a TPU cost model needs are statically visible in the IR:
**padded-tile FLOPs** (what the MXU actually executes after (8, 128)
sublane/lane padding — not the algorithmic count), **bytes moved**
through HBM (dtype-aware), and **per-launch overhead**. This module
computes exactly those features from a jaxpr and folds them through a
per-op roofline::

    t(op)  = max(flops_padded / (peak * eff * rate(dtype)),
                 bytes / (bw * mem_eff))
    t(step) = sum_ops t(op) + launch_overhead / steps_per_launch

The constants (``compute_eff``, ``mem_eff``, ``fusion_discount``,
``launch_overhead_us``…) are **calibrated** against the banked TPU
corpus in ``benchmark/results_*.json`` (:mod:`.calibration`) — the repo
has been paying for that training data on every daemon capture — and
the fit is validated offline by rank correlation (:func:`spearman`)
between predicted and banked step times, no TPU required.

The model is deliberately analytic and inspectable: every estimate
carries a per-op breakdown (:class:`CostEstimate.top`) so a rewrite or
autotune decision can be justified in one printed line. It never
touches a backend — pure tracing + host arithmetic (tpulint A001-clean
by construction).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field, replace
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as onp

from ..jaxpr_rules import TILE_LANE, TILE_SUBLANE

__all__ = [
    "CostModel", "CostEstimate", "OpCost", "OpFeatures",
    "extract_features", "spearman",
]


def _pad_up(d: int, tile: int) -> int:
    return -(-int(d) // tile) * tile


def np_dtype(name) -> onp.dtype:
    """``numpy.dtype`` that also resolves the ml_dtypes smalls
    (``bfloat16`` & friends, which plain numpy refuses)."""
    try:
        return onp.dtype(name)
    except TypeError:
        import ml_dtypes

        return onp.dtype(getattr(ml_dtypes, str(name)))


#: matmul/conv rate multipliers vs the native one-pass bf16 MXU peak.
#: fp32 on the MXU is the bf16_3x emulation ("high", the bench default:
#: ~1/3 rate; "highest" is 6-pass); f64 is software-emulated; int8 runs
#: the int8 MXU path (banked micro: 1.157x bf16 on matmul).
_DTYPE_RATE = {
    "bfloat16": 1.0,
    "float16": 1.0,
    "float32": 1.0 / 3.0,
    "float64": 0.1,
    "int8": 1.157,
    "uint8": 1.157,
}


def _matmul_rate(dtype: str, fp32_rate: float) -> float:
    if dtype == "float32":
        return fp32_rate
    return _DTYPE_RATE.get(dtype, fp32_rate)


#: primitives whose operand/result bytes are charged in full — they
#: materialize real HBM traffic (matrix units, reductions, data
#: movement). Everything else is assumed fusable and charged at
#: ``fusion_discount`` of its naive bytes.
_MAJOR_PRIMS = {
    "dot_general", "conv_general_dilated", "gather", "scatter",
    "scatter-add", "dynamic_slice", "dynamic_update_slice",
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "argmax", "argmin", "sort", "cumsum", "transpose", "reduce_window",
    "select_and_scatter_add",
}


@dataclass(frozen=True)
class OpFeatures:
    """Constant-independent features of one equation — the calibration
    set stores arrays of these so refitting constants never re-traces."""
    prim: str
    flops_raw: float          # 2*MACs on algorithmic dims
    flops_padded: float       # 2*MACs on (8,128)-tile-padded dims
    bytes: float              # operand + result bytes, dtype-aware
    major: bool               # charged in full vs fusion-discounted
    dtype: str                # compute dtype (rate selection)
    detail: str = ""


@dataclass
class OpCost:
    features: OpFeatures
    t_compute_s: float
    t_memory_s: float

    @property
    def t_s(self) -> float:
        return max(self.t_compute_s, self.t_memory_s)

    @property
    def bound(self) -> str:
        return "compute" if self.t_compute_s >= self.t_memory_s \
            else "memory"

    def render(self) -> str:
        f = self.features
        return (f"{f.prim:22s} {f.detail:28s} {self.t_s * 1e3:8.3f} ms "
                f"[{self.bound}-bound, {f.flops_padded / 1e9:.2f} "
                f"padded GFLOP, {f.bytes / 1e6:.2f} MB, {f.dtype}]")


@dataclass
class CostEstimate:
    """One scored program: totals + the per-op breakdown that justifies
    every rewrite/tune decision built on it."""
    flops_raw: float = 0.0
    flops_padded: float = 0.0
    bytes_total: float = 0.0        # post-fusion-discount charged bytes
    bytes_naive: float = 0.0        # raw per-eqn operand+result bytes
    t_compute_s: float = 0.0        # sum of per-op compute terms
    t_memory_s: float = 0.0         # sum of per-op memory terms
    t_ops_s: float = 0.0            # sum of per-op rooflines
    t_launch_s: float = 0.0
    n_ops: int = 0
    ops: List[OpCost] = field(default_factory=list)

    @property
    def t_total_s(self) -> float:
        return self.t_ops_s + self.t_launch_s

    @property
    def tile_waste(self) -> float:
        """Fraction of padded-tile FLOPs that are padding (0 = perfectly
        tile-aligned) — the J001 aggregate for a whole program."""
        if not self.flops_padded:
            return 0.0
        return 1.0 - self.flops_raw / self.flops_padded

    def top(self, n: int = 5) -> List[OpCost]:
        return sorted(self.ops, key=lambda o: -o.t_s)[:n]

    def render(self, n: int = 5) -> str:
        lines = [
            f"predicted {self.t_total_s * 1e3:.3f} ms/launch "
            f"({self.t_ops_s * 1e3:.3f} ops + "
            f"{self.t_launch_s * 1e3:.3f} launch); "
            f"{self.flops_padded / 1e9:.2f} padded GFLOP "
            f"({100 * self.tile_waste:.0f}% tile waste), "
            f"{self.bytes_total / 1e6:.1f} MB charged HBM",
        ]
        lines += ["  " + o.render() for o in self.top(n)]
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# feature extraction (constant-free)
# ---------------------------------------------------------------------------
def _aval_bytes(var) -> float:
    aval = getattr(var, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0.0
    try:
        itemsize = np_dtype(str(dtype)).itemsize
    except (TypeError, AttributeError):
        itemsize = 4
    return float(math.prod(shape) or 1) * itemsize


def _dot_dims(eqn) -> Optional[Tuple[List[int], List[int], List[int],
                                     List[int]]]:
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs = getattr(eqn.invars[0], "aval", None)
    rhs = getattr(eqn.invars[1], "aval", None)
    if lhs is None or rhs is None:
        return None
    b = [lhs.shape[i] for i in lb]
    k = [lhs.shape[i] for i in lc]
    m = [d for i, d in enumerate(lhs.shape) if i not in lc and i not in lb]
    n = [d for i, d in enumerate(rhs.shape) if i not in rc and i not in rb]
    return b, m, k, n


def _dot_features(eqn) -> OpFeatures:
    dims = _dot_dims(eqn)
    if dims is None:
        return OpFeatures("dot_general", 0, 0, 0, True, "float32")
    b, m, k, n = dims
    raw = 2.0 * math.prod(b) * math.prod(m) * math.prod(k) * math.prod(n)
    # MXU tiling: M rides sublanes (8), K and N ride lanes (128). Pad
    # the innermost dim of each class (the one the tiling bites); outer
    # dims of the same class multiply through unpadded.
    pm = math.prod(m[:-1]) * _pad_up(m[-1], TILE_SUBLANE) if m else 1
    pk = math.prod(k[:-1]) * _pad_up(k[-1], TILE_LANE) if k else 1
    pn = math.prod(n[:-1]) * _pad_up(n[-1], TILE_LANE) if n else 1
    padded = 2.0 * math.prod(b) * pm * pk * pn
    dtype = str(eqn.invars[0].aval.dtype)
    detail = (f"M{math.prod(m)}K{math.prod(k)}N{math.prod(n)}"
              + (f"B{math.prod(b)}" if b else ""))
    bytes_ = sum(_aval_bytes(v) for v in eqn.invars) \
        + sum(_aval_bytes(v) for v in eqn.outvars)
    return OpFeatures("dot_general", raw, padded, bytes_, True, dtype,
                      detail)


def _conv_features(eqn) -> OpFeatures:
    dn = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval
    rhs = eqn.invars[1].aval
    out = eqn.outvars[0].aval
    groups = int(eqn.params.get("feature_group_count", 1))
    c_in_g = rhs.shape[dn.rhs_spec[1]]       # in channels per group
    c_out = rhs.shape[dn.rhs_spec[0]]
    kernel_sp = math.prod(rhs.shape[d] for d in dn.rhs_spec[2:])
    out_sp = math.prod(out.shape[d] for d in dn.out_spec[2:])
    batch = out.shape[dn.out_spec[0]]
    raw = 2.0 * batch * out_sp * c_out * kernel_sp * c_in_g
    # conv as implicit matmul: M = batch*out_spatial (sublane), K =
    # C_in/g * kernel (C_in rides the sublane register tiling the J001
    # rule checks), N = C_out (lane)
    padded = (2.0 * _pad_up(batch * out_sp, TILE_SUBLANE)
              * _pad_up(c_in_g, TILE_SUBLANE) * kernel_sp
              * (groups * _pad_up(-(-c_out // groups), TILE_LANE)
                 if groups > 1 else _pad_up(c_out, TILE_LANE)))
    dtype = str(lhs.dtype)
    bytes_ = sum(_aval_bytes(v) for v in eqn.invars) \
        + sum(_aval_bytes(v) for v in eqn.outvars)
    return OpFeatures("conv_general_dilated", raw, padded, bytes_, True,
                      dtype, f"C{c_in_g * groups}->{c_out}x{kernel_sp}")


def _generic_features(eqn) -> OpFeatures:
    prim = eqn.primitive.name
    bytes_ = sum(_aval_bytes(v) for v in eqn.invars) \
        + sum(_aval_bytes(v) for v in eqn.outvars)
    dtype = "float32"
    for v in eqn.outvars:
        aval = getattr(v, "aval", None)
        if aval is not None and getattr(aval, "dtype", None) is not None:
            dtype = str(aval.dtype)
            break
    return OpFeatures(prim, 0.0, 0.0, bytes_, prim in _MAJOR_PRIMS, dtype)


def _sub_jaxprs_weighted(eqn):
    """Yield (sub_jaxpr, weight) under an eqn: scan bodies run ``length``
    times, cond branches are alternatives (the walk charges the heaviest
    via weight=-1 sentinel handled by caller), everything else once."""
    prim = eqn.primitive.name
    if prim == "scan":
        length = eqn.params.get("length", 1)
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield inner, float(length)
        return
    for v in eqn.params.values():
        for item in (v if isinstance(v, (tuple, list)) else (v,)):
            inner = getattr(item, "jaxpr", item)
            if hasattr(inner, "eqns") and hasattr(inner, "outvars"):
                yield inner, 1.0


def extract_features(closed) -> List[Tuple[OpFeatures, float]]:
    """Walk a (Closed)Jaxpr recursively into ``(features, weight)``
    rows — the constant-free half of an estimate, cacheable per
    program (calibration refits constants against these without
    re-tracing)."""
    jaxpr = getattr(closed, "jaxpr", closed)
    rows: List[Tuple[OpFeatures, float]] = []

    def walk(jx, weight: float):
        for eqn in jx.eqns:
            prim = eqn.primitive.name
            if prim == "dot_general":
                rows.append((_dot_features(eqn), weight))
            elif prim == "conv_general_dilated":
                rows.append((_conv_features(eqn), weight))
            elif prim == "cond":
                # one branch executes — charge the heaviest by bytes
                subs = [b for b in eqn.params.get("branches", ())
                        if hasattr(getattr(b, "jaxpr", b), "eqns")]
                if subs:
                    best = max(subs, key=lambda b: sum(
                        _aval_bytes(v) for e in getattr(b, "jaxpr", b).eqns
                        for v in e.outvars))
                    walk(getattr(best, "jaxpr", best), weight)
                continue
            else:
                has_sub = False
                for sub, w in _sub_jaxprs_weighted(eqn):
                    has_sub = True
                    walk(sub, weight * w)
                if not has_sub:
                    rows.append((_generic_features(eqn), weight))
        return rows

    return walk(jaxpr, 1.0)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
@dataclass
class CostModel:
    """Analytic roofline with calibratable constants.

    Defaults are the v5e fit against the banked corpus (see
    ``benchmark/results_opt_cpu.json`` → ``calibration``); use
    :meth:`for_backend` to resolve peaks for the live (or a target)
    device, and :meth:`calibrate` to refit constants when the corpus
    grows.
    """
    peak_tflops: float = 197.0       # native-dtype MXU peak
    hbm_gbps: float = 542.8          # measured v5e (results_hbm_tpu.json)
    compute_eff: float = 0.45        # achievable fraction of peak
    mem_eff: float = 0.55
    # per launch, fitted to the banked corpus: every row of it predates
    # PR 1, so for today's stack this constant is not measured
    launch_overhead_us: float = 4500.0
    fusion_discount: float = 0.08    # charged fraction of fusable bytes
    fp32_matmul_rate: float = 1.0 / 3.0  # "high" = bf16_3x
    backend: str = "tpu"
    device_kind: str = "TPU v5 lite"

    # -- construction -----------------------------------------------------
    @classmethod
    def for_backend(cls, backend: Optional[str] = None,
                    device_kind: Optional[str] = None) -> "CostModel":
        """Model for the live backend (or an explicit target: pass
        ``backend='tpu', device_kind='TPU v5 lite'`` to score TPU
        deployments from a CPU process — how the lint/rewrite gate runs
        in CI). TPU peaks resolve through :mod:`mxnet_tpu.telemetry.mfu`
        (measured HBM row when banked, spec otherwise)."""
        if backend is None:
            import jax

            backend = jax.default_backend()
            if device_kind is None:
                device_kind = jax.devices()[0].device_kind
        device_kind = device_kind or ""
        if backend == "cpu":
            # XLA:CPU: no MXU, no tile padding. Peak ~ a few
            # vectorized cores; dispatch is a local call. fp32 runs full
            # rate (there is no bf16 unit to emulate against).
            return cls(peak_tflops=0.05, hbm_gbps=12.0, compute_eff=0.5,
                       mem_eff=0.5, launch_overhead_us=40.0,
                       fusion_discount=0.25, fp32_matmul_rate=1.0,
                       backend="cpu", device_kind=device_kind or "cpu")
        from ...telemetry import mfu

        peak = mfu.peak_bf16_tflops(device_kind) or cls.peak_tflops
        bw = mfu.bank().hbm_gbps(device_kind) or cls.hbm_gbps
        return cls(peak_tflops=peak, hbm_gbps=bw, backend=backend,
                   device_kind=device_kind or "tpu")

    # -- scoring ----------------------------------------------------------
    def op_cost(self, f: OpFeatures) -> OpCost:
        flops = f.flops_padded if self.backend == "tpu" else f.flops_raw
        rate = _matmul_rate(f.dtype, self.fp32_matmul_rate) \
            if flops else 1.0
        t_c = flops / (self.peak_tflops * 1e12 * self.compute_eff * rate) \
            if flops else 0.0
        charged = f.bytes * (1.0 if f.major else self.fusion_discount)
        t_m = charged / (self.hbm_gbps * 1e9 * self.mem_eff)
        return OpCost(f, t_c, t_m)

    def estimate_features(self, rows: Sequence[Tuple[OpFeatures, float]],
                          steps_per_launch: int = 1) -> CostEstimate:
        est = CostEstimate()
        for f, w in rows:
            oc = self.op_cost(f)
            est.flops_raw += w * f.flops_raw
            est.flops_padded += w * f.flops_padded
            est.bytes_naive += w * f.bytes
            est.bytes_total += w * f.bytes * (
                1.0 if f.major else self.fusion_discount)
            est.t_compute_s += w * oc.t_compute_s
            est.t_memory_s += w * oc.t_memory_s
            est.t_ops_s += w * oc.t_s
            est.n_ops += 1
            est.ops.append(oc)
        est.t_launch_s = self.launch_overhead_us * 1e-6 / max(
            1, int(steps_per_launch))
        return est

    def estimate_jaxpr(self, closed,
                       steps_per_launch: int = 1) -> CostEstimate:
        return self.estimate_features(extract_features(closed),
                                      steps_per_launch=steps_per_launch)

    def estimate_callable(self, fn, *args,
                          steps_per_launch: int = 1) -> CostEstimate:
        """Trace ``fn`` (no compile, no execute) and estimate it."""
        import jax

        closed = jax.make_jaxpr(fn)(*args)
        return self.estimate_jaxpr(closed,
                                   steps_per_launch=steps_per_launch)

    # -- calibration ------------------------------------------------------
    def calibrate(self, samples: Sequence[Tuple[
            Sequence[Tuple[OpFeatures, float]], int, float]],
            passes: int = 3) -> Tuple["CostModel", Dict[str, Any]]:
        """Refit constants against ``(feature_rows, steps_per_launch,
        observed_step_s)`` samples by deterministic coordinate descent
        over per-constant grids, minimizing mean squared log error
        (ranking-friendly: log-space symmetric). Returns the fitted
        model + a diagnostics dict (spearman/msle before and after)."""
        grids = {
            "compute_eff": [0.2, 0.3, 0.4, 0.45, 0.5, 0.6, 0.7, 0.8],
            "mem_eff": [0.3, 0.4, 0.5, 0.55, 0.6, 0.7, 0.8],
            "fusion_discount": [0.02, 0.05, 0.08, 0.12, 0.2, 0.3, 0.5],
            "launch_overhead_us": [50., 500., 1500., 3000., 4500., 6000.],
            "fp32_matmul_rate": [0.2, 1 / 3, 0.5, 1.0],
        }

        def msle(model: "CostModel") -> float:
            errs = []
            for rows, spl, obs in samples:
                pred = model.estimate_features(rows, spl).t_total_s
                errs.append(math.log(max(pred, 1e-9) / max(obs, 1e-9)) ** 2)
            return sum(errs) / max(1, len(errs))

        def rank(model: "CostModel") -> float:
            preds = [model.estimate_features(r, s).t_total_s
                     for r, s, _ in samples]
            return spearman(preds, [o for _, _, o in samples])

        before = {"msle": msle(self), "spearman": rank(self)}
        best = self
        best_err = before["msle"]
        for _ in range(passes):
            for name, grid in grids.items():
                for val in grid:
                    cand = replace(best, **{name: val})
                    err = msle(cand)
                    if err < best_err - 1e-12:
                        best, best_err = cand, err
        diag = {"before": before,
                "after": {"msle": best_err, "spearman": rank(best)},
                "n_samples": len(samples)}
        return best, diag


def spearman(xs: Sequence[float], ys: Sequence[float]) -> float:
    """Spearman rank correlation (average ranks on ties; no scipy)."""
    def ranks(vs):
        order = sorted(range(len(vs)), key=lambda i: vs[i])
        r = [0.0] * len(vs)
        i = 0
        while i < len(order):
            j = i
            while j + 1 < len(order) \
                    and vs[order[j + 1]] == vs[order[i]]:
                j += 1
            avg = (i + j) / 2.0
            for k in range(i, j + 1):
                r[order[k]] = avg
            i = j + 1
        return r

    if len(xs) != len(ys) or len(xs) < 2:
        return 0.0
    rx, ry = ranks(list(xs)), ranks(list(ys))
    mx = sum(rx) / len(rx)
    my = sum(ry) / len(ry)
    cov = sum((a - mx) * (b - my) for a, b in zip(rx, ry))
    vx = math.sqrt(sum((a - mx) ** 2 for a in rx))
    vy = math.sqrt(sum((b - my) ** 2 for b in ry))
    if not vx or not vy:
        return 0.0
    return cov / (vx * vy)
