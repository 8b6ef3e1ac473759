"""Cost-model-pruned autotuner over the repo's discrete knob space.

TVM (arXiv:1802.04799) demonstrated the loop this module implements:
enumerate a discrete schedule space, let a cost model rank it, confirm
the survivors with short timed probes, persist the winner. The knobs
here are the ones the repo already exposes end to end:

- ``steps_per_launch`` — serial ``lax.scan`` chaining inside one
  executable (``train_bench --scan-steps``; amortizes the per-launch
  cost),
- ``stem_s2d`` — the conv-stem space-to-depth rewrite knob
  (``MXNET_TPU_STEM_S2D``),
- ``remat`` — rematerialize the forward in backward
  (``jax.checkpoint`` around the loss),
- serving ``bucket_sizes`` / ``max_delay_ms`` — the engine ladder.

The winner is a :class:`TunedConfig` persisted under
``MXNET_TPU_OPT_DIR`` (default: ``<MXNET_TPU_AOT_CACHE>/tuned`` when
the AOT store is armed), **fingerprint-keyed via** :func:`aot.fingerprint`
— the same key that folds in the jaxpr hash, avals, backend, jax/jaxlib
versions and the A002 env-knob signature, so a knob flip or a jaxlib
upgrade invalidates a stale config instead of silently applying it.
``gluon.Trainer(tuned=…)`` and ``serving.InferenceEngine(tuned=…)``
consume configs at build time (:meth:`TunedConfig.for_trainer` /
knob accessors), and every probe lands in the telemetry registry
(``opt_tune_*``)."""
from __future__ import annotations

import itertools
import json
import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from .cost_model import CostModel

__all__ = ["TunedConfig", "KnobSpace", "autotune", "store_dir",
           "load_tuned", "lookup", "DEFAULT_SPACE"]

#: the default discrete space — every knob is one the repo already
#: consumes (docs/auto_opt.md lists the consumption sites)
DEFAULT_SPACE: Dict[str, Tuple] = {
    "steps_per_launch": (1, 2, 4, 8, 16, 32),
}

KnobSpace = Dict[str, Tuple]


def store_dir() -> Optional[str]:
    """Where tuned configs persist: ``MXNET_TPU_OPT_DIR``, else
    ``<MXNET_TPU_AOT_CACHE>/tuned`` when the AOT store is armed, else
    None (tuning still works, nothing persists)."""
    env = os.environ.get("MXNET_TPU_OPT_DIR")
    if env:
        return env
    aot_dir = os.environ.get("MXNET_TPU_AOT_CACHE")
    if aot_dir:
        return os.path.join(aot_dir, "tuned")
    return None


@dataclass
class TunedConfig:
    """A persisted tuning verdict: the chosen knobs plus the full
    provenance needed to (a) refuse to apply itself when stale and
    (b) justify itself in a bench row."""
    label: str
    key: str                      # aot.fingerprint hex over the probe fn
    knobs: Dict[str, Any]
    predicted_ms: Optional[float] = None
    measured_ms: Optional[float] = None
    baseline_ms: Optional[float] = None
    probes: int = 0
    tune_spend_s: float = 0.0
    backend: str = ""
    device_kind: str = ""
    jax_version: str = ""
    jaxlib_version: str = ""
    knob_signature: List = field(default_factory=list)
    created_unix: float = 0.0
    candidates: List[Dict] = field(default_factory=list)
    #: resolved mesh axis map at tune time ({"dp": 8, ...}; None =
    #: tuned off-mesh). Part of the config identity: a knob verdict
    #: probed at dp=8 says nothing about dp=256 — collective shapes,
    #: per-device batch and launch overheads all change with the mesh.
    mesh_axes: Optional[Dict[str, int]] = None

    # -- persistence ------------------------------------------------------
    def filename(self) -> str:
        safe = "".join(c if c.isalnum() or c in "-_" else "_"
                       for c in self.label)
        return f"{safe}-{self.key[:16]}.json"

    def save(self, directory: Optional[str] = None) -> Optional[str]:
        directory = directory or store_dir()
        if not directory:
            return None
        os.makedirs(directory, exist_ok=True)
        path = os.path.join(directory, self.filename())
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.to_dict(), f, indent=1, sort_keys=True)
        os.replace(tmp, path)  # atomic publish (CheckpointManager rule)
        return path

    def to_dict(self) -> dict:
        return {k: getattr(self, k) for k in (
            "label", "key", "knobs", "predicted_ms", "measured_ms",
            "baseline_ms", "probes", "tune_spend_s", "backend",
            "device_kind", "jax_version", "jaxlib_version",
            "knob_signature", "created_unix", "candidates",
            "mesh_axes")}

    @classmethod
    def from_dict(cls, d: dict) -> "TunedConfig":
        return cls(**{k: d.get(k) for k in (
            "label", "key", "knobs", "predicted_ms", "measured_ms",
            "baseline_ms", "probes", "tune_spend_s", "backend",
            "device_kind", "jax_version", "jaxlib_version",
            "knob_signature", "created_unix", "candidates",
            "mesh_axes")
            if d.get(k) is not None} | {"label": d["label"],
                                        "key": d["key"],
                                        "knobs": d["knobs"]})

    # -- staleness --------------------------------------------------------
    def is_current(self) -> bool:
        """True while the environment still matches the one that tuned
        this config: jax/jaxlib versions and the live A002 knob
        signature. A consumer must treat a stale config as absent —
        warn once and fall back to defaults, never apply blindly."""
        from ...aot import knob_signature
        from ...aot.cache import jaxlib_version
        import jax

        if self.jaxlib_version and self.jaxlib_version != jaxlib_version():
            return False
        if self.jax_version and self.jax_version != jax.__version__:
            return False
        if self.knob_signature:
            live = [[k, v] for k, v in knob_signature()]
            if [list(p) for p in self.knob_signature] != live:
                return False
        if self._live_mesh_axes() != (
                dict(self.mesh_axes) if self.mesh_axes else None):
            # tuned under one mesh, consumed under another (or tuned
            # off-mesh, consumed on one): stale — a dp=8 verdict must
            # never be applied at dp=256
            return False
        return True

    @staticmethod
    def _live_mesh_axes() -> Optional[Dict[str, int]]:
        try:
            from ...parallel.sharding import mesh_topology

            topo = mesh_topology()
        except Exception:  # noqa: BLE001
            return None
        return dict(topo["axes"]) if topo else None

    def provenance(self) -> dict:
        """The compact dict bench rows embed (tuned-config provenance
        in ``train_bench`` / ``serve_bench``)."""
        return {"label": self.label, "key": self.key[:16],
                "knobs": self.knobs, "measured_ms": self.measured_ms,
                "predicted_ms": self.predicted_ms,
                "created_unix": self.created_unix}


def load_tuned(path: str) -> TunedConfig:
    with open(path) as f:
        return TunedConfig.from_dict(json.load(f))


def fingerprint_key(fn: Callable, example_args, label: str,
                    space: Optional[KnobSpace] = None) -> str:
    """The config identity: :func:`aot.fingerprint` of the *reference*
    (knob-default) program + the knob space searched. Everything that
    must invalidate a config — program change, aval change, backend,
    jax/jaxlib, env-knob flips — is already inside the fingerprint."""
    from ...aot import fingerprint

    extra = [json.dumps({k: list(v) for k, v in sorted(
        (space or {}).items())}, sort_keys=True)]
    key, _ = fingerprint(fn, example_args, label=f"opt.tune/{label}",
                         extra=extra)
    return key


def lookup(label: str, fn: Callable = None, example_args=None,
           space: Optional[KnobSpace] = None,
           directory: Optional[str] = None) -> Optional[TunedConfig]:
    """Load the persisted config for ``label`` **iff it is still
    valid**: the stored key must equal the freshly computed fingerprint
    (when ``fn``/``example_args`` are given) and :meth:`is_current`
    must hold. Returns None otherwise — a miss, never a stale apply."""
    directory = directory or store_dir()
    if not directory or not os.path.isdir(directory):
        return None
    want_key = None
    if fn is not None and example_args is not None:
        want_key = fingerprint_key(fn, example_args, label, space)
    best = None
    for name in sorted(os.listdir(directory)):
        if not name.endswith(".json"):
            continue
        try:
            cfg = load_tuned(os.path.join(directory, name))
        except (OSError, ValueError, KeyError, TypeError):
            continue
        if cfg.label != label:
            continue
        if want_key is not None and cfg.key != want_key:
            continue
        if not cfg.is_current():
            continue
        if best is None or cfg.created_unix > best.created_unix:
            best = cfg
    return best


# -- telemetry --------------------------------------------------------------
def _gauges():
    from ...telemetry import get_registry

    reg = get_registry()
    return {
        "probe_ms": reg.gauge(
            "opt_tune_probe_ms",
            "Measured ms/step of the latest autotune probe",
            ("label", "config")),
        "best_ms": reg.gauge(
            "opt_tune_best_ms", "Winning measured ms/step", ("label",)),
        "predicted_ms": reg.gauge(
            "opt_tune_predicted_ms",
            "Cost-model predicted ms/step of the winner", ("label",)),
        "probes": reg.counter(
            "opt_tune_probes_total", "Timed autotune probes", ("label",)),
        "spend_s": reg.gauge(
            "opt_tune_spend_s",
            "Wall seconds spent probing in the last tune", ("label",)),
    }


def _knob_id(knobs: Dict[str, Any]) -> str:
    return ",".join(f"{k}={knobs[k]}" for k in sorted(knobs))


def autotune(builder: Callable[..., Tuple[Callable, tuple]], *,
             label: str,
             space: Optional[KnobSpace] = None,
             model: Optional[CostModel] = None,
             probe_top_k: int = 3,
             probe_reps: int = 3,
             min_probe_wall_s: float = 0.05,
             warmup_reps: int = 1,
             budget_s: Optional[float] = None,
             steps_per_probe_knob: str = "steps_per_launch",
             timer: Callable[[], float] = time.perf_counter,
             save: bool = True,
             directory: Optional[str] = None,
             log=None) -> TunedConfig:
    """Search ``space`` for the fastest configuration of ``builder``.

    ``builder(**knobs)`` returns ``(step_fn, args)``; one *probe* calls
    ``step_fn(*args)`` and blocks on the result. The search is the TVM
    loop shrunk to the repo's knob count: the **cost model ranks every
    candidate first** (tracing only — no compile), the top
    ``probe_top_k`` get ``probe_reps`` timed probes each (after
    ``warmup_reps`` untimed compile/warm calls), and the best measured
    median wins. ``budget_s`` bounds total probe wall time: when
    exceeded, remaining candidates keep their cost-model ranking and
    the best *measured* one wins (never an unmeasured candidate).

    Deterministic by construction: candidates enumerate in sorted knob
    order, ties break toward the earlier candidate, and the ``timer``
    is injectable (tests pin a fake clock; the tier-1 determinism test
    runs the whole loop twice and asserts identical verdicts).

    Returns the persisted (``save=True`` + a store dir) or in-memory
    :class:`TunedConfig`.
    """
    import jax

    space = dict(space or DEFAULT_SPACE)
    model = model or CostModel.for_backend()
    # an explicit caller budget wins; the env knob only fills the
    # default, and a typo'd value warns instead of killing the tune
    if budget_s is not None:
        budget = float(budget_s)
    else:
        from ...base import env_float

        budget = env_float("MXNET_TPU_OPT_TUNE_BUDGET_S", 60.0)
    gauges = _gauges()
    names = sorted(space)
    combos = [dict(zip(names, vals)) for vals in
              itertools.product(*(space[n] for n in names))]

    # 1) cost-model ranking (trace each candidate, no compile)
    ranked: List[Tuple[float, int, Dict[str, Any], Callable, tuple]] = []
    for idx, knobs in enumerate(combos):
        step_fn, args = builder(**knobs)
        spl = int(knobs.get(steps_per_probe_knob, 1))
        try:
            est = model.estimate_callable(step_fn, *args,
                                          steps_per_launch=1)
            # the builder's program already contains the scan chain, so
            # its per-launch estimate covers spl steps; normalize /step
            pred = (est.t_ops_s + model.launch_overhead_us * 1e-6) / spl
        except Exception as e:  # noqa: BLE001 — unrankable: probe last
            if log:
                log(f"autotune[{label}]: cost model failed for "
                    f"{_knob_id(knobs)}: {e!r}")
            pred = float("inf")
        ranked.append((pred, idx, knobs, step_fn, args))
    ranked.sort(key=lambda t: (t[0], t[1]))

    # 2) timed probes over the cost-model survivors — PLUS the
    # all-defaults combo, always and FIRST: the tuner must never crown
    # a config it didn't measure against the measured default (the
    # no-regression floor), and probing defaults first keeps that
    # guarantee even when the budget expires mid-loop
    probe_set = list(ranked[:max(1, probe_top_k)])
    defaults = {n: space[n][0] for n in names}
    probe_set = ([r for r in ranked if r[2] == defaults]
                 + [r for r in probe_set if r[2] != defaults])
    t_start = timer()
    results: List[Dict] = []
    best: Optional[Dict] = None
    for pred, idx, knobs, step_fn, args in probe_set:
        spent = timer() - t_start
        if results and budget and spent > budget:
            if log:
                log(f"autotune[{label}]: budget {budget:.1f}s exhausted "
                    f"after {len(results)} candidates")
            break
        spl = int(knobs.get(steps_per_probe_knob, 1))
        try:
            for _ in range(max(0, warmup_reps)):
                jax.block_until_ready(step_fn(*args))
            times = []
            for _ in range(max(1, probe_reps)):
                # each rep loops until a minimum wall so a sub-ms step
                # is measured above timer/scheduler noise — a 4 ms
                # single-launch sample on a busy host will happily
                # crown the wrong candidate (observed)
                launches, t0 = 0, timer()
                while True:
                    jax.block_until_ready(step_fn(*args))
                    launches += 1
                    dt = timer() - t0
                    if dt >= min_probe_wall_s or launches >= 1000:
                        break
                times.append(dt / launches)
            med = sorted(times)[len(times) // 2] / spl
        except Exception as e:  # noqa: BLE001 — a broken candidate loses
            if log:
                log(f"autotune[{label}]: probe failed for "
                    f"{_knob_id(knobs)}: {e!r}")
            continue
        gauges["probe_ms"].labels(
            label=label, config=_knob_id(knobs)).set(med * 1e3)
        gauges["probes"].labels(label=label).inc(len(times))
        row = {"knobs": knobs, "predicted_ms": None if pred == float(
            "inf") else round(pred * 1e3, 4),
            "measured_ms": round(med * 1e3, 4), "probes": len(times)}
        results.append(row)
        if best is None or med < best["_med"]:
            best = {**row, "_med": med}
    spend = timer() - t_start
    gauges["spend_s"].labels(label=label).set(spend)

    if best is None:
        raise RuntimeError(
            f"autotune[{label}]: every probed candidate failed")
    gauges["best_ms"].labels(label=label).set(best["_med"] * 1e3)
    if best.get("predicted_ms") is not None:
        gauges["predicted_ms"].labels(label=label).set(
            best["predicted_ms"])

    # the reference (all-defaults) row for the speedup bookkeeping
    baseline_row = next(
        (r for r in results
         if all(r["knobs"][n] == space[n][0] for n in names)), None)

    from ...aot import knob_signature
    from ...aot.cache import jaxlib_version

    ref_fn, ref_args = builder(**{n: space[n][0] for n in names})
    cfg = TunedConfig(
        label=label,
        key=fingerprint_key(ref_fn, ref_args, label, space),
        knobs=best["knobs"],
        predicted_ms=best.get("predicted_ms"),
        measured_ms=best["measured_ms"],
        baseline_ms=baseline_row["measured_ms"] if baseline_row else None,
        probes=sum(r["probes"] for r in results),
        tune_spend_s=round(spend, 3),
        backend=model.backend,
        device_kind=model.device_kind,
        jax_version=jax.__version__,
        jaxlib_version=jaxlib_version(),
        knob_signature=[list(p) for p in knob_signature()],
        created_unix=time.time(),
        candidates=results,
        mesh_axes=TunedConfig._live_mesh_axes(),
    )
    if log:
        log(f"autotune[{label}]: chose {_knob_id(cfg.knobs)} "
            f"({cfg.measured_ms:.3f} ms/step measured, "
            f"{cfg.probes} probes, {spend:.2f}s)")
    if save:
        cfg.save(directory)
    return cfg
