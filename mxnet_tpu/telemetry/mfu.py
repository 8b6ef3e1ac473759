"""Online efficiency gauges: MFU, roofline ratios, HBM utilization.

The 0.34 / 0.17 MFU anchors in ``benchmark/results_*.json`` are one-shot
bench numbers. This module makes them **continuously observed**: a
training/serving loop calls :func:`observe_step` with what it just did
(examples, seconds, model FLOPs, optional bytes moved) and the gauges
land in the process registry —

- ``telemetry_examples_per_s{name}`` — achieved throughput,
- ``telemetry_achieved_tflops{name}`` / ``telemetry_mfu{name}`` —
  model-FLOPs utilization against the device's bf16 MXU peak,
- ``telemetry_hbm_util{name}`` — bytes-moved estimate against measured
  (``results_hbm_tpu.json``) or spec HBM bandwidth,
- ``telemetry_vs_banked{name,metric}`` — achieved vs the banked bench
  anchor for the same metric (the "are we at yesterday's roofline?"
  gauge the fleet autoscaler will watch).

All inputs are host scalars the caller already has — reading these
gauges never touches the device (tpulint A001: an instrumentation
layer must not add transfers to the hot path).
"""
from __future__ import annotations

import json
import os
import threading
from typing import Dict, Optional

from .registry import get_registry

__all__ = ["RooflineBank", "bank", "peak_bf16_tflops", "peak_hbm_gbps",
           "observe_step"]

#: bf16 MXU peak TFLOP/s by device_kind substring (public TPU specs;
#: mirrors the headline bench table in ``bench.py``). Unknown kinds
#: report mfu as None rather than guessing.
PEAK_BF16_TFLOPS = {
    "v5 lite": 197.0, "v5e": 197.0,
    "v5p": 459.0,
    "v4": 275.0,
    "v3": 123.0,
    "v2": 46.0,
    "v6": 918.0,  # trillium
}

#: HBM bandwidth GB/s by device_kind substring (public specs) — the
#: fallback when no measured ``results_hbm_tpu.json`` row is banked.
PEAK_HBM_GBPS = {
    "v5 lite": 819.0, "v5e": 819.0,
    "v5p": 2765.0,
    "v4": 1228.0,
    "v3": 900.0,
    "v2": 700.0,
    "v6": 1640.0,
}


def peak_bf16_tflops(device_kind: str) -> Optional[float]:
    kind = (device_kind or "").lower()
    for sub, peak in PEAK_BF16_TFLOPS.items():
        if sub in kind:
            return peak
    return None


def peak_hbm_gbps(device_kind: str) -> Optional[float]:
    kind = (device_kind or "").lower()
    for sub, peak in PEAK_HBM_GBPS.items():
        if sub in kind:
            return peak
    return None


def _default_bank_dir() -> Optional[str]:
    env = os.environ.get("MXNET_TPU_ROOFLINE_DIR")
    if env:
        return env
    here = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    cand = os.path.join(here, "benchmark")
    return cand if os.path.isdir(cand) else None


class RooflineBank:
    """Read-only view over the banked ``benchmark/results_*.json``
    corpus: measured HBM bandwidth and the throughput/MFU anchors that
    online gauges compare against. Loads lazily, once, and tolerates a
    missing/partial bank (installed package without the repo checkout:
    every lookup returns None)."""

    def __init__(self, directory: Optional[str] = None):
        self._dir = directory if directory is not None \
            else _default_bank_dir()
        self._lock = threading.Lock()
        self._loaded = False
        self._anchors: Dict[str, Dict] = {}
        self._hbm_gbps: Optional[float] = None

    def _walk(self, obj) -> None:
        """Harvest any dict carrying the bench row shape
        (``metric``/``value``[/``unit``/``mfu``]) anywhere in a results
        file — the bank's files nest rows differently per harness."""
        if isinstance(obj, dict):
            m = obj.get("metric")
            if isinstance(m, str) and isinstance(
                    obj.get("value"), (int, float)):
                self._anchors.setdefault(m, obj)
            if isinstance(obj.get("hbm_gbps"), (int, float)):
                self._hbm_gbps = float(obj["hbm_gbps"])
            for v in obj.values():
                self._walk(v)
        elif isinstance(obj, list):
            for v in obj:
                self._walk(v)

    def _ensure(self) -> None:
        if self._loaded:
            return
        with self._lock:
            if self._loaded:
                return
            if self._dir and os.path.isdir(self._dir):
                for name in sorted(os.listdir(self._dir)):
                    if not (name.startswith("results_")
                            and name.endswith(".json")):
                        continue
                    try:
                        with open(os.path.join(self._dir, name)) as f:
                            self._walk(json.load(f))
                    except (OSError, ValueError):
                        continue  # a torn/foreign file is not an anchor
            self._loaded = True

    def anchor(self, metric: str) -> Optional[Dict]:
        """The banked row for ``metric`` (e.g.
        ``resnet50_v1_infer_bs256_bf16``), or None."""
        self._ensure()
        return self._anchors.get(metric)

    def anchor_value(self, metric: str) -> Optional[float]:
        row = self.anchor(metric)
        return float(row["value"]) if row else None

    def anchors(self) -> Dict[str, float]:
        self._ensure()
        return {m: float(r["value"]) for m, r in self._anchors.items()}

    def hbm_gbps(self, device_kind: str = "") -> Optional[float]:
        """Measured HBM bandwidth from the bank when present (the
        honest roofline — what THIS deployment's chip actually
        streams), else the spec number for the device kind."""
        self._ensure()
        return self._hbm_gbps or peak_hbm_gbps(device_kind)


_bank: Optional[RooflineBank] = None
_bank_lock = threading.Lock()


def bank() -> RooflineBank:
    """The process roofline bank (``MXNET_TPU_ROOFLINE_DIR`` or the
    repo's ``benchmark/`` directory)."""
    global _bank
    if _bank is None:
        with _bank_lock:
            if _bank is None:
                _bank = RooflineBank()
    return _bank


_reg = get_registry()
_g_examples = _reg.gauge(
    "telemetry_examples_per_s",
    "Achieved examples/s (img/s, tok/s) of the observed loop", ("name",))
_g_tflops = _reg.gauge(
    "telemetry_achieved_tflops",
    "Achieved model TFLOP/s of the observed loop", ("name",))
_g_mfu = _reg.gauge(
    "telemetry_mfu",
    "Online model-FLOPs utilization vs bf16 MXU peak", ("name",))
_g_hbm = _reg.gauge(
    "telemetry_hbm_util",
    "Estimated HBM bandwidth utilization of the observed loop",
    ("name",))
_g_vs_banked = _reg.gauge(
    "telemetry_vs_banked",
    "Achieved throughput vs the banked bench anchor", ("name", "metric"))


def observe_step(name: str, examples: float, dt_s: float, *,
                 flops: Optional[float] = None,
                 bytes_hbm: Optional[float] = None,
                 device_kind: str = "",
                 banked_metric: Optional[str] = None) -> Dict:
    """Record one measured window of a loop into the efficiency gauges.

    Parameters
    ----------
    name : str
        Gauge label (``resnet50_train``, ``serving``, ...).
    examples, dt_s : float
        Examples processed and the wall seconds they took.
    flops : float, optional
        Model FLOPs **per example** (the jaxpr 2*MAC walk convention of
        ``bench.py``) — enables achieved-TFLOPs and MFU.
    bytes_hbm : float, optional
        Estimated HBM bytes moved per example — enables the
        HBM-utilization gauge.
    device_kind : str
        ``jax.devices()[0].device_kind`` (caller passes the string; this
        module never touches the backend).
    banked_metric : str, optional
        A ``results_*.json`` metric name to compare against
        (``telemetry_vs_banked``).

    Returns the computed values (the dict bench rows embed).
    """
    dt_s = max(float(dt_s), 1e-9)
    eps = float(examples) / dt_s
    out: Dict = {"examples_per_s": round(eps, 2)}
    _g_examples.labels(name=name).set(eps)
    if flops:
        achieved = eps * float(flops) / 1e12
        out["achieved_tflops"] = round(achieved, 4)
        _g_tflops.labels(name=name).set(achieved)
        peak = peak_bf16_tflops(device_kind)
        if peak:
            out["mfu"] = round(achieved / peak, 4)
            _g_mfu.labels(name=name).set(achieved / peak)
    if bytes_hbm:
        bw = bank().hbm_gbps(device_kind)
        if bw:
            util = (eps * float(bytes_hbm) / 1e9) / bw
            out["hbm_util"] = round(util, 4)
            _g_hbm.labels(name=name).set(util)
    if banked_metric:
        anchor = bank().anchor_value(banked_metric)
        if anchor:
            ratio = eps / anchor
            out["vs_banked"] = round(ratio, 4)
            out["banked_metric"] = banked_metric
            _g_vs_banked.labels(name=name, metric=banked_metric).set(ratio)
    return out
