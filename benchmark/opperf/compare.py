#!/usr/bin/env python
"""CPU-vs-TPU opperf comparison: the 10 worst ops with one-line causes.

Reading the raw tables side by side is misleading: every TPU row pays a
per-launch + fetch floor, which dwarfs the microseconds of compute in a
64x64 elementwise op — by raw ratio every cheap op is "slower than CPU"
and the ranking is pure launch noise. This tool therefore:

1. estimates the launch floor as the 5th-percentile TPU forward time
   across all measured ops (the cheapest ops are pure launch);
2. ranks ops by EXCESS time over that floor — the compute/lowering cost
   actually attributable to the op;
3. flags the 10 worst by excess with a one-line cause each (CAUSES map,
   curated; uncurated flagged ops get 'uncharacterized — investigate').

No TPU table is committed (the one that was predates PR 1): give one
with ``--tpu``. Usage:
    python benchmark/opperf/compare.py --tpu TABLE --out PATH [--top 10]
"""
from __future__ import annotations

import argparse
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))

# one-line causes for ops that rank worst by excess-over-launch-floor.
# Curated against the banked table; the artifact marks any flagged op
# missing here as uncharacterized so the gap is visible, not silent.
CAUSES = {
    # the dominant class: dynamic-output-size ops. XLA requires static
    # shapes, so 'emit the elements that match' becomes full-length
    # cumsum-scan + padded scatter/gather on TPU, vs one linear pass on
    # CPU. These top the excess ranking in every window.
    "np.nonzero": "dynamic output size: static-shape lowering = "
                  "full-length cumsum scan + padded scatter; CPU is one "
                  "linear pass",
    "np.argwhere": "dynamic output size (see np.nonzero) across all "
                   "dims, then index unravel",
    "np.flatnonzero": "dynamic output size (see np.nonzero)",
    "np.extract": "dynamic output size (see np.nonzero) plus value "
                  "gather",
    "np.compress": "dynamic output size (see np.nonzero) plus value "
                   "gather",
    "np.mask_indices": "builds the full (n,n) mask then nonzero (see "
                       "np.nonzero)",
    "np.insert": "dynamic re-layout: scatter into a padded buffer at "
                 "runtime-computed offsets",
    "np.delete": "dynamic re-layout (see np.insert)",
    "np.bincount": "scatter-add histogram: duplicate-index scatter "
                   "serializes on TPU; CPU is one linear pass",
    "np.histogram": "bincount-based (see np.bincount) after bin-id "
                    "computation",
    "np.histogram2d": "bincount-based (see np.bincount) over flattened "
                      "2-D bin ids",
    "np.histogramdd": "bincount-based (see np.bincount) over flattened "
                      "N-D bin ids",
    "np.choose": "per-element select over K stacked choice arrays: "
                 "lowered as K-way masked sum, K full passes",
    "np.digitize": "binary-search gather (see np.interp)",
    "np.linalg.svd": "iterative one-sided Jacobi on TPU; no MXU path "
                     "for the bidiagonalization — latency is algorithmic",
    "np.linalg.eig": "general (non-symmetric) eig has no native TPU "
                     "lowering; XLA runs a host-callback/QR hybrid",
    "np.linalg.eigh": "symmetric eig = iterative Jacobi sweeps on TPU; "
                      "serial dependency chain, VPU-bound",
    "np.linalg.qr": "Householder panels are sequential; small panels "
                    "can't fill the MXU",
    "np.linalg.pinv": "svd-based (see svd) plus two extra matmuls",
    "np.linalg.lstsq": "svd-based (see svd)",
    "np.linalg.matrix_rank": "svd-based (see svd)",
    "np.linalg.cond": "svd-based (see svd)",
    "np.sort": "bitonic sort network: O(log^2 n) serial stages on the "
               "VPU, each a full pass over the lanes",
    "np.argsort": "bitonic sort plus index gather (see np.sort)",
    "np.median": "sort-based reduction (see np.sort)",
    "np.quantile": "sort-based (see np.sort) plus interpolation gather",
    "np.percentile": "sort-based (see np.sort) plus interpolation gather",
    "np.partition": "lowered as full bitonic sort on TPU (no "
                    "partial-selection primitive)",
    "np.unique": "sort + adjacent-compare + variable-size compaction "
                 "padded to static shape",
    "npx.topk": "bitonic top-k; serial stage chain on the VPU",
    "np.cumsum": "log-depth scan: multiple full passes over the lane "
                 "dimension",
    "np.cumprod": "log-depth scan (see np.cumsum)",
    "npx.rnn": "sequence-serial lax.scan: T dependent steps, each a "
               "small matmul that can't fill the MXU alone",
    "np.interp": "per-element binary-search gather; scatter/gather is "
                 "the TPU's weakest primitive class",
    "np.searchsorted": "per-element binary-search gather (see np.interp)",
    "npx.roi_pooling": "data-dependent gather windows; dynamic-slice "
                       "per ROI serializes",
    "npx.psroi_pooling": "data-dependent gather windows (see roi_pooling)",
    "np.repeat": "dynamic output extent lowered as gather from a "
                 "precomputed index map",
    "np.fft.fft": "FFT butterflies are VPU shuffle chains, not MXU work",
    "np.fft.ifft": "see np.fft.fft",
    "np.fft.rfft": "see np.fft.fft",
    "np.fft.irfft": "see np.fft.fft",
}


def _fwd_ms(entry_list):
    """First record's forward ms from an opperf per-op list."""
    if not (isinstance(entry_list, list) and entry_list
            and isinstance(entry_list[0], dict)):
        return None
    for k, v in entry_list[0].items():
        if k.startswith("avg_time_forward_") and \
                not k.startswith("avg_time_forward_backward"):
            return float(v)
    return None


def compare(cpu_table, tpu_table, top=10):
    cpu_ms = {k: _fwd_ms(v) for k, v in cpu_table.items() if k != "_meta"}
    tpu_ms = {k: _fwd_ms(v) for k, v in tpu_table.items() if k != "_meta"}
    both = sorted(k for k in cpu_ms if k in tpu_ms
                  and cpu_ms[k] is not None and tpu_ms[k] is not None)
    if not both:
        return {"error": "no overlapping measured ops"}
    tpu_sorted = sorted(tpu_ms[k] for k in both)
    floor = tpu_sorted[max(0, len(tpu_sorted) // 20 - 1)]  # p5: launch floor
    rows = []
    for k in both:
        t, c = tpu_ms[k], cpu_ms[k]
        rows.append({
            "op": k,
            "tpu_fwd_ms": round(t, 3),
            "cpu_fwd_ms": round(c, 3),
            "tpu_excess_ms": round(max(0.0, t - floor), 3),
            "tpu_over_cpu": round(t / c, 1) if c else None,
        })
    rows.sort(key=lambda r: -r["tpu_excess_ms"])
    worst = []
    for r in rows[:top]:
        r = dict(r)
        r["cause"] = CAUSES.get(
            r["op"], "uncharacterized — investigate")
        worst.append(r)
    return {
        "_meta": {
            "ops_compared": len(both),
            "cpu_measured": cpu_table.get("_meta", {}).get("measured"),
            "tpu_measured": tpu_table.get("_meta", {}).get("measured"),
            "tpu_partial": bool(tpu_table.get("_meta", {}).get("partial")),
            "launch_floor_ms": round(floor, 3),
            "method": "rank by TPU forward time MINUS the p5 launch "
                      "floor — raw per-op latency "
                      "is launch-bound (~floor ms) for every cheap op, "
                      "so raw ratios rank noise; excess attributes cost "
                      "to the op itself",
            "note": "single-op launch latency is NOT the framework's "
                    "operating regime: real models run fused graphs "
                    "(see results_train_tpu.json steps_per_launch); "
                    "this table is for finding ops with pathological "
                    "TPU lowerings",
        },
        "worst": worst,
        "rows": rows,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--top", type=int, default=10)
    ap.add_argument("--cpu", default=os.path.join(HERE,
                                                  "results_cpu_full.json"))
    ap.add_argument("--tpu", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    with open(args.cpu) as f:
        cpu = json.load(f)
    with open(args.tpu) as f:
        tpu = json.load(f)
    rec = compare(cpu, tpu, args.top)
    text = json.dumps(rec, indent=2)
    tmp = args.out + ".tmp"
    with open(tmp, "w") as f:
        f.write(text + "\n")
    os.replace(tmp, args.out)
    meta = rec.get("_meta", {})
    print(json.dumps({"ops_compared": meta.get("ops_compared"),
                      "launch_floor_ms": meta.get("launch_floor_ms"),
                      "worst": [r["op"] for r in rec.get("worst", [])]}))


if __name__ == "__main__":
    main()
