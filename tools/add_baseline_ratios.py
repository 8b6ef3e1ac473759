#!/usr/bin/env python
"""Recompute baseline-ratio fields on banked benchmark artifacts.

Ratios are DERIVED fields (measured img/s ÷ the reference's published
V100 row) — recomputing them offline from the single source of truth
(benchmark/baselines.py) is bookkeeping, not measurement. Used when the
ratio policy changes (e.g. the bs256 record must compare against the
published bs256/bs128 rows, not the bs32 ones — VERDICT r3 weak #8).

Usage: python tools/add_baseline_ratios.py   (idempotent, in-place)
"""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.baselines import (attach_headline_ratios,  # noqa: E402
                                 attach_infer_ratios, attach_train_ratios)

HERE = os.path.join(ROOT, "benchmark")


def patch(path, fn):
    p = os.path.join(HERE, path)
    if not os.path.exists(p):
        print(f"skip {path} (absent)")
        return
    with open(p) as f:
        data = json.load(f)
    changed = fn(data)
    if changed:
        tmp = p + ".tmp"
        with open(tmp, "w") as f:
            json.dump(data, f, indent=2)
            f.write("\n")
        os.replace(tmp, p)
        print(f"patched {path}")
    else:
        print(f"no change {path}")


def patch_headline_like(data):
    """bench.py single-record artifacts ({..record fields..} or
    {record: {...}}): recompute vs_baseline against the batch-matched
    published rows."""
    rec = data.get("record", data)
    metric = rec.get("metric", "")
    if "infer_bs" not in metric:
        return False
    batch = int(metric.split("infer_bs")[1].split("_")[0])
    before = json.dumps(rec, sort_keys=True)
    attach_headline_ratios(rec, batch)
    return json.dumps(rec, sort_keys=True) != before


def patch_table(key_fn):
    def go(data):
        changed = False
        for rec in data.get("results", []):
            before = json.dumps(rec, sort_keys=True)
            key_fn(rec)
            changed |= json.dumps(rec, sort_keys=True) != before
        return changed
    return go


def main():
    patch("results_bench_tpu_bs256.json", patch_headline_like)
    patch("results_infer_tpu.json", patch_table(attach_infer_ratios))
    patch("results_train_tpu.json", patch_table(attach_train_ratios))


if __name__ == "__main__":
    main()
