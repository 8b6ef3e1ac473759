"""One process, one cell, once.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's model on the device from the seed, warms up only that
cell's shapes, measures for ``--seconds`` and prints one JSON object as
the last line of standard output: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` and, traced, ``breakdown``. ``--trace 0`` reports
the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics, as
``BENCHMARK.json`` lists them. Earlier lines are notes.

Everything about a cell is data found by name (see ``PERF.md``):

    BENCHMARK.json                      the cell's config, traffic, chips; the metrics
    chipbench/workloads/<cell>.json     runner, engine/trainer arguments, warm-up, mesh
    <the config's "file">               published sizes
    chipbench/traffic/<traffic>.json    kind + parameters of the traffic mix
    chipbench/runners/<runner>.py       run(ctx) -> result
    chipbench/traffic/<kind>.py         when the next request is due
    chipbench/layers/<reader>.py        read(result, trace, ctx) -> value; the reader of
                                        metric "a.b" is "a"

Without the accelerator the cell asks for, it exits non-zero and prints no
result; nothing falls back to the CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_T_IMPORT = time.perf_counter()

from . import harness, trace_reduce                   # noqa: E402

# The platform every cell must run on. No option changes it; the tests
# that rehearse the cells on the CPU steer it (and ROOT) from the test.
PLATFORM = "tpu"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"chipbench: no {what} named {name!r} in BENCHMARK.json "
                     f"(known: {[e['name'] for e in entries]})")


def metrics_of(bench: dict, group: str, cell: str) -> list:
    """The metrics of ``group`` this cell reports: those with no
    ``workloads`` key and those that list it."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def device_info(devices, trace) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": peak}
    if trace is not None:
        out["busy_s"], out["window_s"] = trace["busy_s"], trace["window_s"]
    return out


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    bench = harness.load_json(ROOT, "BENCHMARK.json")
    entry = find(bench["workloads"], args.workload, "workload")
    conf_entry = find(bench["configs"], entry["config"], "config")
    cell = harness.load_json(ROOT, "chipbench", "workloads",
                             entry["name"] + ".json")
    config = harness.load_json(ROOT, conf_entry["file"])
    traffic = harness.load_json(ROOT, "chipbench", "traffic",
                                entry["traffic"] + ".json")
    chips = int(entry["chips"])

    import jax

    found = jax.devices()
    if found[0].platform != PLATFORM or len(found) < chips:
        print(f"chipbench: {args.workload} needs {chips} {PLATFORM} "
              f"device(s); jax found {len(found)} x {found[0].platform}",
              file=sys.stderr)
        return 1
    devices = found[:chips]
    harness.note(f"set-up: {time.perf_counter() - t_start:7.2f} s  jax and "
                 f"the {PLATFORM}'s runtime started")

    from mxnet_tpu.base import arm_compile_cache

    harness.note(f"{args.workload}: seed {args.seed}, {args.seconds:g} s, "
                 f"trace {args.trace}, compile cache {arm_compile_cache()}")
    ctx = harness.Context(
        root=ROOT, cell=cell, config=config, traffic=traffic,
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        devices=devices, compiles=harness.Compiles(), spans=harness.Spans(),
        t_start=t_start)
    result = harness.load_module(ROOT, "runners", cell["runner"]).run(ctx)

    trace = result["trace"]
    if args.trace:
        wanted = metrics_of(bench, "per_layer", args.workload)
        values = {m["name"]: harness.load_module(
            ROOT, "layers", m["name"].split(".")[0]).read(result, trace, ctx)
            for m in wanted}
    else:
        wanted = metrics_of(bench, "end_to_end", args.workload)
        values = result["end_to_end"]
    for name, v in result["end_to_end"].items():
        harness.note(f"{name} = {v}")
    metrics = {m["name"]: {"value": float(values[m["name"]]),
                           "unit": m["unit"]}
               for m in wanted if values.get(m["name"]) is not None}
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": metrics,
            "device": device_info(devices, trace)}
    if trace is not None:
        line["breakdown"] = trace_reduce.breakdown(trace)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(t_start=_T_IMPORT))
