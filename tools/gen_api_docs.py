#!/usr/bin/env python
"""Generate the API reference tree (reference
``docs/python_docs/python/api/`` — one page per public module).

Walks the public surface of ``mxnet_tpu`` and writes one markdown page
per module into ``docs/api/``: the module docstring, then each public
class/function with its signature and docstring first paragraph. The
output is committed (docs are part of the framework), and
``tests/test_tooling.py`` regenerates to assert the tree stays in sync.

Usage:
    python tools/gen_api_docs.py [--out docs/api]
"""
from __future__ import annotations

import argparse
import importlib
import inspect
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# module -> one-line description; the curated public tree (matches the
# reference's api/ layout where a counterpart exists)
MODULES = {
    "mxnet_tpu.numpy": "mx.np — NumPy-compatible array API",
    "mxnet_tpu.numpy.random": "mx.np.random — random sampling",
    "mxnet_tpu.numpy.linalg": "mx.np.linalg — linear algebra",
    "mxnet_tpu.numpy_extension": "mx.npx — operators beyond NumPy "
                                 "(nn, control flow, util)",
    "mxnet_tpu.ndarray": "mx.nd — legacy NDArray surface + sparse",
    "mxnet_tpu.ndarray.sparse": "row_sparse / CSR arrays",
    "mxnet_tpu.autograd": "autograd tape: record/pause/grad/Function",
    "mxnet_tpu.gluon.block": "Block / HybridBlock / SymbolBlock",
    "mxnet_tpu.gluon.parameter": "Parameter / ParameterDict",
    "mxnet_tpu.gluon.trainer": "Trainer — optimizer driver",
    "mxnet_tpu.gluon.nn": "neural-network layers",
    "mxnet_tpu.gluon.rnn": "recurrent cells and fused layers",
    "mxnet_tpu.gluon.loss": "loss functions",
    "mxnet_tpu.gluon.metric": "evaluation metrics",
    "mxnet_tpu.gluon.data": "datasets, samplers, DataLoader",
    "mxnet_tpu.gluon.data.vision.transforms": "vision transforms",
    "mxnet_tpu.gluon.model_zoo.vision": "vision model zoo",
    "mxnet_tpu.gluon.contrib.estimator": "Estimator fit() loop",
    "mxnet_tpu.initializer": "weight initializers",
    "mxnet_tpu.optimizer": "optimizers (20 update rules)",
    "mxnet_tpu.optimizer.lr_scheduler": "learning-rate schedules",
    "mxnet_tpu.kvstore": "KVStore — local/device/dist_tpu_sync comm",
    "mxnet_tpu.parallel": "mesh parallelism: dp/tp/pp/sp/ep",
    "mxnet_tpu.parallel.ring_attention": "ring / Ulysses / blockwise "
                                         "sequence parallelism",
    "mxnet_tpu.parallel.sharding": "partition-rule sharding trees: "
                                   "regex rules → PartitionSpec pytrees, "
                                   "shard/gather closures, zoo catalog",
    "mxnet_tpu.symbol": "mx.sym — symbolic graphs + Executor",
    "mxnet_tpu.amp": "automatic mixed precision",
    "mxnet_tpu.profiler": "profiler — chrome-trace + aggregates",
    "mxnet_tpu.contrib.quantization": "INT8 post-training quantization",
    "mxnet_tpu.contrib.onnx": "ONNX export / import",
    "mxnet_tpu.contrib.text": "text vocab + token embeddings",
    "mxnet_tpu.checkpoint": "sharded (orbax) + .params checkpointing",
    "mxnet_tpu.context": "device contexts (cpu/gpu/tpu)",
    "mxnet_tpu.engine": "dependency-engine semantics shims",
    "mxnet_tpu.registry": "generic class registries",
    "mxnet_tpu.test_utils": "testing utilities (oracle asserts)",
    "mxnet_tpu.image": "legacy image augmentation pipeline",
    "mxnet_tpu.io": "legacy DataIter pipeline",
    "mxnet_tpu.io.service": "fault-tolerant dataset service: decode-"
                            "worker fault domain, exactly-once range "
                            "re-dispatch, named resumable cursors",
    "mxnet_tpu.io.transport": "network block-transfer plane: checksum-"
                              "verified framed socket protocol, pooled "
                              "BlockClient with deadlines + endpoint "
                              "failover",
    "mxnet_tpu.recordio": "RecordIO containers",
    "mxnet_tpu.library": "extension-library loading (mxtpu_ext ABI)",
    "mxnet_tpu.runtime": "build-feature introspection",
    "mxnet_tpu.operator": "python CustomOp",
    "mxnet_tpu.monitor": "Monitor / TensorInspector taps",
    "mxnet_tpu.analysis.opt": "cost-model-guided auto-optimization: "
                              "jaxpr rewrites, analytic TPU cost "
                              "model, knob autotuner",
    "mxnet_tpu.analysis": "tpulint — TPU anti-pattern analyzer "
                          "(jaxpr + AST rules, runtime sentinel)",
    "mxnet_tpu.analysis.concurrency": "concurrency lint: interprocedural "
                                      "lock-order cycles, blocking-under-"
                                      "lock, thread-lifecycle leaks",
    "mxnet_tpu.analysis.contracts": "contract lint: swallowed/untyped "
                                    "fault handling, code-vs-docs drift "
                                    "gates (chaos sites, env vars, "
                                    "metrics)",
    "mxnet_tpu.analysis.lockwatch": "runtime lock-order witness: "
                                    "threading factory wrap, per-thread "
                                    "held-stack edges, cycle assertion",
    "mxnet_tpu.aot": "persistent compile cache + ahead-of-time warmup",
    "mxnet_tpu.resilience": "chaos injection, retry + transient-vs-fatal "
                            "classifier, watchdog, supervised training",
    "mxnet_tpu.resilience.elastic": "elastic fault domain: heartbeats, "
                                    "rank-loss detection, mesh "
                                    "auto-degrade resume",
    "mxnet_tpu.serving": "dynamic-batching inference serving engine",
    "mxnet_tpu.serving.fleet": "serving fleet fault domain: "
                               "health-checked replica router, hedged "
                               "retries, circuit breakers, tenant-fair "
                               "shedding, drain/restart lifecycle",
    "mxnet_tpu.serving.autoscale": "fleet autoscaler: SLO-edge + "
                                   "gauge-trip scale-up, hysteresis "
                                   "scale-down, warm-pool spare "
                                   "activation",
    "mxnet_tpu.serving.llm": "continuous-batching LLM serving: paged "
                             "KV block pool, prefill/decode split, "
                             "in-flight admission, speculative decode, "
                             "shared-prefix block caching",
    "mxnet_tpu.serving.kv_cache": "the KV-cache manager under LLMEngine: "
                                  "pools, free list, refcounts, prefix "
                                  "index, eviction, spill and re-attach",
    "mxnet_tpu.serving.kv_hash": "the one chain-hash discipline shared "
                                 "by the prefix cache, prefix-affinity "
                                 "routing and the KV spill tiers",
    "mxnet_tpu.serving.kv_spill": "tiered KV block storage: host-RAM / "
                                  "disk / remote-peer spill under the "
                                  "paged pool, re-attach over re-prefill",
    "mxnet_tpu.serving.kv_codec": "byte-exact KV block row wire codec "
                                  "shared by the spill tiers and the "
                                  "prefill/decode handoff",
    "mxnet_tpu.serving.disagg": "disaggregated serving: prefill/decode "
                                "role fleets, KV-block handoff over the "
                                "transport, miss-never-loss staging",
    "mxnet_tpu.gluon.model_zoo.generation": "autoregressive generation: "
                                            "compiled decode/beam "
                                            "programs, paged serving "
                                            "programs, speculative "
                                            "draft/verify",
    "mxnet_tpu.ops.pallas": "hand-written Pallas TPU kernels: flash "
                            "attention, paged attention, fused decode "
                            "step",
    "mxnet_tpu.telemetry": "unified telemetry: metrics registry, step "
                           "tracing, MFU gauges, flight recorder",
    "mxnet_tpu.telemetry.cluster": "cluster observability: shared-root "
                                   "scraping, merged exposition, "
                                   "incident bundles",
    "mxnet_tpu.telemetry.slo": "declarative SLO rules + sentinel over "
                               "cluster snapshots",
}


def first_paragraph(doc: str | None) -> str:
    if not doc:
        return ""
    para = doc.strip().split("\n\n")[0]
    return " ".join(line.strip() for line in para.splitlines())


def signature_of(obj) -> str:
    import re

    try:
        sig = str(inspect.signature(obj))
    except (TypeError, ValueError):
        return "(...)"
    # function-object defaults repr with a memory address — scrub it so
    # regeneration is byte-stable across processes
    return re.sub(r" at 0x[0-9a-f]+", "", sig)


def public_members(mod):
    names = getattr(mod, "__all__", None)
    if names is None:
        names = [n for n in dir(mod) if not n.startswith("_")]
    out = []
    for n in sorted(set(names)):
        obj = getattr(mod, n, None)
        if obj is None or inspect.ismodule(obj):
            continue
        if not (inspect.isclass(obj) or callable(obj)):
            continue
        out.append((n, obj))
    return out


def render(mod_name: str, blurb: str) -> str:
    mod = importlib.import_module(mod_name)
    lines = [f"# `{mod_name}`", "", f"*{blurb}*", ""]
    if mod.__doc__:
        lines += [first_paragraph(mod.__doc__), ""]
    members = public_members(mod)
    classes = [(n, o) for n, o in members if inspect.isclass(o)]
    funcs = [(n, o) for n, o in members if not inspect.isclass(o)]
    if classes:
        lines += ["## Classes", ""]
        for n, o in classes:
            lines.append(f"### `{n}{signature_of(o)}`")
            # o.__doc__, NOT inspect.getdoc: the latter inherits the base
            # class docstring, which would stamp HybridBlock's blurb onto
            # every layer page
            doc = first_paragraph(o.__doc__)
            if doc:
                lines.append(f"\n{doc}")
            lines.append("")
    if funcs:
        lines += ["## Functions", ""]
        for n, o in funcs:
            lines.append(f"### `{n}{signature_of(o)}`")
            doc = first_paragraph(inspect.getdoc(o))
            if doc:
                lines.append(f"\n{doc}")
            lines.append("")
    return "\n".join(lines).rstrip() + "\n"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "docs", "api"))
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)

    index = ["# API reference", "",
             "One page per public module (generated by "
             "`tools/gen_api_docs.py`; regenerate after API changes — "
             "`tests/test_tooling.py` keeps it honest).", ""]
    count = 0
    for mod_name, blurb in MODULES.items():
        page = mod_name.replace("mxnet_tpu.", "").replace(".", "_") + ".md"
        try:
            text = render(mod_name, blurb)
        except Exception as e:  # noqa: BLE001 — a broken module must be loud
            print(f"FAILED {mod_name}: {e!r}", file=sys.stderr)
            raise
        with open(os.path.join(args.out, page), "w") as f:
            f.write(text)
        index.append(f"- [`{mod_name}`]({page}) — {blurb}")
        count += 1
    with open(os.path.join(args.out, "index.md"), "w") as f:
        f.write("\n".join(index) + "\n")
    print(f"wrote {count} pages + index to {args.out}")


if __name__ == "__main__":
    main()
