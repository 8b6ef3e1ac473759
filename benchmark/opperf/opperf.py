#!/usr/bin/env python
"""Per-operator latency harness (reference ``benchmark/opperf/opperf.py``).

Measures forward and forward+backward wall time per op on the current
device and emits the reference README's result schema:

    {"op_name": [{"avg_time_forward_<op>": ms, "avg_time_backward_<op>": ms,
                  "inputs": {...}}], ...}

TPU-native notes: each op is timed as a jitted XLA executable (compile
excluded via warmup) with a blocking fetch per iteration — the honest
per-dispatch latency, matching how the reference timed engine-pushed
kernels with MXNET_ENGINE_TYPE=NaiveEngine. Backward times jit(grad) of a
sum-projected scalar.

CLI:
    python benchmark/opperf/opperf.py [--output out.json] [--ops add,dot]
                                      [--warmup 5] [--runs 25] [--cpu]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as onp

# runnable from any cwd: the repo root holds mxnet_tpu/
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def _op_specs():
    """(name, fn(jnp, inputs)->out, input shapes, differentiable)."""
    specs = []

    def add(name, fn, shapes, diff=True):
        specs.append((name, fn, shapes, diff))

    L = (1024, 1024)
    add("add", lambda jnp, a, b: a + b, [L, L])
    add("multiply", lambda jnp, a, b: a * b, [L, L])
    add("exp", lambda jnp, a: jnp.exp(a), [L])
    add("tanh", lambda jnp, a: jnp.tanh(a), [L])
    add("sigmoid", lambda jnp, a: 1 / (1 + jnp.exp(-a)), [L])
    add("sum", lambda jnp, a: jnp.sum(a), [L])
    add("mean_axis", lambda jnp, a: jnp.mean(a, axis=1), [L])
    add("dot", lambda jnp, a, b: jnp.dot(a, b), [L, L])
    add("batch_dot", lambda jnp, a, b: jnp.matmul(a, b),
        [(32, 256, 256), (32, 256, 256)])
    add("transpose", lambda jnp, a: jnp.transpose(a), [L])
    add("softmax", lambda jnp, a: __import__("jax").nn.softmax(a, axis=-1), [L])
    add("log_softmax",
        lambda jnp, a: __import__("jax").nn.log_softmax(a, axis=-1), [L])
    add("relu", lambda jnp, a: jnp.maximum(a, 0), [L])
    add("layer_norm",
        lambda jnp, a: (a - a.mean(-1, keepdims=True))
        / jnp.sqrt(a.var(-1, keepdims=True) + 1e-5), [L])
    add("conv2d",
        lambda jnp, x, w: __import__("jax").lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NCHW", "OIHW", "NCHW")),
        [(32, 64, 56, 56), (64, 64, 3, 3)])
    add("embedding_take", lambda jnp, w, i: jnp.take(w, i, axis=0),
        [(50000, 512), None], diff=False)
    add("argsort", lambda jnp, a: jnp.argsort(a, axis=-1), [(1024, 256)],
        diff=False)
    add("cumsum", lambda jnp, a: jnp.cumsum(a, axis=-1), [L])
    add("rfft", lambda jnp, a: jnp.fft.rfft(a, axis=-1), [L], diff=False)
    add("roi_align",
        lambda jnp, d, r: __import__(
            "mxnet_tpu.ops.contrib", fromlist=["roi_align"]).roi_align(
                d, r, (7, 7), spatial_scale=1.0 / 16),
        [(4, 256, 56, 56), (64, 5)], diff=False)
    add("box_iou",
        lambda jnp, a, b: __import__(
            "mxnet_tpu.ops.contrib", fromlist=["box_iou"]).box_iou(
                jnp.abs(a), jnp.abs(b)),
        [(1024, 4), (1024, 4)], diff=False)
    add("count_sketch",
        lambda jnp, d: __import__(
            "mxnet_tpu.ops.contrib", fromlist=["count_sketch"]).count_sketch(
                d, onp.arange(1024) % 256,
                onp.where(onp.arange(1024) % 2 == 0, 1.0, -1.0)
                .astype(onp.float32), 256),
        [(512, 1024)], diff=False)
    add("flash_attention",
        lambda jnp, q, k, v: __import__(
            "mxnet_tpu.ops.pallas.flash_attention",
            fromlist=["flash_attention"]).flash_attention(
                q, k, v, causal=True),
        [(4, 8, 512, 64), (4, 8, 512, 64), (4, 8, 512, 64)], diff=False)
    return specs


def bench_op(name, fn, shapes, diff, warmup, runs):
    import jax
    import jax.numpy as jnp

    rng = onp.random.RandomState(0)
    args = []
    for s in shapes:
        if s is None:  # integer index input (embedding)
            args.append(jnp.asarray(
                rng.randint(0, 50000, size=(32, 128)), jnp.int32))
        else:
            args.append(jnp.asarray(rng.randn(*s).astype(onp.float32)))

    def _fetch(o):
        # completion barrier: a one-element device->host fetch of the
        # last output (in-order execution covers the loop), which also
        # surfaces asynchronous errors
        from benchmark.opperf.utils.op_registry_utils import \
            fetch_with_timeout
        fetch_with_timeout(jax.tree_util.tree_leaves(o)[-1])

    fwd = jax.jit(lambda *a: fn(jnp, *a))
    out = fwd(*args)
    _fetch(out)  # compile
    for _ in range(warmup):
        out = fwd(*args)
    _fetch(out)
    t0 = time.perf_counter()
    for _ in range(runs):
        out = fwd(*args)
    _fetch(out)
    fwd_ms = (time.perf_counter() - t0) / runs * 1e3

    result = {f"avg_time_forward_{name}": round(fwd_ms, 4),
              "inputs": {f"arg{i}": list(a.shape) for i, a in enumerate(args)}}

    if diff:
        float_idx = [i for i, a in enumerate(args)
                     if jnp.issubdtype(a.dtype, jnp.floating)]

        def loss(*fargs):
            full = list(args)
            for i, v in zip(float_idx, fargs):
                full[i] = v
            return jnp.sum(fn(jnp, *full))

        bwd = jax.jit(jax.grad(loss, argnums=tuple(range(len(float_idx)))))
        g = bwd(*[args[i] for i in float_idx])
        _fetch(g)
        for _ in range(warmup):
            g = bwd(*[args[i] for i in float_idx])
        _fetch(g)
        t0 = time.perf_counter()
        for _ in range(runs):
            g = bwd(*[args[i] for i in float_idx])
        _fetch(g)
        result[f"avg_time_backward_{name}"] = round(
            (time.perf_counter() - t0) / runs * 1e3, 4)
    return result


def run_benchmark(ops=None, warmup=5, runs=25, log=print):
    import jax

    results = {"_meta": {"device": str(jax.devices()[0]),
                         "platform": jax.devices()[0].platform,
                         "warmup": warmup, "runs": runs}}
    for name, fn, shapes, diff in _op_specs():
        if ops and name not in ops:
            continue
        try:
            results[name] = [bench_op(name, fn, shapes, diff, warmup, runs)]
            log(f"{name}: {results[name][0]}")
        except Exception as e:  # noqa: BLE001 — keep sweeping
            results[name] = [{"error": repr(e)}]
            log(f"{name}: ERROR {e!r}")
    return results


def run_full_registry(warmup=2, runs=10, log=print, checkpoint=None,
                      resume=None):
    """Walk EVERY public op in the registry with auto-synthesized inputs
    (reference opperf auto-enumeration, VERDICT r3 item 8). Eager per-op
    latency + autograd round trip where differentiable.

    ``checkpoint``: path that receives the partial table (atomic rewrite)
    every few ops, so an outer-harness kill mid-sweep loses at most a few
    measurements instead of the whole table.

    ``resume``: path to a previously banked table (same platform, mode
    full); its measured rows are carried forward and their ops skipped,
    so repeated short runs make monotonic progress through the
    registry instead of re-measuring the alphabetical head every time."""
    import jax

    from benchmark.opperf.utils.op_registry_utils import (
        bench_registry_op, build_call, list_all_ops)

    import signal

    results = {"_meta": {"device": str(jax.devices()[0]),
                         "platform": jax.devices()[0].platform,
                         "warmup": warmup, "runs": runs, "mode": "full"}}
    measured = skipped = errored = 0

    # per-op watchdog for Python-level runaways (the observed hang class:
    # an array iterated as a shape). A hang INSIDE a native XLA call
    # would not be interruptible this way — the tiny fixed shapes used
    # by the input rules keep native work bounded, and the driver-level
    # harnesses add child-process kills as the outer net.
    def _alarm(_sig, _frm):
        raise TimeoutError("op exceeded the per-op time budget")

    def _write_checkpoint(partial=True):
        if checkpoint is None:
            return
        results["_meta"].update(measured=measured, skipped=skipped,
                                errored=errored, partial=partial)
        tmp = checkpoint + ".tmp"
        with open(tmp, "w") as f:
            json.dump(results, f, indent=1)
        os.replace(tmp, checkpoint)

    platform = jax.devices()[0].platform
    prior = {}
    poison_counts = {}  # op -> prior poison strikes (see resume below)
    if resume:
        try:
            with open(resume) as f:
                prev = json.load(f)
            if (prev.get("_meta", {}).get("platform") == platform
                    and prev.get("_meta", {}).get("mode") == "full"):
                # carry forward every DETERMINISTIC classification, not
                # just measurements: a backend-poisoning op (e.g.
                # np.sort_complex — async UNIMPLEMENTED kills every later
                # dispatch) retried each sweep would abort the sweep at
                # the same op forever, so the registry tail behind it
                # could never be reached. Timeouts ARE retried — they can
                # be window contention rather than the op's own nature.
                n_meas = n_cls = n_retry = 0
                for k, v in prev.items():
                    if (k.startswith("_") or not isinstance(v, list)
                            or not v or not isinstance(v[0], dict)):
                        continue
                    e0 = v[0]
                    if "avg_time" in str(e0):
                        prior[k] = v
                        n_meas += 1
                    elif "skipped" in e0:
                        prior[k] = v
                        n_cls += 1
                    elif "error" in e0:
                        # poison strike rule FIRST: a hang-then-poison op
                        # (alarm fires, then the canary finds the backend
                        # dead) carries a TimeoutError STRING but is a
                        # poisoner — routing it to the timeout-retry
                        # branch would reset its strikes every sweep and
                        # wall off the registry tail behind it forever
                        poisoned = bool(e0.get("backend_poisoned"))
                        if poisoned and int(e0.get("poison_count")
                                            or 1) < 2:
                            # a poisoned-abort can mean EITHER a
                            # deterministic poisoner op (np.sort_complex
                            # UNIMPLEMENTED) or the backend dying
                            # mid-op; give the op ONE more run before
                            # the classification sticks
                            poison_counts[k] = int(
                                e0.get("poison_count") or 1)
                            n_retry += 1
                        elif (not poisoned and "TimeoutError"
                                in str(e0.get("error"))):
                            n_retry += 1  # contention-shaped: retry
                        else:
                            prior[k] = v
                            n_cls += 1
                log(f"resume: carrying forward {n_meas} measured + "
                    f"{n_cls} classified (skip/deterministic-error) ops; "
                    f"retrying {n_retry} (timeouts + first-strike "
                    "poisons)")
        except Exception as e:  # noqa: BLE001 — no/bad resume file
            log(f"resume file unusable ({e!r}); full sweep")
    def _canary_ok():
        try:
            import jax.numpy as _jnp
            from benchmark.opperf.utils.op_registry_utils import \
                fetch_with_timeout
            return float(fetch_with_timeout(_jnp.ones(()) + 1.0,
                                            seconds=120.0)) == 2.0
        except Exception:  # noqa: BLE001 — any failure = backend gone
            return False

    old = signal.signal(signal.SIGALRM, _alarm)
    try:
        for i, (name, fn) in enumerate(sorted(list_all_ops().items())):
            if checkpoint is not None and i % 20 == 0 and i:
                _write_checkpoint()
            if name in prior:
                results[name] = prior[name]
                e0 = prior[name][0]
                if "avg_time" in str(e0):
                    measured += 1
                elif "skipped" in e0:
                    skipped += 1
                else:
                    errored += 1
                continue
            log(f"-> {name}")
            signal.alarm(45)
            try:
                call = build_call(name, fn)
                if call is None:
                    results[name] = [{"skipped": "no input rule matched"}]
                    skipped += 1
                    continue
                args, kwargs, diff = call
                results[name] = [bench_registry_op(name, fn, args, kwargs,
                                                   diff, warmup, runs)]
                measured += 1
                log(f"{name}: {results[name][0]}")
            except Exception as e:  # noqa: BLE001 — keep sweeping
                results[name] = [{"error": repr(e)}]
                errored += 1
                log(f"{name}: ERROR {e!r}")
                signal.alarm(0)  # disarm BEFORE the canary: a sliver of
                # leftover alarm budget must not interrupt it, and its
                # generous timeout lets queued in-order device work drain
                if not _canary_ok():
                    # the error wasn't the op's own — the backend died
                    # (observed: one async-UNIMPLEMENTED op breaks every
                    # later dispatch). Stop; the checkpoint keeps what
                    # was honestly measured.
                    results[name][0]["backend_poisoned"] = True
                    # strike count across sweeps: 2 poisoned aborts on
                    # the same op = deterministic poisoner, carried
                    # forward and never retried; 1 may be the backend
                    # dying mid-op (see resume carry-forward)
                    results[name][0]["poison_count"] = \
                        poison_counts.get(name, 0) + 1
                    results["_meta"]["aborted_at"] = name
                    log(f"backend poisoned at {name}; aborting sweep")
                    break
            finally:
                signal.alarm(0)
    finally:
        signal.signal(signal.SIGALRM, old)
    complete = "aborted_at" not in results["_meta"]
    results["_meta"].update(measured=measured, skipped=skipped,
                            errored=errored, partial=not complete)
    _write_checkpoint(partial=not complete)
    log(f"full registry: {measured} measured, {skipped} skipped, "
        f"{errored} errored")
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--output", default=None)
    ap.add_argument("--ops", default=None,
                    help="comma-separated subset of op names")
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--runs", type=int, default=25)
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU platform")
    ap.add_argument("--full", action="store_true",
                    help="walk the ENTIRE op registry with auto inputs "
                         "(reference opperf auto-enumeration)")
    ap.add_argument("--checkpoint", default=None,
                    help="(--full only) atomically rewrite the partial "
                         "table here every few ops, so a harness kill "
                         "mid-sweep keeps what was measured")
    ap.add_argument("--resume-from", default=None,
                    help="(--full only) carry forward measured rows from "
                         "this banked table and skip their ops")
    args = ap.parse_args()
    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    if args.full:
        if args.ops:
            ap.error("--ops filters the curated suite; it does not "
                     "compose with --full (which always walks everything)")
        warmup, runs = min(args.warmup, 2), min(args.runs, 10)
        if (warmup, runs) != (args.warmup, args.runs):
            print(f"[opperf] --full clamps warmup/runs to {warmup}/{runs} "
                  "(one pass over ~480 ops)", file=sys.stderr)
        results = run_full_registry(
            warmup, runs, log=lambda m: print(m, file=sys.stderr),
            checkpoint=args.checkpoint, resume=args.resume_from)
    else:
        ops = set(args.ops.split(",")) if args.ops else None
        results = run_benchmark(ops, args.warmup, args.runs,
                                log=lambda m: print(m, file=sys.stderr))
    text = json.dumps(results, indent=1)
    if args.output:
        with open(args.output, "w") as f:
            f.write(text)
    print(text)


if __name__ == "__main__":
    main()
