#!/usr/bin/env python
"""Auto-optimization benchmark: default vs rewritten vs tuned.

Measures what ``mx.analysis.opt`` actually buys on a **deliberately
tile-misaligned, convert-churny model** (the shapes tpulint J001/J003
flag) and on a training step, in three stages:

1. **default** — the model as written, one launch per step;
2. **rewritten** — ``opt.rewrite_callable`` with the live backend's
   cost model: exact J003 churn is cancelled everywhere, J001 tile
   padding applies only where the model predicts a win (on the CPU
   bench backend it is *refused* — the no-regression guard in action —
   and the refusals are recorded in the artifact; the TPU daemon
   capture banks the applied-padding row);
3. **tuned** — ``opt.autotune`` over ``steps_per_launch`` on the
   rewritten step (cost-model pruning + timed probes), the winning
   :class:`TunedConfig` persisted and replayed.

Every applied rewrite is verified by the **interpret-mode equivalence
oracle** (bitwise for the integer/argmax path, dtype-tolerance for
floats) and every timed stage carries a **retrace check** (jit cache
size must stay 1 across the timed window — a rewrite that broke shape
stability would show up right there). The full run also banks the
cost-model **calibration table** against the banked TPU corpus
(predicted-vs-observed + Spearman rank correlation).

Artifact: ``results_opt_cpu.json`` (CPU, this harness); no chip row
exists. ``--quick`` is the seconds-scale tier-1 smoke
(``tests/test_opt.py::test_opt_bench_quick``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as onp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HERE = os.path.dirname(os.path.abspath(__file__))


# ---------------------------------------------------------------------------
# the tile-misaligned, churny workload
# ---------------------------------------------------------------------------
def build_misaligned_model(batch=16, dims=(130, 190, 60, 130), depth=1,
                           seed=0):
    """An MLP whose every matmul pads badly against the (8, 128) MXU
    tiles (J001 bait: 130->256 is 49% tile waste) with exact
    ``bf16 -> f32 -> bf16`` convert round-trips between layers (J003
    bait), plus an int32 argmax head so the oracle has a bitwise path.
    Returns ``(step, args)``; ``step``'s output feeds its input, so
    chained steps serialize (the bench.py protocol: no dispatch layer
    can elide work)."""
    import jax.numpy as jnp

    rng = onp.random.RandomState(seed)
    widths = []
    for i in range(depth):
        for a, b in zip(dims[:-1], dims[1:]):
            widths.append((a, b))
    ws = [jnp.asarray(rng.randn(a, b) * (1.0 / onp.sqrt(a)),
                      jnp.bfloat16) for a, b in widths]
    x0 = jnp.asarray(rng.randn(batch, dims[0]), jnp.bfloat16)

    def step(x, ws):
        h = x
        for w in ws:
            # the churn: a precision boundary drawn one op too narrow
            h = h.astype(jnp.float32).astype(jnp.bfloat16)
            h = jnp.tanh(h @ w)
        ids = jnp.argmax(h.astype(jnp.float32), axis=-1)  # bitwise path
        # close the loop so step k+1 depends on step k
        nxt = h * (1.0 + 1e-3 * jnp.cos(
            jnp.float32(1.0)).astype(h.dtype))
        return nxt, ids

    return step, (x0, ws)


def build_train_step(batch=64, feat=64, hidden=250, classes=10, seed=0):
    """A small train step (fwd+bwd+SGD-momentum, train_bench shape)
    with a tile-misaligned hidden dim — the second acceptance workload.
    Returns ``(step, args)`` where the output params feed the next
    step."""
    import jax
    import jax.numpy as jnp

    rng = onp.random.RandomState(seed)
    p = {"w1": jnp.asarray(rng.randn(feat, hidden) * 0.1, jnp.float32),
         "w2": jnp.asarray(rng.randn(hidden, classes) * 0.1,
                           jnp.float32)}
    vel = {k: jnp.zeros_like(v) for k, v in p.items()}
    x = jnp.asarray(rng.randn(batch, feat), jnp.float32)
    y = jnp.asarray(rng.randint(0, classes, (batch,)), jnp.int32)

    def loss_fn(p, x, y):
        h = jnp.tanh(x @ p["w1"])
        logits = h @ p["w2"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, y[..., None], axis=-1).mean()

    def step(p, vel, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(p, x, y)
        new_p, new_v = {}, {}
        for k in p:
            v = 0.9 * vel[k] + grads[k]
            new_v[k] = v
            new_p[k] = p[k] - 0.05 * v
        return new_p, new_v, loss

    return step, (p, vel, x, y)


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------
def measure_chain(jitted, args, duration_s, log, label,
                  min_iters=8, windows=3):
    """steps/s of a self-feeding jitted step — best of ``windows``
    timed windows (a single window on a busy 1-core host measures the
    scheduler, not the program; observed ±15% swings). Returns
    ``(steps_per_s, retrace_count)`` where retraces = jit cache growth
    across ALL timed windows (must be 0: one compile, then a stable
    executable)."""
    import jax

    out = jitted(*args)
    jax.block_until_ready(out)
    cache0 = jitted._cache_size()
    x = out[0] if isinstance(out, tuple) else out
    args_rest = args[1:]
    per = max(duration_s / windows, 0.2)
    best, total_n = 0.0, 0
    for _ in range(windows):
        n, t0 = 0, time.perf_counter()
        while True:
            out = jitted(x, *args_rest)
            x = out[0] if isinstance(out, tuple) else out
            n += 1
            if n >= min_iters and time.perf_counter() - t0 >= per:
                break
        jax.block_until_ready(x)
        best = max(best, n / (time.perf_counter() - t0))
        total_n += n
    retraces = jitted._cache_size() - cache0
    log(f"{label}: {best:.1f} steps/s (best of {windows} windows, "
        f"{total_n} steps, retraces={retraces})")
    return best, retraces


def measure_train(jitted, p, vel, x, y, duration_s, log, label,
                  min_iters=8, windows=3):
    import jax

    p, vel, loss = jitted(p, vel, x, y)
    jax.block_until_ready(loss)
    cache0 = jitted._cache_size()
    per = max(duration_s / windows, 0.2)
    best, total_n = 0.0, 0
    for _ in range(windows):
        n, t0 = 0, time.perf_counter()
        while True:
            p, vel, loss = jitted(p, vel, x, y)
            n += 1
            if n >= min_iters and time.perf_counter() - t0 >= per:
                break
        jax.block_until_ready(loss)
        best = max(best, n / (time.perf_counter() - t0))
        total_n += n
    if not onp.isfinite(float(loss)):
        raise RuntimeError(f"{label}: non-finite loss — refusing to bank")
    retraces = jitted._cache_size() - cache0
    log(f"{label}: {best:.1f} steps/s (best of {windows} windows, "
        f"{total_n} steps, retraces={retraces})")
    return best, retraces


def scan_chain(step, k):
    """K serially-chained self-feeding steps in ONE executable (the
    steps_per_launch knob; train_bench's lax.scan pattern)."""
    import jax

    def chained(x, ws):
        def body(c, _):
            nxt, ids = step(c, ws)
            return nxt, ids[:1]
        x, idss = jax.lax.scan(body, x, None, length=k)
        return x, idss[-1]

    return chained


def scan_train(step, k):
    import jax

    def chained(p, vel, x, y):
        def body(carry, _):
            p, vel = carry
            p, vel, loss = step(p, vel, x, y)
            return (p, vel), loss
        (p, vel), losses = jax.lax.scan(body, (p, vel), None, length=k)
        return p, vel, losses[-1]

    return chained


# ---------------------------------------------------------------------------
# the bench
# ---------------------------------------------------------------------------
def run(quick=False, output=None, bank=True, duration_s=3.0,
        log=lambda *a: print("[opt_bench]", *a, file=sys.stderr,
                             flush=True)):
    import jax

    # no platform pinning here: the daemon's capture_opt must run on
    # the live TPU backend (bank_if_tpu refuses cpu rows), and the
    # tier-1 quick smoke passes JAX_PLATFORMS=cpu through the env
    from mxnet_tpu.analysis import opt
    from mxnet_tpu.analysis.jaxpr_rules import lint_callable

    if quick:
        duration_s = min(duration_s, 0.6)
    dev = jax.devices()[0]
    model = opt.CostModel.for_backend()
    log(f"backend={model.backend} ({model.device_kind}); "
        f"cost model peak={model.peak_tflops} TFLOPs, "
        f"bw={model.hbm_gbps} GB/s")

    # ---- workload A: misaligned + churny inference chain ---------------
    # serving-shaped micro-batch: small steps are exactly where launch
    # overhead dominates (the knob's reason to exist; on this CPU
    # harness the jit dispatch plays that role)
    step, (x0, ws) = build_misaligned_model(batch=8 if quick else 16)
    lint_before = [f.rule for f in lint_callable(step, x0, ws,
                                                 scope="opt_bench")]
    est_default = model.estimate_callable(step, x0, ws)

    j_default = jax.jit(step)
    sps_default, rt_default = measure_chain(
        j_default, (x0, ws), duration_s, log, "default")

    step_rw, report = opt.rewrite_callable(
        step, x0, ws, model=model, mode_override="rewrite",
        scope="opt_bench")
    log(report.render())
    oracle = opt.check_equivalence(step, step_rw, x0, ws)
    if not oracle["equal"]:
        raise RuntimeError(f"equivalence oracle FAILED: {oracle}")
    log(f"oracle: {oracle['n_leaves']} leaves equal "
        f"(int path bitwise, float within dtype tolerance)")
    est_rewritten = model.estimate_callable(step_rw, x0, ws)

    j_rw = jax.jit(step_rw)
    sps_rewritten, rt_rewritten = measure_chain(
        j_rw, (x0, ws), duration_s, log, "rewritten")

    # ---- tuned: steps_per_launch over the rewritten step ---------------
    spl_space = (1, 4, 16) if quick else (1, 2, 4, 8, 16, 32)

    def builder(steps_per_launch=1):
        fn = step_rw if steps_per_launch == 1 \
            else scan_chain(step_rw, steps_per_launch)
        return jax.jit(fn), (x0, ws)

    cfg = opt.autotune(
        builder, label="opt_bench.chain",
        space={"steps_per_launch": spl_space}, model=model,
        probe_top_k=2 if quick else 4,
        probe_reps=2 if quick else 3,
        # the banked verdict needs probes well above scheduler noise on
        # small shared hosts (a 50 ms probe crowned a config the 3 s
        # re-measure then contradicted — observed)
        min_probe_wall_s=0.05 if quick else 0.3,
        budget_s=10.0 if quick else 60.0, save=bool(opt.store_dir()),
        log=log)
    spl = int(cfg.knobs["steps_per_launch"])
    j_tuned = jax.jit(scan_chain(step_rw, spl) if spl > 1 else step_rw)
    sps_launches, rt_tuned = measure_chain(
        j_tuned, (x0, ws), duration_s, log, f"tuned(spl={spl})")
    sps_tuned = sps_launches * spl

    speedup_rewritten = sps_rewritten / sps_default
    speedup_tuned = sps_tuned / sps_default
    efficiency = opt.record_prediction(
        "opt_bench.chain", est_rewritten.t_total_s / 1.0,
        1.0 / max(sps_rewritten, 1e-9))

    # ---- workload B: the train step ------------------------------------
    # no donation here: the same (p, vel) arrays seed every stage and
    # every autotune probe — donating the first measurement would hand
    # later probes deleted buffers (XLA:CPU ignores donation anyway)
    tstep, (p, vel, tx, ty) = build_train_step(
        hidden=120 if quick else 250)
    jt_default = jax.jit(tstep)
    tsps_default, trt_default = measure_train(
        jt_default, p, vel, tx, ty, duration_s, log, "train default")

    def tbuilder(steps_per_launch=1):
        fn = tstep if steps_per_launch == 1 \
            else scan_train(tstep, steps_per_launch)
        return jax.jit(fn), (p, vel, tx, ty)

    tcfg = opt.autotune(
        tbuilder, label="opt_bench.train",
        space={"steps_per_launch": spl_space}, model=model,
        probe_top_k=2 if quick else 4,
        probe_reps=2 if quick else 3,
        min_probe_wall_s=0.05 if quick else 0.3,
        budget_s=10.0 if quick else 60.0, save=bool(opt.store_dir()),
        log=log)
    tspl = int(tcfg.knobs["steps_per_launch"])
    jt_tuned = jax.jit(scan_train(tstep, tspl) if tspl > 1 else tstep)
    tsps_launches, trt_tuned = measure_train(
        jt_tuned, p, vel, tx, ty, duration_s, log,
        f"train tuned(spl={tspl})")
    tsps_tuned = tsps_launches * tspl
    train_speedup = tsps_tuned / tsps_default

    # ---- calibration vs the banked TPU corpus --------------------------
    calibration = None
    if not quick:
        from mxnet_tpu.analysis.opt import calibration as cal

        t0 = time.perf_counter()
        samples = cal.corpus(log=log)
        fitted, diag = cal.calibrate_banked(samples=samples)
        table = diag["table"]
        rho = table[0]["spearman_all"] if table else None
        calibration = {
            "n_rows": len(samples),
            "spearman": rho,
            "msle_before": round(diag["before"]["msle"], 4),
            "msle_after": round(diag["after"]["msle"], 4),
            "fitted": {
                "compute_eff": fitted.compute_eff,
                "mem_eff": fitted.mem_eff,
                "fusion_discount": fitted.fusion_discount,
                "launch_overhead_us": fitted.launch_overhead_us,
                "fp32_matmul_rate": round(fitted.fp32_matmul_rate, 4),
            },
            "trace_s": round(time.perf_counter() - t0, 1),
            "table": table,
        }
        log(f"calibration: {len(samples)} banked rows, spearman "
            f"{rho}, msle {diag['before']['msle']:.3f} -> "
            f"{diag['after']['msle']:.3f}")

    retraces_total = (rt_default + rt_rewritten + rt_tuned
                      + trt_default + trt_tuned)
    rec = {
        "metric": "opt_auto_cpu" if model.backend == "cpu"
        else "opt_auto_tpu",
        "value": round(speedup_tuned, 3),
        "unit": "x vs default",
        "quick": quick,
        "device": dev.platform,
        "device_kind": getattr(dev, "device_kind", ""),
        "workload": {
            "kind": "tile-misaligned churny MLP chain",
            "batch": int(x0.shape[0]),
            "layers": len(ws),
            "lint_rules_before": sorted(set(lint_before)),
        },
        "stages": {
            "default_steps_s": round(sps_default, 2),
            "rewritten_steps_s": round(sps_rewritten, 2),
            "tuned_steps_s": round(sps_tuned, 2),
            "speedup_rewritten": round(speedup_rewritten, 3),
            "speedup_tuned": round(speedup_tuned, 3),
        },
        "rewrites": report.to_dict(),
        "oracle": {"equal": oracle["equal"],
                   "n_leaves": oracle["n_leaves"],
                   "leaves": oracle["leaves"]},
        "retraces": retraces_total,
        "tuned": cfg.provenance(),
        "train": {
            "default_steps_s": round(tsps_default, 2),
            "tuned_steps_s": round(tsps_tuned, 2),
            "speedup": round(train_speedup, 3),
            "tuned_knobs": tcfg.knobs,
        },
        "predicted": {
            "default_ms": round(est_default.t_total_s * 1e3, 4),
            "rewritten_ms": round(est_rewritten.t_total_s * 1e3, 4),
            "tile_waste_default": round(est_default.tile_waste, 4),
            "tile_waste_rewritten": round(
                est_rewritten.tile_waste, 4),
        },
        "efficiency": efficiency,
        "calibration": calibration,
        "acceptance": {
            "tuned_ge_1_15x": speedup_tuned >= 1.15,
            "oracle_pass": bool(oracle["equal"]),
            "zero_retraces": retraces_total == 0,
            "spearman_ge_0_8": (
                None if calibration is None
                or calibration["spearman"] is None
                else calibration["spearman"] >= 0.8),
        },
    }
    try:
        from bench import code_rev
        rec["code_rev"] = code_rev()
    except Exception:  # noqa: BLE001
        pass
    text = json.dumps(rec, indent=1)
    print(text)
    if output:
        with open(output, "w") as f:
            f.write(text + "\n")
    if bank and not quick:
        out_path = os.path.join(
            HERE, f"results_opt_{model.backend}.json")
        payload = {"captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                                time.gmtime()),
                   "captured_unix": time.time(), "record": rec}
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=1)
        os.replace(tmp, out_path)
        log(f"banked -> {out_path}")
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="default vs rewritten vs autotuned (mx.analysis.opt)")
    ap.add_argument("--quick", action="store_true",
                    help="seconds-scale tier-1 smoke: small dims, short "
                         "probes, no calibration, no banking")
    ap.add_argument("--duration", type=float, default=3.0,
                    help="timed seconds per stage")
    ap.add_argument("--output", default=None)
    ap.add_argument("--no-bank", action="store_true")
    args = ap.parse_args(argv)
    run(quick=args.quick, output=args.output, bank=not args.no_bank,
        duration_s=args.duration)
    return 0


if __name__ == "__main__":
    sys.exit(main())
