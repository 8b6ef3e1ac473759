"""Headline benchmark: ResNet-50 inference throughput, batch 32.

Baselines (BASELINE.md / reference docs perf.md): 2085.51 img/s V100
**fp16** bs32 (perf.md:202-216) — the reference's reduced-precision
headline, the apples-to-apples peer of TPU-native bf16 — and 1076.81
img/s V100 fp32 (perf.md:186-198). Prints exactly ONE JSON line on
stdout with the bf16 result as the headline metric and the fp32 run
as secondary fields:
    {"metric": ..., "value": N, "unit": "img/s", "vs_baseline": N,
     "fp32_img_s": N, "fp32_vs_baseline": N}

The measurement runs once, on the chip, in a child process: the parent
never imports jax, so the child is the one process that holds the chip,
and a child that hangs can be timed out. Without a chip, or when the
child fails, nothing is printed on stdout and the exit code is non-zero:
there is no retry, no banked result and no CPU stand-in. Diagnostics go
to stderr only.

MFU: TPU records carry ``model_gflops_per_img`` (XLA cost analysis of
the compiled step), ``achieved_tflops``, and ``mfu`` (achieved vs the
chip's bf16 peak — the per-chip-efficiency north star, VERDICT round-2
weak #7).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

BASELINE_FP16_IMG_S = 2085.51  # ResNet-50 fp16 inference bs32, V100 (perf.md:202-216)
BASELINE_FP32_IMG_S = 1076.81  # ResNet-50 fp32 inference bs32, V100 (perf.md:186-198)


METRIC = "resnet50_v1_infer_bs32_bf16"
CHILD_TIMEOUT_S = 900

# bf16 MXU peak TFLOP/s by device_kind substring (public TPU specs); used
# for the MFU field. Unknown kinds report mfu=null rather than guessing.
PEAK_BF16_TFLOPS = {
    "v5 lite": 197.0, "v5e": 197.0,   # v5e
    "v5p": 459.0,
    "v4": 275.0,
    "v3": 123.0,
    "v2": 46.0,
    "v6": 918.0,                       # trillium
}


def peak_bf16_tflops(device_kind: str):
    kind = device_kind.lower()
    for sub, peak in PEAK_BF16_TFLOPS.items():
        if sub in kind:
            return peak
    return None


def log(*a):
    print("[bench]", *a, file=sys.stderr, flush=True)


def code_rev() -> str:
    """Short git HEAD of the repo at measurement time. Banked rows carry
    this (VERDICT r4 item #10) so 'which code produced this number' is a
    field, not an archaeology exercise."""
    here = os.path.dirname(os.path.abspath(__file__))
    try:
        rev = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=here,
            capture_output=True, text=True, timeout=10).stdout.strip() or "?"
        dirty = subprocess.run(
            ["git", "status", "--porcelain", "--untracked-files=no"],
            cwd=here, capture_output=True, text=True, timeout=10).stdout
        return rev + ("+dirty" if dirty.strip() else "")
    except Exception:  # noqa: BLE001 — provenance must never kill a bench
        return "?"


def jaxpr_flops(fn, *args) -> float:
    """Model FLOPs of one call by walking the jaxpr: 2*MACs over every
    dot_general and conv_general_dilated (the MFU convention — matmul/
    conv work, elementwise excluded). Pure tracing: no compile, no
    backend.

    Traced with the stem space-to-depth rewrite DISABLED: the rewrite
    executes extra zero-taps (ops/nn.py:_stem_space_to_depth), and MFU
    must charge the model's algorithmic FLOPs, not the lowering's."""
    import jax
    import math

    def eqn_flops(eqn):
        prim = eqn.primitive.name
        if prim == "dot_general":
            (lc, rc), (lb, _rb) = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            rhs = eqn.invars[1].aval.shape
            batch = math.prod(lhs[d] for d in lb)
            contract = math.prod(lhs[d] for d in lc)
            lhs_free = math.prod(
                d for i, d in enumerate(lhs) if i not in set(lc) | set(lb))
            rhs_free = math.prod(
                d for i, d in enumerate(rhs)
                if i not in set(rc) | set(_rb))
            return 2.0 * batch * contract * lhs_free * rhs_free
        if prim == "conv_general_dilated":
            out = eqn.outvars[0].aval.shape
            rhs = eqn.invars[1].aval.shape
            dn = eqn.params["dimension_numbers"]
            kernel_spatial = math.prod(rhs[d] for d in dn.rhs_spec[2:])
            in_per_group = rhs[dn.rhs_spec[1]]
            return 2.0 * math.prod(out) * kernel_spatial * in_per_group
        return 0.0

    def sub_flops(sub):
        if hasattr(sub, "jaxpr"):      # ClosedJaxpr
            return walk(sub.jaxpr)
        if hasattr(sub, "eqns"):       # raw Jaxpr
            return walk(sub)
        return 0.0

    def walk(jaxpr):
        total = 0.0
        for eqn in jaxpr.eqns:
            total += eqn_flops(eqn)
            prim = eqn.primitive.name
            if prim == "cond":
                # one branch executes per call — charge the heaviest
                total += max((sub_flops(b)
                              for b in eqn.params.get("branches", ())),
                             default=0.0)
                continue
            # a scan body executes `length` times; everything else that
            # carries a subjaxpr (pjit, custom_vjp, while — trip count
            # unknowable statically, counted once) runs it once per call
            mult = eqn.params.get("length", 1) if prim == "scan" else 1
            for v in eqn.params.values():
                vs = v if isinstance(v, (list, tuple)) else [v]
                for sub in vs:
                    total += mult * sub_flops(sub)
        return total

    prev = os.environ.get("MXNET_TPU_STEM_S2D")
    os.environ["MXNET_TPU_STEM_S2D"] = "0"
    try:
        # unwrap a jitted fn AND re-wrap in a fresh function object:
        # jax's trace cache is keyed on (fn identity, avals) — not on the
        # knob — so tracing the same object again would return a jaxpr
        # traced under the other knob state (measured: it does)
        inner = getattr(fn, "__wrapped__", fn)

        def fresh(*a):
            return inner(*a)

        return walk(jax.make_jaxpr(fresh)(*args).jaxpr)
    finally:
        if prev is None:
            os.environ.pop("MXNET_TPU_STEM_S2D", None)
        else:
            os.environ["MXNET_TPU_STEM_S2D"] = prev


def finite_barrier(val, what="barrier value"):
    """Fetch-barrier with a finiteness check: every bench ends its
    timing with a host fetch of a scalar the serially-chained work feeds
    into — asserting it is finite makes each banked number ALSO evidence
    that the measured math worked. Added after the quant bench was found
    timing an all-NaN forward at full speed without noticing (the padded
    max-pool bf16 overflow, 2026-08-02): NaN propagates through the
    chain silently, float() doesn't raise, and a throughput row banked
    from NaN math is worse than no row."""
    import math

    f = float(val)
    if not math.isfinite(f):
        raise RuntimeError(
            f"non-finite {what} ({f}): the measured computation is "
            "producing NaN/inf — refusing to bank a throughput of "
            "broken math")
    return f


_WINDOW_CONTROL = {"tflops": None}


def window_control_tflops(refresh=False):
    """Same-window effective-peak control, memoized per process: TFLOPs
    of 16 serially-chained 8192^3 bf16 matmuls in ONE executable
    (peak_probe.chained_matmul_rate), so that a row's `mfu` against the
    nominal peak can be read beside what the same chip delivered on a
    plain matmul in the same run. Children stamp rows via
    stamp_window_control(); `mfu_effective` = achieved / same-run
    control. ``refresh=True`` re-measures. Returns None off-TPU or on
    failure."""
    if refresh:
        _WINDOW_CONTROL["tflops"] = None
    if _WINDOW_CONTROL["tflops"] is None:
        try:
            import jax

            if jax.devices()[0].platform != "tpu":
                _WINDOW_CONTROL["tflops"] = False
            else:
                from benchmark.peak_probe import chained_matmul_rate

                tf, _ = chained_matmul_rate(8192, 16, runs=2)
                _WINDOW_CONTROL["tflops"] = round(tf, 1)
        except Exception:  # noqa: BLE001 — control is supplemental
            _WINDOW_CONTROL["tflops"] = False
    return _WINDOW_CONTROL["tflops"] or None


def stamp_window_control(rec):
    """Attach `window_control_tflops` (+ `mfu_effective` where the row
    has bf16 achieved_tflops) to one measured row, in place. Call AFTER
    the row's own measurement so the ~1-2s control never competes with
    it for the chip."""
    ctl = window_control_tflops()
    if not ctl:
        return rec
    rec["window_control_tflops"] = ctl
    ach = rec.get("achieved_tflops")
    # 0.0 is a real (maximally broken) value, not missing
    if ach is not None and rec.get("precision", "bf16") == "bf16":
        rec["mfu_effective"] = round(ach / ctl, 4)
    return rec


def cast_params_bf16(p):
    """The bench AMP pattern shared by every harness (bench.py,
    train_bench, llm_bench, profile_bench): fp32 master weights with an
    in-graph bf16 cast, whose HBM cost is part of what the benches
    measure. ONE definition so an AMP-policy change can't silently fork
    one harness's numerics from the profile that claims to decompose
    it."""
    import jax.numpy as jnp

    return {k: v.astype(jnp.bfloat16) if v.dtype == jnp.float32 else v
            for k, v in p.items()}


def child(batch: int = 32) -> None:
    """Measure in-process, on the chip, and print one JSON line. May
    crash/hang — the parent handles that. ``batch`` other than 32 is the
    supplemental large-batch exhibit (the driver contract stays bs32);
    its metric name carries the batch and vs_baseline still divides by
    the bs32 V100 rows (the only published reference numbers)."""
    batch = int(batch)
    import jax
    import jax.numpy as jnp
    import numpy as onp

    t0 = time.time()
    devs = jax.devices()
    log(f"backend up in {time.time() - t0:.1f}s: {devs}")
    if devs[0].platform != "tpu":
        sys.exit(f"bench.py measures on the chip; jax found {devs}")

    import mxnet_tpu as mx
    from mxnet_tpu.base import arm_compile_cache
    from mxnet_tpu.gluon.model_zoo import vision

    log(f"compile cache: {arm_compile_cache()}")

    net = vision.resnet50_v1(classes=1000)
    net.initialize()
    x_np = onp.random.uniform(size=(batch, 3, 224, 224)).astype(onp.float32)
    fn, params = net.functionalize(mx.np.array(x_np), training=False)

    # serially-chained steps per launch (see step_k)
    SCAN_STEPS = 16

    def measure(params, x_host, dtype, want_flops=True):
        """Throughput of a serially-chained forward at the given dtype."""

        def step(params, x):
            logits, _ = fn(params, x)
            # fold the output back into the next input: forces a true
            # serial dependency chain so no dispatch/caching layer can
            # elide work
            perturb = jnp.tanh(jnp.mean(logits)) * 1e-6
            return logits, x * (1.0 + perturb).astype(x.dtype)

        def step_k(params, x):
            # the chain, run SCAN_STEPS at a time inside ONE executable,
            # so that per-launch dispatch is amortized over the chain.
            # Math and serial dependency are unchanged: each forward
            # feeds the next input, and the returned last chained sum
            # cannot exist until every step ran.
            def body(cx, _):
                logits, nx = step(params, cx)
                return nx, jnp.sum(logits.astype(jnp.float32))
            x, sums = jax.lax.scan(body, x, None, length=SCAN_STEPS)
            return sums[-1], x

        jstep = jax.jit(step_k)
        x = jnp.asarray(x_host, dtype)
        t0 = time.time()
        out0, xw = jstep(params, x)
        # measurement protocol: a device->host scalar fetch of the
        # chain's final value is the barrier — the value cannot exist
        # until every step in the serial chain ran. Warm the sum-fetch
        # over BOTH output shapes so calibration pays no first-compile
        # cost.
        float(jnp.sum(xw))
        float(jnp.sum(out0))
        log(f"{dtype.__name__}: compiled + warm in {time.time() - t0:.1f}s")

        # calibrate pass size from one launch (the timing includes a host
        # round-trip, so it overestimates per-launch cost — fine for
        # sizing), then accumulate passes until >=5s of steady-state has
        # elapsed so a single fetch round-trip can't dominate the window
        t0 = time.perf_counter()
        out, x = jstep(params, x)
        float(jnp.sum(out))
        per_launch = max(time.perf_counter() - t0, 1e-4)
        # floor: at least ~8 chained steps per pass so a pass is never a
        # 2-sample measurement, whatever SCAN_STEPS is
        pass_iters = max(-(-8 // SCAN_STEPS),
                         min(200, int(10.0 / per_launch)))
        max_launches = max(1, 3000 // SCAN_STEPS)

        total_launches, total_dt = 0, 0.0
        while total_dt < 5.0 and total_launches < max_launches:
            t0 = time.perf_counter()
            for _ in range(pass_iters):
                out, x = jstep(params, x)
            finite_barrier(jnp.sum(out), "headline chain output")
            total_dt += time.perf_counter() - t0
            total_launches += pass_iters
        total_iters = total_launches * SCAN_STEPS
        img_s = batch * total_iters / total_dt
        log(f"{dtype.__name__}: {img_s:.1f} img/s over {total_iters} steps "
            f"({total_launches} launches, {total_dt:.1f}s)")

        # Model FLOPs of one step, the basis for the MFU field: matmul/
        # conv MACs counted from the jaxpr with the stem rewrite pinned
        # off (``step`` is the single forward, so this is per step).
        # XLA's cost_analysis is not used: it counts a lax.scan body
        # once whatever the trip count, and it counts the stem
        # space-to-depth rewrite's zero-taps, which MFU must not charge.
        step_flops = None
        if want_flops:
            step_flops = jaxpr_flops(step, params, x)
            log(f"flops via jaxpr walk: {step_flops/1e9:.2f} GF/step")
        return img_s, total_iters, step_flops

    # headline: bf16, the TPU-native precision (the reference's headline
    # reduced-precision number is V100 fp16, perf.md:202-216); fp32 kept
    # as a secondary field against the fp32 baseline (perf.md:186-198).
    # explicit fp32 matmul policy for the secondary fp32 row: "high"
    # (bf16_3x — above-TF32 mantissa coverage, the accepted fp32-class on
    # tensor hardware) unless overridden; recorded in the artifact. Set
    # ONLY around the fp32 measurement — a process-wide HIGHEST would
    # force f32 math into the bf16 headline convs too. The package
    # default is the one-pass MXU precision (docs/precision.md).
    fp32_prec = os.environ.get("MXNET_BENCH_FP32_PRECISION", "high")
    p_bf16 = cast_params_bf16(params)
    bf16_img_s, bf16_iters, flops = measure(p_bf16, x_np, jnp.bfloat16)
    with jax.default_matmul_precision(fp32_prec):
        fp32_img_s, fp32_iters, _ = measure(params, x_np, jnp.float32,
                                            want_flops=False)
    rec = {
        "metric": METRIC if batch == 32 else
                  f"resnet50_v1_infer_bs{batch}_bf16",
        "value": round(bf16_img_s, 2),
        "unit": "img/s",
        "vs_baseline": round(bf16_img_s / BASELINE_FP16_IMG_S, 3),
        "fp32_img_s": round(fp32_img_s, 2),
        "fp32_vs_baseline": round(fp32_img_s / BASELINE_FP32_IMG_S, 3),
        "device": str(devs[0].platform),
        "device_kind": getattr(devs[0], "device_kind", ""),
        "bf16_iters": bf16_iters,
        "fp32_iters": fp32_iters,
        "steps_per_launch": SCAN_STEPS,  # lax.scan serial chain per launch
        "fp32_matmul_precision": fp32_prec,
        "code_rev": code_rev(),
    }
    try:  # batch-matched published rows (shared table) override the
        from benchmark.baselines import attach_headline_ratios  # bs32 ones
        attach_headline_ratios(rec, batch)
    except Exception:  # noqa: BLE001 — never let ratios kill the bench
        pass
    if flops:
        gflops_img = flops / batch / 1e9
        achieved = bf16_img_s * gflops_img / 1e3  # TFLOP/s
        rec["model_gflops_per_img"] = round(gflops_img, 2)
        rec["achieved_tflops"] = round(achieved, 2)
        peak = peak_bf16_tflops(rec["device_kind"])
        if peak:
            rec["peak_bf16_tflops"] = peak
            rec["mfu"] = round(achieved / peak, 4)
            # same-window effective-peak control (after all measurement)
            stamp_window_control(rec)
    print(json.dumps(rec), flush=True)


def parse_json_output(text: str):
    """LAST parseable JSON object in ``text`` — single- or multi-line,
    tolerating log noise around it."""
    dec = json.JSONDecoder()
    obj = None
    idx = text.find("{")
    while idx != -1:
        try:
            obj, end = dec.raw_decode(text, idx)
            idx = text.find("{", end)
        except json.JSONDecodeError:
            idx = text.find("{", idx + 1)
    return obj


def main() -> int:
    """One attempt, on the chip; the parent stays off jax."""
    log(f"measuring in a child, timeout {CHILD_TIMEOUT_S}s")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child"],
        stdout=subprocess.PIPE, text=True, timeout=CHILD_TIMEOUT_S)
    rec = parse_json_output(proc.stdout)
    if proc.returncode != 0 or rec is None or rec.get("value", 0) <= 0:
        log(f"no measurement: child exited {proc.returncode}")
        return proc.returncode or 1
    print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        child(int(sys.argv[2]) if len(sys.argv) > 2 else 32)
    else:
        sys.exit(main())
