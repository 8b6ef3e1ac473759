#!/usr/bin/env python
"""Weak-scaling efficiency on the virtual device mesh + an ICI model
extrapolating to pod scale (VERDICT r4 item #5; BASELINE.md north star:
>=90% scaling efficiency 8->256 chips).

## What is measured

Data-parallel weak scaling of a real train step (ResNet-18 and an
MLP proxy for the composed transformer block) at dp = 1, 2, 4, 8 on the
8-virtual-device mesh: per-device batch fixed, params replicated, batch
sharded over ``dp`` — GSPMD inserts the gradient all-reduce exactly as
it would on a pod.

## Efficiency on a shared-core virtual mesh

All 8 virtual devices share ONE physical host core, so compute
serializes: a ZERO-overhead sharded program takes N x the single-device
step. The honest virtual-mesh metric is therefore

    eff(N) = N * t(1) / t(N)

which is 1.0 iff sharding+collectives add nothing on top of the
serialized compute. It measures the program overhead the builder
controls (partitioning quality, collective placement), NOT wire time —
wire time is what the ICI model below adds.

## The 8->256 pod model

step(N) = t_compute + t_allreduce(N) with ring all-reduce over ICI:
t_allreduce = 2*(N-1)/N * grad_bytes / ici_bw, reported both unoverlapped
(worst case) and with the backward pass hiding comm (best case, XLA's
latency-hiding scheduler overlaps layer-k grads' all-reduce with
layer-(k-1) backprop. The reference could not overlap under PS-kvstore
without priority tuning; XLA does this by default).

CLI: python benchmark/scaling_bench.py [--output out.json] [--iters 4]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as onp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# v5e: 4 ICI links/chip x ~100 GB/s each in a 2D torus; the per-chip
# bidirectional ring bandwidth usable by one all-reduce is ~2 links.
# (Public "How to Scale Your Model" v5e numbers; conservative.)
ICI_GBPS = 186.0
PEAK_BF16_TFLOPS = 197.0


def log(*a):
    print("[scaling_bench]", *a, file=sys.stderr, flush=True)


def _dp_step_time(make_model, per_dev_batch, n_dev, iters, log,
                  local_stats=True):
    """Steady-state step time of a donated DP train step over an n_dev
    mesh (params replicated, batch sharded).

    ``local_stats=True`` (default) runs the model inside ``shard_map``:
    batch statistics (BatchNorm) are computed PER dp shard and only the
    grads/loss are ``pmean``-ed — the reference's DP semantics (each
    kvstore worker normalizes over its local batch) and how real pods
    train. ``False`` uses plain GSPMD auto-sharding, where BN's batch
    reduction becomes a cross-replica all-reduce (SyncBN) per BN layer —
    semantically different and far chattier."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()[:n_dev]
    mesh = Mesh(onp.array(devs), ("dp",))
    loss_fn, params, make_batch = make_model()
    x_np, y_np = make_batch(per_dev_batch * n_dev)

    repl = NamedSharding(mesh, P())
    shard = NamedSharding(mesh, P("dp"))
    params = jax.device_put(params, repl)
    x = jax.device_put(jnp.asarray(x_np), shard)
    y = jax.device_put(jnp.asarray(y_np), shard)

    lr = 0.05

    if local_stats:
        from jax import shard_map

        def local_step(p, x, y):
            loss, grads = jax.value_and_grad(loss_fn)(p, x, y)
            grads = {k: jax.lax.pmean(g, "dp") for k, g in grads.items()}
            loss = jax.lax.pmean(loss, "dp")
            new_p = {k: v - lr * grads[k] for k, v in p.items()}
            return loss, new_p

        step = shard_map(local_step, mesh=mesh,
                         in_specs=(P(), P("dp"), P("dp")),
                         out_specs=(P(), P()))
    else:
        def step(p, x, y):
            loss, grads = jax.value_and_grad(loss_fn)(p, x, y)
            new_p = {k: v - lr * grads[k] for k, v in p.items()}
            return loss, new_p

    jstep = jax.jit(step, donate_argnums=(0,),
                    in_shardings=(repl, shard, shard),
                    out_shardings=(repl, repl))
    loss, params = jstep(params, x, y)
    float(loss)  # compile + settle
    # MIN over single-step timings: this host is 1 shared core with a
    # probing daemon — the minimum is the uncontended step time, the
    # mean is whatever else ran that second
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        loss, params = jstep(params, x, y)
        float(loss)
        best = min(best, time.perf_counter() - t0)
    log(f"  dp={n_dev}: {best * 1e3:.1f} ms/step (min of {iters})")
    return best


def model_resnet18():
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision

    net = vision.resnet18_v1(classes=100)
    net.initialize()
    probe = mx.np.array(onp.zeros((2, 3, 48, 48), "float32"))
    fn, params = net.functionalize(probe, training=True)

    def loss_fn(p, x, y):
        out, _ = fn(p, x)
        logp = jax.nn.log_softmax(out.astype(jnp.float32))
        return -jnp.take_along_axis(logp, y[:, None], -1).mean()

    def make_batch(total):
        rng = onp.random.RandomState(0)
        return (rng.uniform(size=(total, 3, 48, 48)).astype("float32"),
                rng.randint(0, 100, (total,)).astype("int32"))

    return loss_fn, dict(params), make_batch


def model_mlp_block():
    """Transformer-block proxy (the composed step's MLP shape): two big
    matmuls + gelu, grads all-reduced — the communication:compute ratio
    of the real block without its CPU-hostile attention cost."""
    import jax
    import jax.numpy as jnp

    rng = onp.random.RandomState(0)
    U = 512
    params = {
        "w1": jnp.asarray(rng.standard_normal((U, 4 * U)) * 0.02, jnp.float32),
        "w2": jnp.asarray(rng.standard_normal((4 * U, U)) * 0.02, jnp.float32),
        "wout": jnp.asarray(rng.standard_normal((U, 64)) * 0.02, jnp.float32),
    }

    def loss_fn(p, x, y):
        h = jax.nn.gelu(x @ p["w1"]) @ p["w2"]
        out = h @ p["wout"]
        logp = jax.nn.log_softmax(out)
        return -jnp.take_along_axis(logp, y[:, None], -1).mean()

    def make_batch(total):
        return (rng.standard_normal((total, U)).astype("float32"),
                rng.randint(0, 64, (total,)).astype("int32"))

    return loss_fn, params, make_batch


def weak_scaling(name, make_model, per_dev_batch, iters):
    times = {}
    log(f"{name}: weak scaling, per-device batch {per_dev_batch}")
    for n in (1, 2, 4, 8):
        times[n] = _dp_step_time(make_model, per_dev_batch, n, iters, log)
    effs = {str(n): round(n * times[1] / times[n], 4) for n in times}
    return {"per_device_batch": per_dev_batch,
            "step_ms": {str(n): round(t * 1e3, 2) for n, t in times.items()},
            "efficiency_vs_serialized": effs}


def fixed_work_scaling(name, build_step, iters):
    """t(N) for a FIXED total problem sharded over N devices (tp/sp, the
    strategies the reference lacked entirely — SURVEY §2.3 rows 56/58).
    On the shared-core mesh total compute is constant as N grows, so

        eff(N) = t(1) / t(N)

    which is 1.0 iff partitioning + collectives (psum for Megatron-TP,
    ppermute rings for SP) add nothing over the serialized compute."""
    import jax

    times = {}
    log(f"{name}: fixed-work scaling over 1,2,4,8 devices")
    for n in (1, 2, 4, 8):
        jstep, step_args = build_step(n)
        out = jstep(*step_args)
        jax.block_until_ready(out)  # compile + settle
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            out = jstep(*step_args)
            jax.block_until_ready(out)
            best = min(best, time.perf_counter() - t0)
        times[n] = best
        log(f"  {name} n={n}: {best * 1e3:.1f} ms (min of {iters})")
    effs = {str(n): round(times[1] / times[n], 4) for n in times}
    return {"protocol": "fixed-work: eff(N) = t(1)/t(N)",
            "step_ms": {str(n): round(t * 1e3, 2) for n, t in times.items()},
            "efficiency_vs_serialized": effs}


def build_tp_mlp(n):
    """Megatron-TP transformer MLP block (column-parallel W1, row-parallel
    W2, ONE psum on the output) fwd+bwd at fixed (batch, d_model)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax import shard_map

    d, h, b = 512, 2048, 256
    rng = onp.random.RandomState(0)
    mesh = Mesh(onp.array(jax.devices()[:n]), ("tp",))
    w1 = jax.device_put(jnp.asarray(rng.normal(0, 0.02, (d, h)), jnp.float32),
                        NamedSharding(mesh, P(None, "tp")))
    w2 = jax.device_put(jnp.asarray(rng.normal(0, 0.02, (h, d)), jnp.float32),
                        NamedSharding(mesh, P("tp", None)))
    x = jax.device_put(jnp.asarray(rng.normal(0, 1, (b, d)), jnp.float32),
                       NamedSharding(mesh, P()))

    def local_loss(x, w1, w2):
        y = jax.lax.psum(jax.nn.gelu(x @ w1) @ w2, "tp")
        return jnp.mean(y * y)

    def local_step(x, w1, w2):
        loss, (g1, g2) = jax.value_and_grad(
            local_loss, argnums=(1, 2))(x, w1, w2)
        return loss, g1, g2

    step = shard_map(local_step, mesh=mesh,
                     in_specs=(P(), P(None, "tp"), P("tp", None)),
                     out_specs=(P(), P(None, "tp"), P("tp", None)))
    return jax.jit(step), (x, w1, w2)


def build_sp_ring(n):
    """Ring attention (sequence-parallel, ppermute ring) forward at fixed
    (B, L, H, D) — the long-context strategy SURVEY §5 calls out as
    absent from the reference."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from mxnet_tpu.parallel.ring_attention import ring_self_attention

    B, L, H, D = 2, 2048, 4, 64
    rng = onp.random.RandomState(0)
    mesh = Mesh(onp.array(jax.devices()[:n]), ("sp",))
    shard = NamedSharding(mesh, P(None, "sp"))
    q, k, v = (jax.device_put(
        jnp.asarray(rng.normal(0, 1, (B, L, H, D)), jnp.float32), shard)
        for _ in range(3))

    def fwd(q, k, v):
        out = ring_self_attention(q, k, v, mesh=mesh, causal=True)
        return jnp.sum(out)

    return jax.jit(fwd), (q, k, v)


def pod_model(grad_mbytes, step_compute_ms):
    """Predicted dp weak-scaling efficiency 8..256 chips from the ICI
    ring-all-reduce model, unoverlapped and fully-overlapped bounds."""
    out = {"assumptions": {
        "ici_GBps_per_chip": ICI_GBPS,
        "grad_bytes_mb": grad_mbytes,
        "step_compute_ms": step_compute_ms,
        "algorithm": "ring all-reduce, 2*(N-1)/N * bytes / bw",
        "overlap": "bounds: none vs fully hidden behind backward (~2/3 of step)",
    }, "per_chips": {}}
    for n in (8, 16, 32, 64, 128, 256):
        t_comm = 2 * (n - 1) / n * grad_mbytes * 1e6 / (ICI_GBPS * 1e9) * 1e3
        eff_no = step_compute_ms / (step_compute_ms + t_comm)
        hidden = min(t_comm, step_compute_ms * 2 / 3)
        eff_ov = step_compute_ms / (step_compute_ms + t_comm - hidden)
        out["per_chips"][str(n)] = {
            "allreduce_ms": round(t_comm, 3),
            "efficiency_no_overlap": round(eff_no, 4),
            "efficiency_overlapped": round(eff_ov, 4),
        }
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--output", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "results_scaling_virtual8.json"))
    ap.add_argument("--iters", type=int, default=4)
    ap.add_argument("--skip-resnet", action="store_true")
    args = ap.parse_args()

    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    assert len(jax.devices()) >= 8, "need the 8-virtual-device mesh"

    rec = {"protocol": ("shared-core virtual mesh, two row families: "
                        "dp rows (mlp_block, resnet18) are WEAK scaling, "
                        "eff(N) = N*t(1)/t(N); tp/sp rows (tp_mlp_block, "
                        "sp_ring_attention) are FIXED-WORK scaling, "
                        "eff(N) = t(1)/t(N). Both are 1.0 iff "
                        "partitioning+collectives add nothing over the "
                        "serialized compute (see module docstring)"),
           "n_virtual_devices": 8,
           "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())}
    # sub-second MLP steps need more min-of samples than the 2-23s
    # resnet steps to reject background blips on the shared host
    rec["mlp_block"] = weak_scaling(
        "mlp_block", model_mlp_block, per_dev_batch=256,
        iters=max(10, args.iters))
    rec["mlp_block"]["note"] = (
        "30-300ms steps on the 1-core shared host: high run-to-run "
        "variance (observed 0.79-0.97 at dp=8) even with min-of-N; the "
        "resnet18 row (2.6-23s steps) is the reliable efficiency signal")
    if not args.skip_resnet:
        # per-device batch 16: small batches are sync-latency-bound on
        # the shared-core mesh in a way no real pod is (pods run >=128
        # per chip); 16 is the smallest batch where the conv work
        # dominates the per-step sync cost
        rec["resnet18"] = weak_scaling(
            "resnet18", model_resnet18, per_dev_batch=16, iters=args.iters)
        # fixed-work resnet18 (VERDICT r4 item #9): TOTAL batch fixed at
        # 64 and sharded over N — on the shared core total compute is
        # constant, so eff(N) = t(1)/t(N) isolates partitioning +
        # collective overhead with the conv-heavy real model, free of
        # the weak-scaling protocol's N*t(1) extrapolation
        log("resnet18_fixed_work: fixed-work DP over 1,2,4,8 devices")
        fw_times = {}
        for n in (1, 2, 4, 8):
            fw_times[n] = _dp_step_time(
                model_resnet18, 64 // n, n, args.iters, log)
        rec["resnet18_fixed_work"] = {
            "protocol": "fixed-work DP: total batch 64 sharded over N, "
                        "eff(N) = t(1)/t(N)",
            "step_ms": {str(n): round(t * 1e3, 2)
                        for n, t in fw_times.items()},
            "efficiency_vs_serialized": {
                str(n): round(fw_times[1] / fw_times[n], 4)
                for n in fw_times},
        }
    # the dryrun's own probe shape, captured IN THIS SAME RUN so the
    # committed curve and the in-dryrun number can be reconciled: one
    # min-of-3 single-shot of the mlp proxy (what __graft_entry__ logs,
    # the source of the round-4 "0.851" reading)
    p1 = _dp_step_time(model_mlp_block, 64, 1, 3, log)
    p8 = _dp_step_time(model_mlp_block, 64, 8, 3, log)
    rec["dryrun_style_probe"] = {
        "protocol": "min-of-3 single-shot mlp weak probe, the "
                    "__graft_entry__ dryrun tail shape",
        "eff_dp8": round(8 * p1 / p8, 4),
        "step_ms": {"1": round(p1 * 1e3, 2), "8": round(p8 * 1e3, 2)},
    }
    rec["which_number_to_trust"] = (
        "Trust the resnet18 WEAK-scaling row for the 'does sharding add "
        "overhead' question: conv-dominated 2-23s steps, min-of-N timing, "
        "dp8 eff 0.95-1.01 across clean captures. The lower numbers are "
        "real but answer a different question: fixed-work dp8 (0.85) and "
        "the dryrun-style mlp probe (0.82, the round-4 '0.851' reading) "
        "shrink per-device work until per-step partition/sync overhead is "
        "a visible fraction — on a 1-core host that overhead is paid "
        "serially, which no pod does. So: weak-scaling resnet = the "
        "committed efficiency claim; fixed-work/probe rows = the overhead "
        "floor at small per-device work; 8+ real chips = the analytic ICI "
        "model (pod_model_resnet50), assumptions stated inline.")
    # fixed-work scaling of the strategies the reference lacked: TP
    # (Megatron MLP, one psum) and SP (ring attention, ppermute ring) —
    # eff(N) = t(1)/t(N) since total compute is constant
    rec["tp_mlp_block"] = fixed_work_scaling(
        "tp_mlp_block", build_tp_mlp, iters=max(10, args.iters))
    rec["sp_ring_attention"] = fixed_work_scaling(
        "sp_ring_attention", build_sp_ring, iters=max(10, args.iters))
    rec["sp_ring_attention"]["note"] = (
        "eff > 1 is a shared-core cache artifact: n=1 materializes one "
        "(2048, 2048) f32 score block (16 MB, spills L2), n=8 works in "
        "(256, 256) blocks; on a real pod the ring's ppermute wire time "
        "replaces this win. The signal is that ring overhead does NOT "
        "degrade t(N) as rounds grow 1 -> 8.")

    # pod model anchored on the banked single-chip ResNet-50 bf16 train
    # step (falls back to the r3 number if no artifact)
    grad_mb = 25.6 * 2  # ResNet-50 grads in bf16
    step_ms = 21.3
    try:
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "results_train_tpu.json")) as f:
            for row in json.load(f).get("results", []):
                if row.get("model") == "resnet50_v1" \
                        and row.get("precision") == "bf16" \
                        and row.get("train_img_s"):
                    step_ms = row["batch"] / row["train_img_s"] * 1e3
    except Exception:  # noqa: BLE001 — keep the fallback anchor
        pass
    rec["pod_model_resnet50"] = pod_model(grad_mb, round(step_ms, 2))

    text = json.dumps(rec, indent=2)
    head = rec.get("resnet18") or rec["mlp_block"]  # conv train step is
    print(json.dumps({"metric": "weak_scaling_dp8_efficiency",  # the north star
                      "value": head["efficiency_vs_serialized"]["8"],
                      "unit": "eff", "device": "cpu_virtual8"}), flush=True)
    with open(args.output, "w") as f:
        f.write(text + "\n")
    log(f"wrote {args.output}")


if __name__ == "__main__":
    main()
