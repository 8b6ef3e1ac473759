#!/usr/bin/env python
"""Model training-throughput benchmark (reference ``perf.md:246-257``
training table: ResNet-50 298.51 img/s, Inception-v3 214.48 img/s,
AlexNet 2585.61 img/s — V100 fp32 bs32, train_imagenet.py era).

Measures img/s of a full training step (forward + backward + SGD-momentum
update) on the current device, per model and precision. The step is the
framework's idiomatic TPU training program: ``HybridBlock.functionalize``
forward, ``jax.value_and_grad``, and the optimizer update fused into ONE
jitted XLA executable with donated weights/states — the same design
``gluon.Trainer`` compiles (mxnet_tpu/gluon/trainer.py:137). Steps
serialize naturally (each consumes the previous step's weights), so
throughput needs no artificial dependency chain; a scalar loss fetch at
the end of each pass is the completion barrier.

bf16 rows use the AMP pattern: bf16 compute with fp32 master weights
(multi-precision, reference optimizer.py multi_precision semantics).

CLI:
    python benchmark/train_bench.py [--models resnet50_v1,...] [--batch 32]
                                    [--output results.json] [--cpu]
Emits one JSON object per (model, precision) with img/s and the matching
reference-baseline ratio where one exists.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as onp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The reference's published V100 rows (perf.md via BASELINE.md) live in
# ONE shared table so ratios are computed identically everywhere and the
# gate test can enforce coverage (benchmark/baselines.py).
from benchmark.baselines import (attach_infer_ratios,  # noqa: E402
                                 attach_row_analysis, attach_train_ratios)
from bench import finite_barrier  # noqa: E402 — NaN-refusing fetch barrier


def build_step(net_name, batch, dtype_name, seq_len=128, scan_steps=1):
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx

    if net_name.startswith("bert"):
        # BERT pretraining step (MLM over all positions + NSP), seq 128 —
        # the BASELINE stretch-goal config (SURVEY §7.8)
        from mxnet_tpu.gluon.model_zoo import bert as bert_zoo

        core = getattr(bert_zoo, net_name)(dropout=0.0)
        net = bert_zoo.BERTForPretraining(core)
        net.initialize()
        x_np = onp.random.randint(0, 30522, (batch, seq_len)).astype(onp.int32)
        y_np = x_np.copy()  # MLM labels; throughput is label-agnostic
        fn, params = net.functionalize(mx.np.array(x_np), training=True)
    else:
        from mxnet_tpu.gluon.model_zoo import vision

        net = getattr(vision, net_name)(classes=1000)
        net.initialize()
        size = 299 if "inception" in net_name else 224
        x_np = onp.random.uniform(
            size=(batch, 3, size, size)).astype(onp.float32)
        y_np = onp.random.randint(0, 1000, size=(batch,)).astype(onp.int32)
        fn, params = net.functionalize(mx.np.array(x_np), training=True)

    compute_dtype = jnp.bfloat16 if dtype_name == "bf16" else jnp.float32
    momentum, lr = 0.9, 0.05
    velocity = {k: jnp.zeros_like(v) for k, v in params.items()
                if v.dtype == jnp.float32}

    def loss_fn(p, x, y, key):
        if compute_dtype != jnp.float32:
            # AMP multi-precision: fp32 master weights, bf16 compute; the
            # in-graph cast makes grads flow back to the fp32 masters
            pc = {k: v.astype(compute_dtype) if v.dtype == jnp.float32 else v
                  for k, v in p.items()}
            x = x.astype(compute_dtype)
        else:
            pc = p
        out, state = fn(pc, x, key=key)
        logits = out[0] if isinstance(out, tuple) else out  # BERT: (mlm, nsp)
        # forward-mutated state (BN running stats) back in master precision
        state = {k: s.astype(p[k].dtype) for k, s in state.items()}
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        nll = -jnp.take_along_axis(logp, y[..., None], axis=-1).mean()
        return nll, state

    def train_step(p, vel, x, y, key):
        (loss, state), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(p, x, y, key)
        new_p, new_v = {}, {}
        for k, s in state.items():
            if k in vel:  # fp32 learnable (BN stats get zero grads anyway)
                v = momentum * vel[k] + grads[k].astype(jnp.float32)
                new_v[k] = v
                new_p[k] = s - lr * v
            else:
                new_p[k] = s
        return new_p, new_v, loss

    if scan_steps > 1:
        # K serially-chained steps inside ONE executable (lax.scan over
        # the params/velocity carry): the math is identical to K single
        # launches — verified step-for-step on CPU — but per-launch
        # dispatch cost is paid once per K steps. The chain and the
        # scalar-fetch barrier survive: the fetched loss is the last
        # step's, which cannot exist until every prior step ran.
        def train_step_k(p, vel, x, y, key):
            def body(carry, _):
                cp, cv = carry
                cp, cv, loss = train_step(cp, cv, x, y, key)
                return (cp, cv), loss
            (p, vel), losses = jax.lax.scan(
                body, (p, vel), None, length=scan_steps)
            return p, vel, losses[-1]

        jstep = jax.jit(train_step_k, donate_argnums=(0, 1))
    else:
        jstep = jax.jit(train_step, donate_argnums=(0, 1))
    return jstep, params, velocity, jnp.asarray(x_np), jnp.asarray(y_np)


def build_infer_step(net_name, batch, dtype_name, scan_steps=1):
    """Serial-chained inference step (bench.py protocol: the output
    perturbs the next input so no dispatch layer can elide work).
    With scan_steps>1, the chain runs inside ONE executable (lax.scan
    over the perturbed-input carry) so per-launch dispatch cost is
    amortized K-fold; the returned chain value still depends on every
    step."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision

    net = getattr(vision, net_name)(classes=1000)
    net.initialize()
    size = 299 if "inception" in net_name else 224
    x_np = onp.random.uniform(size=(batch, 3, size, size)).astype(onp.float32)
    fn, params = net.functionalize(mx.np.array(x_np), training=False)
    dt = jnp.bfloat16 if dtype_name == "bf16" else jnp.float32
    if dt != jnp.float32:
        params = {k: v.astype(dt) if v.dtype == jnp.float32 else v
                  for k, v in params.items()}

    def step(p, x):
        logits, _ = fn(p, x)
        perturb = jnp.tanh(jnp.mean(logits)) * 1e-6
        return logits, x * (1.0 + perturb).astype(x.dtype)

    if scan_steps > 1:
        def step_k(p, x):
            def body(cx, _):
                logits, nx = step(p, cx)
                return nx, jnp.sum(logits.astype(jnp.float32))
            x, sums = jax.lax.scan(body, x, None, length=scan_steps)
            # the last chained sum is the barrier value: it cannot exist
            # until all K forwards (each feeding the next input) ran
            return sums[-1], x

        return jax.jit(step_k), params, jnp.asarray(x_np, dt)
    return jax.jit(step), params, jnp.asarray(x_np, dt)


def measure_infer(net_name, batch, dtype_name, log, scan_steps=1):
    import jax.numpy as jnp

    jstep, p, x = build_infer_step(net_name, batch, dtype_name,
                                   scan_steps=scan_steps)
    t0 = time.time()
    out, x = jstep(p, x)
    float(jnp.sum(x))
    float(jnp.sum(out))
    log(f"{net_name}/{dtype_name}: compiled in {time.time() - t0:.1f}s")

    t0 = time.perf_counter()
    out, x = jstep(p, x)
    float(jnp.sum(out))
    per = max(time.perf_counter() - t0, 1e-4)
    max_launches = max(1, 3000 // scan_steps)
    # floor: >=8 chained steps per pass regardless of scan_steps
    pass_iters = max(-(-8 // scan_steps), min(200, int(5.0 / per)))

    total_launches, total_dt = 0, 0.0
    while total_dt < 5.0 and total_launches < max_launches:
        t0 = time.perf_counter()
        for _ in range(pass_iters):
            out, x = jstep(p, x)
        finite_barrier(jnp.sum(out), "infer chain output")
        total_dt += time.perf_counter() - t0
        total_launches += pass_iters
    total_iters = total_launches * scan_steps
    img_s = batch * total_iters / total_dt
    rec = {"model": net_name, "precision": dtype_name, "batch": batch,
           "steps": total_iters, "steps_per_launch": scan_steps,
           "infer_img_s": round(img_s, 2)}
    log(f"{net_name}/{dtype_name}: {img_s:.1f} img/s inference "
        f"({total_iters} steps, {total_dt:.1f}s)")
    attach_infer_ratios(rec)
    attach_row_analysis(rec)
    return rec


def measure(net_name, batch, dtype_name, log, scan_steps=1):
    import jax
    import jax.numpy as jnp

    jstep, p, vel, x, y = build_step(net_name, batch, dtype_name,
                                     scan_steps=scan_steps)
    key = jax.random.PRNGKey(0)
    # FLOPs via the jaxpr MAC walk (bench.py convention: 2*MACs over
    # dot/conv, elementwise excluded — keeps mfu comparable across
    # artifacts). Pure tracing, no backend. The walk multiplies scan
    # bodies by trip count, so this is K steps' worth when scan_steps>1
    # — divided back below.
    launch_flops = None
    try:
        from bench import jaxpr_flops
        launch_flops = jaxpr_flops(jstep, p, vel, x, y, key)
    except Exception as e:  # noqa: BLE001
        log(f"jaxpr flop walk failed: {e!r}")
    t0 = time.time()
    p, vel, loss = jstep(p, vel, x, y, key)
    float(loss)
    log(f"{net_name}/{dtype_name}: compiled in {time.time() - t0:.1f}s")

    t0 = time.perf_counter()
    p, vel, loss = jstep(p, vel, x, y, key)
    float(loss)
    per = max(time.perf_counter() - t0, 1e-4)
    max_launches = max(1, 1500 // scan_steps)
    # floor: >=8 chained steps per pass regardless of scan_steps
    pass_iters = max(-(-8 // scan_steps), min(100, int(5.0 / per)))

    total_launches, total_dt = 0, 0.0
    while total_dt < 5.0 and total_launches < max_launches:
        t0 = time.perf_counter()
        for _ in range(pass_iters):
            p, vel, loss = jstep(p, vel, x, y, key)
        finite_barrier(loss, "train loss")
        total_dt += time.perf_counter() - t0
        total_launches += pass_iters
    total_iters = total_launches * scan_steps
    img_s = batch * total_iters / total_dt
    step_flops = launch_flops / scan_steps if launch_flops else None
    rec = {"model": net_name, "precision": dtype_name, "batch": batch,
           "steps": total_iters, "steps_per_launch": scan_steps}
    if net_name.startswith("bert"):
        rec["train_seq_s"] = round(img_s, 2)
        rec["train_tok_s"] = round(img_s * 128, 1)
        log(f"{net_name}/{dtype_name}: {img_s:.1f} seq/s "
            f"({total_iters} steps, {total_dt:.1f}s)")
    else:
        rec["train_img_s"] = round(img_s, 2)
        log(f"{net_name}/{dtype_name}: {img_s:.1f} img/s "
            f"({total_iters} steps, {total_dt:.1f}s)")
    attach_train_ratios(rec)
    if step_flops:
        from bench import peak_bf16_tflops
        achieved = img_s / batch * step_flops / 1e12
        rec["flops_per_step"] = step_flops
        rec["flops_source"] = "jaxpr_walk_2mac"
        rec["achieved_tflops"] = round(achieved, 2)
        dev = jax.devices()[0]
        peak = peak_bf16_tflops(getattr(dev, "device_kind", ""))
        if peak and dtype_name == "bf16" and dev.platform == "tpu":
            rec["peak_bf16_tflops"] = peak
            rec["mfu"] = round(achieved / peak, 4)
        # online gauges: the same throughput/MFU lands in the telemetry
        # registry (telemetry_examples_per_s / telemetry_mfu), making
        # the one-shot bench anchor a continuously observed number
        try:
            from mxnet_tpu import telemetry
            rec["efficiency"] = telemetry.mfu.observe_step(
                f"{net_name}_train_{dtype_name}", batch * total_iters,
                total_dt, flops=step_flops / batch,
                device_kind=getattr(dev, "device_kind", ""))
        except Exception as e:  # noqa: BLE001 — gauges never fail a row
            log(f"telemetry gauges skipped: {e!r}")
    attach_row_analysis(rec)
    return rec


def measure_recordio_train(net_name, batch, dtype_name, log, n_images=512,
                           io_engine="sharded"):
    """Train-step throughput fed from REAL RecordIO JPEG bytes, next to
    the same step on synthetic device-resident data — the input-pipeline
    overhead number (VERDICT r4 item #4: overhead <10% of the synthetic
    row).

    ``io_engine='legacy'``: the PR-before-this pipeline (one C++ decode
    process + double buffer). ``'sharded'``: the full ingestion engine —
    multi-process sharded decode at a padded canvas, decoded-batch epoch
    cache (epoch 1 banks, epoch 2+ stream at memory bandwidth), random-
    resized-crop + flip ON-DEVICE inside the jitted step (stateless
    (epoch, batch, sample) keys), pad_last static shapes, and depth-3
    device staging whose starved-time counter lands in the row — so a
    starved step says WHERE it starved, not just that it did."""
    import tempfile

    import jax
    import jax.numpy as jnp

    from mxnet_tpu import recordio
    from mxnet_tpu.image import augment_key, random_resized_crop_flip
    from mxnet_tpu.io import (CachedImagePipeline, DevicePrefetch,
                              NativeImagePipeline, ShardedImagePipeline)

    jstep, p, vel, x_syn, y_syn = build_step(net_name, batch, dtype_name)
    size = int(x_syn.shape[-1])
    key = jax.random.PRNGKey(0)
    sharded = io_engine == "sharded"
    # cache canvas: modest headroom above the train crop so the
    # on-device random crop has pixels to cut from (a full
    # canvas_for(min_area=0.08) would be 3.5x — the ImageNet convention
    # is ~256 for 224 and upscale the rare tiny crop)
    canvas = ((int(size * 1.15) + 7) // 8) * 8 if sharded else size

    def step_from_u8(p, vel, raw, y, key):
        # on-device input transform: one fused op, not a host pass
        x = raw.astype(jnp.float32).transpose(0, 3, 1, 2) / 255.0
        return jstep(p, vel, x, y, key)

    def step_from_canvas(p, vel, raw, y, epoch, bidx):
        # on-device augment: random-resized-crop + flip fused INTO the
        # train step, keyed statelessly on (epoch, batch, sample)
        akey = augment_key(0, epoch, bidx)
        x = random_resized_crop_flip(raw, akey, (size, size)) / 255.0
        return jstep(p, vel, x.transpose(0, 3, 1, 2), y, key)

    jstep_u8 = jax.jit(step_from_u8, donate_argnums=(0, 1))
    jstep_aug = jax.jit(step_from_canvas, donate_argnums=(0, 1))

    import shutil

    tmpd = tempfile.mkdtemp(prefix="train_rec_")
    stats = {}
    try:
        rng = onp.random.RandomState(0)
        rec_path = os.path.join(tmpd, "train.rec")
        rec = recordio.MXRecordIO(rec_path, "w")
        for i in range(n_images):
            im = rng.randint(0, 255, (480, 640, 3)).astype(onp.uint8)
            rec.write(recordio.pack_img(
                recordio.IRHeader(0, float(i % 1000), i, 0), im,
                quality=85))
        rec.close()
        log(f"packed {n_images} jpegs -> {rec_path}")

        if sharded:
            try:
                workers = max(2, min(4, len(os.sched_getaffinity(0))))
            except AttributeError:
                workers = 4
            cache_dir = os.path.join(tmpd, "iocache")
            engine_desc = (f"sharded x{workers} + epoch cache "
                           f"(canvas {canvas}) + on-device augment + "
                           "DevicePrefetch depth-3")

            def make_pipe():
                return CachedImagePipeline(
                    lambda: ShardedImagePipeline(
                        rec_path, (3, canvas, canvas), batch,
                        num_workers=workers, n_threads=1, ring_depth=3),
                    cache_dir, rec_path, (3, canvas, canvas), batch,
                    pad_last=True)
        else:
            engine_desc = "C++ libjpeg pool (2 threads) + DevicePrefetch"

            def make_pipe():
                return NativeImagePipeline(rec_path, (3, size, size),
                                           batch, n_threads=2,
                                           pad_last=True)

        pipe = make_pipe()

        def run_epoch(pp, vv, epoch):
            pipe.reset() if epoch > 1 else None
            dp = DevicePrefetch(pipe, depth=3)
            n, bidx, loss = 0, 0, None
            for data, label, valid in dp:
                y = jnp.asarray(onp.asarray(label)[:, 0], jnp.int32)
                if sharded:
                    pp, vv, loss = jstep_aug(pp, vv, data, y, epoch, bidx)
                else:
                    pp, vv, loss = jstep_u8(pp, vv, data, y, key)
                n += int(valid)
                bidx += 1
            if loss is not None:
                finite_barrier(loss, "recordio train loss")
            st = dp.stats
            dp.close()  # join the feeder BEFORE touching the source
            return pp, vv, n, st

        # warm: compile + bank the epoch cache + page cache
        p, vel, _, _ = run_epoch(p, vel, 1)
        t0 = time.perf_counter()
        p, vel, n, stats = run_epoch(p, vel, 2)
        dt_rec = time.perf_counter() - t0
        pipe.close()
        rec_img_s = n / dt_rec
    finally:
        shutil.rmtree(tmpd, ignore_errors=True)

    # synthetic row with the SAME u8 step (so the comparison isolates
    # the input pipeline, not the in-graph cast)
    raw_syn = jnp.asarray(
        rng.randint(0, 255, (batch,) + (size, size, 3)), jnp.uint8)
    y = jnp.asarray(rng.randint(0, 1000, (batch,)), jnp.int32)
    p, vel, loss = jstep_u8(p, vel, raw_syn, y, key)
    float(loss)
    steps = max(3, int(n / batch))
    t0 = time.perf_counter()
    for _ in range(steps):
        p, vel, loss = jstep_u8(p, vel, raw_syn, y, key)
    float(loss)
    dt_syn = time.perf_counter() - t0
    syn_img_s = steps * batch / dt_syn

    overhead = max(0.0, syn_img_s / max(rec_img_s, 1e-9) - 1.0)
    rec_row = {
        "model": net_name, "precision": dtype_name, "batch": batch,
        "input": "recordio_jpeg_480x640_q85",
        "io_engine": io_engine,
        "pipeline": engine_desc,
        "recordio_img_s": round(rec_img_s, 2),
        "synthetic_img_s": round(syn_img_s, 2),
        "input_overhead_pct": round(overhead * 100, 1),
        # starved-time attribution: how much of the measured epoch the
        # consumer spent waiting on the input queue (vs compute-bound)
        "prefetch_starved_s": stats.get("starved_s"),
        "prefetch_bytes_staged": stats.get("bytes_staged"),
        "prefetch_depth": stats.get("depth"),
    }
    log(f"{net_name}: recordio {rec_img_s:.1f} img/s vs synthetic "
        f"{syn_img_s:.1f} img/s -> overhead {overhead * 100:.1f}% "
        f"(starved {stats.get('starved_s')}s)")
    return rec_row


def run_quick(output=None, trace=None, steps=60, batch=64, hidden=256,
              log=lambda *a: print("[train_bench]", *a, file=sys.stderr,
                                   flush=True)):
    """The telemetry smoke (tier-1: ``test_trace_quick``): a tiny MLP
    training loop on CPU, run twice over the same warm executables —
    once under ``telemetry.step`` timelines, once bare — emitting

    - a Perfetto-loadable Chrome trace (``--trace``) whose per-step
      attribution buckets (compile/device/input-starved/host) sum to the
      measured step wall time,
    - the armed-vs-bare throughput row (instrumentation overhead), and
    - the online efficiency gauges (examples/s through
      ``telemetry.mfu.observe_step``),

    banked at ``benchmark/results_telemetry_cpu.json``.
    """
    import jax

    jax.config.update("jax_platforms", "cpu")
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, telemetry
    from mxnet_tpu.io import DevicePrefetch
    from mxnet_tpu.ndarray.ndarray import _wrap

    rng = onp.random.RandomState(0)
    feat, classes, n_slots = 64, 10, 8
    xs = [rng.uniform(-1, 1, (batch, feat)).astype("float32")
          for _ in range(n_slots)]
    ys = [rng.randint(0, classes, (batch,)).astype("int32")
          for _ in range(n_slots)]

    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(hidden), gluon.nn.Activation("relu"),
            gluon.nn.Dense(hidden), gluon.nn.Activation("relu"),
            gluon.nn.Dense(classes))
    net.initialize()
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.01, "momentum": 0.9})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def batches(n):
        for i in range(n):
            yield xs[i % n_slots], ys[i % n_slots]

    def body(data, label):
        x, y = _wrap(data), _wrap(label)
        with autograd.record():
            loss = loss_fn(net(x), y).mean()
        loss.backward()
        trainer.step(batch)
        return loss

    _end = object()

    def run_loop(n, instrumented):
        """n batches through DevicePrefetch; returns (steps_per_s,
        per-step attributions, walls). The step opens BEFORE the data
        pull so prefetch starved waits land in input_starved."""
        dp = DevicePrefetch(batches(n), depth=2)
        it = iter(dp)
        atts, walls = [], []
        i = 0
        t0 = time.perf_counter()
        try:
            while True:
                if instrumented:
                    with telemetry.step("train_quick", i) as st:
                        item = next(it, _end)
                        if item is _end:
                            st.cancel()
                            break
                        loss = body(*item)
                        with st.phase("device", "loss_barrier"):
                            float(loss)  # completion barrier: the
                            # device-execute wait lands in 'device'
                    atts.append(st.attribution())
                    walls.append(st.wall_s)
                else:
                    item = next(it, _end)
                    if item is _end:
                        break
                    # per-step completion barrier, deliberately matching
                    # the armed loop's barrier so the A/B isolates the
                    # instrumentation  # tpulint: disable=A001
                    float(body(*item))
                i += 1
            dt = time.perf_counter() - t0
        finally:
            dp.close()
        return n / dt, atts, walls

    # first pass is INSTRUMENTED and untimed: step 0's attribution
    # records the real compile cost (hybridize trace + fused-update
    # compile) for the banked first_step_attribution_ms row
    _, cold_atts, cold_walls = run_loop(3, True)
    # throughput: alternate bare/armed windows over the SAME warm
    # executables and take each mode's best — back-to-back single
    # windows on a small shared container measure scheduler noise, not
    # the instrumentation (observed swings >10% either direction)
    plain_sps, armed_sps = [], []
    atts, walls = list(cold_atts), list(cold_walls)
    for _rep in range(3):
        sps, _, _ = run_loop(steps, False)
        plain_sps.append(sps)
        sps, a, w = run_loop(steps, True)
        armed_sps.append(sps)
        atts += a
        walls += w
    sps_plain, sps_armed = max(plain_sps), max(armed_sps)
    overhead_pct = max(0.0, (sps_plain / sps_armed - 1.0) * 100.0)
    log(f"quick: armed {sps_armed:.1f} steps/s vs bare "
        f"{sps_plain:.1f} steps/s -> overhead {overhead_pct:.2f}%")

    # attribution integrity: buckets must reconstruct the measured wall
    ratios = [sum(a.values()) / w for a, w in zip(atts, walls) if w]
    mean_ms = {k: round(sum(a[k] for a in atts) / len(atts) * 1e3, 3)
               for k in atts[0]}
    log(f"attribution mean (ms): {mean_ms}; sum/wall in "
        f"[{min(ratios):.4f}, {max(ratios):.4f}]")

    if trace:
        telemetry.dump_chrome(trace)
        log(f"chrome trace ({len(telemetry.buffer())} events) -> {trace}")

    # deterministic instrumentation cost: the armed-vs-bare A/B above
    # is at the mercy of scheduler noise on small shared boxes, so the
    # row also carries a direct microbench of the timeline machinery
    # (after the trace dump — probe steps stay out of the artifact)
    t0 = time.perf_counter()
    for j in range(1000):
        with telemetry.step("overhead_probe", j) as st:
            with st.phase("device"):
                pass
    probe_us = (time.perf_counter() - t0) / 1000 * 1e6
    instr_pct = probe_us * 1e-6 * sps_armed * 100.0
    log(f"instrumentation: {probe_us:.1f} us/step = "
        f"{instr_pct:.3f}% of a {1e3 / sps_armed:.1f} ms step")

    # cluster observability cost (ISSUE 15): the same loop with the
    # whole cluster plane armed — file exporter into a shared root +
    # ClusterScraper + SLO sentinel scraping it — plus a deterministic
    # microbench of one scrape+evaluate pass. The scraper runs on its
    # own thread at MXNET_TPU_TELEMETRY_SCRAPE_S cadence, so its
    # steady-state cost to the serving/training loop is the scrape
    # wall amortized over the period (fraction of one core) — that is
    # the banked <2% gate; the A/B row rides along loosely (scheduler
    # noise, same caveat as overhead_pct).
    import shutil as _shutil
    import tempfile as _tempfile

    from mxnet_tpu.telemetry import (ClusterScraper, SloRule,
                                     SloSentinel)
    from mxnet_tpu.telemetry import cluster as _tcluster
    from mxnet_tpu.telemetry import exporter as _texp

    croot = _tempfile.mkdtemp(prefix="mxt_cluster_probe_")
    cluster_row = None
    try:
        cexp = _texp.Exporter({"mode": "file", "dir": croot,
                               "period_s": 0.2}).start()
        scraper = ClusterScraper(croot)
        sentinel = SloSentinel(
            [SloRule("p99_gate", "p99_ms_max", 1e12,
                     metric="telemetry_step_ms")],
            scraper, bundle=False)
        snap = scraper.scrape()
        n_probe = 50
        t0 = time.perf_counter()
        for _ in range(n_probe):
            sentinel.evaluate()
        scrape_ms = (time.perf_counter() - t0) / n_probe * 1e3
        period = _tcluster.scrape_period_s()
        cluster_pct = scrape_ms / (period * 1e3) * 100.0
        scraper.start(period_s=0.2)
        sentinel.start(period_s=0.2)
        sps_cluster, _, _ = run_loop(steps, True)
        sentinel.stop()
        scraper.stop()
        cexp.stop(final_flush=False)
        cluster_overhead_pct = max(
            0.0, (sps_armed / sps_cluster - 1.0) * 100.0)
        cluster_row = {
            "scrape_eval_ms": round(scrape_ms, 3),
            "scrape_period_s": period,
            "scrape_pct_of_core": round(cluster_pct, 4),
            "steps_s_cluster_armed": round(sps_cluster, 2),
            "cluster_overhead_pct": round(cluster_overhead_pct, 2),
            "processes_seen": snap["cluster"]["processes"],
            "slo_rules": 1,
        }
        log(f"cluster plane: scrape+evaluate {scrape_ms:.2f} ms "
            f"(={cluster_pct:.3f}% of a core at the {period:g}s "
            f"period); armed loop {sps_cluster:.1f} steps/s -> "
            f"overhead {cluster_overhead_pct:.2f}%")
    finally:
        _shutil.rmtree(croot, ignore_errors=True)

    n_params = sum(int(onp.prod(p.data().shape))
                   for p in net.collect_params().values())
    dev = jax.devices()[0]
    efficiency = telemetry.mfu.observe_step(
        "train_quick", steps * batch, steps / sps_armed,
        flops=6.0 * n_params,  # fwd 2P + bwd 4P per example (MLP)
        device_kind=getattr(dev, "device_kind", ""))

    from bench import code_rev
    rec = {
        "metric": "telemetry_quick",
        "value": round(sps_armed, 2),
        "unit": "steps/s",
        "quick": True,
        "steps": steps,
        "batch": batch,
        "hidden": hidden,
        "steps_s_armed": round(sps_armed, 2),
        "steps_s_plain": round(sps_plain, 2),
        "overhead_pct": round(overhead_pct, 2),
        "instrumentation_us_per_step": round(probe_us, 1),
        "instrumentation_pct_of_step": round(instr_pct, 3),
        "first_step_attribution_ms":
            {k: round(v * 1e3, 3) for k, v in atts[0].items()},
        "first_step_wall_ms": round(walls[0] * 1e3, 3),
        "attribution_ms_mean": mean_ms,
        "attribution_sum_ratio_min": round(min(ratios), 4),
        "attribution_sum_ratio_max": round(max(ratios), 4),
        "trace_events": len(telemetry.buffer()),
        "cluster": cluster_row,
        "efficiency": efficiency,
        "device": dev.platform,
        "device_kind": getattr(dev, "device_kind", ""),
        "code_rev": code_rev(),
    }
    text = json.dumps(rec, indent=2)
    print(text)
    if output:
        with open(output, "w") as f:
            f.write(text + "\n")
    return rec


def child_main(name, batch, prec, cpu, infer=False, recordio_input=False,
               scan_steps=None, io_engine="sharded", tuned=None):
    """Measure ONE (model, precision) pair and print its JSON record.
    Runs in a child process: the parent stays off jax, so each child in
    turn is the one process that holds the chip, and a hung child can be
    timed out — same engineering as bench.py."""
    import threading

    if cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    import jax

    def log(*a):
        print("[train_bench]", *a, file=sys.stderr, flush=True)

    up = threading.Event()

    def _watchdog():
        if not up.wait(180):
            log("backend init watchdog fired — aborting child")
            os._exit(3)

    threading.Thread(target=_watchdog, daemon=True).start()
    # explicit per-run fp32 matmul policy (docs/precision.md): "high"
    # (bf16_3x, ≈21-bit mantissa — above TF32's 10, the Ampere-era
    # accepted meaning of fp32 training) unless overridden. bf16 rows are
    # native one-pass MXU regardless of this knob. The package no longer
    # pins "highest" process-wide (VERDICT r3 weak #2: the 6-pass fp32
    # emulation taxed every fp32 row).
    fp32_prec = os.environ.get("MXNET_BENCH_FP32_PRECISION", "high")
    if prec == "fp32":
        jax.config.update("jax_default_matmul_precision", fp32_prec)
    devs = jax.devices()
    up.set()
    log("devices:", devs)
    # mx.analysis.opt consumption: a persisted TunedConfig supplies the
    # launch-chain depth (and any env-backed knobs like stem_s2d) where
    # the caller left the defaults; explicit --scan-steps wins. Stale
    # configs are dropped by the loader with a warning.
    tuned_cfg = None
    if tuned:
        from mxnet_tpu.analysis.opt import load_tuned
        cfg = load_tuned(tuned)
        if cfg.is_current():
            tuned_cfg = cfg
            if scan_steps is None and cfg.knobs.get("steps_per_launch"):
                scan_steps = int(cfg.knobs["steps_per_launch"])
            if cfg.knobs.get("stem_s2d") is not None:
                v = cfg.knobs["stem_s2d"]
                # bools survive the JSON round-trip as true/false, but
                # the knob parser treats only the literal "0" as off —
                # normalize bools; string values ("force") pass through
                os.environ["MXNET_TPU_STEM_S2D"] = \
                    str(int(v)) if isinstance(v, bool) else str(v)
            log(f"tuned config {cfg.label}: {cfg.knobs}")
        else:
            log(f"tuned config {cfg.label} is STALE — ignoring")
    if scan_steps is None:
        scan_steps = 16 if devs[0].platform == "tpu" else 1
    if recordio_input:
        rec = measure_recordio_train(name, batch, prec, log,
                                     io_engine=io_engine)
    elif infer:
        rec = measure_infer(name, batch, prec, log, scan_steps=scan_steps)
    else:
        rec = measure(name, batch, prec, log, scan_steps=scan_steps)
    rec["matmul_precision"] = fp32_prec if prec == "fp32" else "bf16-native"
    rec["device"] = devs[0].platform
    rec["device_kind"] = devs[0].device_kind
    if tuned_cfg is not None:
        rec["tuned"] = tuned_cfg.provenance()
    # AOT compile-cache counters (mxnet_tpu.aot): nonzero only when the
    # child ran with MXNET_TPU_AOT_CACHE armed — then the row records
    # how much cold-compile the store absorbed for this measurement
    try:
        from mxnet_tpu import aot as _aot
        if any(_aot.stats().values()):
            rec["aot"] = _aot.stats()
    except Exception:  # noqa: BLE001 — observability must not fail a row
        pass
    # provenance stamped by the MEASURING child at measurement time (a
    # daemon-side stamp could misattribute if a commit lands mid-child)
    from bench import code_rev, stamp_window_control
    rec["code_rev"] = code_rev()
    # same-window effective-peak control AFTER the measurement: separates
    # model/code efficiency (mfu_effective) from window throttle (mfu)
    if devs[0].platform == "tpu":
        stamp_window_control(rec)
        if rec.get("window_control_tflops"):
            log(f"window control: {rec['window_control_tflops']} TFLOPs"
                + (f", mfu_effective={rec['mfu_effective']}"
                   if "mfu_effective" in rec else ""))
    print(json.dumps(rec), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", default="resnet50_v1")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--precisions", default="fp32,bf16")
    ap.add_argument("--output", default=None)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--child", nargs=2, metavar=("MODEL", "PREC"),
                    default=None, help=argparse.SUPPRESS)
    ap.add_argument("--infer", action="store_true",
                    help="measure the inference table (bench.py serial-"
                         "chain protocol) instead of training steps")
    ap.add_argument("--recordio-input", action="store_true",
                    help="train from real RecordIO JPEG bytes through "
                         "the ingestion engine and report input-pipeline "
                         "overhead vs synthetic")
    ap.add_argument("--io-engine", default="sharded",
                    choices=("sharded", "legacy"),
                    help="--recordio-input pipeline: 'sharded' = multi-"
                         "process decode + epoch cache + on-device "
                         "augment (the ingestion engine); 'legacy' = "
                         "single-process C++ pool + double buffer")
    ap.add_argument("--tuned", default=None,
                    help="path to a persisted mx.analysis.opt "
                         "TunedConfig: supplies steps_per_launch / "
                         "stem_s2d where flags are left default "
                         "(provenance recorded in the row; stale "
                         "configs ignored with a log line)")
    ap.add_argument("--scan-steps", type=int, default=None,
                    help="serially-chained steps per launch (lax.scan "
                         "inside one executable). Default: 16 on TPU "
                         "(amortizes the per-launch cost), 1 on CPU "
                         "(XLA:CPU compiles scanned conv bodies ~5x "
                         "slower)")
    ap.add_argument("--quick", action="store_true",
                    help="telemetry smoke on CPU: tiny-MLP loop under "
                         "step timelines, Chrome trace + attribution + "
                         "instrumentation-overhead row (tier-1: "
                         "test_trace_quick)")
    ap.add_argument("--trace", default=None,
                    help="--quick: write the Chrome trace_event JSON "
                         "here (Perfetto-loadable)")
    ap.add_argument("--quick-steps", type=int, default=60,
                    help="--quick: timed steps per loop")
    ap.add_argument("--quick-batch", type=int, default=64,
                    help="--quick: batch size of the smoke loop")
    ap.add_argument("--timeout", type=int, default=600,
                    help="per-(model,precision) child timeout, seconds")
    ap.add_argument("--retries", type=int, default=2)
    ap.add_argument("--bail-after", type=int, default=2,
                    help="stop the sweep after this many CONSECUTIVE "
                         "no-result combos SPANNING 2+ models (one model "
                         "failing both precisions is a model problem, not a "
                         "dead backend); 0 disables early bail-out")
    args = ap.parse_args()

    if args.quick:
        run_quick(output=args.output, trace=args.trace,
                  steps=args.quick_steps, batch=args.quick_batch)
        return

    if args.child:
        child_main(args.child[0], args.batch, args.child[1], args.cpu,
                   infer=args.infer, recordio_input=args.recordio_input,
                   scan_steps=args.scan_steps, io_engine=args.io_engine,
                   tuned=args.tuned)
        return

    def log(*a):
        print("[train_bench]", *a, file=sys.stderr, flush=True)

    results = []
    device = {}
    consecutive_failures = 0
    failed_models = set()
    combos = [(name, prec) for name in args.models.split(",")
              for prec in args.precisions.split(",")]
    for name, prec in combos:
        rec = None
        # bail only when the failures span MULTIPLE models: one model
        # failing both its precisions (OOM, unsupported op) is a model
        # problem, not a dead backend, and must not skip the rest
        if args.bail_after > 0 and \
                consecutive_failures >= args.bail_after and \
                len(failed_models) >= 2:
            log(f"bailing out: {consecutive_failures} consecutive "
                "combos failed (backend likely unreachable)")
            results.append({"model": name, "precision": prec,
                            "batch": args.batch, "error": "skipped: bail"})
            continue
        for attempt in range(args.retries + 1):
            cmd = [sys.executable, os.path.abspath(__file__),
                   "--child", name, prec, "--batch", str(args.batch)]
            if args.scan_steps is not None:
                cmd += ["--scan-steps", str(args.scan_steps)]
            if args.tuned:
                cmd += ["--tuned", args.tuned]
            if args.infer:
                cmd.append("--infer")
            if args.recordio_input:
                cmd.append("--recordio-input")
            if args.cpu:
                cmd.append("--cpu")
            try:
                proc = subprocess.run(cmd, capture_output=True,
                                      text=True, timeout=args.timeout)
                sys.stderr.write(proc.stderr[-2000:])
                for line in reversed(proc.stdout.strip().splitlines()):
                    if line.startswith("{"):
                        rec = json.loads(line)
                        break
            except subprocess.TimeoutExpired:
                log(f"{name}/{prec} attempt {attempt}: "
                    f"timeout {args.timeout}s")
            except Exception as e:  # noqa: BLE001
                log(f"{name}/{prec} attempt {attempt}: {e!r}")
            if rec:
                break
        if rec:
            consecutive_failures = 0
            failed_models.clear()
            device["device"] = rec.pop("device", None)
            device["device_kind"] = rec.pop("device_kind", None)
            results.append(rec)
        else:
            consecutive_failures += 1
            failed_models.add(name)
            results.append({"model": name, "precision": prec,
                            "batch": args.batch, "error": "no result"})
    out = {**device, "results": results}
    text = json.dumps(out, indent=2)
    print(text)
    if args.output:
        with open(args.output, "w") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()
