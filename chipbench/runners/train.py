"""A cell that trains: ``gluon.Trainer`` steps on a hybridized ``gpt_like``
— the calls of ``chip_smoke.train_phase``, which PR 22 proved on the chip.

Cell file keys: ``trainer`` (``optimizer``, ``optimizer_params``),
``warm_steps``, ``fetch_every``, ``trace_seconds``, ``check_grads`` (the
parameters whose gradients are held to the reference, ``{layers-1}``
standing for the last block) and optionally ``mesh`` (for example
``{"tp": 4}``: ``parallel.use_mesh`` + ``Trainer.shard``, as
``chip_smoke.sharded_phase`` does).
"""
from __future__ import annotations

import contextlib
import time

import numpy as onp

from chipbench import flops, harness
from chipbench.harness import make_net, raw
from chipbench.reference import gpt as reference


def check_first_step(net, x, labels, loss_sum, names, heads) -> bool:
    """The program's loss and named gradients (its own ``backward()``, read
    before the first optimizer step) against the plain reference."""
    params = {k: raw(p.data()) for k, p in net.collect_params().items()}
    want_loss, want = reference.loss_and_grads(
        params, x, labels.reshape(x.shape), names, heads)
    rel = abs(loss_sum - want_loss) / abs(want_loss)
    ok = rel <= reference.LOSS_RTOL
    harness.note(f"check: loss/token {loss_sum / x.size:.5f} against the "
                 f"reference's {want_loss / x.size:.5f}, relative "
                 f"{rel:.2e} (limit {reference.LOSS_RTOL})")
    for name in names:
        got = raw(net.collect_params()[name].grad())
        cos, ratio = reference.compare_grad(got, want[name])
        good = cos >= reference.GRAD_COS_MIN and \
            abs(ratio - 1) <= reference.GRAD_NORM_RTOL
        ok = ok and good
        harness.note(f"check: grad {name}: cosine {cos:.5f} (min "
                     f"{reference.GRAD_COS_MIN}), norm ratio {ratio:.4f} "
                     f"(within {reference.GRAD_NORM_RTOL})"
                     f"{'' if good else '  <-- FAILS'}")
    return ok


def run(ctx: harness.Context) -> dict:
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon

    cell, sz = ctx.cell, flops.sizes(ctx.config)
    vocab, heads = sz["vocab_size"], sz["num_heads"]
    net = make_net(sz, ctx.config["dtype"], ctx.seed,
                   float(ctx.config["initializer_range"]))
    net.hybridize()
    ctx.mark("weights made on the device")
    trainer = gluon.Trainer(net.collect_params(), cell["trainer"]["optimizer"],
                            dict(cell["trainer"]["optimizer_params"]))
    load = harness.load_module(ctx.root, "traffic", ctx.traffic["kind"])
    batches = load.batches(ctx.traffic, vocab, ctx.seed)
    tokens_per_step = batches[0][0].size
    names = [n.replace("{layers-1}", str(sz["num_layers"] - 1))
             for n in cell["check_grads"]]

    with contextlib.ExitStack() as stack:
        if cell.get("mesh"):
            from mxnet_tpu.parallel import use_mesh
            from mxnet_tpu.parallel.mesh import make_mesh
            from mxnet_tpu.parallel.sharding import TRANSFORMER_RULES

            stack.enter_context(use_mesh(make_mesh(dict(cell["mesh"]),
                                                   devices=ctx.devices)))
            trainer.shard(TRANSFORMER_RULES)

        span = ctx.spans.span

        def forward_backward(i):
            x, labels = batches[i % len(batches)]
            with span("batch"):
                xa, la = mx.np.array(x), mx.np.array(labels)
            with span("forward"), autograd.record():
                logits = net(xa)
                loss = mx.npx.softmax_cross_entropy(
                    logits.reshape(-1, vocab), la)
            with span("backward"):
                loss.backward()
            return loss

        def step(i):
            """One step, enqueued: nothing in here waits for the device."""
            with span("dispatch"):
                loss = forward_backward(i)
                with span("update"):
                    trainer.step(tokens_per_step)
            return loss

        # set-up: the first warm-up step is also the correctness sample —
        # its backward() is read before the optimizer moves anything
        loss_sum = float(forward_backward(0).asnumpy()[0])
        ctx.mark("first forward and backward")
        first_ok = check_first_step(net, *batches[0], loss_sum, names, heads)
        ctx.mark("reference loss and gradients")
        trainer.step(tokens_per_step)
        for i in range(1, int(cell["warm_steps"])):
            step(i).asnumpy()

        # the window: whole steps until --seconds have passed, between two
        # syncs (the optimizer's states before the first step and after
        # the last); the loss is fetched every fetch_every-th step, as a
        # logging loop does, and the others stay on the device
        fetch_every = int(cell["fetch_every"])
        tracer = harness.WindowTrace(ctx.trace, float(cell["trace_seconds"]))
        watch = harness.HostWatch()
        losses, starts, n = [], [], 0
        c0 = ctx.compiles.n
        jax.block_until_ready(jax.tree_util.tree_leaves(trainer._states))
        ctx.mark("warm-up steps; the window opens")
        with watch:
            t0 = time.perf_counter()
            setup_s = t0 - ctx.t_start
            while True:
                now = time.perf_counter()
                if now - t0 >= ctx.seconds:
                    break
                tracer.maybe_start(now, t0 + ctx.seconds)
                starts.append(now)
                losses.append(step(n))
                n += 1
                if n % fetch_every == 0:
                    losses[-1].asnumpy()
            losses[-1].asnumpy()
            jax.block_until_ready(jax.tree_util.tree_leaves(trainer._states))
            t1 = time.perf_counter()
        harness.note(watch.summary())
        trace = tracer.stop()
        compiled = ctx.compiles.n - c0

    values = onp.array([float(v.asnumpy()[0]) for v in losses]) \
        / tokens_per_step
    finite = onp.isfinite(values)
    placed = set(ctx.devices)
    on_device = all(set(raw(p.data()).devices()) <= placed
                    for p in net.collect_params().values()) and all(
        set(s.devices()) <= placed
        for s in jax.tree_util.tree_leaves(trainer._states))
    steps = harness.step_times(starts, t1)
    harness.note_steps(steps)
    harness.note(f"window: {n} steps of {tokens_per_step} tokens in "
                 f"{t1 - t0:.3f} s, {compiled} programs compiled, losses/token "
                 f"{values[0]:.4f} -> {values[-1]:.4f}, all finite "
                 f"{bool(finite.all())}, everything on the device {on_device}")
    return {
        "correct": bool(first_ok and finite.all() and compiled == 0
                        and on_device),
        "attempted": n, "failed": int((~finite).sum()),
        "end_to_end": {
            "train_tokens_per_s": n * tokens_per_step / (t1 - t0),
            "setup_s": setup_s},
        "trace": trace,
        "window": (t0, t1), "sizes": sz, "batch": batches[0][0].shape,
        "steps": steps, "trace_span": (tracer.started_at, tracer.stopped_at),
    }
