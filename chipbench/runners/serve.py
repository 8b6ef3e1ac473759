"""A cell that serves: ``serving.LLMEngine`` under one traffic mix — the
calls of ``chip_smoke.serve_phase`` (``LLMEngine(...)``, ``warmup``,
``submit``), which PR 22 proved on the chip.

Cell file keys: ``engine`` (keyword arguments of ``LLMEngine``),
``warm_prompt_lengths`` (one per prefill bucket the traffic uses),
``ramp_seconds`` (traffic before the window opens, counted in set-up),
``trace_seconds``, ``check_requests`` (the size of the correctness
sample) and optionally ``mesh`` (``LLMEngine(mesh=, rules=)`` as
``chip_smoke.sharded_phase``).

One thread — this one — offers the load; the engine's own scheduler
thread answers. Timestamps are the benchmark's: ``submit()`` here,
``on_token`` in the scheduler's thread, both ``time.perf_counter``.
"""
from __future__ import annotations

import contextlib
import gc
import statistics
import time

import numpy as onp

from chipbench import flops, harness
from chipbench.harness import make_net, raw
from chipbench.reference import gpt as reference

POLL_S = 0.002


class Sent:
    """One request and the benchmark's timestamps of it."""

    __slots__ = ("prompt", "new", "cut", "t_due", "t_submit", "times",
                 "handle", "t_done", "failed")

    def __init__(self, prompt, new, cut, t_due):
        self.prompt, self.new, self.cut = prompt, new, cut
        self.t_due = t_due
        self.t_submit = None
        self.times: list = []
        self.handle = None
        self.t_done = None
        self.failed = False

    def on_token(self, tok) -> None:          # the scheduler's thread
        self.times.append(time.perf_counter())


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of a non-empty list."""
    v = sorted(values)
    return v[min(len(v) - 1, max(0, int(onp.ceil(q * len(v))) - 1))]


def check_sample(params, done, heads, pad_to, n, seed) -> bool:
    """Hold the tokens of ``n`` answered requests — half of them the
    longest prompts seen, the rest drawn from the seed — to the plain
    reference: each within ``TIE_STEPS`` bf16 steps of the best logit."""
    rng = onp.random.RandomState(seed % 2**32)
    by_len = sorted(done, key=lambda s: -len(s.prompt))
    sample = by_len[:n // 2]
    rest = by_len[n // 2:]
    if rest:
        pick = rng.choice(len(rest), min(n - len(sample), len(rest)),
                          replace=False)
        sample += [rest[i] for i in pick]
    worst = 0.0
    for s in sample:
        behind = reference.tokens_behind(
            params, s.prompt, s.handle.result(), heads, pad_to)
        worst = max(worst, float(behind.max()))
    harness.note(f"check: {len(sample)} requests (prompts "
                 f"{[len(s.prompt) for s in sample]}), every token within "
                 f"{worst:.1f} bf16 steps of the reference's best logit "
                 f"(limit {reference.TIE_STEPS})")
    return bool(sample) and worst <= reference.TIE_STEPS


def run(ctx: harness.Context) -> dict:
    import jax
    from mxnet_tpu.serving import LLMEngine

    cell, sz = ctx.cell, flops.sizes(ctx.config)
    vocab = sz["vocab_size"]
    net = make_net(sz, ctx.config["dtype"], ctx.seed,
                   float(ctx.config["initializer_range"]), grad_req="null")
    ctx.mark("weights made on the device")
    kind = harness.load_module(ctx.root, "traffic", ctx.traffic["kind"])
    requests = harness.load_module(ctx.root, "traffic", "requests")
    stream = requests.draw(ctx.traffic, vocab, ctx.seed)
    plan = kind.plan(ctx.traffic, ctx.seed)
    rng = onp.random.RandomState((ctx.seed + 2) % 2**32)

    tick_span = [None]
    ticks: list = []

    def hook():                      # the scheduler's thread, once a tick
        ticks.append(time.perf_counter())
        if tick_span[0] is not None:
            tick_span[0].__exit__(None, None, None)
        tick_span[0] = jax.profiler.TraceAnnotation("chipbench.tick")
        tick_span[0].__enter__()

    engine_kw = dict(cell["engine"])
    if cell.get("mesh"):
        from mxnet_tpu.parallel.mesh import make_mesh
        from mxnet_tpu.parallel.sharding import TRANSFORMER_RULES

        engine_kw.update(mesh=make_mesh(dict(cell["mesh"]),
                                        devices=ctx.devices),
                         rules=TRANSFORMER_RULES)
    eng = LLMEngine(net, step_hook=hook, **engine_kw)
    ctx.mark("engine and pools built")
    lanes = eng.max_running
    sent: list = []
    watch, window_open = harness.HostWatch(), contextlib.ExitStack()
    try:
        with ctx.spans.span("warmup"):
            eng.warmup(prompt_lengths=cell["warm_prompt_lengths"])
        ctx.mark("decode and prefill programs warm; the ramp begins")
        t_traffic = time.perf_counter()
        t0 = t_traffic + float(cell["ramp_seconds"])
        t_end = t0 + ctx.seconds
        tracer = harness.WindowTrace(ctx.trace, float(cell["trace_seconds"]))
        first_wave = kind.first_wave(ctx.traffic, lanes)
        in_flight: list = []
        at_open = None
        late = []
        while True:
            now = time.perf_counter()
            if at_open is None and now >= t0:
                at_open = (ctx.compiles.n, eng.stats())
                window_open.enter_context(watch)
            if now >= t_end:
                break
            tracer.maybe_start(now, t_end)
            still = []
            for s in in_flight:
                if s.handle.done:
                    s.t_done = s.times[-1] if s.times else now
                    s.failed = s.handle.exception() is not None \
                        or len(s.times) != s.new
                else:
                    still.append(s)
            in_flight = still
            n_due = first_wave - len(sent) if len(sent) < first_wave else \
                kind.due(plan, ctx.traffic, now - t_traffic,
                         len(sent) - first_wave, len(in_flight), lanes)
            for _ in range(n_due):
                prompt, new = next(stream)
                cut = len(sent) < first_wave
                if cut:      # the lanes fall out of step at once
                    new = int(rng.randint(1, new + 1))
                t_due = None
                if kind.OPEN_LOOP:
                    t_due = t_traffic + float(plan[len(sent) - first_wave])
                    late.append(now - t_due)
                s = Sent(prompt, new, cut, t_due)
                s.t_submit = time.perf_counter()
                s.handle = eng.submit(prompt, new, on_token=s.on_token)
                sent.append(s)
                in_flight.append(s)
            time.sleep(POLL_S)
        at_close = (ctx.compiles.n, eng.stats())
        window_open.close()
        trace = tracer.stop()
    finally:
        window_open.close()
        eng.close(drain=False, timeout_s=120.0)

    # -- the window's numbers, from the benchmark's own timestamps ---------
    window = ctx.seconds

    def in_win(t):
        return t0 <= t < t_end

    # the rate is taken over the whole scheduler ticks inside the window:
    # a decode step hands out a token to every lane at one instant, so a
    # window that ends just before or just after a step would read a
    # step's tokens (0.6% at 32 lanes) higher or lower for nothing
    inside = [t for t in ticks if in_win(t)]
    lo, hi = (inside[0], inside[-1]) if len(inside) > 1 else (t0, t_end)
    out_tokens = sum(1 for s in sent for t in s.times if lo <= t < hi)
    ttft, gaps = [], []
    for s in sent:
        start = s.t_due if s.t_due is not None else s.t_submit
        if in_win(start):
            ttft.append((s.times[0] - start) if s.times and not s.failed
                        else window)
        gaps.extend(b - a for a, b in zip(s.times, s.times[1:])
                    if a >= t0 and b < t_end)
    ended = [s for s in sent if s.t_done is not None and in_win(s.t_done)]
    failed = [s for s in ended if s.failed]
    compiled = at_close[0] - at_open[0]
    engine_compiled = at_close[1]["counters"]["compiles"] \
        - at_open[1]["counters"]["compiles"]
    engine_failed = at_close[1]["counters"]["failed"] \
        - at_open[1]["counters"]["failed"]
    e2e = {"setup_s": t0 - ctx.t_start,
           "serve_out_tokens_per_s": out_tokens / (hi - lo)}
    if ttft:
        e2e["ttft_p95_ms"] = percentile(ttft, 0.95) * 1e3
    if gaps:
        e2e["itl_p95_ms"] = percentile(gaps, 0.95) * 1e3
    harness.note(
        f"window: {len(sent)} requests sent in all, {len(ttft)} inside the "
        f"window, {len(ended)} ended inside it ({len(failed)} failed), "
        f"{out_tokens} tokens out in {len(inside) - 1} whole ticks "
        f"({hi - lo:.3f} s), {len(gaps)} gaps; "
        f"ttft p50 {statistics.median(ttft) * 1e3 if ttft else 0:.1f} ms, "
        f"itl p50 {statistics.median(gaps) * 1e3 if gaps else 0:.1f} ms; "
        f"{compiled} programs compiled, engine compiles {engine_compiled}, "
        f"engine failures {engine_failed}")
    harness.note(watch.summary())
    harness.note_steps(harness.step_times(inside[:-1], inside[-1])
                       if len(inside) > 1 else [])
    if late:
        harness.note(f"generator: requests went out {max(late) * 1e3:.1f} ms "
                     f"late at worst, {statistics.median(late) * 1e3:.2f} ms "
                     "at the median")

    # -- correctness, after the window and outside it ----------------------
    # the pools go first: the reference needs the room they held
    params = {k: raw(p.data()) for k, p in net.collect_params().items()}
    max_context = eng.max_context
    block_size, kv_dtype = eng.block_size, at_close[1]["kv_cache_dtype"]
    del eng
    gc.collect()
    good = [s for s in ended if not s.failed and not s.cut]
    tokens_ok = check_sample(params, good, sz["num_heads"],
                                    max_context, int(cell["check_requests"]),
                                    ctx.seed)
    return {
        "correct": bool(tokens_ok and compiled == 0 and engine_compiled == 0
                        and not failed and engine_failed == 0),
        "attempted": len(ended), "failed": len(failed),
        "end_to_end": e2e, "trace": trace,
        "window": (t0, t_end), "sizes": sz, "sent": sent,
        "stats_open": at_open[1], "stats_close": at_close[1],
        "lanes": lanes, "block_size": block_size, "kv_dtype": kv_dtype,
        "trace_span": (tracer.started_at, tracer.stopped_at),
    }
