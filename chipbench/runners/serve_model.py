"""A cell that serves, with the model and its reference named by the cell:
``serving.LLMEngine`` under one traffic mix, the loop of ``runners/serve.py``
(whose ``Sent``, ``percentile`` and polling it shares) without that file's
tie to ``gpt_like`` and ``reference/gpt.py`` — the next served
configuration is data.

Cell file keys, beside ``serve.py``'s (``engine``, ``ramp_seconds``,
``trace_seconds``, ``check_requests``): ``model`` — ``factory``
(``module:function``, called with the sizes, ``dtype`` and the optional
``extra``: the CPU rehearsal's toy cell asks for a small prefill chunk),
``flops`` (the module under ``chipbench/`` whose ``sizes(config)`` turns the
configuration's published keys into the factory's arguments and whose
optional ``overrides(config, sizes, seed)`` names parameters that are not
drawn from normal(0, ``initializer_range``)) — and ``reference`` (the
module under ``chipbench/reference/`` with ``TIE_STEPS`` and
``tokens_behind(params, prompt, emitted, sizes, pad_to, rows_to)``).
A model whose cache is a state names ``model.state_readings``
(``module:function(k, v, probes)``: what one request's blocks of the two
pools answer to probe queries) and ``check_states``: so many states of
requests still in flight at the window's end are read from the timed
engine (``LLMEngine.snapshot_cache``) and held to the reference's
``state_apart`` within its ``STATE_LIMIT`` — what the tokens cannot show,
a state kept in less than the precision the configuration states.
``warm_prompt_lengths`` is optional: an engine that prefills in chunks of
one size has one prefill program whatever the lengths.

The rate. ``serve_out_tokens_per_s`` is tokens out over time elapsed between
two tick boundaries inside the window, as in ``runners/serve.py``; which two
is decided by the work between them and never by a clock
(``balanced_stretch``): the longest stretch in which the prompt tokens taken
in and the answer tokens given out stand as they do in the traffic's whole
pool. Where a prompt's prefill is a large share of the time, a window cut by
the clock holds a few prompts more or fewer than its answers' share, by the
order of the sizes, and its rate follows that and not the program.
"""
from __future__ import annotations

import contextlib
import gc
import importlib
import statistics
import threading
import time

import numpy as onp

from chipbench import harness
from chipbench.harness import raw


# how far a stretch's prompt tokens in : answer tokens out may lie from the
# pool's own ratio (a decode step moves it by a thousandth at 16 lanes)
BALANCE_TOL = 0.002


def balanced_stretch(bounds, token_times, first_tokens, ratio, least_s):
    """The longest stretch between two of ``bounds`` (tick boundaries,
    ascending) that lasts ``least_s`` or more and in which prompt tokens
    taken in over answer tokens given out is ``ratio`` to within
    ``BALANCE_TOL``: ``(lo, hi)``, or None where no stretch is so.
    ``token_times`` are the times of all tokens out; ``first_tokens`` are
    ``(time of a request's first token, its prompt's length)``: a prompt is
    taken in, whole, in the tick that hands out its first token."""
    b = onp.asarray(bounds, onp.float64)
    y = onp.searchsorted(onp.sort(onp.asarray(token_times, onp.float64)), b)
    first = sorted(first_tokens)
    taken = onp.concatenate([[0], onp.cumsum([n for _, n in first])])
    x = taken[onp.searchsorted(onp.asarray([t for t, _ in first]), b)]
    best = None
    for i in range(len(b) - 1):
        dy, dx = y[i + 1:] - y[i], x[i + 1:] - x[i]
        ok = (b[i + 1:] - b[i] >= least_s) & (dy > 0) \
            & (onp.abs(dx - ratio * dy) <= BALANCE_TOL * ratio * dy)
        if ok.any():
            j = i + 1 + int(onp.flatnonzero(ok)[-1])
            if best is None or b[j] - b[i] > best[1] - best[0]:
                best = (float(b[i]), float(b[j]))
    return best


def make_net(factory, kw: dict, dtype: str, seed: int, std: float,
             fixed: dict):
    """The cell's model with seeded random weights, made on the device in
    one jitted call (``harness._weights``: matrices normal(0, ``std``),
    norm gains 1 + normal) and handed over as a checkpoint would be, kept
    without gradient buffers; ``fixed`` are set as they are."""
    import jax
    import jax.numpy as jnp

    net = factory(dtype=dtype, **kw)
    params = net.collect_params()
    spec = tuple((k, tuple(p.shape), str(onp.dtype(p.dtype)))
                 for k, p in params.items())
    words = onp.array([seed & 0xffffffff, seed >> 32, 0x9e3779b9,
                       0x7f4a7c15], onp.uint32)
    made = jax.jit(harness._weights, static_argnums=(1, 2))(words, spec, std)
    for k, p in params.items():
        p.grad_req = "null"
        p.set_data(jnp.asarray(fixed[k], made[k].dtype) if k in fixed
                   else made[k])
    return net


def check_sample(reference, params, done, sz, pad_to, rows_to, n, seed):
    """Hold the tokens of ``n`` answered requests — half of them the
    longest prompts seen, the rest drawn from the seed — to the plain
    reference: each within ``TIE_STEPS`` bf16 steps of the best logit."""
    rng = onp.random.RandomState(seed % 2**32)
    by_len = sorted(done, key=lambda s: -len(s.prompt))
    sample, rest = by_len[:n // 2], by_len[n // 2:]
    if rest:
        pick = rng.choice(len(rest), min(n - len(sample), len(rest)),
                          replace=False)
        sample += [rest[i] for i in pick]
    worst, t0 = 0.0, time.perf_counter()
    for s in sample:
        behind = reference.tokens_behind(
            params, s.prompt, s.handle.result(), sz, pad_to, rows_to)
        worst = max(worst, float(behind.max()))
    harness.note(f"check: {len(sample)} requests (prompts "
                 f"{[len(s.prompt) for s in sample]}), every token within "
                 f"{worst:.1f} bf16 steps of the reference's best logit "
                 f"(limit {reference.TIE_STEPS}), "
                 f"{time.perf_counter() - t0:.1f} s")
    return bool(sample) and worst <= reference.TIE_STEPS


def read_states(eng, sent, readings, probes, n):
    """The cache of ``n`` requests that the timed engine carries when the
    window has closed — those that have decoded longest, whose state has
    been through the most steps — read with the reference's probes while
    the engine holds it: ``(tokens fed, how many, (num, den))``. Called
    between two ticks, on the scheduler's thread (``step_hook``)."""
    out = []
    for s in sorted(sent, key=lambda s: -len(s.times)):
        snap = eng.snapshot_cache(s.handle) \
            if len(out) < n and not s.handle.done else None
        if snap is None:            # ended, or still queued
            continue
        pos, emitted, k, v = snap
        fed = onp.concatenate([s.prompt, emitted]).astype(onp.int32)[:pos]
        got = readings(k[:, 0], v[:, 0], probes)
        out.append((fed, pos, tuple(onp.asarray(a) for a in got)))
    return out


def check_states(reference, params, states, sz, pad_to, seed):
    """Hold those states to the plain reference: each layer's and head's
    answer to the probes within ``STATE_LIMIT`` of the quadratic form's."""
    worst, t0 = {k: 0.0 for k in reference.STATE_LIMIT}, time.perf_counter()
    for fed, pos, got in states:
        apart = reference.state_apart(params, fed, pos, got, sz, pad_to, seed)
        worst = {k: max(worst[k], apart[k]) for k in worst}
    harness.note(
        f"check: {len(states)} states of the timed engine (after "
        f"{[pos for _, pos, _ in states]} tokens), apart from the "
        "reference's readings by at most " + ", ".join(
            f"{k} {worst[k]:.3e} (limit {reference.STATE_LIMIT[k]:g})"
            for k in worst) + f", {time.perf_counter() - t0:.1f} s")
    return bool(states) and all(
        worst[k] <= reference.STATE_LIMIT[k] for k in worst)


def run(ctx: harness.Context) -> dict:
    import jax
    from mxnet_tpu.serving import LLMEngine

    serve = harness.load_module(ctx.root, "runners", "serve")
    cell, model = ctx.cell, ctx.cell["model"]
    counts = harness.load_module(ctx.root, "", model["flops"])
    reference = harness.load_module(ctx.root, "reference", cell["reference"])
    sz = counts.sizes(ctx.config)
    module, _, name = model["factory"].partition(":")
    factory = getattr(importlib.import_module(module), name)
    fixed = counts.overrides(ctx.config, sz, ctx.seed) \
        if hasattr(counts, "overrides") else {}
    net = make_net(factory, {**sz, **model.get("extra", {})},
                   ctx.config["dtype"], ctx.seed,
                   float(ctx.config["initializer_range"]), fixed)
    ctx.mark("weights made on the device")
    kind = harness.load_module(ctx.root, "traffic", ctx.traffic["kind"])
    requests = harness.load_module(ctx.root, "traffic", "requests")
    stream = requests.draw(ctx.traffic, sz["vocab_size"], ctx.seed)
    plan = kind.plan(ctx.traffic, ctx.seed)
    rng = onp.random.RandomState((ctx.seed + 2) % 2**32)

    tick_span = [None]
    ticks: list = []
    # the states to check are read once the window is closed and counted
    states, states_due, states_read = [], threading.Event(), threading.Event()
    readings = None
    if "state_readings" in model:
        module, _, name = model["state_readings"].partition(":")
        readings = getattr(importlib.import_module(module), name)

    def hook():                      # the scheduler's thread, once a tick
        ticks.append(time.perf_counter())
        if tick_span[0] is not None:
            tick_span[0].__exit__(None, None, None)
        tick_span[0] = jax.profiler.TraceAnnotation("chipbench.tick")
        tick_span[0].__enter__()
        if states_due.is_set() and not states_read.is_set():
            states.extend(read_states(
                eng, sent, readings, reference.probes(sz, ctx.seed),
                int(cell["check_states"])))
            states_read.set()

    eng = LLMEngine(net, step_hook=hook, **cell["engine"])
    ctx.mark("engine and pools built")
    lanes = eng.max_running
    sent: list = []
    watch, window_open = harness.HostWatch(), contextlib.ExitStack()
    try:
        with ctx.spans.span("warmup"):
            eng.warmup(prompt_lengths=cell.get("warm_prompt_lengths"))
        ctx.mark("decode and prefill programs warm; the ramp begins")
        t_traffic = time.perf_counter()
        t0 = t_traffic + float(cell["ramp_seconds"])
        t_end = t0 + ctx.seconds
        tracer = harness.WindowTrace(ctx.trace, float(cell["trace_seconds"]))
        first_wave = kind.first_wave(ctx.traffic, lanes)
        in_flight: list = []
        at_open = at_close = None
        late = []
        while True:
            now = time.perf_counter()
            if at_open is None and now >= t0:
                at_open = (ctx.compiles.n, eng.stats())
                window_open.enter_context(watch)
            if now >= t_end:
                if at_close is None:
                    at_close = (ctx.compiles.n, eng.stats())
                    window_open.close()
                    trace = tracer.stop()
                    states_due.set()
                # the traffic goes on until the states are read: an engine
                # left to drain soon carries none
                if readings is None or states_read.is_set() \
                        or now >= t_end + 60.0:
                    break
            else:
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
                s = serve.Sent(prompt, new, cut, t_due)
                s.t_submit = time.perf_counter()
                s.handle = eng.submit(prompt, new, on_token=s.on_token)
                sent.append(s)
                in_flight.append(s)
            time.sleep(serve.POLL_S)
    finally:
        window_open.close()
        eng.close(drain=False, timeout_s=120.0)

    # -- the window's numbers, from the benchmark's own timestamps ---------
    window = ctx.seconds

    def in_win(t):
        return t0 <= t < t_end

    # the rate is taken over whole scheduler ticks inside the window
    # (runners/serve.py: a decode step hands every lane its token at once),
    # and of those over the longest stretch that holds prompts and answers
    # in the pool's own proportion; where there is none, over all of them
    inside = [t for t in ticks if in_win(t)]
    lo, hi = (inside[0], inside[-1]) if len(inside) > 1 else (t0, t_end)
    token_times = [t for s in sent for t in s.times]
    whole = (sum(1 for t in token_times if lo <= t < hi), hi - lo)
    pool = requests.size_pool(ctx.traffic)
    ratio = sum(p for p, _, _ in pool) / sum(n for _, n, _ in pool)
    stretch = balanced_stretch(
        inside, token_times, [(s.times[0], len(s.prompt)) for s in sent
                              if s.times], ratio, window / 2)
    if stretch is not None:
        lo, hi = stretch
    out_tokens = sum(1 for t in token_times if lo <= t < hi)
    prompts_in = sum(len(s.prompt) for s in sent
                     if s.times and lo <= s.times[0] < hi)
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
        e2e["ttft_p95_ms"] = serve.percentile(ttft, 0.95) * 1e3
    if gaps:
        e2e["itl_p95_ms"] = serve.percentile(gaps, 0.95) * 1e3
    harness.note(
        f"window: {len(sent)} requests sent in all, {len(ttft)} inside the "
        f"window, {len(ended)} ended inside it ({len(failed)} failed), "
        f"{out_tokens} tokens out and {prompts_in} prompt tokens in over "
        f"{'the balanced stretch of' if stretch else 'all whole ticks,'} "
        f"{hi - lo:.3f} s (the pool's ratio {ratio:.3f}; all "
        f"{len(inside) - 1} whole ticks, {whole[1]:.3f} s: {whole[0]} tokens "
        f"out, {whole[0] / whole[1]:.2f} a second), {len(gaps)} gaps; "
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
    max_context, block_size = eng.max_context, eng.block_size
    kv_dtype = at_close[1]["kv_cache_dtype"]
    del eng
    gc.collect()
    good = [s for s in ended if not s.failed and not s.cut]
    rows_to = max(c["new_tokens"]["hi"] for c in ctx.traffic["classes"])
    tokens_ok = check_sample(reference, params, good, sz, max_context,
                             rows_to, int(cell["check_requests"]), ctx.seed)
    states_ok = readings is None or check_states(
        reference, params, states, sz, max_context, ctx.seed)
    return {
        "correct": bool(tokens_ok and states_ok and compiled == 0
                        and engine_compiled == 0 and not failed
                        and engine_failed == 0),
        "attempted": len(ended), "failed": len(failed),
        "end_to_end": e2e, "trace": trace,
        "window": (t0, t_end), "sizes": sz, "sent": sent,
        "stats_open": at_open[1], "stats_close": at_close[1],
        "lanes": lanes, "block_size": block_size, "kv_dtype": kv_dtype,
        "trace_span": (tracer.started_at, tracer.stopped_at),
    }
