#!/usr/bin/env python3
"""The quickest proof that mxnet_tpu still starts on the chip.

One process, no child. With no option it needs one TPU chip and drives
the two paths users depend on at GPT-2-small width (12 layers, units
768, 12 heads, vocabulary 50,257, context 1024, bf16, random weights
from ``--seed``):

- **train**: ``gluon.model_zoo.bert.gpt_like`` hybridized, a
  ``gluon.Trainer`` with adam, five ``autograd.record()`` /
  ``loss.backward()`` / ``trainer.step()`` steps on one repeated batch
  of 8 x 1024 tokens;
- **serve**: ``serving.LLMEngine`` (int8 KV pools, 2,048 blocks of 16)
  answering eight requests of mixed prompt lengths submitted together,
  checked against the offline ``model_zoo.generation.generate``; then a
  short second engine with float pools, the paged kernel's other arm.

``--chips 4`` runs instead, and only, the sharded path: a ``tp=4`` mesh,
``LLMEngine(mesh=, rules=)`` against a one-device engine, and
``Trainer.shard`` steps against unsharded ones.

Nothing around a phase catches: any exception ends the run non-zero.
The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed.
The lines before it are notes (seconds, memory, which attention path a
program holds), not metrics: nothing in them is claimed.

``--tiny`` shrinks every size so that the control flow can be rehearsed;
it changes no check, the platform check included.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as onp

# The platform every phase must run on. No option changes it; the test
# that rehearses the phases on the CPU steers it from the test.
PLATFORM = "tpu"

TIE_STEPS = 12   # bf16 steps of the logit's own size; see check_tokens


def sizes(tiny: bool, chips: int) -> dict:
    """Every size the phases use. Full = GPT-2-small at gpt_like's own
    defaults; the four-chip vocabulary is padded to 50,304 because every
    sharded dim must divide the mesh axis."""
    if tiny:
        return dict(
            model=dict(vocab_size=96 if chips == 4 else 97, units=32,
                       hidden_size=64, num_layers=2, num_heads=4,
                       max_length=64),
            train_batch=(2, 32), max_context=64, max_running=4,
            prompt_lens=(3, 3, 9, 9, 17, 17, 40, 40), new_tokens=6,
            float_prompt_lens=(5, 5, 20, 20),
            sharded_prompt_lens=(3, 9, 17, 40))
    return dict(
        model=dict(vocab_size=50304 if chips == 4 else 50257,
                   max_length=1024),
        train_batch=(8, 1024), max_context=1024, max_running=32,
        prompt_lens=(16, 16, 48, 48, 200, 200, 768, 768), new_tokens=32,
        float_prompt_lens=(24, 24, 300, 300),
        sharded_prompt_lens=(16, 48, 200, 768))


class Compiles:
    """Counts backend compiles through jax's own monitoring events."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def note(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def attention_path(lowered_text: str) -> str:
    return ("Pallas kernel (tpu_custom_call)"
            if "tpu_custom_call" in lowered_text else "XLA ops, no kernel")


def peak_memory_note(devices) -> str:
    parts = []
    for d in devices:
        stats = d.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        parts.append("n/a" if peak is None else f"{peak / 2**30:.2f} GiB")
    return ", ".join(parts)


def make_net(model_kw, seed):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import bert

    mx.random.seed(seed)
    net = bert.gpt_like(dtype="bfloat16", **model_kw)
    net.initialize()
    return net


def on_device(arr, devices) -> bool:
    return set(arr.devices()) == set(devices)


def raw(nd):
    """The jax array under an mx ndarray (its one pytree leaf)."""
    import jax

    return jax.tree_util.tree_leaves(nd)[0]


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------
def lm_batch(vocab, batch, seq, seed):
    """Tokens and next-token labels; the last position has no next token
    and carries the loss's ignore index."""
    rng = onp.random.RandomState(seed)
    x = rng.randint(0, vocab, (batch, seq)).astype(onp.int32)
    labels = onp.concatenate(
        [x[:, 1:], onp.full((batch, 1), -1, onp.int32)], axis=1)
    return x, labels.reshape(-1)


def train_steps(net, trainer, x, labels, n_steps, compiles):
    """``n_steps`` record/backward/step rounds on one batch. Returns the
    losses, each step's seconds and each step's compile count."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd

    vocab = net.word_embed.weight.shape[0]
    xa, la = mx.np.array(x), mx.np.array(labels)
    losses, secs, ncomp = [], [], []
    for _ in range(n_steps):
        c0, t0 = compiles.n, time.perf_counter()
        with autograd.record():
            logits = net(xa)
            loss = mx.npx.softmax_cross_entropy(
                logits.reshape(-1, vocab), la)
        loss.backward()
        trainer.step(x.size)
        losses.append(float(loss.asnumpy()[0]) / x.size)   # syncs
        secs.append(time.perf_counter() - t0)
        ncomp.append(compiles.n - c0)
    return losses, secs, ncomp


def train_phase(sz, seed, compiles, devices):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import gluon

    net = make_net(sz["model"], seed)
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 3e-4})
    batch, seq = sz["train_batch"]
    x, labels = lm_batch(sz["model"]["vocab_size"], batch, seq, seed)
    losses, secs, ncomp = train_steps(net, trainer, x, labels, 5, compiles)
    note(f"train: losses/token {[round(v, 4) for v in losses]}")
    assert all(onp.isfinite(losses)), losses
    assert losses[4] < losses[0], losses
    assert ncomp[2:] == [0, 0, 0], f"steps 3-5 compiled: {ncomp}"
    for name, p in net.collect_params().items():
        assert on_device(raw(p.data()), devices), name
    # the optimizer's states have no public accessor that stays on the
    # device (states_tree() copies to the host)
    states = jax.tree_util.tree_leaves(trainer._states)
    assert states and all(on_device(s, devices) for s in states)
    fn, params = net.functionalize(mx.np.array(x), training=True)
    text = jax.jit(fn).lower(params, x).as_text()
    note(f"train: attention path = {attention_path(text)}")
    note(f"train: compile {sum(secs[:2]):.1f} s (steps 1-2, "
         f"{sum(ncomp[:2])} programs), steady "
         f"{sum(secs[2:]) / 3:.3f} s/step, peak memory "
         f"{peak_memory_note(devices)}")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------
def make_prompts(vocab, lens, seed):
    rng = onp.random.RandomState(seed + 1)
    return [rng.randint(0, vocab, (p,)).astype(onp.int32) for p in lens]


def reference_tokens(net, prompts, new_tokens, kv_cache_dtype):
    """The offline reference the engine is tested against, prompts of one
    length batched into one ``generate`` call (one program per length)."""
    from mxnet_tpu.gluon.model_zoo.generation import generate

    out = [None] * len(prompts)
    for p_len in sorted({len(p) for p in prompts}):
        idx = [i for i, p in enumerate(prompts) if len(p) == p_len]
        toks = generate(net, onp.stack([prompts[i] for i in idx]),
                        max_new_tokens=new_tokens, greedy=True,
                        kv_cache_dtype=kv_cache_dtype).asnumpy()
        for i, row in zip(idx, toks):
            out[i] = row
    return out


def check_tokens(net, prompts, ref, got, kv_cache_dtype, label,
                 ref_name="the reference"):
    """Hold every token an engine emitted to the dense model.

    Token for token, an engine cannot be compared with ``ref``: on the
    chip the paged kernel and the dense path round differently, and under
    random bf16 weights the top two of 50,257 logits are a few bf16 steps
    apart, so greedy decoding forks within a few tokens and never comes
    back (measured on the v5e: 7 of 8 requests, at token 1 to 13). What
    can be held is each choice on its own. One dense forward over the prompt
    and the engine's own tokens gives the logits the reference model
    would have chosen from at each position; the engine's token must lie
    within ``TIE_STEPS`` bf16 steps of the best one there. A wrong
    block, mask or scale moves logits by whole units, not steps. Where
    ``got`` and ``ref`` fork, the fork is printed, and ``ref``'s token
    is held to the same rule."""
    import mxnet_tpu as mx

    cache_dtype = kv_cache_dtype or onp.dtype(
        net.word_embed.weight.dtype).name
    forks, worst = 0, 0.0
    for i, (prompt, r, g) in enumerate(zip(prompts, ref, got)):
        r, g = onp.asarray(r), onp.asarray(g)
        assert r.shape == g.shape == (len(r),), (label, i, r.shape, g.shape)
        seq = onp.concatenate([prompt, g[:-1]])[None]
        ck, cv = net.init_cache(1, seq.shape[1], dtype=cache_dtype)
        logits, _, _ = net.decode_step(
            mx.np.array(seq), ck, cv, mx.np.array(onp.zeros((), onp.int32)))
        rows = logits[0, len(prompt) - 1:].asnumpy().astype(onp.float32)
        assert rows.shape[0] == len(g) and onp.isfinite(rows).all()
        best = rows.max(axis=-1)
        step = 2.0 ** (onp.floor(onp.log2(onp.abs(best))) - 7)   # bf16
        behind = (best - rows[onp.arange(len(g)), g]) / step
        assert (behind <= TIE_STEPS).all(), (
            f"{label}: request {i}, token {int(behind.argmax())} is "
            f"{behind.max():.1f} bf16 steps behind the dense model's best")
        worst = max(worst, float(behind.max()))
        if not onp.array_equal(r, g):
            at = int(onp.argmax(r != g))       # same prefix up to here
            ref_behind = (best[at] - rows[at, r[at]]) / step[at]
            note(f"{label}: request {i} (prompt {len(prompt)}) forks from "
                 f"{ref_name} at token {at}: {int(g[at])} against "
                 f"{int(r[at])}, {behind[at]:.1f} and {ref_behind:.1f} "
                 f"bf16 steps behind the best logit {best[at]:.3f}")
            assert ref_behind <= TIE_STEPS, (label, i, at, ref_behind)
            forks += 1
    note(f"{label}: {len(prompts) - forks} of {len(prompts)} requests "
         f"token-identical to {ref_name}, {forks} forked at a near-tie; "
         f"every token within {worst:.1f} bf16 steps of the dense model's "
         f"best (limit {TIE_STEPS})")


def run_engine(eng, prompts, new_tokens, compiles, label):
    """Warm the engine up, then submit every prompt together. Returns the
    tokens and asserts that serving compiled nothing."""
    t0 = time.perf_counter()
    eng.warmup(prompt_lengths=[len(p) for p in prompts])
    warm_s = time.perf_counter() - t0
    engine_compiles = eng.stats()["counters"]["compiles"]
    c0, t0 = compiles.n, time.perf_counter()
    handles = [eng.submit(p, new_tokens) for p in prompts]
    got = [h.wait(timeout=600) for h in handles]
    steady_s = time.perf_counter() - t0
    assert eng.stats()["counters"]["compiles"] == engine_compiles, \
        f"{label}: the engine retraced after warm-up"
    assert compiles.n == c0, \
        f"{label}: {compiles.n - c0} programs compiled while serving"
    note(f"{label}: compile {warm_s:.1f} s (warm-up), steady "
         f"{steady_s:.2f} s for {len(prompts)} requests x {new_tokens} "
         "tokens")
    return got


def decode_program_text(eng, compiled=False) -> str:
    """Text of the engine's own decode program (there is no public
    handle on it), lowered — or, for the collectives the partitioner
    adds, compiled — at the shapes the engine runs it with."""
    import jax

    toks = onp.zeros((eng.max_running, 1), onp.int32)
    bt = onp.zeros((eng.max_running, eng.max_blocks_per_seq), onp.int32)
    pos = onp.zeros((eng.max_running,), onp.int32)
    with eng._mesh_ctx():
        lowered = jax.jit(eng._decode.run._fn).lower(
            eng._decode.params, toks, *eng._kv.pools[0], bt, pos, eng._key)
        return lowered.compile().as_text() if compiled \
            else lowered.as_text()


def serve_phase(net, sz, prompt_lens, kv_cache_dtype, seed, compiles,
                devices, label):
    from mxnet_tpu.serving import LLMEngine

    vocab = sz["model"]["vocab_size"]
    prompts = make_prompts(vocab, prompt_lens, seed)
    new_tokens = sz["new_tokens"]
    t0 = time.perf_counter()
    ref = reference_tokens(net, prompts, new_tokens, kv_cache_dtype)
    note(f"{label}: offline reference took {time.perf_counter() - t0:.1f}"
         " s, compiles included")
    eng = LLMEngine(net, kv_cache_dtype=kv_cache_dtype,
                    max_context=sz["max_context"],
                    max_running=sz["max_running"])
    try:
        st = eng.stats()
        note(f"{label}: pools {st['kv_cache_dtype']}, "
             f"{st['pool_blocks_total']} blocks of {st['block_size']}")
        got = run_engine(eng, prompts, new_tokens, compiles, label)
        for pool in eng._kv.pools[0]:
            assert on_device(pool, devices), pool.devices()
        note(f"{label}: paged attention in the decode program = "
             f"{attention_path(decode_program_text(eng))}")
    finally:
        eng.close()
    check_tokens(net, prompts, ref, got, kv_cache_dtype, label,
                 "the offline generate()")
    note(f"{label}: peak memory {peak_memory_note(devices)}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
def sharded_phase(sz, seed, compiles, devices):
    import jax
    from jax.sharding import PartitionSpec as P

    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import use_mesh
    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.parallel.sharding import TRANSFORMER_RULES
    from mxnet_tpu.serving import LLMEngine

    mesh = make_mesh({"tp": 4}, devices=devices)
    vocab = sz["model"]["vocab_size"]
    new_tokens = sz["new_tokens"]

    # serving: the sharded engine against a one-device engine
    net = make_net(sz["model"], seed)
    prompts = make_prompts(vocab, sz["sharded_prompt_lens"], seed)
    kw = dict(max_context=sz["max_context"], max_running=sz["max_running"])
    one = LLMEngine(net, **kw)
    try:
        base = run_engine(one, prompts, new_tokens, compiles, "serve/1")
        assert on_device(one._kv.pools[0][0], devices[:1])
        one_pool_bytes = int(one.metrics.shard_pool_bytes.get())
    finally:
        one.close()
    eng = LLMEngine(net, mesh=mesh, rules=TRANSFORMER_RULES, **kw)
    try:
        got = run_engine(eng, prompts, new_tokens, compiles, "serve/tp4")
        st = eng.stats()["sharding"]
        assert st["devices"] == 4, st
        assert st["pool_bytes_per_device"] * 4 == one_pool_bytes, \
            (st, one_pool_bytes)
        for pool in eng._kv.pools[0]:
            shards = pool.addressable_shards
            assert {s.device for s in shards} == set(devices)
            assert all(s.data.shape[-1] * 4 == pool.shape[-1]
                       for s in shards)          # a row's heads, in groups
        params = eng._decode.params
        sharded = [k for k, v in params.items()
                   if not v.sharding.is_fully_replicated]
        for k in sharded:
            assert {s.device for s in params[k].addressable_shards} \
                == set(devices), k
        assert any("qkv" in k for k in sharded), sharded
        note(f"serve/tp4: {len(sharded)} of {len(params)} parameters "
             f"and both pools spread over 4 devices; pool bytes per "
             f"device {st['pool_bytes_per_device']} = 1/4 of "
             f"{one_pool_bytes}")
        text = decode_program_text(eng, compiled=True)
        found = [c for c in ("all-reduce", "all-gather", "reduce-scatter",
                             "collective-permute", "all-to-all")
                 if c in text]
        assert found, "no collective in the sharded decode program"
        note(f"serve/tp4: compiled decode program holds {found}; paged "
             f"attention = {attention_path(text)}")
    finally:
        eng.close()
    check_tokens(net, prompts, base, got, "int8", "serve/tp4",
                 "the one-device engine")
    del one, eng, net      # device 0 holds the unsharded steps next

    # training: Trainer.shard steps against unsharded steps
    batch, seq = sz["train_batch"]
    x, labels = lm_batch(vocab, batch, seq, seed)

    def three_steps(shard):
        net = make_net(sz["model"], seed)
        net.hybridize()
        trainer = gluon.Trainer(net.collect_params(), "adam",
                                {"learning_rate": 3e-4})
        if not shard:
            return train_steps(net, trainer, x, labels, 3, compiles)[0]
        with use_mesh(mesh):
            specs = trainer.shard(TRANSFORMER_RULES)
            assert any(s != P() for s in specs.values()), specs
            losses = train_steps(net, trainer, x, labels, 3, compiles)[0]
        for name, spec in specs.items():
            if "tp" in str(spec):      # the mesh has no other axis
                w = raw(net.collect_params()[name].data())
                assert {sh.device for sh in w.addressable_shards} \
                    == set(devices) and not w.sharding.is_fully_replicated, \
                    name
        leaves = jax.tree_util.tree_leaves(trainer._states)
        assert any(not s.sharding.is_fully_replicated for s in leaves)
        return losses

    base_losses = three_steps(False)
    tp_losses = three_steps(True)
    note(f"train/1:   losses/token {[round(v, 4) for v in base_losses]}")
    note(f"train/tp4: losses/token {[round(v, 4) for v in tp_losses]}")
    onp.testing.assert_allclose(tp_losses, base_losses, rtol=2e-2)
    note(f"four chips: peak memory {peak_memory_note(devices)}")


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded phase (default 1)")
    ap.add_argument("--tiny", action="store_true",
                    help="toy sizes, to rehearse the control flow")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    found = jax.devices()
    if found[0].platform != PLATFORM or len(found) < args.chips:
        print(f"chip_smoke: needs {args.chips} {PLATFORM} device(s); jax "
              f"found {len(found)} x {found[0].platform}", file=sys.stderr)
        return 1

    from mxnet_tpu.base import arm_compile_cache

    cache_dir = arm_compile_cache()

    def entries():
        return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    before = entries()
    note(f"compile cache at {cache_dir}: {before} entries before")
    devices = found[:args.chips]
    compiles = Compiles()
    sz = sizes(args.tiny, args.chips)
    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase(sz, args.seed, compiles, devices)
    else:
        train_phase(sz, args.seed, compiles, devices)
        net = make_net(sz["model"], args.seed)
        serve_phase(net, sz, sz["prompt_lens"], "int8", args.seed,
                    compiles, devices, "serve/int8")
        serve_phase(net, sz, sz["float_prompt_lens"], None, args.seed,
                    compiles, devices, "serve/float")
    note(f"compile cache at {cache_dir}: {entries()} entries after "
         f"({before} before); {compiles.n} programs compiled; "
         f"{time.perf_counter() - t0:.0f} s in all")
    print(json.dumps({"ok": True, "device": {
        "platform": found[0].platform, "kind": found[0].device_kind,
        "count": len(found)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
