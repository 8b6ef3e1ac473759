"""Times the kernels of the ``qwen3next_like`` serving path alone, on the
chip, at the cell's two shapes: decode (64 lanes) and one prefill chunk
(2,048 tokens of one lane). One JSON line per measurement.

    python3 benchmark/qwen3next_kernels_bench.py [--reps 20] [--only delta]

The grouped expert FFN is timed three ways on the same sorted rows — the
kernel ``moe_grouped_ffn`` (``ops/pallas/moe_ffn.py``), three
``lax.ragged_dot`` and three ``megablox.gmm`` (which does not lower
with ``jax_enable_x64`` on, as the package sets it, and says so) — which
is how the one the program uses was chosen (``PERF.md``, section 6, PR
33). A kernel alone in a program is timed with its launch: its share of a
roofline inside the serving program is the benchmark's. Needs the chip:
nothing here runs in interpret mode.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as onp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

U, F, E, HELD, K = 2048, 512, 512, 128, 10
HV, HK, D = 32, 16, 128
BF = jnp.bfloat16


def timed(fn, args, reps, donate=None):
    """Median milliseconds of ``fn(*args)``; ``donate`` is the index of an
    argument that each call consumes and returns first in its result."""
    out = fn(*args)
    jax.block_until_ready(out)
    took = []
    for _ in range(reps):
        if donate is not None:
            args = list(args)
            args[donate] = out[0] if isinstance(out, (tuple, list)) else out
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        took.append((time.perf_counter() - t0) * 1e3)
    return float(onp.median(took))


def say(**row):
    print(json.dumps(row), flush=True)


def experts(tokens, reps, rng):
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    from mxnet_tpu.ops import experts as ex
    from mxnet_tpu.ops.pallas.moe_ffn import grouped_ffn

    key = jax.random.PRNGKey(rng.randint(2**31))
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (tokens, U), BF)
    logits = jax.random.normal(ks[1], (tokens, E), jnp.float32)
    wg, wu = (0.02 * jax.random.normal(k, (HELD, U, F), BF) for k in ks[2:4])
    wd = 0.02 * jax.random.normal(ks[4], (HELD, F, U), BF)
    idx, _ = ex.route(logits, K)
    order, sizes = jax.jit(ex.sort_by_expert, static_argnums=(1, 2))(
        idx, 0, HELD)
    rows = x[order // K]
    hit, touched = int(sizes.sum()), int((sizes > 0).sum())
    floor_ms = max(touched * 3 * U * F * 2 / 819e9,
                   hit * 6 * U * F / 197e12) * 1e3

    def gmm3(r, s, a, b, c):
        tile = (128 if r.shape[0] < 4096 else 512, 512, 512)
        h = (jax.nn.silu(gmm(r, a, s, jnp.float32, tile))
             * gmm(r, b, s, jnp.float32, tile)).astype(r.dtype)
        return gmm(h, c, s, r.dtype, tile)

    for name, fn in (("moe_grouped_ffn", grouped_ffn),
                     ("ragged_dot_x3", ex.grouped_ffn_jnp),
                     ("megablox_gmm_x3", gmm3)):
        try:
            ms = timed(jax.jit(fn), (rows, sizes, wg, wu, wd), reps)
        except Exception as e:  # noqa: BLE001 — a candidate may not lower
            say(kernel=name, tokens=tokens, error=repr(e)[:300])
            continue
        say(kernel=name, tokens=tokens, rows=int(rows.shape[0]),
            assignments=hit, touched=touched, ms=ms, floor_ms=floor_ms,
            roofline=100 * floor_ms / ms)
    whole = jax.jit(lambda a, i, w: ex.moe_grouped_ffn(
        a, i, w, wg, wu, wd, 0)[0])
    w = jnp.full((tokens, K), 0.1, jnp.float32)
    say(kernel="moe layer (sort + kernel + unsort)", tokens=tokens,
        ms=timed(whole, (x, idx, w), reps), floor_ms=floor_ms)


def delta(reps, rng):
    from mxnet_tpu.ops import gated_delta as gd
    from mxnet_tpu.ops.pallas.gated_delta import gated_delta_step

    lanes = 64
    pool = jnp.asarray(rng.randn(6, lanes + 1, HV, D, D), jnp.float32)
    q, k = (gd.l2norm(jnp.asarray(rng.randn(lanes, HK, D), jnp.float32))
            for _ in range(2))
    v = jnp.asarray(rng.randn(lanes, HV, D), jnp.float32)
    g = -jnp.asarray(rng.rand(lanes, HV), jnp.float32) * 0.01
    beta = jnp.asarray(rng.rand(lanes, HV), jnp.float32)
    slots = jnp.arange(lanes, dtype=jnp.int32)
    floor_ms = lanes * 2 * HV * D * D * 4 / 819e9 * 1e3
    fn = jax.jit(lambda p: gated_delta_step(
        q, k, v, g, beta, p, slots, 2)[::-1], donate_argnums=(0,))
    ms = timed(fn, (pool,), reps, donate=0)
    pool = jnp.asarray(rng.randn(6, lanes + 1, HV, D, D), jnp.float32)
    say(kernel="gated_delta_step", ms=ms, floor_ms=floor_ms,
        roofline=100 * floor_ms / ms)
    c = 2048
    qs, ks = (gd.l2norm(jnp.asarray(rng.randn(c, HK, D), jnp.float32))
              for _ in range(2))
    vs = jnp.asarray(rng.randn(c, HV, D), jnp.float32)
    gs = -jnp.asarray(rng.rand(c, HV), jnp.float32) * 0.01
    bs = jnp.asarray(rng.rand(c, HV), jnp.float32)
    fn = jax.jit(lambda p: gd.delta_chunk(
        qs, ks, vs, gs, bs, p, jnp.int32(3), 2, False, jnp.int32(c))[::-1],
        donate_argnums=(0,))
    say(kernel="delta_chunk (XLA)", tokens=c,
        ms=timed(fn, (pool,), reps, donate=0))


def attention(reps, rng):
    from mxnet_tpu.ops.gated_attention import paged_chunk_attention
    from mxnet_tpu.ops.pallas.paged_attention import paged_attention_kernel

    lanes, nb, bs, mb = 64, 40961, 16, 1088
    pool_k = jnp.zeros((2, nb, bs, 512), BF) + 0.1
    pool_v = jnp.zeros((2, nb, bs, 512), BF) + 0.1
    lengths = onp.exp(rng.uniform(onp.log(2560), onp.log(17400),
                                  lanes)).astype(onp.int32)
    table = onp.full((lanes, mb), nb - 1, onp.int32)
    at = 0
    for i, n in enumerate(lengths):
        blocks = -(-int(n) // bs)
        table[i, :blocks] = (at + onp.arange(blocks)) % (nb - 1)
        at += blocks
    q = jnp.asarray(rng.randn(lanes, 16, 256), jnp.float32)
    fn = jax.jit(lambda a: paged_attention_kernel(
        a, pool_k, pool_v, jnp.asarray(table), jnp.asarray(lengths), 1))
    floor_ms = float(lengths.sum()) * 2048 / 819e9 * 1e3
    ms = timed(fn, (q,), reps)
    say(kernel="paged attention (16 / 2 heads of 256)", lanes=lanes,
        positions=int(lengths.sum()), ms=ms, floor_ms=floor_ms,
        roofline=100 * floor_ms / ms)
    qc = jnp.asarray(rng.randn(2048, 16, 256), jnp.float32)
    for start in (0, 14336):
        fn = jax.jit(lambda a, s=start: paged_chunk_attention(
            a, pool_k, pool_v, jnp.asarray(table[0]), jnp.int32(s), 1))
        pairs = 2048 * start + 2048 * 2049 / 2
        floor_ms = 4 * 16 * 256 * pairs / 197e12 * 1e3
        say(kernel="paged_chunk_attention (XLA loop over key blocks)",
            start=start, ms=timed(fn, (qc,), reps), floor_ms=floor_ms)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--only", choices=("experts", "delta", "attention"))
    args = ap.parse_args()
    import mxnet_tpu  # noqa: F401 — sets jax_enable_x64 as the program does

    say(device=jax.devices()[0].device_kind)
    rng = onp.random.RandomState(0)
    if args.only in (None, "experts"):
        for tokens in (64, 2048):
            experts(tokens, args.reps, rng)
    if args.only in (None, "delta"):
        delta(args.reps, rng)
    if args.only in (None, "attention"):
        attention(args.reps, rng)


if __name__ == "__main__":
    main()
