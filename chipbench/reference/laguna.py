"""The plain reference of the ``laguna_like`` equations (Laguna-S-2.1:
one full-attention layer to three sliding-window layers, 48 and 72 query
heads over 8 K/V heads of 128 by kind, a rotary rule by kind, one sigmoid
gate a head on the attention's output, a leading dense layer and a
mixture of experts behind every other), and the rule that decides
``correct`` for its cells.

Float32, ``jax.default_matmul_precision("highest")``, plain ``jax.numpy``:
no kernel, no cache, no ring, no chunk, nothing from ``mxnet_tpu.ops``.
The program prefills in chunks of 1,024, keeps a window layer's last 512
rows in a ring a lane and decodes through paged blocks and the ring; this
file is one forward over the whole sequence with the band written as a
mask over explicit positions. It reads the net's parameters by the names
``collect_params()`` gives them and upcasts each where it is used.

For hidden rows ``x (T, 3072)``, layer ``i`` of kind ``layer_types[i]``
with ``H = heads_per_layer[i]`` query heads, ``RMS(x; w) = x / sqrt(mean(
x^2) + eps) * w``, token ``t`` at position ``t``::

    h = RMS(x; input_norm);  x = x + Attention_i(h)
    h2 = RMS(x; post_norm);  x = x + FFN_i(h2)
    logits = RMS(x_L; final_norm) W_head                      (untied)

    Attention (H query heads, 8 K/V heads of 128, no bias, no q/k norm):
      q = h Wq (H, 128);  k = h Wk, v = h Wv (8, 128);  g = sigmoid(h Wg) (H,)
      q, k: rotary. sliding: pair n of all 64 pairs of a head turns by
            t * 10000^(-2n/128), halves against each other. full: YaRN
            on the first 64 values (32 pairs), theta 500,000, factor 128,
            original length 8,192, beta_fast 32, beta_slow 1
            (``yarn_frequencies``: the closed form), cos and sin times
            ``attention_factor``; the other 64 values unchanged
      a_h = softmax_j(q_h . k_{h // (H/8), j} / sqrt(128)) v_j over the j
            the mask admits: full j <= t; sliding t - 512 < j <= t
      out = concat_h(g_h a_h) Wo

    FFN: layer in ``dense_layers``: (silu(h2 Wg) * (h2 Wu)) Wd of 12,288
    otherwise (router over all 256, 10 per token; experts ``first ..
    first + held - 1`` held here; one shared expert, ungated):
      p = softmax(h2 Wr);  top = the 10 largest;  w_e = 2.5 p_e / sum_top p
      y = sum_{e in top, held} w_e (silu(h2 Wg_e) * (h2 Wu_e)) Wd_e
      out = y + E_shared(h2)

**The share.** As ``reference/qwen3next.py``: the reference is given the
same share of the experts as the program (``sizes["experts_held"]`` from
``sizes["first_expert"]``), a plain loop over them (that file's
``_experts``), routing by its own float32 logits; what the absent experts
would have added is left out in both.

**Departures, none of which changes a value.** So that 33,792 positions
fit beside 8.65 GB of bfloat16 weights it is computed in blocks: one
layer's weights upcast at a time, the experts one at a time, query rows
in blocks of ``Q_BLOCK`` (a full layer's block against every key under
the causal mask; a sliding layer's block against the ``Q_BLOCK`` + 512
keys at the positions its band can reach, under the band mask — the keys
outside are the ones the mask would set to zero weight), the dense FFN
and the projections by the same row blocks, the head on the checked rows
alone.

``TIE_STEPS`` and ``STATE_LIMIT`` are each set between two readings on
the chip, given beside them below and in ``PERF.md``, section 6 (PR 36).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as onp

from chipbench.reference.gpt import bf16_steps_behind  # noqa: F401
from chipbench.reference.qwen3next import _experts

Q_BLOCK = 256
# Steps of bf16, of the best logit's own size, that an emitted token may
# lie behind the reference's best logit (``reference/gpt.py``'s rule).
# Between two readings on the chip (PERF.md, section 6, PR 36; my chip
# runs, PR 36, each control once on a seed of its own). The sound program:
# 8.7 to 22.6 over twenty-two runs on distinct seeds (a bfloat16 program
# and a float32 reference take another expert for some tokens and layers,
# whatever the weights). The nearest control: K/V rows rounded to
# float8_e4m3fn's bits before they are stored, 33.5 and, run again at
# this limit, 42.0 — a precision under the bfloat16 the configuration
# states, and the only reading of ``correct`` that reaches the three full
# layers' rows, which the rings' rule below does not read: the limit
# stands between the two. The other
# controls: the routed scale 2.5 dropped 50.8; the head-wise gate dropped
# 294.1; the YaRN factor on cos and sin dropped 394.9; a last chunk's
# padding written into the ring 178.7. **What this rule cannot see on
# the chip:** a chunk's band one position short (13.0) or long (10.9) and
# a ring row written one token late (15.0) read as the sound program does
# — one key of 512 is under the bfloat16 program's own distance; the CPU
# tests hold the band at windows of 16 and 32, and the rings' rule the
# late row.
TIE_STEPS = 28
# The rings' own rule (``state_apart``): how far the rows a window
# layer's ring of the timed engine holds may lie from this file's K and V
# rows of the same positions, relative to them. ``rows``: the largest
# over the nine window layers and the two of K and V (most of a sound
# reading is the drift of the bfloat16 hidden rows with depth): sound
# 3.66% to 4.04% over twenty-one runs; rows rounded to float8_e4m3fn's
# bits 15.4% and 15.6% (two runs), the scale dropped 17.1%, padding in
# the ring 68.9%, a row one token late 141%.
# ``rows_first``: the first window layer's alone, whose input has one
# layer before it, so that the stored row's own rounding is most of it:
# sound 1.11% to 1.29%; float8's bits 9.19% in both runs; the gate
# dropped 60%, a row one token late 141%.
STATE_LIMIT = {"rows": 0.07, "rows_first": 0.03}

F32 = jnp.float32
FULL = "full_attention"


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def yarn_frequencies(sz: dict):
    """The 32 frequencies of a full layer's rotary pairs, by the closed
    form: ``f_i = theta^(-2i/64)``; ``low = floor(64 ln(L / (beta_fast 2
    pi)) / (2 ln theta))``, ``high = ceil(64 ln(L / (beta_slow 2 pi)) / (2
    ln theta))``, both clipped to 0..63; ``r_i = clip((i - low) / (high -
    low), 0, 1)``; the frequency is ``f_i (1 - r_i) + (f_i / s) r_i``."""
    rot, theta = sz["rotary_dim"], sz["rope_theta"]
    span, s = sz["yarn_original"], sz["yarn_factor"]
    i = onp.arange(rot // 2, dtype=onp.float64)
    f = theta ** (-2.0 * i / rot)

    def pair(turns):
        return rot * math.log(span / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = min(max(math.floor(pair(sz["yarn_beta_fast"])), 0), rot - 1)
    high = min(max(math.ceil(pair(sz["yarn_beta_slow"])), 0), rot - 1)
    r = onp.clip((i - low) / (high - low), 0.0, 1.0)
    return (f * (1 - r) + f / s * r).astype(onp.float32)


def _rotate(x, pos, freq, scale):
    """``x (T, H, D)`` at positions ``pos (T,)``: the first ``2 *
    len(freq)`` values of each head, halves against each other."""
    half = len(freq)
    angle = pos.astype(F32)[:, None, None] * jnp.asarray(freq, F32)
    cos, sin = jnp.cos(angle) * F32(scale), jnp.sin(angle) * F32(scale)
    lo, hi = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate(
        [lo * cos - hi * sin, hi * cos + lo * sin, x[..., 2 * half:]], -1)


def kind_of(sz, i):
    """Layer ``i``'s ``(is it a full layer, its query heads)``."""
    kinds, heads = sz["layer_types"], sz["heads_per_layer"]
    return kinds[i % len(kinds)] == FULL, heads[i % len(heads)]


def _rule(sz, full):
    """A kind's ``(frequencies, scale on cos and sin, window)``."""
    if full:
        return yarn_frequencies(sz), sz["yarn_attention_factor"], None
    rot = sz["window_rotary_dim"]
    freq = sz["window_rope_theta"] ** (
        -2.0 * onp.arange(rot // 2, dtype=onp.float64) / rot)
    return freq.astype(onp.float32), 1.0, sz["window"]


def _attention(h, p, sz, full, nh):
    """A layer's attention (``full`` or sliding, ``nh`` query heads) over
    rows ``h (T, units)``, a block of query rows at a time; beside it the
    rotated K and the V rows ``(T, 8 * 128)`` (what a cache of this layer
    must hold)."""
    t = h.shape[0]
    freq, scale, window = _rule(sz, full)
    hk, d = sz["num_kv_heads"], sz["head_dim"]
    qb = math.gcd(t, Q_BLOCK)
    pos = jnp.arange(t, dtype=jnp.int32)
    wq, wk, wv, wg, wo = (p["mixer." + name + "_proj.weight"].astype(F32)
                          for name in "qkvgo")
    k = _rotate((h @ wk.T).reshape(t, hk, d), pos, freq, scale)
    v = (h @ wv.T).reshape(t, hk, d)
    # a full layer's block meets every key; a sliding layer's the window
    # before its first row and its own rows (zeros stand before position 0)
    reach = t if window is None else min(window, t) + qb
    lead = 0 if window is None else reach - qb
    kp = jnp.concatenate([jnp.zeros((lead, hk, d), F32), k])
    vp = jnp.concatenate([jnp.zeros((lead, hk, d), F32), v])

    def block(b):
        rows = jax.lax.dynamic_slice_in_dim(h, b * qb, qb)
        pq = b * qb + jnp.arange(qb, dtype=jnp.int32)
        q = _rotate((rows @ wq.T).reshape(qb, nh, d), pq, freq,
                    scale).reshape(qb, hk, nh // hk, d)
        gate = jax.nn.sigmoid(rows @ wg.T)
        first = 0 if window is None else b * qb
        kk = jax.lax.dynamic_slice_in_dim(kp, first, reach)
        vv = jax.lax.dynamic_slice_in_dim(vp, first, reach)
        pk = first - lead + jnp.arange(reach, dtype=jnp.int32)
        seen = (pk[None, :] <= pq[:, None]) & (pk[None, :] >= 0)
        if window is not None:
            seen = seen & (pk[None, :] > pq[:, None] - window)
        s = jnp.einsum("tjgd,sjd->jgts", q, kk) / jnp.sqrt(F32(d))
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        o = jnp.einsum("jgts,sjd->tjgd", a, vv).reshape(qb, nh, d)
        return (o * gate[..., None]).reshape(qb, nh * d) @ wo.T

    out = jax.lax.map(block, jnp.arange(t // qb, dtype=jnp.int32))
    return out.reshape(t, -1), k.reshape(t, hk * d), v.reshape(t, hk * d)


def _ffn(h2, p, prefix):
    """``(silu(h2 Wg) * (h2 Wu)) Wd``, a block of rows at a time."""
    gate, up, down = (p[prefix + name + "_proj.weight"].astype(F32).T
                      for name in ("gate", "up", "down"))
    qb = math.gcd(h2.shape[0], Q_BLOCK)
    out = jax.lax.map(lambda a: (jax.nn.silu(a @ gate) * (a @ up)) @ down,
                      h2.reshape(-1, qb, h2.shape[1]))
    return out.reshape(h2.shape)


def _route(h2, p, sz, n):
    """``(experts (T, k), weights (T, k))`` by the reference's own
    logits, the weights renormalised over the ``k`` and times the routed
    scale; rows from ``n`` on (padding) get weight 0."""
    probs = jax.nn.softmax(h2 @ p["experts.router.weight"].astype(F32).T, -1)
    w, idx = jax.lax.top_k(probs, sz["experts_per_token"])
    w = w / jnp.sum(w, -1, keepdims=True) * F32(sz["routed_scale"])
    return idx, jnp.where((jnp.arange(h2.shape[0]) < n)[:, None], w, 0.0)


_STATIC = ("epsilon", "layer_types", "heads_per_layer", "num_kv_heads",
           "head_dim", "window", "rope_theta", "rotary_dim", "yarn_factor",
           "yarn_original", "yarn_beta_fast", "yarn_beta_slow",
           "yarn_attention_factor", "window_rope_theta",
           "window_rotary_dim", "experts_per_token", "routed_scale")


def _freeze(sz: dict) -> tuple:
    return tuple((k, sz[k]) for k in _STATIC)


@functools.partial(jax.jit,
                   static_argnames=("full", "nh", "dense", "frozen"))
def _layer_jit(x, p, n, full, nh, dense, frozen):
    """A layer up to its routing: behind a dense FFN the finished rows;
    otherwise the rows after the mixer, their normed form and the
    routing. And the layer's K and V rows."""
    sz = dict(frozen)
    with jax.default_matmul_precision("highest"):
        mixed, k, v = _attention(_rms(x, p["input_norm"], sz["epsilon"]), p,
                                 sz, full, nh)
        x = x + mixed
        h2 = _rms(x, p["post_norm"], sz["epsilon"])
        if dense:
            return x + _ffn(h2, p, "ffn."), None, None, None, k, v
        idx, w = _route(h2, p, sz, n)
        return x, h2, idx, w, k, v


@jax.jit
def _shared_jit(x, y, h2, p):
    with jax.default_matmul_precision("highest"):
        return x + y + _ffn(h2, p, "experts.shared.")


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_jit(rows, w, head, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(rows, w, eps) @ head.astype(F32).T


def hidden(params, tokens, sz: dict, n=None, keep=None):
    """``(T,)`` token ids -> the last layer's ``(T, units)`` rows, before
    the final norm (rows from ``n`` on are padding: they reach no expert
    and no checked value). With ``keep = (first, count)`` also every
    sliding layer's rotated K and V rows ``first .. first + count - 1``.
    One sequence, one layer's weights upcast at a time."""
    x = params["word_embed.weight"][jnp.asarray(tokens, jnp.int32)] \
        .astype(F32)
    n = jnp.int32(len(tokens) if n is None else n)
    frozen, kept = _freeze(sz), []
    for i in range(sz["num_layers"]):
        pre = f"layer{i}."
        p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        full, nh = kind_of(sz, i)
        x, h2, idx, w, k, v = _layer_jit(
            x, p, n, full, nh, i in sz["dense_layers"], frozen)
        if h2 is not None:
            x = _shared_jit(x, _experts(h2, idx, w, p, sz), h2, p)
        if keep is not None and not full:
            kept.append((k[keep[0]:keep[0] + keep[1]],
                         v[keep[0]:keep[0] + keep[1]]))
    return x, kept


def logits(params, tokens, sz: dict, rows=None, n=None):
    """Float32 logits of ``tokens``' rows (all of them, or ``rows``)."""
    x, _ = hidden(params, tokens, sz, n)
    if rows is not None:
        x = x[jnp.asarray(rows, jnp.int32)]
    return _head_jit(x, params["final_norm"], params["lm_head.weight"],
                     sz["epsilon"])


def tokens_behind(params, prompt, emitted, sz: dict, pad_to: int,
                  rows_to: int):
    """For one answered request, how many bf16 steps each emitted token
    lies behind the reference's best logit at its position,
    teacher-forced over prompt + answer (``reference/qwen3next.py``'s, by
    this file's ``logits``). The sequence is padded to ``pad_to`` and the
    checked rows to ``rows_to``, so that one compiled program serves
    every sample; nothing after a row reaches it (every mask here is
    causal, and the padding reaches no expert)."""
    prompt, emitted = onp.asarray(prompt), onp.asarray(emitted)
    seq = onp.concatenate([prompt, emitted]).astype(onp.int32)
    if len(seq) > pad_to or len(emitted) > rows_to:
        raise ValueError(f"sequence {len(seq)} / answer {len(emitted)} "
                         f"longer than {pad_to} / {rows_to}")
    padded = onp.zeros((pad_to,), onp.int32)
    padded[:len(seq)] = seq
    rows = onp.full((rows_to,), len(prompt) - 1, onp.int32)
    rows[:len(emitted)] = onp.arange(len(prompt) - 1, len(seq) - 1)
    picked = onp.zeros((rows_to,), onp.int32)
    picked[:len(emitted)] = emitted
    got = logits(params, padded, sz, rows, len(seq))
    best = onp.asarray(got.max(-1))[:len(emitted)]
    chosen = onp.asarray(jnp.take_along_axis(
        got, jnp.asarray(picked)[:, None], axis=-1))[:len(emitted), 0]
    if not (onp.isfinite(best).all() and onp.isfinite(chosen).all()):
        return onp.full(len(emitted), onp.inf)
    return bf16_steps_behind(best, chosen)


def probes(sz: dict, seed: int):
    """Nothing: a ring's rows are read as they are, not asked."""
    return None


def state_apart(params, tokens, n: int, got, sz: dict, pad_to: int,
                seed: int):
    """How far the rings a program holds after the first ``n`` of
    ``tokens`` lie from the rows this file computes for the same
    positions. ``got`` is ``(K, V)``, each ``(Lw, W, 8 * 128)``: every
    sliding layer's ring of one lane, position ``p`` in row ``p mod W``.
    The live rows are those of positions ``max(0, n - W) .. n - 1``.
    Returns ``{"rows": ..., "rows_first": ...}``: the largest relative
    distance (norm of the difference over the norm of the reference's
    rows) over the layers and the two of K and V, and the first sliding
    layer's alone; ``STATE_LIMIT``'s numbers are compared with them."""
    w = sz["window"]
    first = max(0, n - w)
    padded = onp.zeros((pad_to,), onp.int32)
    padded[:n] = onp.asarray(tokens)[:n]
    _, kept = hidden(params, padded, sz, n, (first, n - first))
    at = onp.arange(first, n) % w
    apart = []
    for layer, want in enumerate(kept):
        for mine, theirs in zip(got, want):
            a = onp.asarray(mine, onp.float32)[layer][at]
            b = onp.asarray(theirs, onp.float32)
            rel = onp.sqrt(((a - b) ** 2).sum() / (b ** 2).sum())
            apart.append(float(rel) if onp.isfinite(rel) else float("inf"))
    return {"rows": max(apart), "rows_first": max(apart[:2])}
