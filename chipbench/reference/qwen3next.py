"""The plain reference of the ``qwen3next_like`` equations
(Qwen3-Next-80B-A3B: gated delta-rule layers, every fourth a gated
softmax-attention layer, a mixture of experts behind each), and the rule
that decides ``correct`` for its cells.

Float32, ``jax.default_matmul_precision("highest")``, plain ``jax.numpy``:
no kernel, no cache, no chunks, no sort, nothing from ``mxnet_tpu.ops``.
The program prefills in chunks whose delta rule solves a triangular
system per 64 tokens and decodes through a state and paged K/V rows; this
file runs the delta rule **token by token** over the whole sequence
(``lax.scan`` over the recurrence as written) and holds every query
against every earlier key, so the two check each other through different
formulations. It reads the net's parameters by the names
``collect_params()`` gives them and upcasts each where it is used.

For hidden rows ``x (T, 2048)``, layer ``i`` (full attention where
``(i + 1) % 4 == 0``), ``RMS0(x; w) = x / sqrt(mean(x^2) + eps) (1 + w)``::

    h = RMS0(x; input_norm);  x = x + Mixer_i(h)
    h2 = RMS0(x; post_norm);  x = x + MoE(h2)
    logits = RMS0(x_L; final_norm) W_head                     (untied)

    Gated attention (16 query heads, 2 K/V heads of 256, no bias):
      [q | gate] = h Wq  per head;  q = RMS0_head(q; q_norm)
      k = RMS0_head(h Wk; k_norm);  v = h Wv
      q, k: rotary on the first 64 of a head's values (halves of those 64
            rotate, pair n by t * theta^(-2n/64)), the rest unchanged
      a = softmax(q k^T / 16) causal, query head j reads K/V head j // 8
      out = ((a v) * sigmoid(gate)) Wo

    Gated DeltaNet (16 key and 32 value heads of 128; value head j uses
    key head j // 2):
      [q | k | v | z] = h Wqkvz;   [b | a] = h Wba
      [q | k | v] = silu(causal depthwise conv, 4 taps, over their 8,192
                    channels: y[t] = sum_j w[j] x[t - 3 + j])
      beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
      q = q / |q| / sqrt(128);  k = k / |k|          (eps under the root)
      S = exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S = S + k_t d^T
      o_t = S^T q_t;   o = out_norm * o / rms(o) * silu(z);   out = o Wout

    MoE (router over all 512, 10 per token; experts ``first .. first +
    held - 1`` held here; one shared expert):
      p = softmax(h2 Wr);  top = the 10 largest;  w_e = p_e / sum_top p
      y = sum_{e in top, held} w_e (silu(h2 Wg_e) * (h2 Wu_e)) Wd_e
      out = y + sigmoid(h2 . w_sg) E_shared(h2)

**The share.** The reference is given the same share of the experts as
the program (``sizes["experts_held"]`` from ``sizes["first_expert"]``):
what the absent experts would have added is left out in both, and that
partial result goes on to the next layer. The held experts are a plain
loop: each is computed for the rows routed to it alone, picked out of
the routing on the host (every one of them: there is no capacity).

So that it fits beside 7.3 GB of bfloat16 weights it is computed in
blocks, none of which changes a value: one layer's weights upcast at a
time, the experts one at a time, query rows in blocks of ``Q_BLOCK``, the
head on the checked rows alone.

**Routing near a tie.** With 512 experts the 10th and 11th probabilities
lie close, so a bfloat16 program and this float32 file choose differently
for some tokens and layers whatever the weights; the reference routes by
its own logits all the same. ``TIE_STEPS`` and ``STATE_LIMIT`` are each
set between two readings on the chip — the sound program's worst over its
seeds, and a control's (the state rounded to bfloat16 after every
program; the shared expert's gate dropped) — given beside them below and
in ``PERF.md``, section 6 (PR 33).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as onp

from chipbench.reference.gpt import bf16_steps_behind  # noqa: F401

Q_BLOCK = 512
PROBES = 8
# Steps of bf16, of the best logit's own size, that an emitted token may
# lie behind the reference's best logit (``reference/gpt.py``'s rule).
# Between two readings on the chip (PERF.md, section 6, PR 33): the sound
# program's worst, 14.2 over twenty-one runs on distinct seeds (7.0 to
# 11.8 in the other twenty: a bfloat16 program and a float32 reference
# take another expert for some tokens and layers, whatever the weights),
# and the controls': 189.2 and 186.8 with the shared expert's gate dropped,
# 391.4 with the convolution's tail kept one token late.
TIE_STEPS = 28
# The states' own rule (``state_apart``): how far what a state of the timed
# engine answers may lie from the recurrence's, relative to it. ``S``: the
# largest over the delta-rule layers, so most of a sound reading is the
# drift of the bfloat16 hidden rows with depth; the sound program read 5.98%
# to 7.04% in those runs, the control whose state is rounded to bfloat16
# after every program 12.0%, 12.6% and 13.4%. ``S_first``: the first
# layer's ``S`` alone — its input is the embedding, so neither drift nor
# routing lies before it and the state's own arithmetic is what is read:
# sound 0.46% (PERF.md has the later runs' range), the bfloat16 control
# 2.47%. ``tail``: sound 2.89% to 4.31%; the tail one token late 142%, the
# shared gate dropped 57%.
STATE_LIMIT = {"S": 0.095, "S_first": 0.012, "tail": 0.08}

F32 = jnp.float32


def _rms0(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w.astype(F32))


def _rope(x, theta, rot):
    """``x (T, H, D)`` at positions ``0..T-1``: the first ``rot`` values
    of each head, halves against each other."""
    t = x.shape[0]
    half = rot // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    angle = jnp.arange(t, dtype=F32)[:, None, None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    lo, hi = x[..., :half], x[..., half:rot]
    return jnp.concatenate(
        [lo * cos - hi * sin, hi * cos + lo * sin, x[..., rot:]], -1)


def _attention(h, p, sz):
    t = h.shape[0]
    nh, hk, d = sz["num_heads"], sz["num_kv_heads"], sz["head_dim"]
    eps = sz["epsilon"]
    qg = (h @ p["mixer.q_proj.weight"].astype(F32).T).reshape(t, nh, 2 * d)
    q = _rms0(qg[..., :d], p["mixer.q_norm"], eps)
    k = _rms0((h @ p["mixer.k_proj.weight"].astype(F32).T)
              .reshape(t, hk, d), p["mixer.k_norm"], eps)
    v = (h @ p["mixer.v_proj.weight"].astype(F32).T).reshape(t, hk, d)
    q = _rope(q, sz["rope_theta"], sz["rotary_dim"])
    k = _rope(k, sz["rope_theta"], sz["rotary_dim"])
    out = []
    for lo in range(0, t, Q_BLOCK):
        hi = min(lo + Q_BLOCK, t)
        qb = q[lo:hi].reshape(hi - lo, hk, nh // hk, d)
        s = jnp.einsum("tjgd,sjd->jgts", qb, k[:hi]) / jnp.sqrt(F32(d))
        seen = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("jgts,sjd->tjgd", a, v[:hi])
                   .reshape(hi - lo, nh, d))
    o = jnp.concatenate(out) * jax.nn.sigmoid(qg[..., d:])
    return o.reshape(t, nh * d) @ p["mixer.o_proj.weight"].astype(F32).T


def _delta(h, p, sz, n, probes):
    """The delta-rule mixer over rows ``h (T, units)``, token by token;
    beside its output, what the state after the first ``n`` tokens
    answers to ``probes`` (``S^T u``: ``(Hv, P, dv)``) and the last
    ``W - 1`` inputs of the convolution before token ``n``."""
    t = h.shape[0]
    hk, hv = sz["linear_key_heads"], sz["linear_value_heads"]
    dk, dv = sz["linear_key_dim"], sz["linear_value_dim"]
    width, eps = sz["conv_width"], sz["epsilon"]
    ch = 2 * hk * dk + hv * dv
    mixed = h @ p["mixer.qkvz_proj.weight"].astype(F32).T
    ba = h @ p["mixer.ba_proj.weight"].astype(F32).T
    qkv, z = mixed[:, :ch], mixed[:, ch:].reshape(t, hv, dv)
    w = p["mixer.conv"].astype(F32)                     # (W, ch)
    xpad = jnp.concatenate([jnp.zeros((width - 1, ch), F32), qkv])
    y = jax.nn.silu(sum(w[j] * xpad[j:j + t] for j in range(width)))
    tail = jax.lax.dynamic_slice_in_dim(xpad, n, width - 1, 0)
    q = y[:, :hk * dk].reshape(t, hk, dk)
    k = y[:, hk * dk:2 * hk * dk].reshape(t, hk, dk)
    v = y[:, 2 * hk * dk:].reshape(t, hv, dv)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(p["mixer.a_log"].astype(F32)) * jax.nn.softplus(
        ba[:, hv:] + p["mixer.dt_bias"].astype(F32))

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)

    q = jnp.repeat(unit(q) / jnp.sqrt(F32(dk)), hv // hk, axis=1)
    k = jnp.repeat(unit(k), hv // hk, axis=1)

    def token(carry, x):
        s, kept = carry
        q_t, k_t, v_t, g_t, b_t, i = x
        s = jnp.exp(g_t)[:, None, None] * s
        d = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[:, :, None] * d[:, None, :]
        kept = jnp.where(i == n - 1, s, kept)
        return (s, kept), jnp.einsum("hkv,hk->hv", s, q_t)

    zero = jnp.zeros((hv, dk, dv), F32)
    (_, kept), o = jax.lax.scan(
        token, (zero, zero), (q, k, v, g, beta, jnp.arange(t)))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
    o = p["mixer.out_norm"].astype(F32) * o * jax.nn.silu(z)
    out = o.reshape(t, hv * dv) @ p["mixer.out_proj.weight"].astype(F32).T
    return out, (jnp.einsum("hkv,pk->hpv", kept, probes), tail)


def _route(h2, p, sz, n):
    """``(experts (T, k), weights (T, k))`` by the reference's own
    logits; rows from ``n`` on (padding) get weight 0."""
    probs = jax.nn.softmax(h2 @ p["experts.router.weight"].astype(F32).T, -1)
    w, idx = jax.lax.top_k(probs, sz["experts_per_token"])
    w = w / jnp.sum(w, -1, keepdims=True)
    return idx, jnp.where((jnp.arange(h2.shape[0]) < n)[:, None], w, 0.0)


@functools.partial(jax.jit, donate_argnums=(0,))
def _one_expert(y, h2, rows, scale, gate, up, down, e):
    """``y`` plus expert ``e``'s result for the rows ``rows`` of ``h2``
    (padded with an index past the last row, which reads zeros and
    writes nowhere), each scaled by its routing weight ``scale``."""
    with jax.default_matmul_precision("highest"):
        x = jnp.take(h2, rows, axis=0, mode="fill", fill_value=0.0)
        out = (jax.nn.silu(x @ gate[e].astype(F32))
               * (x @ up[e].astype(F32))) @ down[e].astype(F32)
        return y.at[rows].add(out * scale[:, None], mode="drop")


def _experts(h2, idx, w, p, sz):
    """The held experts' part, a plain loop over them: each for the rows
    routed to it and no others, picked out on the host from the routing
    (every one of them: the padding up to a power of two only keeps the
    compiled shapes few), one expert's weights upcast at a time."""
    t = h2.shape[0]
    idx, w = onp.asarray(idx), onp.asarray(w)
    y = jnp.zeros_like(h2)
    for e in range(sz["experts_held"]):
        we = onp.where(idx == sz["first_expert"] + e, w, 0.0).sum(-1)
        rows, = onp.nonzero(we > 0)
        if not len(rows):
            continue
        bound = max(64, 1 << (len(rows) - 1).bit_length())
        padded = onp.full((bound,), t, onp.int32)
        padded[:len(rows)] = rows
        scale = onp.zeros((bound,), onp.float32)
        scale[:len(rows)] = we[rows]
        y = _one_expert(y, h2, padded, scale, p["experts.gate"],
                        p["experts.up"], p["experts.down"], e)
    return y


def _shared(h2, p):
    def dense(a, name):
        return a @ p["experts.shared." + name + ".weight"].astype(F32).T

    gate = jax.nn.sigmoid(h2 @ p["experts.shared_gate.weight"].astype(F32).T)
    return gate * dense(jax.nn.silu(dense(h2, "gate_proj"))
                        * dense(h2, "up_proj"), "down_proj")


_STATIC = ("epsilon", "num_heads", "num_kv_heads", "head_dim", "rotary_dim",
           "rope_theta", "linear_key_heads", "linear_value_heads",
           "linear_key_dim", "linear_value_dim", "conv_width",
           "experts_per_token", "experts_held", "first_expert")


def _freeze(sz: dict) -> tuple:
    return tuple((k, sz[k]) for k in _STATIC)


@functools.partial(jax.jit, static_argnames=("full", "frozen"))
def _mixer_jit(x, p, n, probes, full, frozen):
    """The layer up to its routing: rows after the mixer, their normed
    form and the routing."""
    sz = dict(frozen)
    with jax.default_matmul_precision("highest"):
        h = _rms0(x, p["input_norm"], sz["epsilon"])
        if full:
            mixed, read = _attention(h, p, sz), None
        else:
            mixed, read = _delta(h, p, sz, n, probes)
        x = x + mixed
        h2 = _rms0(x, p["post_norm"], sz["epsilon"])
        idx, w = _route(h2, p, sz, n)
        return x, h2, idx, w, read


@jax.jit
def _shared_jit(x, y, h2, p):
    with jax.default_matmul_precision("highest"):
        return x + y + _shared(h2, p)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_jit(rows, w, head, eps):
    with jax.default_matmul_precision("highest"):
        return _rms0(rows, w, eps) @ head.astype(F32).T


def hidden(params, tokens, sz: dict, n=None, probes=None):
    """``(T,)`` token ids -> the last layer's ``(T, units)`` rows, before
    the final norm, and every delta-rule layer's readings after the
    first ``n`` tokens (all of them, unless given; rows from ``n`` on are
    padding: they reach no expert and no checked value). One sequence,
    one layer's weights upcast at a time."""
    x = params["word_embed.weight"][jnp.asarray(tokens, jnp.int32)] \
        .astype(F32)
    n = jnp.int32(len(tokens) if n is None else n)
    if probes is None:
        probes = jnp.zeros((PROBES, sz["linear_key_dim"]), F32)
    frozen, read = _freeze(sz), []
    for i in range(sz["num_layers"]):
        pre = f"layer{i}."
        p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        full = (i + 1) % sz["full_attention_interval"] == 0
        x, h2, idx, w, got = _mixer_jit(x, p, n, probes, full, frozen)
        if got is not None:
            read.append(got)
        x = _shared_jit(x, _experts(h2, idx, w, p, sz), h2, p)
    return x, read


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
    teacher-forced over prompt + answer. The sequence is padded to
    ``pad_to`` and the checked rows to ``rows_to``, so that one compiled
    program serves every sample; nothing after a row reaches it (every
    form here is causal, and the padding reaches no expert)."""
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
    """``PROBES`` unit vectors of a key head's size, drawn from the seed:
    the queries a state is asked."""
    u = onp.random.RandomState((seed + 5) % 2**32).randn(
        PROBES, sz["linear_key_dim"])
    return jnp.asarray(u / onp.linalg.norm(u, axis=-1, keepdims=True), F32)


def state_apart(params, tokens, n: int, got, sz: dict, pad_to: int,
                seed: int):
    """How far the state a program holds after the first ``n`` of
    ``tokens`` lies from what this file's recurrence says it must answer.
    ``got`` is ``(S^T u (Ld, Hv, P, dv), tail (Ld, W - 1, channels))``:
    the program's state read with ``probes(sz, seed)`` and its
    convolution tail. Returns ``{"S": ..., "S_first": ..., "tail": ...}``:
    for each, the largest relative distance (norm of the difference over
    the norm of the reference's reading) over the layers (and, for ``S``,
    the value heads; ``S_first`` is the first layer's ``S`` alone, whose
    input is the embedding: no drift and no routing lie before it), which
    ``STATE_LIMIT``'s numbers are compared with."""
    padded = onp.zeros((pad_to,), onp.int32)
    padded[:n] = onp.asarray(tokens)[:n]
    _, read = hidden(params, padded, sz, n, probes(sz, seed))
    want_s = onp.stack([onp.asarray(r[0]) for r in read])
    want_t = onp.stack([onp.asarray(r[1]) for r in read])
    got_s, got_t = (onp.asarray(a, onp.float32) for a in got)
    got_t = got_t.reshape(want_t.shape)     # however the rows are folded

    def worst(a, b, axes):
        rel = onp.sqrt(((a - b) ** 2).sum(axes) / (b ** 2).sum(axes))
        return float(rel.max()) if onp.isfinite(rel).all() else float("inf")

    return {"S": worst(got_s, want_s, (-2, -1)),
            "S_first": worst(got_s[0], want_s[0], (-2, -1)),
            "tail": worst(got_t, want_t, (-2, -1))}
