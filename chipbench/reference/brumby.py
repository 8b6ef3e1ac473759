"""The plain reference of the ``brumby_like`` equations (Brumby-14B-Base:
power retention in every layer), in the **quadratic form**, and the rule
that decides ``correct`` for its cells.

Float32, ``jax.default_matmul_precision("highest")``, plain ``jax.numpy``:
no kernel, no state, no chunks, no cache, nothing from ``mxnet_tpu.ops``.
The program computes a recurrence over a fixed-size state (decode) and a
chunked form that carries it (prefill); this file computes neither — it
holds every token against every earlier token — so the two check each
other through different formulations. It reads the net's parameters by the
names ``collect_params()`` gives them and upcasts each where it is used.

The equations, per layer, for hidden rows ``x (T, units)``::

    h    = rms(x; input_norm)                          eps from the config
    q    = rms_head(h Wq)   (Hq heads of d)     k = rms_head(h Wk)  (Hk)
    v    = h Wv  (Hk)                           no bias on these four
    q, k = rope(q, k; theta, position t)        halves rotate, pair n by
                                                t * theta^(-2n/d)
    lg   = logsigmoid(h Wg + bg)  (T, Hk)       G[t] = sum_{s<=t} lg[s]
    a[t, s] = (q_i[t] . k_j[s])^2 exp(G_j[t] - G_j[s])   s <= t, j = i // (Hq/Hk)
    o_i[t]  = sum_s a[t, s] v_j[s] / (sum_s a[t, s] + EPS)
    x    = x + concat_i(o_i) Wo
    x    = x + (silu(rms(x; post_norm) Wgate) * (rms(x; post_norm) Wup)) Wdown
    logits = rms(x_L; final_norm) Whead         (untied)

Departures from the source (``configs/brumby-14b-l8.json`` lists what the
source's ``config.json`` does not say and how it was set, under
``assumed``): the power (2), the gate (one per K/V head, with a bias), the
normaliser and ``EPS``, per-head RMSNorm on q and k, the rotary
convention (halves, not interleaved pairs) — all shared with the program;
no scale on ``q . k`` (it cancels between numerator and denominator).

So that it fits beside 8.4 GB of bfloat16 weights on one chip it is
computed in blocks, none of which changes a value: one layer's weights
upcast at a time, query rows in blocks of ``Q_BLOCK`` against all earlier
keys, the head on the checked rows alone and in ``V_BLOCKS`` slices of the
vocabulary.

``TIE_STEPS`` and ``bf16_steps_behind`` are ``reference/gpt.py``'s rule
and number, imported from it: an emitted token
must lie within 12 bf16 steps (of the best logit's own size) of the
reference's best logit at its position, teacher-forced over prompt +
answer. What a comparison in that unit catches is measured by
``tests/chipbench_tests/test_brumby.py`` on a toy (2 layers, 52 tokens,
every logit of the checked rows): on float32 weights the program reads
0.000 steps; on bfloat16 weights 3.3 (its first norm runs on the bfloat16
embedding row); a state zeroed between chunks, a dropped gate, a dropped
normaliser and a power of 1 read 410 to 510 on either; a bfloat16 state
reads 3.9 on float32 weights after 52 tokens and grows with the context.
On the chip, at the cell's contexts of 2.3k to 8.7k, the emitted tokens
lay within 1.3 to 3.4 steps (PERF.md, section 6, PR 29).

**What the tokens cannot show, the states do.** At random weights a state
kept in bfloat16 moves the logits less than the bfloat16 weights already
do (0.8 steps behind on the chip, below the program's own 1.3 to 3.4), so
``TIE_STEPS`` cannot hold the program to the float32 state the
configuration states. ``state_apart`` can: the states of requests still
in flight are read from the timed engine with a few probe queries ``u`` —
``S phi(u)`` and ``z . phi(u)``, numbers free of phi's layout — and held
to the same sums in the quadratic form, ``sum_s w_s (u . k_s)^2 v_s`` and
``sum_s w_s (u . k_s)^2`` over the tokens fed (``_readings``). The
normaliser's sum is coherent — every term is positive — so the bfloat16
rounding of each token's ``k`` averages out of it (0.5% to 1.1% at worst
over 8 layers x 8 heads in eight runs, most of it drift of the hidden
rows with depth: 0.08% in the first layer), while a bfloat16 state drops
the small increments a long memory is made of (5.8% and 8.4% in two
control runs). For ``S`` the program reads 1.6% to 2.0% and the control
6.1% and 24.6%. ``STATE_LIMIT`` lies between the two, for each.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as onp

from chipbench.reference.gpt import TIE_STEPS, bf16_steps_behind  # noqa: F401

EPS = 1e-6
Q_BLOCK = 512
V_BLOCKS = 8
PROBES = 8
# The state's own rule (``state_apart``): how far the answers of a state the
# timed engine holds may lie from the quadratic form's, relative to them.
# Each limit lies between two readings on the chip (PERF.md, section 6,
# PR 29): the program's largest over its seeds, and the same cell with the
# state rounded to bfloat16 after every program that writes it.
STATE_LIMIT = {"S": 0.035, "z": 0.025}

F32 = jnp.float32


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gain.astype(F32)


def _rope(x, theta):
    """``x (T, H, d)`` at positions ``0..T-1``."""
    t, _, d = x.shape
    half = d // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    angle = jnp.arange(t, dtype=F32)[:, None, None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def _retention(q, k, v, lg):
    """The quadratic form. ``q (T, Hq, d)``, ``k``/``v (T, Hk, d)``,
    ``lg (T, Hk)`` -> ``(T, Hq, d)``. Query rows go in blocks, each
    against the keys up to its own last row."""
    t, hq, d = q.shape
    hk = k.shape[1]
    big_g = jnp.cumsum(lg, axis=0)
    out = []
    for lo in range(0, t, Q_BLOCK):
        hi = min(lo + Q_BLOCK, t)
        qb = q[lo:hi].reshape(hi - lo, hk, hq // hk, d)
        dot = jnp.einsum("tjgd,sjd->jgts", qb, k[:hi])
        seen = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        decay = jnp.exp(jnp.where(
            seen, big_g[lo:hi].T[:, :, None] - big_g[:hi].T[:, None, :],
            -jnp.inf))
        a = dot * dot * decay[:, None]
        num = jnp.einsum("jgts,sjv->tjgv", a, v[:hi])
        den = a.sum(-1).transpose(2, 0, 1)[..., None]
        out.append((num / (den + EPS)).reshape(hi - lo, hq, d))
    return jnp.concatenate(out)


def _readings(k, v, lg, n, probes):
    """What a state that had absorbed tokens ``0..n-1`` answers to the
    probe queries ``probes (R, d)``, in the quadratic form — no phi, no
    state: ``num[j, r] = sum_{s<n} w_j[s] (u_r . k_j[s])^2 v_j[s]`` and
    ``den[j, r]`` the same sum without ``v``, with ``w_j[s] = exp(G_j[n-1]
    - G_j[s])``. ``(Hk, R, d)`` and ``(Hk, R)``."""
    big_g = jnp.cumsum(lg, axis=0)
    seen = (jnp.arange(k.shape[0]) < n)[:, None]
    w = jnp.exp(jnp.where(seen, big_g[n - 1][None] - big_g, -jnp.inf))
    dot = jnp.einsum("rd,sjd->jrs", probes, k)
    a = dot * dot * w.T[:, None, :]
    return jnp.einsum("jrs,sjv->jrv", a, v), a.sum(-1)


def _layer(x, p, sz, n, probes):
    """One layer; ``p`` holds its parameters by their names inside it.
    Beside the layer's rows, the readings of ``_readings`` at ``n``."""
    def dense(h, name):
        return h @ p[name + ".weight"].astype(F32).T

    eps, d = sz["epsilon"], sz["head_dim"]
    t = x.shape[0]
    r = "retention."
    h = _rms(x, p["input_norm.gamma"], eps)
    q = _rms(dense(h, "retention.q_proj").reshape(t, -1, d),
             p[r + "q_norm.gamma"], eps)
    k = _rms(dense(h, "retention.k_proj").reshape(t, -1, d),
             p[r + "k_norm.gamma"], eps)
    v = dense(h, "retention.v_proj").reshape(t, -1, d)
    lg = jax.nn.log_sigmoid(dense(h, "retention.g_proj")
                            + p[r + "g_proj.bias"].astype(F32))
    k = _rope(k, sz["rope_theta"])
    o = _retention(_rope(q, sz["rope_theta"]), k, v, lg)
    x = x + dense(o.reshape(t, -1), "retention.o_proj")
    h = _rms(x, p["post_norm.gamma"], eps)
    return x + dense(jax.nn.silu(dense(h, "ffn.gate_proj"))
                     * dense(h, "ffn.up_proj"), "ffn.down_proj"), \
        _readings(k, v, lg, n, probes)


def _freeze(sz: dict) -> tuple:
    return tuple(sorted((k, sz[k]) for k in (
        "head_dim", "epsilon", "rope_theta")))


@functools.partial(jax.jit, static_argnames=("frozen",))
def _layer_jit(x, layer_params, n, probes, frozen):
    with jax.default_matmul_precision("highest"):
        return _layer(x, layer_params, dict(frozen), n, probes)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_jit(rows, gain, head, eps):
    """Logits of ``rows (n, units)`` in slices of the vocabulary. The
    slices are equal: the vocabulary is padded up with rows of zeros,
    whose logits (0) are cut off again."""
    with jax.default_matmul_precision("highest"):
        h = _rms(rows, gain, eps)
        vocab = head.shape[0]
        size = -(-vocab // V_BLOCKS)
        parts = [h @ head[i * size:(i + 1) * size].astype(F32).T
                 for i in range(V_BLOCKS)]
        return jnp.concatenate(parts, axis=-1)[:, :vocab]


def hidden(params, tokens, sz: dict, n=None, probes=None):
    """``(T,)`` token ids -> the last layer's ``(T, units)`` rows, before
    the final norm, and every layer's ``_readings`` of the first ``n``
    tokens (all of them, to one probe, unless given). One sequence, one
    layer's weights upcast at a time."""
    x = params["word_embed.weight"][jnp.asarray(tokens, jnp.int32)] \
        .astype(F32)
    n = jnp.int32(len(tokens) if n is None else n)
    if probes is None:
        probes = jnp.zeros((PROBES, sz["head_dim"]), F32)
    frozen, read = _freeze(sz), []
    for i in range(sz["num_layers"]):
        pre = f"layer{i}."
        x, got = _layer_jit(x, {k[len(pre):]: v for k, v in params.items()
                                if k.startswith(pre)}, n, probes, frozen)
        read.append(got)
    return x, read


def logits(params, tokens, sz: dict, rows=None):
    """Float32 logits of ``tokens``' rows (all of them, or ``rows``)."""
    x, _ = hidden(params, tokens, sz)
    if rows is not None:
        x = x[jnp.asarray(rows, jnp.int32)]
    return _head_jit(x, params["final_norm.gamma"],
                     params["lm_head.weight"], sz["epsilon"])


def tokens_behind(params, prompt, emitted, sz: dict, pad_to: int,
                  rows_to: int):
    """For one answered request, how many bf16 steps each emitted token
    lies behind the reference's best logit at its position. The sequence
    is padded to ``pad_to`` and the checked rows to ``rows_to``, so that
    one compiled program serves every sample; nothing after a row reaches
    it (the form is causal), so the padding changes no checked value."""
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
    got = logits(params, padded, sz, rows)      # stays on the device
    best = onp.asarray(got.max(-1))[:len(emitted)]
    chosen = onp.asarray(jnp.take_along_axis(
        got, jnp.asarray(picked)[:, None], axis=-1))[:len(emitted), 0]
    if not (onp.isfinite(best).all() and onp.isfinite(chosen).all()):
        return onp.full(len(emitted), onp.inf)
    return bf16_steps_behind(best, chosen)


def probes(sz: dict, seed: int):
    """``PROBES`` unit vectors of a head's size, drawn from the seed: the
    queries a state is asked."""
    u = onp.random.RandomState((seed + 5) % 2**32).randn(
        PROBES, sz["head_dim"])
    return jnp.asarray(u / onp.linalg.norm(u, axis=-1, keepdims=True), F32)


def state_apart(params, tokens, n: int, got, sz: dict, pad_to: int, seed: int):
    """How far the state a program holds after the first ``n`` of
    ``tokens`` lies from what this file's quadratic form says it must
    answer. ``got`` is ``(num (L, Hk, R, d), den (L, Hk, R))``: the
    program's state read with ``probes(sz, seed)`` (``S phi(u)`` and
    ``z . phi(u)``, by whatever layout it keeps). Returns ``{"S": ...,
    "z": ...}``: for each, the largest relative distance (norm of the
    difference over the norm of the reference's reading) over the layers
    and K/V heads, and with it ``STATE_LIMIT``'s two numbers are compared.
    Padded to ``pad_to``, so that it shares ``tokens_behind``'s program."""
    padded = onp.zeros((pad_to,), onp.int32)
    padded[:n] = onp.asarray(tokens)[:n]
    _, read = hidden(params, padded, sz, n, probes(sz, seed))
    num = onp.stack([onp.asarray(r[0]) for r in read])
    den = onp.stack([onp.asarray(r[1]) for r in read])
    got_num, got_den = (onp.asarray(a, onp.float32) for a in got)

    def worst(a, b, axes):
        rel = onp.sqrt(((a - b) ** 2).sum(axes) / (b ** 2).sum(axes))
        return float(rel.max()) if onp.isfinite(rel).all() else float("inf")

    return {"S": worst(got_num, num, (-2, -1)), "z": worst(got_den, den, -1)}
