"""The plain reference of the ``gpt_like`` equations, and the rules that
decide ``correct``.

Float32, ``jax.default_matmul_precision("highest")``, plain ``jax.numpy``:
no kernel, no cache, no batching, nothing from ``mxnet_tpu.ops``. It reads
the net's parameters by the names ``collect_params()`` gives them and
upcasts each where it is used.

The equations (what ``gluon.model_zoo.bert.gpt_like`` computes; where they
leave the published GPT-2 block is listed in ``configs/*.json``):

    h0      = word_embed[tokens] + pos_embed[:L]
    a       = LN1(h);  q|k|v = a @ Wqkv.T + bqkv  (three slices of d, heads
              are consecutive groups of d/H features)
    h       = h + softmax(causal(q k^T / sqrt(d/H))) v @ Wo.T + bo
    h       = h + gelu_erf(LN2(h) @ W1.T + b1) @ W2.T + b2
    logits  = LNf(h) @ word_embed.T                     (tied head)
    loss    = sum over positions with label >= 0 of -log softmax(logits)[label]

Tolerances, each with the measurement that set it:

- ``TIE_STEPS``: an emitted token must lie within this many bf16 steps
  (of the best logit's own size) of the reference's best logit at its
  position. Greedy paths of two roundings fork at near-ties under random
  weights, so tokens cannot be compared one to one (PR 22 measured 7 of 8
  requests forking); a wrong block, mask or scale moves logits by whole
  units — with normal(0, 0.02) weights the best logit is about 3 and a
  step 2**-6, so a unit is 64 steps. PR 22 saw 5.6 steps at worst between
  int8 pools and a dense path at GPT-2-small. PR 25's serving runs at
  GPT-2-large on the chip read 46 to 70 steps — about one unit — and
  therefore ``correct: false``. A diagnostic (PR 25, chip) put it on the
  int8 rows in decode on the TPU: float pools read 0.0 to 0.3 steps at
  GPT-2-small and -large, int8 pools 18 to 47, the prefill token 0.0 in
  both, and on the CPU both read 0.0. The limit was *not* raised to let
  the cells pass: see PERF.md, "What stopped cells 2 and 3".
- ``LOSS_RTOL``, ``GRAD_COS_MIN``, ``GRAD_NORM_RTOL``: the bf16 train step
  against this reference on one batch. Measured on the chip (PR 25, nine
  runs at GPT-2-small): loss 2.8e-7 to 2.2e-6 relative, gradient cosines
  0.99993 to 0.99998, norm ratios 1.0000 — closer than bf16 arithmetic
  would be, because today only the weights are bf16 (activations are
  float32). The limits stand a hundred times (loss) and some fourteen
  times (1 - cosine) above that, which is room for bf16 activations
  (8 bits of mantissa: about 1e-5 on a loss averaged over 8,192 tokens,
  1 - cosine about 5e-5 through 12 blocks). At random weights the loss is
  about ln V + 0.6 almost whatever the blocks compute, so it is the weaker
  of the two checks and the gradients carry most of the verdict. The test
  ``test_the_train_check_passes_the_program_and_fails_a_mutated_one``
  shows what the limits catch, on a bf16 toy whose attention scores spread
  as the cell's do (std about 0.3): the program as it is reads 1.4e-5 and
  0.99999; a last block without its causal mask 3.4e-4 and 0.93; a wrong
  head size and scale 1.0e-3 and 0.94; a dropped bias 5.1e-3 and 0.88.
  Every parameter is drawn at random (``harness._weights``), none sits at
  0 or 1, so that a dropped bias, offset or gain shows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as onp

TIE_STEPS = 12.0
LOSS_RTOL = 2e-4
GRAD_COS_MIN = 0.999
GRAD_NORM_RTOL = 0.01
LN_EPS = 1e-5

F32 = jnp.float32


def _ln(x, gamma, beta):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * gamma.astype(F32) \
        + beta.astype(F32)


def _dense(x, p, name):
    return x @ p[name + ".weight"].astype(F32).T + p[name + ".bias"].astype(F32)


def n_layers(params) -> int:
    return 1 + max(int(k.split(".")[1][len("layer"):]) for k in params
                   if k.startswith("encoder.layer"))


def logits_fn(params, tokens, heads: int):
    """(L,) token ids -> (L, V) float32 logits. One sequence."""
    seq = tokens.shape[0]
    emb = params["word_embed.weight"].astype(F32)
    h = emb[tokens] + params["pos_embed"].astype(F32)[:seq]
    d = h.shape[-1]
    hd = d // heads
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    for i in range(n_layers(params)):
        pre = f"encoder.layer{i}."
        a = _ln(h, params[pre + "ln1.gamma"], params[pre + "ln1.beta"])
        qkv = _dense(a, params, pre + "attn.qkv")
        q, k, v = (qkv[:, j * d:(j + 1) * d].reshape(seq, heads, hd)
                   for j in range(3))
        s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(hd))
        s = jnp.where(causal[None], s, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
        h = h + _dense(o.reshape(seq, d), params, pre + "attn.out_proj")
        f = _ln(h, params[pre + "ln2.gamma"], params[pre + "ln2.beta"])
        f = jax.nn.gelu(_dense(f, params, pre + "ffn.ffn_1"),
                        approximate=False)
        h = h + _dense(f, params, pre + "ffn.ffn_2")
    h = _ln(h, params["encoder.final_ln.gamma"],
            params["encoder.final_ln.beta"])
    return h @ emb.T


def loss_fn(params, tokens, labels, heads: int):
    """Summed token cross-entropy of one sequence; labels < 0 are
    ignored."""
    logp = jax.nn.log_softmax(logits_fn(params, tokens, heads), axis=-1)
    picked = jnp.take_along_axis(logp, jnp.clip(labels, 0)[:, None],
                                 axis=-1)[:, 0]
    return -jnp.where(labels >= 0, picked, 0.0).sum()


@functools.partial(jax.jit, static_argnames=("heads",))
def _logits_jit(params, tokens, heads):
    with jax.default_matmul_precision("highest"):
        return logits_fn(params, tokens, heads)


@functools.partial(jax.jit, static_argnames=("heads",))
def _loss_and_grads_jit(wrt, rest, tokens, labels, heads):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda w: loss_fn({**rest, **w}, tokens, labels, heads))(wrt)


@functools.partial(jax.jit, static_argnames=("heads",))
def _margins_jit(params, tokens, heads):
    with jax.default_matmul_precision("highest"):
        logits = logits_fn(params, tokens, heads)
    nxt = jnp.concatenate([tokens[1:], tokens[:1]])
    return logits.max(-1), jnp.take_along_axis(
        logits, nxt[:, None], axis=-1)[:, 0]


def logits(params, tokens, heads: int):
    return _logits_jit(params, jnp.asarray(tokens, jnp.int32), heads)


def loss_and_grads(params, tokens, labels, wrt_names, heads: int):
    """Loss summed over a (B, L) batch, the sequences taken one at a
    time, and the float32 gradients of the parameters in ``wrt_names``."""
    wrt = {k: params[k].astype(F32) for k in wrt_names}
    rest = {k: v for k, v in params.items() if k not in wrt}
    total, grads = 0.0, None
    for x, y in zip(onp.asarray(tokens), onp.asarray(labels)):
        val, g = _loss_and_grads_jit(wrt, rest, jnp.asarray(x, jnp.int32),
                                     jnp.asarray(y, jnp.int32), heads)
        total += float(val)
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    return total, grads


def compare_grad(got, want) -> tuple[float, float]:
    """(cosine, norm of ``got`` / norm of ``want``) in float64."""
    a = onp.asarray(got, onp.float64).ravel()
    b = onp.asarray(want, onp.float64).ravel()
    na, nb = onp.linalg.norm(a), onp.linalg.norm(b)
    return float(a @ b / (na * nb)), float(na / nb)


def bf16_steps_behind(best, chosen):
    """How far ``chosen`` lies under ``best``, in bf16 steps of ``best``'s
    own size (bf16 keeps 8 bits: a step is 2**(floor(log2|x|) - 7))."""
    best = onp.asarray(best, onp.float64)
    step = 2.0 ** (onp.floor(onp.log2(onp.maximum(onp.abs(best), 1e-30)))
                   - 7)
    return (best - onp.asarray(chosen, onp.float64)) / step


def tokens_behind(params, prompt, emitted, heads: int, pad_to: int):
    """For one answered request, how many bf16 steps each emitted token
    lies behind the reference's best logit at its position. The sequence
    is padded to ``pad_to`` so that one compiled program serves every
    sample; causal attention leaves the earlier positions untouched."""
    prompt, emitted = onp.asarray(prompt), onp.asarray(emitted)
    seq = onp.concatenate([prompt, emitted]).astype(onp.int32)
    if len(seq) > pad_to:
        raise ValueError(f"sequence {len(seq)} longer than {pad_to}")
    padded = onp.zeros((pad_to,), onp.int32)
    padded[:len(seq)] = seq
    best, chosen = _margins_jit(params, jnp.asarray(padded), heads)
    at = slice(len(prompt) - 1, len(seq) - 1)     # rows that chose emitted
    best, chosen = onp.asarray(best)[at], onp.asarray(chosen)[at]
    if not (onp.isfinite(best).all() and onp.isfinite(chosen).all()):
        return onp.full(len(emitted), onp.inf)
    return bf16_steps_behind(best, chosen)
