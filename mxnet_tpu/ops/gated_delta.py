"""The gated delta rule (Gated DeltaNet, arXiv 2412.06464): linear
attention whose memory is one ``dk x dv`` matrix per value head, decayed
by a gate and corrected, not just added to, by every token.

For one value head with state ``S (dk, dv)``, log-decay ``g[t] <= 0`` and
write strength ``beta[t]`` in (0, 1), token by token::

    S = exp(g[t]) S;   d = beta[t] (v[t] - S^T k[t]);   S = S + k[t] d^T
    o[t] = S^T q[t]

Value head ``j`` uses key head ``j // (Hv / Hk)``; ``q`` and ``k`` arrive
normalised (:func:`l2norm`). In front of it sits a causal depthwise
convolution of width ``W`` over the channels of ``[q | k | v]``, whose
memory is the last ``W - 1`` inputs (the *tail*).

**The chunked form** (prefill). Over a sub-chunk of ``C`` = 64 tokens
that starts from ``S0``, with ``G[t]`` the running sum of ``g`` inside
it, the corrections ``d`` of all its tokens solve one unit-lower-
triangular system::

    (I + tril(diag(beta) (K K^T * decay), -1)) D = beta * (V - (exp(G) * K) S0)
    decay[t, s] = exp(G[t] - G[s])                                  (s <= t)

so ``D = U - W S0`` with ``U``, ``W`` the system solved for ``beta * V``
and ``beta * exp(G) * K`` — both independent of ``S0`` and computed for
every sub-chunk at once — and then, sub-chunk after sub-chunk under
``lax.scan``::

    O  = (exp(G) * Q) S0 + tril(Q K^T * decay) D
    S1 = exp(G[C-1]) S0 + (exp(G[C-1] - G) * K)^T D

The system is solved through its Neumann product: ``N = -tril(..., -1)``
is nilpotent (``N^C = 0``), so ``(I - N)^-1 = (I + N)(I + N^2)(I + N^4)
...`` in ``log2 C`` squarings — matrix products, which is what the chip
is good at. Padding rows (``g = 0``, ``beta = 0``) neither decay nor
write.

The pools: ``S (Ld, slots, Hv, dk, dv)`` float32 and the convolution's
tail ``(Ld, slots, (W - 1) * channels)`` float32 — a lane's last ``W -
1`` inputs as one row, oldest first — ``Ld`` the model's delta-rule
layers: one slot per lane for its whole life.

This module holds the plain ``jax.numpy`` forms (the CPU's path and the
kernel's oracle) and the one place that chooses between them and the
kernel (``ops.nn._tpu_kernels_selected``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["SUB", "l2norm", "conv_step", "conv_chunk", "gates",
           "gated_norm", "delta_step", "delta_step_jnp", "delta_chunk",
           "state_readings"]

F32 = jnp.float32
SUB = 64            # tokens a triangular system spans
_HI = jax.lax.Precision.HIGHEST


def l2norm(x, eps: float = 1e-6):
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, float32."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def gates(a, b, a_log, dt_bias):
    """``(g, beta)`` per value head: ``g = -exp(A_log) softplus(a +
    dt_bias)``, ``beta = sigmoid(b)``; float32."""
    g = -jnp.exp(a_log.astype(F32)) * jax.nn.softplus(
        a.astype(F32) + dt_bias.astype(F32))
    return g, jax.nn.sigmoid(b.astype(F32))


def gated_norm(o, z, w, eps: float = 1e-6):
    """The delta rule's output: ``w * o / sqrt(mean(o^2) + eps) *
    silu(z)`` per head over its values (``w`` a plain weight, not ``1 +
    w``); float32."""
    o = o.astype(F32)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return w.astype(F32) * o * jax.nn.silu(z.astype(F32))


# --- the convolution in front ----------------------------------------------
def _conv(xpad, w, n):
    """``silu(sum_j w[j] * xpad[j : j + n])`` for ``xpad (..., n + W - 1,
    C)`` and ``w (W, C)``: row ``t`` sees inputs ``t - W + 1 .. t``."""
    y = sum(w[j].astype(F32) * xpad[..., j:j + n, :]
            for j in range(w.shape[0]))
    return jax.nn.silu(y)


def conv_step(x, pool_c, slots, layer, w):
    """One token per lane: ``x (R, C)``, lane ``r``'s tail in slot
    ``slots[r]`` of layer ``layer``. Returns ``(y (R, C) float32,
    pool_c)`` with the tails moved on by the token."""
    r, c = x.shape
    xpad = jnp.concatenate([pool_c[layer, slots].reshape(r, -1, c),
                            x.astype(F32)[:, None]], 1)
    return _conv(xpad, w, 1)[:, 0], pool_c.at[layer, slots].set(
        xpad[:, 1:].reshape(r, -1))


def conv_chunk(x, pool_c, slot, layer, w, fresh, n_real):
    """``c`` tokens of one lane: ``x (c, C)``, of which the first
    ``n_real`` are tokens; the tail counts as zero where ``fresh``.
    Leaves the tail at the last real row — a prompt shorter than the
    convolution keeps what is left of the old tail in front of it."""
    tail = jnp.where(fresh, 0.0, pool_c[layer, slot]).reshape(
        -1, x.shape[1])
    xpad = jnp.concatenate([tail, x.astype(F32)], 0)
    new = jax.lax.dynamic_slice_in_dim(xpad, n_real, tail.shape[0], 0)
    return _conv(xpad, w, x.shape[0]), pool_c.at[layer, slot].set(
        new.reshape(-1))


# --- the recurrent step (decode) -------------------------------------------
def _per_value_head(x, hv):
    """(..., Hk, d) -> (..., Hv, d): value head j reads key head
    j // (Hv / Hk)."""
    return jnp.repeat(x, hv // x.shape[-2], axis=-2)


def delta_step_jnp(q, k, v, g, beta, pool_s, slots, layer):
    """One token per lane. ``q``/``k (R, Hk, dk)`` normalised, ``v (R,
    Hv, dv)``, ``g``/``beta (R, Hv)``; lane ``r``'s state is slot
    ``slots[r]`` of layer ``layer``. Returns ``(o (R, Hv, dv) float32,
    pool_s)`` with the slots advanced by the token."""
    hv = v.shape[1]
    q, k = (_per_value_head(x.astype(F32), hv) for x in (q, k))
    s = jnp.exp(g.astype(F32))[..., None, None] * pool_s[layer, slots]
    d = beta.astype(F32)[..., None] * (
        v.astype(F32) - jnp.einsum("rhkv,rhk->rhv", s, k, precision=_HI))
    s = s + k[..., :, None] * d[..., None, :]
    o = jnp.einsum("rhkv,rhk->rhv", s, q, precision=_HI)
    return o, pool_s.at[layer, slots].set(s)


def delta_step(q, k, v, g, beta, pool_s, slots, layer):
    """:func:`delta_step_jnp`, as the kernel ``gated_delta_step`` on the
    chip (heads of 128 x 128, outside a mesh)."""
    from .nn import _tpu_kernels_selected

    if pool_s.shape[-2:] == (128, 128) and _tpu_kernels_selected():
        from .pallas.gated_delta import gated_delta_step

        return gated_delta_step(q, k, v, g, beta, pool_s, slots, layer)
    return delta_step_jnp(q, k, v, g, beta, pool_s, slots, layer)


# --- the chunked form (prefill) --------------------------------------------
def _solve_unit_lower(low, rhs):
    """``(I + low)^-1 rhs`` for strictly lower ``low (..., C, C)``: the
    Neumann product of the nilpotent ``-low``."""
    c = low.shape[-1]
    eye = jnp.eye(c, dtype=F32)
    n = -low
    inv, span = eye + n, 1
    while 2 * span < c:
        n = jnp.matmul(n, n, precision=_HI)
        inv = inv + jnp.matmul(inv, n, precision=_HI)
        span *= 2
    return jnp.matmul(inv, rhs, precision=_HI)


def delta_chunk(q, k, v, g, beta, pool_s, slot, layer, fresh, n_real):
    """``c`` tokens of one lane, in sub-chunks of ``SUB`` (of what ``c``
    and ``SUB`` both divide by, where ``c`` is no multiple). ``q``/``k
    (c, Hk, dk)`` normalised, ``v (c, Hv, dv)``, ``g``/``beta (c, Hv)``;
    the lane's state is slot ``slot`` of layer ``layer`` and counts as
    zero where ``fresh``. Rows from ``n_real`` on are padding: they
    neither decay nor write, so the slot is left at the state of the
    last real token. Returns ``(o (c, Hv, dv) float32, pool_s)``; the
    padding rows of ``o`` mean nothing."""
    c, hv = v.shape[0], v.shape[1]
    sub = math.gcd(SUB, c)
    n = c // sub
    real = (jnp.arange(c) < n_real)[:, None]
    g = jnp.where(real, g.astype(F32), 0.0)
    beta = jnp.where(real, beta.astype(F32), 0.0)

    def blocks(x):          # (c, H, d) -> (n, H, sub, d)
        return x.reshape(n, sub, *x.shape[1:]).swapaxes(1, 2)

    q, k = (blocks(_per_value_head(x.astype(F32), hv)) for x in (q, k))
    v = blocks(v.astype(F32))
    big_g = jnp.cumsum(blocks(g[..., None])[..., 0], axis=-1)   # (n, H, sub)
    beta = blocks(beta[..., None])    # (n, H, sub, 1)
    lower = jnp.tril(jnp.ones((sub, sub), bool))
    decay = jnp.exp(jnp.where(
        lower, big_g[..., :, None] - big_g[..., None, :], -jnp.inf))
    kk = jnp.einsum("nhtd,nhsd->nhts", k, k, precision=_HI)
    low = jnp.where(lower & ~jnp.eye(sub, dtype=bool),
                    beta * kk * decay, 0.0)
    rise = jnp.exp(big_g)[..., None]                            # since start
    uw = _solve_unit_lower(
        low, jnp.concatenate([beta * v, beta * rise * k], -1))
    u, w = uw[..., :v.shape[-1]], uw[..., v.shape[-1]:]
    qk = jnp.einsum("nhtd,nhsd->nhts", q, k, precision=_HI) * decay
    left = jnp.exp(big_g[..., -1:] - big_g)[..., None]          # to the end
    end = jnp.exp(big_g[..., -1])[..., None, None]              # (n, H, 1, 1)

    def step(s, x):
        u_i, w_i, q_i, qk_i, k_i, rise_i, left_i, end_i = x
        d = u_i - jnp.matmul(w_i, s, precision=_HI)             # (H, sub, dv)
        o = jnp.matmul(q_i * rise_i, s, precision=_HI) \
            + jnp.matmul(qk_i, d, precision=_HI)
        s = end_i * s + jnp.einsum("hsk,hsv->hkv", k_i * left_i, d,
                                   precision=_HI)
        return s, o

    s0 = jnp.where(fresh, 0.0, pool_s[layer, slot])
    s1, o = jax.lax.scan(step, s0, (u, w, q, qk, k, rise, left, end))
    return (o.swapaxes(1, 2).reshape(c, hv, -1),
            pool_s.at[layer, slot].set(s1))


def state_readings(s, tail, probes):
    """What a lane's state answers to probe queries ``probes (P, dk)``:
    ``S^T u`` for ``s (Ld, Hv, dk, dv)`` -> ``(Ld, Hv, P, dv)``, and the
    convolution's tail ``(Ld, (W - 1) * channels)`` as it is. (The cell's
    ``model.state_readings``.)"""
    with jax.default_matmul_precision("highest"):
        return (jnp.einsum("lhkv,pk->lhpv", s.astype(F32),
                           probes.astype(F32)), tail.astype(F32))
