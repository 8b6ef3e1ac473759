"""Power retention (degree 2): a fixed-size recurrent state in place of
keys and values ("Scaling Context Requires Rethinking Attention",
arXiv 2507.04239).

For one K/V head ``j`` with gate ``g[t]`` in (0, 1], and each query head
``i`` of its group::

    a[t, s] = (q_i[t] . k_j[s])^2 * prod_{s < r <= t} g[r]        (s <= t)
    o_i[t]  = sum_s a[t, s] v_j[s] / (sum_s a[t, s] + eps)

``(u . w)^2 = phi(u) . phi(w)`` with ``phi`` the symmetric square of a
vector (``u_a u_b`` for ``a <= b``, the off-diagonal entries times
sqrt 2), so the same thing is a recurrence over a state that does not
grow with the context::

    S[t] = g[t] S[t-1] + v[t] phi(k[t])^T        z[t] = g[t] z[t-1] + phi(k[t])
    o_i[t] = S[t] phi(q_i[t]) / (z[t] . phi(q_i[t]) + eps)

and, over a chunk of tokens that starts from ``S0``, ``z0`` (prefill),
the quadratic form inside the chunk plus ``exp(G[t]) S0 phi(q[t])`` with
``G`` the running sum of ``log g`` from the chunk's first token.

**The layout of phi and of the pools.** ``phi`` of a ``d``-vector is held
in ``d / 2 + 1`` tiles of ``d`` entries (``Dp = d (d / 2 + 1)``: 8,320
for heads of 128, of which 8,256 = 128 * 129 / 2 are the pairs
``a <= b`` and 64 are padding that is always zero). Tile ``a < d / 2``
holds, in its first ``d - 1 - a`` entries, the pairs ``(a, a + 1 + l)``
— ``u[a]`` times ``u`` rotated by ``a + 1`` — and in the rest the pairs
``(d - 2 - a, l)`` — ``u[d - 2 - a]`` times ``u`` as it stands; the
last tile is the diagonal ``u * u``. A tile is therefore two rotations
and a select of the ``d`` lanes a vector already lies in, which is what
lets the chunk kernel build it on the VPU without a gather
(:mod:`mxnet_tpu.ops.pallas.power_retention`). The pools are
``S (L, NB, Hk, d, Dp)`` float32 — a slot's state for one head is
``d`` rows (the value's entries) of ``Dp`` lanes (phi of the key) — and
``z (L, NB, Hk, Dp)``: one slot per request whatever its length.

This module holds the layout, the plain ``jax.numpy`` forms (the CPU's
and a mesh's path, and the kernels' oracle) and the one place that
chooses between them and the kernels (``ops.nn._tpu_kernels_selected``,
as every other kernel of the package).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as onp

__all__ = ["EPS", "phi_size", "phi_layout", "phi", "state_readings",
           "retention_step", "retention_chunk", "retention_step_jnp",
           "retention_chunk_jnp"]

EPS = 1e-6      # added to the normaliser: a first token's o is v, not 0/0
F32 = jnp.float32


def phi_size(d: int) -> int:
    """Entries a phi vector is held in (``Dp``), padding included."""
    if d % 2:
        raise ValueError(f"head size {d} must be even")
    return d * (d // 2 + 1)


@functools.lru_cache(maxsize=None)
def phi_layout(d: int):
    """``(a, b, w)``, each ``(Dp,)``: entry ``n`` of phi(u) is
    ``w[n] * u[a[n]] * u[b[n]]`` (``w`` is sqrt 2 off the diagonal, 1 on
    it and 0 on padding)."""
    a_idx = onp.zeros((d // 2 + 1, d), onp.int32)
    b_idx = onp.zeros((d // 2 + 1, d), onp.int32)
    w = onp.zeros((d // 2 + 1, d), onp.float32)
    lane = onp.arange(d)
    for a in range(d // 2):
        first = lane < d - 1 - a
        a_idx[a] = onp.where(first, a, d - 2 - a)
        b_idx[a] = onp.where(first, (a + 1 + lane) % d, lane)
        w[a] = onp.sqrt(2.0)
    w[d // 2 - 1, d // 2:] = 0.0        # row d/2 - 1 pairs with itself
    a_idx[d // 2] = b_idx[d // 2] = lane
    w[d // 2] = 1.0
    return a_idx.reshape(-1), b_idx.reshape(-1), w.reshape(-1)


def phi(u):
    """``(..., d) -> (..., Dp)`` float32: ``phi(u) . phi(w) == (u . w)^2``."""
    a_idx, b_idx, w = phi_layout(u.shape[-1])
    u = u.astype(F32)
    return jnp.take(u, a_idx, axis=-1) * jnp.take(u, b_idx, axis=-1) * w


def state_readings(s, z, u):
    """What a state answers to probe queries ``u (R, d)``, before the
    division: ``(S phi(u), z . phi(u))`` for ``s (..., d, Dp)`` and
    ``z (..., Dp)`` -> ``(..., R, d)`` and ``(..., R)``. By the identity
    above these are ``sum_s w_s (u . k_s)^2 v_s`` and ``sum_s w_s
    (u . k_s)^2`` over the tokens the state absorbed, ``w_s`` the decay
    since token ``s`` — numbers that no longer depend on how phi is laid
    out, so a check can hold a state to a form that has none."""
    pu = phi(u)
    with jax.default_matmul_precision("highest"):
        return (jnp.einsum("...vn,rn->...rv", s.astype(F32), pu),
                jnp.einsum("...n,rn->...r", z.astype(F32), pu))


def _grouped(x, hk):
    """(..., Hq, n) -> (..., Hk, Hq/Hk, n): query head i reads K/V head
    i // (Hq/Hk)."""
    return x.reshape(x.shape[:-2] + (hk, x.shape[-2] // hk, x.shape[-1]))


# --- the recurrent step (decode) -------------------------------------------
def retention_step_jnp(q, k, v, lg, pool_s, pool_z, slots, layer):
    """One token per lane. ``q (R, Hq, d)``, ``k``/``v (R, Hk, d)``,
    ``lg (R, Hk)`` the log of the gate; lane ``r``'s state is slot
    ``slots[r]`` of layer ``layer`` in the pools. Returns ``(o (R, Hq, d)
    float32, pool_s, pool_z)`` with the slots advanced by the token."""
    hk = k.shape[1]
    g = jnp.exp(lg.astype(F32))
    pk, pq = phi(k), _grouped(phi(q), hk)
    s = g[..., None, None] * pool_s[layer, slots] \
        + v.astype(F32)[..., :, None] * pk[..., None, :]
    z = g[..., None] * pool_z[layer, slots] + pk
    num = jnp.einsum("rjgn,rjvn->rjgv", pq, s)
    den = jnp.einsum("rjgn,rjn->rjg", pq, z)
    o = num / (den[..., None] + EPS)
    return (o.reshape(q.shape), pool_s.at[layer, slots].set(s),
            pool_z.at[layer, slots].set(z))


# --- the chunked form (prefill) --------------------------------------------
def retention_chunk_jnp(q, k, v, lg, pool_s, pool_z, slot, layer, fresh,
                        n_real):
    """``c`` tokens of one lane. ``q (c, Hq, d)``, ``k``/``v (c, Hk, d)``,
    ``lg (c, Hk)``; the lane's state is slot ``slot`` of layer ``layer``
    and counts as zero where ``fresh`` (a slot just handed to a request
    starts from nothing, whatever it held). Rows from ``n_real`` on are
    padding: they neither decay nor add, so the slot is left at the state
    of the last real token. Returns ``(o (c, Hq, d) float32, pool_s,
    pool_z)``; the padding rows of ``o`` mean nothing."""
    c, hk = k.shape[0], k.shape[1]
    real = (jnp.arange(c) < n_real)[:, None]
    lg = jnp.where(real, lg.astype(F32), 0.0)
    k = jnp.where(real[..., None], k.astype(F32), 0.0)
    big_g = jnp.cumsum(lg, axis=0)                      # (c, Hk)
    s0 = jnp.where(fresh, 0.0, pool_s[layer, slot])     # (Hk, d, Dp)
    z0 = jnp.where(fresh, 0.0, pool_z[layer, slot])     # (Hk, Dp)
    qg = _grouped(q.astype(F32), hk)                    # (c, Hk, G, d)
    pq, pk = _grouped(phi(q), hk), phi(k)
    dot = jnp.einsum("tjgd,sjd->jgts", qg, k)
    lower = jnp.tril(jnp.ones((c, c), bool))
    decay = jnp.exp(jnp.where(
        lower, big_g.T[:, :, None] - big_g.T[:, None, :], -jnp.inf))
    a = dot * dot * decay[:, None]                      # (Hk, G, c, c)
    carry = jnp.exp(big_g).T[:, None, :]                # (Hk, 1, c)
    num = jnp.einsum("jgts,sjv->jgtv", a, v.astype(F32)) \
        + carry[..., None] * jnp.einsum("tjgn,jvn->jgtv", pq, s0)
    den = a.sum(-1) + carry * jnp.einsum("tjgn,jn->jgt", pq, z0)
    o = num / (den[..., None] + EPS)                    # (Hk, G, c, d)
    left = jnp.exp(big_g[-1][None] - big_g)             # (c, Hk)
    end = jnp.exp(big_g[-1])                            # (Hk,)
    s1 = end[:, None, None] * s0 + jnp.einsum(
        "sjv,sjn->jvn", v.astype(F32) * left[..., None], pk)
    z1 = end[:, None] * z0 + jnp.einsum("sj,sjn->jn", left, pk)
    return (o.transpose(2, 0, 1, 3).reshape(q.shape),
            pool_s.at[layer, slot].set(s1), pool_z.at[layer, slot].set(z1))


# --- the one place that chooses --------------------------------------------
def _kernels_selected(d: int) -> bool:
    from .nn import _tpu_kernels_selected

    return d == 128 and _tpu_kernels_selected()


def retention_step(q, k, v, lg, pool_s, pool_z, slots, layer):
    """:func:`retention_step_jnp`, as the kernel ``power_retention_step``
    on the chip (heads of 128, outside a mesh)."""
    if _kernels_selected(q.shape[-1]):
        from .pallas.power_retention import power_retention_step

        return power_retention_step(q, k, v, lg, pool_s, pool_z, slots,
                                    layer)
    return retention_step_jnp(q, k, v, lg, pool_s, pool_z, slots, layer)


def retention_chunk(q, k, v, lg, pool_s, pool_z, slot, layer, fresh,
                    n_real):
    """:func:`retention_chunk_jnp`, as the kernel ``power_retention_chunk``
    on the chip (heads of 128, outside a mesh)."""
    if _kernels_selected(q.shape[-1]):
        from .pallas.power_retention import power_retention_chunk

        return power_retention_chunk(q, k, v, lg, pool_s, pool_z, slot,
                                     layer, fresh, n_real)
    return retention_chunk_jnp(q, k, v, lg, pool_s, pool_z, slot, layer,
                               fresh, n_real)
