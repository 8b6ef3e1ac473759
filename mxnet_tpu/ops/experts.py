"""A mixture-of-experts FFN told which experts it holds.

The router scores **all** ``E`` experts, keeps the ``k`` largest per token
and renormalises over those ``k`` (:func:`route`); the layer then computes
the part of the result that the experts *held here* —
``first .. first + Eh - 1``, one chip's share — give::

    y[t] = sum_{e in top(t), first <= e < first + Eh} w[t, e] E_e(x[t])
    E_e(u) = (silu(u Wg_e) * (u Wu_e)) Wd_e

What the absent experts would have added is left out (on the chips that
share the layer it is their part of a sum; on one chip nothing stands in
for it). No token is dropped and no ``(tokens, experts, capacity)`` tensor
is built: the ``T * k`` assignments are sorted by expert — those to
absent experts sort last and fall into no group — and the held experts
run as one grouped matmul over the sorted rows
(:func:`moe_grouped_ffn`): ``lax.ragged_dot`` here and on a mesh, the
kernel ``moe_grouped_ffn`` on the chip.

``counts`` (int32, 4): the assignments that fell on held experts, the
held experts that got any, the largest load of one, and ``Eh`` — what the
engine's counters and the roofline readers are fed, made on the device.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["route", "shared_gate", "sort_by_expert", "grouped_ffn_jnp",
           "moe_grouped_ffn"]

F32 = jnp.float32


def route(logits, k: int):
    """``logits (T, E)`` -> ``(experts (T, k) int32, weights (T, k)
    float32)``: softmax over all ``E`` in float32, the ``k`` largest, and
    their weights divided by their sum."""
    p = jax.nn.softmax(logits.astype(F32), axis=-1)
    w, idx = jax.lax.top_k(p, k)
    return idx.astype(jnp.int32), w / jnp.sum(w, axis=-1, keepdims=True)


def shared_gate(x, w):
    """``sigmoid(x . w)`` per row: what the shared expert's result is
    scaled by. ``x (T, U)``, ``w (1, U)`` -> ``(T, 1)`` float32."""
    return jax.nn.sigmoid(jnp.dot(x, w.T, preferred_element_type=F32))


def sort_by_expert(experts, first: int, held: int, real=None):
    """The ``T * k`` assignments in the order of the held experts:
    ``(order, sizes)`` — ``order (T * k,)`` indexes the flattened
    ``experts`` so that expert ``first``'s assignments come first and
    those to absent experts (and of rows that are not ``real (T,)``:
    padding) last; ``sizes (held,)`` int32 counts each held expert's."""
    e = experts - first
    here = (e >= 0) & (e < held)
    if real is not None:
        here = here & real[:, None]
    key = jnp.where(here, e, held).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    # a group's size is the distance between its ends in the sorted keys
    # (a scatter-add of every assignment into its bin is serial on the chip)
    ends = jnp.searchsorted(key[order], jnp.arange(held + 1, dtype=key.dtype))
    return order, jnp.diff(ends).astype(jnp.int32)


def grouped_ffn_jnp(rows, sizes, wg, wu, wd):
    """``rows (M, U)`` sorted by expert, ``sizes (Eh,)``; ``wg``/``wu
    (Eh, U, F)``, ``wd (Eh, F, U)``. Returns ``(M, U)`` in ``rows``'
    dtype; rows past ``sum(sizes)`` are zero."""
    def mm(a, b):
        return jax.lax.ragged_dot(a, b, sizes, preferred_element_type=F32)

    h = (jax.nn.silu(mm(rows, wg)) * mm(rows, wu)).astype(rows.dtype)
    return mm(h, wd).astype(rows.dtype)


def moe_grouped_ffn(x, experts, weights, wg, wu, wd, first: int = 0,
                    real=None):
    """The held experts' part of the layer for ``x (T, U)`` with
    ``experts``/``weights (T, k)`` from :func:`route`; rows that are not
    ``real`` (a chunk's padding) are neither computed nor counted.
    Returns ``(y (T, U) float32, counts (4,) int32)``."""
    from .nn import _tpu_kernels_selected

    t, k = experts.shape
    held = wg.shape[0]
    order, sizes = sort_by_expert(experts, first, held, real)
    rows = x[order // k]
    if _tpu_kernels_selected():
        from .pallas.moe_ffn import grouped_ffn

        out = grouped_ffn(rows, sizes, wg, wu, wd)
    else:
        out = grouped_ffn_jnp(rows, sizes, wg, wu, wd)
    n_held = jnp.sum(sizes)
    # back to (token, choice): a sorted row's place is its rank, the
    # inverse of the sort's permutation (a second sort, not a scatter)
    rank = jnp.argsort(order).astype(jnp.int32)
    w = jnp.where(rank < n_held, weights.reshape(-1), 0.0)
    out = jnp.where((rank < n_held)[:, None], out[rank].astype(F32), 0.0)
    y = jnp.sum((out * w[:, None]).reshape(t, k, -1), axis=1)
    counts = jnp.stack([n_held, jnp.sum(sizes > 0), jnp.max(sizes),
                        jnp.int32(held)]).astype(jnp.int32)
    return y, counts
