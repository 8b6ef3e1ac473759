"""What a gated softmax-attention layer with grouped K/V heads needs
beside :func:`mxnet_tpu.ops.nn.paged_attention`: the zero-centred
RMSNorm and prefill in
chunks that write their K/V rows through the lane's block table and
attend over everything the lane has written so far.

**The chunk's attention never holds its scores.** A chunk of ``c``
queries at positions ``start .. start + c - 1`` attends over keys ``0 ..
start + t``; with 16 heads, 2,048 queries and 17,408 keys the scores
alone would be 2.3 GB of float32. They are taken a block of keys at a
time (512, or the chunk where it is shorter) in plain XLA, on the chip as
elsewhere: the block's rows gathered out of the pool through the table,
then the flash recurrence (running max, running sum, rescaled
accumulator), under a loop that stops at the last block the chunk can
see. On the chip a chunk of 2,048 costs nearly the same wherever in a
16k prompt it lies, and the flash kernel a segment of keys a call moved
neither the chunk's time nor the cell's rate (PERF.md, section 6, PR 33):
a kernel for it has to show its gain end to end.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["rms0", "output_gate", "store_rows", "paged_chunk_attention"]

F32 = jnp.float32
KEY_BLOCK = 512
_NEG = -1e30


def rms0(x, w, eps: float = 1e-6):
    """``x / sqrt(mean(x^2) + eps) * (1 + w)`` over the last axis, in
    float32: an RMSNorm whose weight is stored around zero."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + w.astype(F32))


def output_gate(o, gate):
    """``o * sigmoid(gate)``: the gate on the attention's output, from the
    second half of every query head's projection; float32."""
    return o.astype(F32) * jax.nn.sigmoid(gate.astype(F32))


def store_rows(pool, rows, table, start, layer):
    """Write ``rows (c, W)`` — the chunk's tokens at positions ``start +
    arange(c)``, ``start`` and ``c`` whole blocks — into ``pool (L, NB,
    bs, W)`` through the lane's ``table (MB,)``. Blocks the table does
    not reach (a last chunk's padding past the context) land in the
    pool's last block, the trash."""
    bs = pool.shape[2]
    nb = rows.shape[0] // bs
    reach = jnp.concatenate(
        [table.astype(jnp.int32),
         jnp.full((nb,), pool.shape[1] - 1, jnp.int32)])
    ids = jax.lax.dynamic_slice_in_dim(reach, start // bs, nb)
    return pool.at[layer, ids].set(
        rows.astype(pool.dtype).reshape(nb, bs, -1))


def paged_chunk_attention(q, pool_k, pool_v, table, start, layer):
    """Causal attention of a chunk's queries ``q (c, H, D)`` at positions
    ``start + arange(c)`` over the lane's rows ``0 .. start + t`` of
    layer ``layer`` in the pools ``(L, NB, bs, Hkv * D)`` (the chunk's
    own rows already stored: :func:`store_rows`); query head ``i`` reads
    K/V head ``i // (H / Hkv)``. Returns ``(c, H, D)`` float32."""
    c, h, d = q.shape
    bs = pool_k.shape[2]
    hkv = pool_k.shape[3] // d
    kb = max(bs, min(KEY_BLOCK, c) // bs * bs)  # keys a step of the loop
    per = kb // bs                              # blocks a key block
    pad = -table.shape[0] % per
    reach = jnp.concatenate(
        [table.astype(jnp.int32),
         jnp.full((pad + per,), pool_k.shape[1] - 1, jnp.int32)])
    native = pool_k.dtype == jnp.bfloat16
    precision = jax.lax.Precision.DEFAULT if native \
        else jax.lax.Precision.HIGHEST
    # the queries once as (Hkv, G * c, D), a K/V head's query heads one
    # after the other: every block is then two plain batched matmuls over
    # the K/V heads, and nothing the size of the scores is transposed
    g = h // hkv
    qg = q.astype(pool_k.dtype).reshape(c, hkv, g, d) \
        .transpose(1, 2, 0, 3).reshape(hkv, g * c, d)
    pos_q = jnp.tile(start + jnp.arange(c, dtype=jnp.int32), g)
    scale = F32(d ** -0.5)

    def block(j, carry):
        m, l, acc = carry
        ids = jax.lax.dynamic_slice_in_dim(reach, j * per, per)
        k = pool_k[layer, ids].reshape(kb, hkv, d).swapaxes(0, 1)
        v = pool_v[layer, ids].reshape(kb, hkv, d).swapaxes(0, 1)
        s = jnp.einsum("jtd,jsd->jts", qg, k, precision=precision,
                       preferred_element_type=F32) * scale
        pos_k = j * kb + jnp.arange(kb, dtype=jnp.int32)
        s = jnp.where(pos_k[None, :] <= pos_q[:, None], s, F32(_NEG))
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "jts,jsd->jtd", p.astype(v.dtype), v, precision=precision,
            preferred_element_type=F32)
        return m_new, l, acc

    init = (jnp.full((hkv, g * c), _NEG, F32), jnp.zeros((hkv, g * c), F32),
            jnp.zeros((hkv, g * c, d), F32))
    last = (start + jnp.int32(c + kb - 1)) // jnp.int32(kb)
    _, l, acc = jax.lax.fori_loop(jnp.int32(0), last, block, init)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(hkv, g, c, d).transpose(2, 0, 1, 3).reshape(c, h, d)
