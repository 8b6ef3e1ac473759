"""What a gated softmax-attention layer with grouped K/V heads needs
beside :func:`mxnet_tpu.ops.nn.paged_attention`: the RMSNorms, the
output gates, rotary positions by given frequencies, and prefill in
chunks that write their K/V rows through the lane's block table and
attend over everything the lane has written so far — or, in a layer with
an attention *window*, over the window's last rows, which a lane keeps
in a ring (below).

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

**A window layer keeps a ring.** A layer whose token at position ``p``
attends to positions ``p - W < j <= p`` needs the last ``W`` rows and no
more, so its rows are not in blocks through a table but in a ring of
``W`` rows a lane and layer, ``ring (L, slots, W, Hkv * D)``, position
``p`` in row ``p mod W`` of the lane's slot. Keys are stored after
rotary, so the order of a ring's rows never matters to the softmax, only
which are live: decoding at position ``p`` (its row stored first) is
paged attention over the lane's ring seen as ``W / 16`` fixed blocks
with length ``min(p + 1, W)`` (:func:`ring_blocks`), and a chunk attends
to the ring's live rows and to its own under the band mask, a block of
queries at a time over the ``W`` + block keys it can see
(:func:`window_chunk_attention`), then leaves its last ``W`` real rows
in the ring; padding rows are never stored.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as onp

__all__ = ["rms0", "rms", "output_gate", "head_gate", "rope_frequencies",
           "yarn_frequencies",
           "rotary", "store_rows", "paged_chunk_attention", "ring_store",
           "ring_blocks", "window_chunk_attention"]

F32 = jnp.float32
KEY_BLOCK = 512
_NEG = -1e30


def rms0(x, w, eps: float = 1e-6):
    """``x / sqrt(mean(x^2) + eps) * (1 + w)`` over the last axis, in
    float32: an RMSNorm whose weight is stored around zero."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + w.astype(F32))


def rms(x, w, eps: float = 1e-6):
    """``x / sqrt(mean(x^2) + eps) * w`` over the last axis, in float32."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def head_gate(o, gate):
    """``o (T, H, D) * sigmoid(gate (T, H))``: one gate a head and token
    on the attention's output (the head-wise form of
    :func:`output_gate`); float32."""
    return o.astype(F32) * jax.nn.sigmoid(gate.astype(F32))[..., None]


def rope_frequencies(rot: int, theta: float):
    """The plain rule's ``rot / 2`` rotation frequencies, ``f_i =
    theta^(-2i / rot)``, float64."""
    return theta ** (-2.0 * onp.arange(rot // 2, dtype=onp.float64) / rot)


def yarn_frequencies(rot: int, theta: float, factor: float, original: int,
                     beta_fast: float, beta_slow: float):
    """The ``rot / 2`` rotation frequencies of YaRN as float32: pair
    ``i`` of the plain rule turns by ``f_i``; pairs that turn more than
    ``beta_fast`` times over the ``original`` length keep ``f_i``, those
    that turn less than ``beta_slow`` times get ``f_i / factor``, and
    between the two bounds (``low``, ``high``, as pair indices, clipped
    to ``0 .. rot - 1``) the two are mixed linearly."""
    def bound(turns):
        return rot * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = min(max(math.floor(bound(beta_fast)), 0), rot - 1)
    high = min(max(math.ceil(bound(beta_slow)), 0), rot - 1)
    f = rope_frequencies(rot, theta)
    r = onp.clip((onp.arange(rot // 2) - low) / max(high - low, 1e-3),
                 0.0, 1.0)
    return (f * (1.0 - r) + f / factor * r).astype(onp.float32)


def rotary(x, positions, freq, scale: float = 1.0):
    """Rotary positions on the first ``2 * len(freq)`` values of every
    head of ``x (T, H, D)`` at absolute ``positions (T,)``: the two
    halves of those values rotate against each other, pair ``i`` by
    ``positions * freq[i]``, ``cos`` and ``sin`` times ``scale``; the
    other values pass unchanged. Float32."""
    half = len(freq)
    angle = positions.astype(F32)[:, None] * jnp.asarray(freq, F32)[None]
    cos = (jnp.cos(angle) * F32(scale))[:, None]
    sin = (jnp.sin(angle) * F32(scale))[:, None]
    x = x.astype(F32)
    lo, hi = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate(
        [lo * cos - hi * sin, hi * cos + lo * sin, x[..., 2 * half:]], -1)


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


# --- a window layer's ring --------------------------------------------------
RING_BLOCK = 16


def ring_store(ring, rows, slots, positions, layer):
    """A decode step's rows ``(R, W_row)``, lane ``r``'s at position
    ``positions[r]``, into row ``positions[r] mod W`` of slot
    ``slots[r]`` of ``ring (L, S, W, W_row)``."""
    w = ring.shape[2]
    return ring.at[layer, slots, positions % w].set(rows.astype(ring.dtype))


def ring_blocks(ring, slots):
    """The ring pool as :func:`~mxnet_tpu.ops.nn.paged_attention` reads
    a pool — ``(L, S * W / b, b, W_row)``, the same bytes — and the
    lanes' tables ``(R, W / b)``: slot ``s`` is blocks ``s * W / b`` and
    on, always the same ones. ``b`` is ``RING_BLOCK``: the window is a
    whole number of them."""
    lyr, s, w, row = ring.shape
    per = w // RING_BLOCK
    table = slots.astype(jnp.int32)[:, None] * per \
        + jnp.arange(per, dtype=jnp.int32)[None]
    return ring.reshape(lyr, s * per, RING_BLOCK, row), table


def window_chunk_attention(q, k, v, ring_k, ring_v, slot, start, n_real,
                           layer):
    """A window layer's chunk: queries ``q (c, H, D)`` at positions
    ``start + arange(c)``, the chunk's own rows ``k``, ``v (c, Hkv * D)``
    (the first ``n_real`` of them tokens), the lane's ring in slot
    ``slot`` of ``ring_k``, ``ring_v (L, S, W, Hkv * D)``. Position ``p``
    attends to ``p - W < j <= p``: to the ring's live rows (positions
    ``start - W .. start - 1`` that are not negative) and to the chunk's
    own, which meet the queries in the ring's dtype as stored rows would.
    A block of queries sees only the ``W`` + block keys of its band; the
    others are never multiplied. Returns ``(out (c, H, D) float32,
    ring_k, ring_v)`` with the chunk's last ``W`` real rows in the ring
    (a padding row is never stored, so a last chunk leaves the rows
    before it live)."""
    c, h, d = q.shape
    w = ring_k.shape[2]
    hkv = k.shape[1] // d
    g = h // hkv
    qb = math.gcd(c, max(w // 2, 1))        # queries a block
    nb = c // qb
    native = ring_k.dtype == jnp.bfloat16
    precision = jax.lax.Precision.DEFAULT if native \
        else jax.lax.Precision.HIGHEST
    i32 = jnp.int32
    start, n_real = start.astype(i32), n_real.astype(i32)
    # the W positions before the chunk, in order: position p is row p mod W
    before = start - i32(w) + jnp.arange(w, dtype=i32)
    scale = F32(d ** -0.5)

    def keys(ring, own):
        old = jax.lax.dynamic_index_in_dim(
            jax.lax.dynamic_index_in_dim(ring, layer, 0, False), slot, 0,
            False)
        rows = jnp.concatenate([old[before % i32(w)],
                                own.astype(ring.dtype)])
        return old, rows.reshape(w + c, hkv, d).swapaxes(0, 1)

    old_k, all_k = keys(ring_k, k)          # (Hkv, W + c, D), by position
    old_v, all_v = keys(ring_v, v)
    qg = q.astype(ring_k.dtype).reshape(nb, qb, hkv, g, d) \
        .transpose(0, 2, 3, 1, 4).reshape(nb, hkv, g * qb, d)
    t_in = jnp.tile(jnp.arange(qb, dtype=i32), g)       # a row's query

    def block(xs):
        i, qi = xs
        kk = jax.lax.dynamic_slice_in_dim(all_k, i * i32(qb), qb + w, 1)
        vv = jax.lax.dynamic_slice_in_dim(all_v, i * i32(qb), qb + w, 1)
        s = jnp.einsum("jtd,jsd->jts", qi, kk, precision=precision,
                       preferred_element_type=F32) * scale
        # key s of the slice lies at position start - W + i qb + s, query
        # t at start + i qb + t: seen where 0 < s - t <= W and not
        # before the sequence
        off = jnp.arange(qb + w, dtype=i32)[None, :] - t_in[:, None]
        seen = (off > 0) & (off <= w) & (
            (before[0] + i * i32(qb)
             + jnp.arange(qb + w, dtype=i32))[None, :] >= 0)
        s = jnp.where(seen[None], s, F32(_NEG))
        p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        o = jnp.einsum("jts,jsd->jtd", p.astype(vv.dtype), vv,
                       precision=precision, preferred_element_type=F32)
        return o / jnp.sum(p, axis=-1)[..., None]

    out = jax.lax.map(block, (jnp.arange(nb, dtype=i32), qg))
    out = out.reshape(nb, hkv, g, qb, d).transpose(0, 3, 1, 2, 4) \
        .reshape(c, h, d)

    # row i of the ring takes the newest real position of the chunk that
    # is i mod W, where the chunk has one
    last = start + n_real - i32(1)
    newest = last - (last - jnp.arange(w, dtype=i32)) % i32(w)
    mine = (newest >= start)[:, None]
    src = jnp.clip(newest - start, 0, c - 1)

    def stored(ring, old, own):
        rows = jnp.where(mine, own.astype(ring.dtype)[src], old)
        return jax.lax.dynamic_update_slice(
            ring, rows[None, None],
            (jnp.asarray(layer, i32), slot.astype(i32), i32(0), i32(0)))

    return out, stored(ring_k, old_k, k), stored(ring_v, old_v, v)
