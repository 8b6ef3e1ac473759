"""The blocks of a Laguna decoder layer (``model_type`` ``laguna``) and the
layer that joins them.

``WindowedAttention`` — softmax attention with grouped K/V heads, rotary
positions by the layer's own frequencies and **one sigmoid gate a head
and token** on the output (``gating: per-head``). A layer is one of two
kinds, and the kinds differ in how many query heads they have, in their
rotary rule and in what they keep of a sequence: a *full* layer keeps
every position's K/V rows in blocks through the lane's table
(:func:`mxnet_tpu.ops.nn.paged_attention`,
:func:`mxnet_tpu.ops.gated_attention.paged_chunk_attention`); a *window*
layer attends to the last ``window`` positions and keeps those rows in a
ring a lane (:mod:`mxnet_tpu.ops.gated_attention`, "A window layer keeps
a ring"). ``RoutedExperts`` — a router over all ``num_experts``, the
``experts_per_token`` largest renormalised and multiplied by
``routed_scale``, the part of the result that the ``experts_held``
experts from ``first_expert`` on give (:mod:`mxnet_tpu.ops.experts`), and
one shared expert, added as it is.

Every block has a step (decode: one token per lane) and a chunk (prefill:
``c`` tokens of one lane). Not imported by ``mxnet_tpu.gluon.nn``
(``from mxnet_tpu.gluon.nn import laguna``).
"""
from __future__ import annotations

import jax.numpy as jnp

from ...numpy_extension import _call
from ..block import HybridBlock
from ..parameter import Parameter
from .qwen3next import _dense, _i32
from .retention import GatedFFN

__all__ = ["WindowedAttention", "RoutedExperts", "LagunaDecoderLayer"]

F32 = jnp.float32


class WindowedAttention(HybridBlock):
    """``window``: None for a full layer, else the positions a token
    attends to, its own among them. ``freq``: the rotation frequencies of
    the first ``2 * len(freq)`` values of a head; ``rotary_scale``
    multiplies ``cos`` and ``sin``."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim, freq,
                 rotary_scale=1.0, window=None, dtype="float32"):
        super().__init__()
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads do not divide over "
                             f"{num_kv_heads} K/V heads")
        self._h, self._hkv, self._d = num_heads, num_kv_heads, head_dim
        self._freq, self._scale = freq, float(rotary_scale)
        self.window = window
        self.q_proj = _dense(num_heads * head_dim, units, dtype)
        self.k_proj = _dense(num_kv_heads * head_dim, units, dtype)
        self.v_proj = _dense(num_kv_heads * head_dim, units, dtype)
        self.g_proj = _dense(num_heads, units, dtype)
        self.o_proj = _dense(units, num_heads * head_dim, dtype)

    def _attend(self, x, rest, run):
        """``run(q, k_rows, v_rows, positions, *rest) -> (attention,
        pool_k, pool_v)`` between the projections and rotary in front
        and the gate and projection behind."""
        from ...ops import gated_attention as ga

        h, hkv, d = self._h, self._hkv, self._d
        freq, scale = self._freq, self._scale

        def fn(q, k, v, gate, pos, *rest):
            t = q.shape[0]
            q = ga.rotary(q.reshape(t, h, d), pos, freq, scale)
            k = ga.rotary(k.reshape(t, hkv, d), pos, freq, scale)
            o, pk, pv = run(q, k.reshape(t, hkv * d), v, pos, *rest)
            o = ga.head_gate(o, gate)
            return o.reshape(t, h * d).astype(gate.dtype), pk, pv

        o, pool_k, pool_v = _call(
            fn, (self.q_proj(x), self.k_proj(x), self.v_proj(x),
                 self.g_proj(x), *rest), name="WindowedAttention", n_out=3)
        return self.o_proj(o), pool_k, pool_v

    def forward_step(self, x, pool_k, pool_v, where, positions, layer):
        """One token per lane at ``positions (R,)``. ``where``: the
        lanes' block tables ``(R, MB)`` (a full layer: the row is stored
        through the table, then attention over the lane's rows) or their
        slots ``(R,)`` (a window layer: the row goes into the ring, then
        attention over the ring's live rows)."""
        from ...ops import gated_attention as ga
        from ...ops.nn import paged_attention

        def full(q, k, v, pos, pk, pv, bt):
            pos = pos.astype(jnp.int32)
            bs = pk.shape[2]
            blk = jnp.take_along_axis(bt, (pos // bs)[:, None], axis=1)[:, 0]
            pk = pk.at[layer, blk, pos % bs].set(k.astype(pk.dtype))
            pv = pv.at[layer, blk, pos % bs].set(v.astype(pv.dtype))
            return paged_attention(q, pk, pv, bt, pos + 1, layer), pk, pv

        def ring(q, k, v, pos, rk, rv, slots):
            pos, slots = pos.astype(jnp.int32), slots.astype(jnp.int32)
            rk = ga.ring_store(rk, k, slots, pos, layer)
            rv = ga.ring_store(rv, v, slots, pos, layer)
            (bk, table), (bv, _) = (ga.ring_blocks(r, slots)
                                    for r in (rk, rv))
            live = jnp.minimum(pos + 1, rk.shape[2])
            return paged_attention(q, bk, bv, table, live, layer), rk, rv

        return self._attend(x, (positions, pool_k, pool_v, where),
                            full if self.window is None else ring)

    def forward_chunk(self, x, pool_k, pool_v, where, start, n_real, layer):
        """A chunk of one lane at positions ``start + arange(c)``, the
        first ``n_real`` rows tokens. ``where``: the lane's table
        ``(MB,)`` (a full layer) or its slot ``()`` (a window layer)."""
        from ...ops import gated_attention as ga

        def fn_pos(st):
            return _i32(st) + jnp.arange(x.shape[0], dtype=jnp.int32)

        def full(q, k, v, pos, pk, pv, tb, n):
            pk = ga.store_rows(pk, k, tb, pos[0], layer)
            pv = ga.store_rows(pv, v, tb, pos[0], layer)
            return ga.paged_chunk_attention(q, pk, pv, tb, pos[0], layer), \
                pk, pv

        def ring(q, k, v, pos, rk, rv, slot, n):
            return ga.window_chunk_attention(q, k, v, rk, rv, _i32(slot),
                                             pos[0], _i32(n), layer)

        positions = _call(fn_pos, (start,), name="ChunkPositions")
        return self._attend(x, (positions, pool_k, pool_v, where, n_real),
                            full if self.window is None else ring)


class RoutedExperts(HybridBlock):
    def __init__(self, units, expert_size, num_experts, experts_per_token,
                 routed_scale=1.0, experts_held=None, first_expert=0,
                 shared_size=None, dtype="float32"):
        super().__init__()
        held = num_experts if experts_held is None else experts_held
        if first_expert < 0 or first_expert + held > num_experts:
            raise ValueError(
                f"experts {first_expert}..{first_expert + held - 1} are "
                f"not among {num_experts}")
        self._k, self._first = experts_per_token, first_expert
        self._scale = float(routed_scale)
        self.router = _dense(num_experts, units, dtype)
        for name, shape in (("gate", (held, units, expert_size)),
                            ("up", (held, units, expert_size)),
                            ("down", (held, expert_size, units))):
            setattr(self, name, Parameter(name, shape=shape, dtype=dtype))
        self.shared = GatedFFN(units, shared_size or expert_size, dtype)

    def forward(self, x, real=None):
        """``x (T, units)`` -> ``(out (T, units), counts (4,) int32)``;
        rows where ``real (T,)`` is false (padding) are not routed."""
        from ...ops import experts as ex

        k, first, scale = self._k, self._first, self._scale

        def fn(h, wr, wg, wu, wd, shared, *real):
            logits = jnp.dot(h, wr.T, preferred_element_type=F32)
            idx, w = ex.route(logits, k)
            y, counts = ex.moe_grouped_ffn(h, idx, w * F32(scale), wg, wu,
                                           wd, first, *real)
            return (y + shared.astype(F32)).astype(h.dtype), counts

        args = (x, self.router.weight.data(), self.gate.data(),
                self.up.data(), self.down.data(), self.shared(x))
        return _call(fn, args if real is None else args + (real,),
                     name="RoutedExperts", n_out=2)


class LagunaDecoderLayer(HybridBlock):
    """``x + mixer(norm(x))``, then ``x + ffn(norm(x))``; the FFN is a
    dense :class:`GatedFFN` of ``dense_size`` where that is given and
    :class:`RoutedExperts` otherwise. RMSNorm ``x / rms(x) * w``."""

    def __init__(self, units, mixer: dict, experts: dict, dense_size=None,
                 epsilon=1e-6, dtype="float32"):
        super().__init__()
        self._eps = float(epsilon)
        self.input_norm = Parameter("input_norm", shape=(units,),
                                    dtype="float32")
        self.post_norm = Parameter("post_norm", shape=(units,),
                                   dtype="float32")
        self.mixer = WindowedAttention(units, dtype=dtype, **mixer)
        self.sparse = dense_size is None
        if self.sparse:
            self.experts = RoutedExperts(units, dtype=dtype, **experts)
        else:
            self.ffn = GatedFFN(units, dense_size, dtype)

    def _norm(self, x, w):
        from ...ops import gated_attention as ga

        eps = self._eps
        return _call(lambda a, b: ga.rms(a, b, eps).astype(a.dtype),
                     (x, w.data()), name="RMSNorm")

    def normed(self, x):
        return self._norm(x, self.input_norm)

    def finish(self, x, h, real=None):
        """The residual around the mixer's ``h`` and the FFN half;
        ``counts`` is None behind a dense FFN."""
        x = x + h
        h2 = self._norm(x, self.post_norm)
        if not self.sparse:
            return x + self.ffn(h2), None
        y, counts = self.experts(h2, real)
        return x + y, counts
