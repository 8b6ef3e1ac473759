"""The three blocks of a Qwen3-Next decoder layer (``model_type``
``qwen3_next``) and the layer that joins them.

``GatedDeltaNet`` — linear attention by the gated delta rule
(:mod:`mxnet_tpu.ops.gated_delta`): a causal depthwise convolution over
``[q | k | v]``, one ``dk x dv`` state per value head and lane, an output
RMSNorm gated by ``silu(z)``. Its cache is the state and the
convolution's tail, one slot a lane. ``GatedAttention`` — softmax
attention with grouped K/V heads, a zero-centred RMSNorm on every q and
k head, rotary positions on the first ``rotary_dim`` values of a head and
a sigmoid gate on the output; its cache is K/V rows in blocks
(:func:`mxnet_tpu.ops.nn.paged_attention`,
:mod:`mxnet_tpu.ops.gated_attention`). ``SparseExperts`` — a router over
all ``num_experts``, the ``experts_per_token`` largest renormalised, the
part of the result that the ``experts_held`` experts from
``first_expert`` on give (:mod:`mxnet_tpu.ops.experts`), and one shared
expert behind a sigmoid gate.

Every block has a step (decode: one token per lane) and a chunk (prefill:
``c`` tokens of one lane). Not imported by ``mxnet_tpu.gluon.nn``
(``from mxnet_tpu.gluon.nn import qwen3next``).
"""
from __future__ import annotations

import jax.numpy as jnp

from ...numpy_extension import _call
from ..block import HybridBlock
from ..parameter import Parameter
from .basic_layers import Dense
from .retention import GatedFFN, rope

__all__ = ["GatedDeltaNet", "GatedAttention", "SparseExperts",
           "Qwen3NextDecoderLayer"]

F32 = jnp.float32


def _dense(out, inp, dtype):
    return Dense(out, use_bias=False, flatten=False, in_units=inp,
                 dtype=dtype)


def _i32(x):
    return jnp.reshape(x, ()).astype(jnp.int32)


class GatedDeltaNet(HybridBlock):
    def __init__(self, units, key_heads, value_heads, key_dim, value_dim,
                 conv_width=4, epsilon=1e-6, dtype="float32"):
        super().__init__()
        if value_heads % key_heads:
            raise ValueError(f"{value_heads} value heads do not divide over "
                             f"{key_heads} key heads")
        self._hk, self._hv = key_heads, value_heads
        self._dk, self._dv, self._eps = key_dim, value_dim, float(epsilon)
        self._width = conv_width
        qk, vz = key_heads * key_dim, value_heads * value_dim
        self.qkvz_proj = _dense(2 * qk + 2 * vz, units, dtype)
        self.ba_proj = _dense(2 * value_heads, units, dtype)
        self.conv = Parameter("conv", shape=(conv_width, 2 * qk + vz),
                              dtype="float32")
        self.a_log = Parameter("a_log", shape=(value_heads,),
                               dtype="float32")
        self.dt_bias = Parameter("dt_bias", shape=(value_heads,),
                                 dtype="float32")
        self.out_norm = Parameter("out_norm", shape=(value_dim,),
                                  dtype="float32")
        self.out_proj = _dense(units, vz, dtype)

    @property
    def channels(self) -> int:
        """Channels the convolution runs over: ``[q | k | v]``."""
        return 2 * self._hk * self._dk + self._hv * self._dv

    def _mix(self, x, pools, run):
        """``run(qkv, gates) -> (q, k, v, g, beta, recur)``'s common
        frame: project, convolve, gate, normalise, recur, norm, project."""
        from ...ops import gated_delta as gd

        hk, hv, dk, dv = self._hk, self._hv, self._dk, self._dv
        eps, ch = self._eps, self.channels

        def fn(mixed, ba, w, a_log, dt_bias, gn, *rest):
            y, conv_done, recur = run(mixed[..., :ch], w, *rest)
            t = y.shape[0]
            q = y[:, :hk * dk].reshape(t, hk, dk)
            k = y[:, hk * dk:2 * hk * dk].reshape(t, hk, dk)
            v = y[:, 2 * hk * dk:].reshape(t, hv, dv)
            g, beta = gd.gates(ba[:, hv:], ba[:, :hv], a_log, dt_bias)
            o, pool_s = recur(gd.l2norm(q, eps) * F32(dk ** -0.5),
                              gd.l2norm(k, eps), v, g, beta)
            o = gd.gated_norm(o, mixed[..., ch:].reshape(t, hv, dv), gn, eps)
            return o.reshape(t, hv * dv).astype(mixed.dtype), pool_s, \
                conv_done

        o, pool_s, pool_c = _call(
            fn, (self.qkvz_proj(x), self.ba_proj(x), self.conv.data(),
                 self.a_log.data(), self.dt_bias.data(),
                 self.out_norm.data(), *pools), name="GatedDeltaNet",
            n_out=3)
        return self.out_proj(o), pool_s, pool_c

    def forward_step(self, x, pool_s, pool_c, slots, layer):
        """One token per lane: ``x (R, units)``, lane ``r``'s state and
        tail in slot ``slots[r]`` of layer ``layer``."""
        from ...ops import gated_delta as gd

        def run(qkv, w, ps, pc, sl):
            sl = sl.astype(jnp.int32)
            y, pc = gd.conv_step(qkv, pc, sl, layer, w)
            return y, pc, lambda *a: gd.delta_step(*a, ps, sl, layer)

        return self._mix(x, (pool_s, pool_c, slots), run)

    def forward_chunk(self, x, pool_s, pool_c, slot, start, n_real, layer):
        """A chunk of one lane: ``x (c, units)``, the first ``n_real``
        rows tokens; the slot counts as zero where ``start == 0``."""
        from ...ops import gated_delta as gd

        def run(qkv, w, ps, pc, sl, st, n):
            sl, fresh, n = _i32(sl), _i32(st) == 0, _i32(n)
            y, pc = gd.conv_chunk(qkv, pc, sl, layer, w, fresh, n)
            return y, pc, lambda *a: gd.delta_chunk(*a, ps, sl, layer,
                                                    fresh, n)

        return self._mix(x, (pool_s, pool_c, slot, start, n_real), run)


class GatedAttention(HybridBlock):
    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 rotary_dim, rope_theta=1e7, epsilon=1e-6, dtype="float32"):
        super().__init__()
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads do not divide over "
                             f"{num_kv_heads} K/V heads")
        self._h, self._hkv, self._d = num_heads, num_kv_heads, head_dim
        self._rot, self._theta = rotary_dim, float(rope_theta)
        self._eps = float(epsilon)
        self.q_proj = _dense(2 * num_heads * head_dim, units, dtype)
        self.k_proj = _dense(num_kv_heads * head_dim, units, dtype)
        self.v_proj = _dense(num_kv_heads * head_dim, units, dtype)
        self.o_proj = _dense(units, num_heads * head_dim, dtype)
        self.q_norm = Parameter("q_norm", shape=(head_dim,), dtype="float32")
        self.k_norm = Parameter("k_norm", shape=(head_dim,), dtype="float32")

    def _attend(self, x, rest, run):
        """``run(q, k_rows, v_rows, *rest) -> (attention, pool_k,
        pool_v)`` between the projections, norms and rotary in front and
        the gate and projection behind."""
        from ...ops import gated_attention as ga

        h, hkv, d, rot = self._h, self._hkv, self._d, self._rot
        theta, eps = self._theta, self._eps

        def turn(y, pos):           # rotary on a head's first ``rot``
            return jnp.concatenate(
                [rope(y[..., :rot], pos, theta), y[..., rot:]], axis=-1)

        def fn(qg, k, v, wq, wk, pos, *rest):
            t = qg.shape[0]
            qg = qg.reshape(t, h, 2 * d)
            q = turn(ga.rms0(qg[..., :d], wq, eps), pos)
            k = turn(ga.rms0(k.reshape(t, hkv, d), wk, eps), pos)
            o, pk, pv = run(q, k.reshape(t, hkv * d), v, pos, *rest)
            o = ga.output_gate(o, qg[..., d:])
            return o.reshape(t, h * d).astype(qg.dtype), pk, pv

        o, pool_k, pool_v = _call(
            fn, (self.q_proj(x), self.k_proj(x), self.v_proj(x),
                 self.q_norm.data(), self.k_norm.data(), *rest),
            name="GatedAttention", n_out=3)
        return self.o_proj(o), pool_k, pool_v

    def forward_step(self, x, pool_k, pool_v, block_table, positions, layer):
        """One token per lane at ``positions (R,)``: its K/V row stored
        through the lane's table, then attention over the lane's rows."""
        from ...ops.nn import paged_attention

        def run(q, k, v, pos, pk, pv, bt):
            pos = pos.astype(jnp.int32)
            bs = pk.shape[2]
            blk = jnp.take_along_axis(bt, (pos // bs)[:, None], axis=1)[:, 0]
            pk = pk.at[layer, blk, pos % bs].set(k.astype(pk.dtype))
            pv = pv.at[layer, blk, pos % bs].set(v.astype(pv.dtype))
            return paged_attention(q, pk, pv, bt, pos + 1, layer), pk, pv

        return self._attend(x, (positions, pool_k, pool_v, block_table), run)

    def forward_chunk(self, x, pool_k, pool_v, table, start, layer):
        """A chunk of one lane at positions ``start + arange(c)``: its
        rows stored through ``table (MB,)``, then attention over rows
        ``0 .. start + t``."""
        from ...ops.gated_attention import paged_chunk_attention, store_rows

        def fn_pos(st):
            return _i32(st) + jnp.arange(x.shape[0], dtype=jnp.int32)

        def run(q, k, v, pos, pk, pv, tb):
            pk = store_rows(pk, k, tb, pos[0], layer)
            pv = store_rows(pv, v, tb, pos[0], layer)
            return paged_chunk_attention(q, pk, pv, tb, pos[0], layer), \
                pk, pv

        positions = _call(fn_pos, (start,), name="ChunkPositions")
        return self._attend(x, (positions, pool_k, pool_v, table), run)


class SparseExperts(HybridBlock):
    def __init__(self, units, expert_size, num_experts, experts_per_token,
                 experts_held=None, first_expert=0, shared_size=None,
                 dtype="float32"):
        super().__init__()
        held = num_experts if experts_held is None else experts_held
        if first_expert < 0 or first_expert + held > num_experts:
            raise ValueError(
                f"experts {first_expert}..{first_expert + held - 1} are "
                f"not among {num_experts}")
        self._k, self._first = experts_per_token, first_expert
        self.router = _dense(num_experts, units, dtype)
        for name, shape in (("gate", (held, units, expert_size)),
                            ("up", (held, units, expert_size)),
                            ("down", (held, expert_size, units))):
            setattr(self, name, Parameter(name, shape=shape, dtype=dtype))
        self.shared = GatedFFN(units, shared_size or expert_size, dtype)
        self.shared_gate = _dense(1, units, dtype)

    def forward(self, x, real=None):
        """``x (T, units)`` -> ``(out (T, units), counts (4,) int32)``;
        rows where ``real (T,)`` is false (padding) are not routed."""
        from ...ops import experts as ex

        k, first = self._k, self._first

        def fn(h, wr, wg, wu, wd, ws, shared, *real):
            logits = jnp.dot(h, wr.T, preferred_element_type=F32)
            idx, w = ex.route(logits, k)
            y, counts = ex.moe_grouped_ffn(h, idx, w, wg, wu, wd, first,
                                           *real)
            out = y + ex.shared_gate(h, ws) * shared.astype(F32)
            return out.astype(h.dtype), counts

        args = (x, self.router.weight.data(), self.gate.data(),
                self.up.data(), self.down.data(),
                self.shared_gate.weight.data(), self.shared(x))
        return _call(fn, args if real is None else args + (real,),
                     name="SparseExperts", n_out=2)


class Qwen3NextDecoderLayer(HybridBlock):
    """``x + mixer(norm(x))``, then ``x + experts(norm(x))``; the mixer is
    a :class:`GatedAttention` where ``full_attention`` and a
    :class:`GatedDeltaNet` otherwise."""

    def __init__(self, full_attention, units, mixer: dict, experts: dict,
                 epsilon=1e-6, dtype="float32"):
        super().__init__()
        self.full_attention = bool(full_attention)
        self._eps = float(epsilon)
        self.input_norm = Parameter("input_norm", shape=(units,),
                                    dtype="float32")
        self.post_norm = Parameter("post_norm", shape=(units,),
                                   dtype="float32")
        kind = GatedAttention if full_attention else GatedDeltaNet
        self.mixer = kind(units, epsilon=epsilon, dtype=dtype, **mixer)
        self.experts = SparseExperts(units, dtype=dtype, **experts)

    def _norm(self, x, w):
        from ...ops import gated_attention as ga

        eps = self._eps
        return _call(lambda a, b: ga.rms0(a, b, eps).astype(a.dtype),
                     (x, w.data()), name="RMSNorm0")

    def finish(self, x, h, real=None):
        """The residual around the mixer's ``h`` and the expert half."""
        x = x + h
        y, counts = self.experts(self._norm(x, self.post_norm), real)
        return x + y, counts

    def normed(self, x):
        return self._norm(x, self.input_norm)
