"""A decoder block whose memory is a state, not keys and values.

``RetentionDecoderLayer``: RMSNorm pre-norm, a power-retention layer
(:mod:`mxnet_tpu.ops.retention`: grouped K/V heads, per-head RMSNorm on
q and k, rotary positions, one gate per K/V head and token), a
SiLU-gated FFN. The layer keeps one fixed-size state per request in the
pools ``S (L, NB, Hk, d, Dp)`` and ``z (L, NB, Hk, Dp)`` whatever the
request's length; ``forward_step`` advances the lanes' slots by one token
(decode), ``forward_chunk`` one lane's slot by a chunk of tokens
(prefill).

Not imported by ``mxnet_tpu.gluon.nn``: a program that serves no such
model pays nothing for it (``from mxnet_tpu.gluon.nn import retention``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...numpy_extension import _call
from ..block import HybridBlock
from .basic_layers import Dense
from .norm_layers import RMSNorm

__all__ = ["GatedFFN", "PowerRetention", "RetentionDecoderLayer", "rope"]


def rope(x, positions, theta: float):
    """Rotary positions on ``x (T, H, d)`` at absolute ``positions (T,)``:
    the two halves of a head rotate against each other, pair ``n`` by
    ``positions * theta ** (-2 n / d)``. Float32."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    x = x.astype(jnp.float32)
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


class GatedFFN(HybridBlock):
    """``down(silu(gate(x)) * up(x))``, no bias."""

    def __init__(self, units, hidden_size, dtype="float32"):
        super().__init__()
        for name, out, inp in (("gate_proj", hidden_size, units),
                               ("up_proj", hidden_size, units),
                               ("down_proj", units, hidden_size)):
            setattr(self, name, Dense(out, use_bias=False, flatten=False,
                                      in_units=inp, dtype=dtype))

    def forward(self, x):
        gate, up = self.gate_proj(x), self.up_proj(x)
        return self.down_proj(_call(
            lambda g, u: jax.nn.silu(g) * u, (gate, up), name="SiLUGate"))


class PowerRetention(HybridBlock):
    """Power retention of degree 2 over ``num_heads`` query heads that
    share ``num_kv_heads`` keys, values, gates and states."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 rope_theta=1e6, epsilon=1e-6, dtype="float32"):
        super().__init__()
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads do not divide over "
                             f"{num_kv_heads} K/V heads")
        self._heads, self._kv_heads = num_heads, num_kv_heads
        self._head_dim, self._theta = head_dim, float(rope_theta)

        def dense(out, inp=units, bias=False):
            return Dense(out, use_bias=bias, flatten=False, in_units=inp,
                         dtype=dtype)

        self.q_proj = dense(num_heads * head_dim)
        self.k_proj = dense(num_kv_heads * head_dim)
        self.v_proj = dense(num_kv_heads * head_dim)
        self.g_proj = dense(num_kv_heads, bias=True)
        self.o_proj = dense(units, num_heads * head_dim)
        self.q_norm = RMSNorm(epsilon=epsilon, in_channels=head_dim)
        self.k_norm = RMSNorm(epsilon=epsilon, in_channels=head_dim)

    def _project(self, x):
        """``x (T, units)`` -> normed q ``(T, Hq, d)``, normed k and v
        ``(T, Hk, d)`` and the gate's pre-activation ``(T, Hk)``."""
        t, d = x.shape[0], self._head_dim
        q = self.q_norm(self.q_proj(x).reshape(t, self._heads, d))
        k = self.k_norm(self.k_proj(x).reshape(t, self._kv_heads, d))
        v = self.v_proj(x).reshape(t, self._kv_heads, d)
        return q, k, v, self.g_proj(x)

    def forward_step(self, x, pool_s, pool_z, slots, positions, layer):
        """One token per lane: ``x (R, units)`` at ``positions (R,)``,
        lane ``r``'s state in slot ``slots[r]``. Returns
        ``(out (R, units), pool_s, pool_z)``."""
        from ...ops.retention import retention_step

        theta = self._theta

        def fn(q, k, v, gate, ps, pz, sl, pos):
            pos = pos.astype(jnp.int32)
            o, ps, pz = retention_step(
                rope(q, pos, theta), rope(k, pos, theta), v,
                jax.nn.log_sigmoid(gate.astype(jnp.float32)), ps, pz,
                sl.astype(jnp.int32), layer)
            return o.reshape(o.shape[0], -1), ps, pz

        o, pool_s, pool_z = _call(
            fn, (*self._project(x), pool_s, pool_z, slots, positions),
            name="PowerRetentionStep", n_out=3)
        return self.o_proj(o), pool_s, pool_z

    def forward_chunk(self, x, pool_s, pool_z, slot, start, n_real, layer):
        """A chunk of one lane: ``x (c, units)`` at positions ``start +
        arange(c)``, of which the first ``n_real`` rows are tokens; the
        slot counts as zero where ``start == 0``."""
        from ...ops.retention import retention_chunk

        theta = self._theta

        def fn(q, k, v, gate, ps, pz, sl, st, n):
            st = jnp.reshape(st, ()).astype(jnp.int32)
            pos = st + jnp.arange(q.shape[0], dtype=jnp.int32)
            o, ps, pz = retention_chunk(
                rope(q, pos, theta), rope(k, pos, theta), v,
                jax.nn.log_sigmoid(gate.astype(jnp.float32)), ps, pz,
                jnp.reshape(sl, ()).astype(jnp.int32), layer, st == 0,
                jnp.reshape(n, ()).astype(jnp.int32))
            return o.reshape(o.shape[0], -1), ps, pz

        o, pool_s, pool_z = _call(
            fn, (*self._project(x), pool_s, pool_z, slot, start, n_real),
            name="PowerRetentionChunk", n_out=3)
        return self.o_proj(o), pool_s, pool_z


class RetentionDecoderLayer(HybridBlock):
    """``x + retention(norm(x))``, then ``x + ffn(norm(x))``."""

    def __init__(self, units, hidden_size, num_heads, num_kv_heads,
                 head_dim, rope_theta=1e6, epsilon=1e-6, dtype="float32"):
        super().__init__()
        self.input_norm = RMSNorm(epsilon=epsilon, in_channels=units)
        self.retention = PowerRetention(units, num_heads, num_kv_heads,
                                        head_dim, rope_theta, epsilon, dtype)
        self.post_norm = RMSNorm(epsilon=epsilon, in_channels=units)
        self.ffn = GatedFFN(units, hidden_size, dtype)

    def _rest(self, x, h):
        x = x + h
        return x + self.ffn(self.post_norm(x))

    def forward_step(self, x, pool_s, pool_z, slots, positions, layer):
        h, pool_s, pool_z = self.retention.forward_step(
            self.input_norm(x), pool_s, pool_z, slots, positions, layer)
        return self._rest(x, h), pool_s, pool_z

    def forward_chunk(self, x, pool_s, pool_z, slot, start, n_real, layer):
        h, pool_s, pool_z = self.retention.forward_chunk(
            self.input_norm(x), pool_s, pool_z, slot, start, n_real, layer)
        return self._rest(x, h), pool_s, pool_z
