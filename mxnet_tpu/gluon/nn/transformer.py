"""Transformer building blocks.

The reference ships only raw attention primitive *ops*
(``src/operator/contrib/transformer.cc:650`` interleaved QK/valatt matmuls)
— the layers lived in gluonnlp. Here the layers are first-class: designed
for TPU (flash-attention Pallas kernel on the hot path, bf16-safe fp32
softmax, optional Megatron tensor parallelism via ``tp_axis``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as onp

from ... import numpy_extension as npx
from ...numpy_extension import _call
from ...ndarray.ndarray import ndarray, _unwrap, _wrap
from ..block import HybridBlock
from ..parameter import Parameter
from .basic_layers import Dense, Dropout, HybridSequential
from .norm_layers import LayerNorm

__all__ = [
    "MultiHeadAttention",
    "PositionwiseFFN",
    "TransformerEncoderLayer",
    "TransformerEncoder",
    "kv_cache_quantize",
    "kv_cache_dequantize",
]


from ...ops.nn import attend as _attend
# int8 KV cache helpers: the canonical implementations moved to
# ``ops.nn`` alongside :func:`~mxnet_tpu.ops.nn.paged_attention` (the
# block-pool decode path shares them); re-exported here unchanged for
# the historical import path.
from ...ops.nn import (_KV_SCALE_BYTES, kv_cache_dequantize,
                       kv_cache_quantize, kv_pool_rows,
                       paged_attention as _paged_attend,
                       paged_attention_multi as _paged_attend_multi)


class MultiHeadAttention(HybridBlock):
    """Self/cross attention over (batch, seq, units) inputs.

    ``mask``: optional (B, H|1, Lq, Lk) boolean (True = attend) or additive
    float mask. ``tp_axis``: shard heads Megatron-style over that mesh axis
    (qkv column-parallel, out row-parallel)."""

    def __init__(self, units, num_heads, dropout=0.0, causal=False,
                 use_bias=True, tp_axis: Optional[str] = None, dtype="float32"):
        super().__init__()
        if units % num_heads:
            raise ValueError(f"units {units} not divisible by heads {num_heads}")
        self._units = units
        self._heads = num_heads
        self._dropout = dropout
        self._causal = causal
        if tp_axis:
            from ...parallel.tensor_parallel import (
                ColumnParallelDense, RowParallelDense)

            self.qkv = ColumnParallelDense(3 * units, axis_name=tp_axis,
                                           use_bias=use_bias, flatten=False,
                                           in_units=units, dtype=dtype)
            self.out_proj = RowParallelDense(units, axis_name=tp_axis,
                                             use_bias=use_bias, flatten=False,
                                             in_units=units, dtype=dtype)
        else:
            self.qkv = Dense(3 * units, use_bias=use_bias, flatten=False,
                             in_units=units, dtype=dtype)
            self.out_proj = Dense(units, use_bias=use_bias, flatten=False,
                                  in_units=units, dtype=dtype)

    def forward(self, x, mask=None, kv=None):
        units, heads = self._units, self._heads
        if kv is None:
            proj = self.qkv(x)
            args = [proj]

            def split(p):
                return p[..., :units], p[..., units:2 * units], p[..., 2 * units:]
        else:
            # cross attention: q from x, k/v from kv through the same proj
            proj_q = self.qkv(x)
            proj_kv = self.qkv(kv)
            args = [proj_q, proj_kv]

            def split(pq, pkv):
                return (pq[..., :units], pkv[..., units:2 * units],
                        pkv[..., 2 * units:])

        from ...autograd import is_training

        training = is_training()
        causal, dropout = self._causal, self._dropout
        if mask is not None:
            args.append(mask)

        from ...numpy_extension import _next_key

        key = _next_key() if (dropout and training) else jnp.zeros(2, jnp.uint32)

        def fn(*arrs):
            # unpack: [proj(s)..., mask?, key]
            k_ = arrs[-1]
            rest = arrs[:-1]
            if mask is not None:
                m = rest[-1]
                rest = rest[:-1]
            else:
                m = None
            q, k, v = split(*rest)
            return _attend(q, k, v, heads, causal, m, dropout, k_, training)

        args.append(_wrap(key))
        return self.out_proj(_call(fn, tuple(args), name="MultiHeadAttention"))

    def forward_step(self, x, cache_k, cache_v, pos):
        """Incremental (KV-cache) attention: ``x`` is (B, T, units) at
        absolute positions [pos, pos+T); caches are (B, H, Lmax, D)
        ring buffers written in place via ``dynamic_update_slice``.
        T = prompt length for prefill, 1 for decode. Returns
        (out, new_cache_k, new_cache_v). Static shapes throughout, so one
        XLA program serves every step — the TPU-idiomatic decode loop."""
        units, heads = self._units, self._heads
        proj = self.qkv(x)

        def fn(p, ck, cv, ps):
            B, T, _ = p.shape
            D = units // heads
            ps = ps.astype(jnp.int32)

            def split_heads(t):  # (B, T, U) -> (B, H, T, D)
                return t.reshape(B, T, heads, D).transpose(0, 2, 1, 3)

            q = split_heads(p[..., :units])
            k = split_heads(p[..., units:2 * units])
            v = split_heads(p[..., 2 * units:])
            zero = jnp.zeros((), jnp.int32)
            quantized = ck.dtype == jnp.int8
            if quantized:
                k_store, v_store = kv_cache_quantize(k), kv_cache_quantize(v)
            else:
                k_store, v_store = k.astype(ck.dtype), v.astype(cv.dtype)
            ck = jax.lax.dynamic_update_slice(
                ck, k_store, (zero, zero, ps, zero))
            cv = jax.lax.dynamic_update_slice(
                cv, v_store, (zero, zero, ps, zero))
            if quantized:  # int8 rides HBM; math runs in q's dtype
                keys = kv_cache_dequantize(ck, q.dtype)
                vals = kv_cache_dequantize(cv, q.dtype)
            else:
                keys, vals = ck, cv
            lmax = ck.shape[2]
            scores = jnp.einsum("bhtd,bhld->bhtl", q, keys).astype(
                jnp.float32)
            scores = scores / onp.sqrt(D).astype(onp.float32)
            col = jnp.arange(lmax)[None, None, None, :]
            row = ps + jnp.arange(T)[None, None, :, None]
            scores = jnp.where(col <= row, scores, -jnp.inf)
            attn = jax.nn.softmax(scores, axis=-1).astype(vals.dtype)
            out = jnp.einsum("bhtl,bhld->bhtd", attn, vals)
            return out.transpose(0, 2, 1, 3).reshape(B, T, units), ck, cv

        out, new_ck, new_cv = _call(fn, (proj, cache_k, cache_v, pos),
                                    name="MultiHeadAttentionStep", n_out=3)
        return self.out_proj(out), new_ck, new_cv

    def forward_step_paged(self, x, pool_k, pool_v, block_table, positions,
                           layer):
        """Paged-KV decode attention: ``x`` is (R, T, units) — lane
        ``r``'s token ``t`` sits at absolute position
        ``positions[r] + t`` — whose K/V are written into the shared
        block pools at ``block_table[r, p // bs]`` slot ``p % bs``, then
        attended through the table
        (:func:`~mxnet_tpu.ops.nn.paged_attention`) as ``R*T`` virtual
        lanes with per-position lengths (the length mask IS the causal
        mask). ``T == 1`` is the continuous-batching decode step;
        ``T > 1`` serves speculative verify (K+1 draft tokens per lane
        in ONE forward) and shared-prefix suffix prefill.

        ``pool_k``/``pool_v`` are the WHOLE ``(L, NB, bs, H*D')`` pools
        (the one layout, :func:`~mxnet_tpu.ops.nn.kv_pool_rows`) and
        ``layer`` this layer's index in them: the rows are stored with
        ``pool.at[layer, blk, slot].set(rows)`` and the pools returned,
        so nothing is sliced out of a pool or stacked back, and with the
        pools donated the write is in place. Rows of ``H*D`` lanes (a
        multiple of 128 at GPT-2 widths) are what lets this scatter, the
        kernel's block and the donated buffer share one row-major layout.
        Static shapes throughout, so one XLA program serves every step
        at every mix of sequence lengths."""
        units, heads = self._units, self._heads
        proj = self.qkv(x)

        def fn(p, pk, pv, bt, pos):
            r, t = p.shape[0], p.shape[1]
            d = units // heads
            bs = pk.shape[2]
            pos = pos.astype(jnp.int32)

            def split(c):                       # (R, T, U) -> (R*T, H, D)
                return c.reshape(r * t, heads, d)

            q = split(p[..., :units])
            k = split(p[..., units:2 * units])
            v = split(p[..., 2 * units:])
            if pk.dtype == jnp.int8:
                k_store, v_store = kv_cache_quantize(k), kv_cache_quantize(v)
            else:
                k_store, v_store = k.astype(pk.dtype), v.astype(pv.dtype)
            # (R, T) absolute position of every written token
            abs_pos = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
            blk = jnp.take_along_axis(bt, abs_pos // bs, axis=1).reshape(-1)
            slot = (abs_pos % bs).reshape(-1)
            # one (R*T, H*D') row per written token, straight into the
            # whole pool
            pk = pk.at[layer, blk, slot].set(kv_pool_rows(k_store))
            pv = pv.at[layer, blk, slot].set(kv_pool_rows(v_store))
            if t == 1:
                # the ONE continuous-batching decode step (unchanged op
                # stream: greedy token-identity with the dense cache)
                out = _paged_attend(q, pk, pv, bt,
                                    (abs_pos + 1).reshape(-1), layer)
                return out.reshape(r, 1, units), pk, pv
            # T > 1 (speculative verify / suffix prefill): gather each
            # lane's blocks ONCE and attend all T queries against the
            # dense view — the cache read amortizes over the chunk,
            # which is the whole roofline win; the per-(lane, t) length
            # mask IS the causal mask
            out = _paged_attend_multi(q.reshape(r, t, heads, d),
                                      pk, pv, bt, pos, layer)  # (R, T, H, D)
            return out.reshape(r, t, units), pk, pv

        out, new_pk, new_pv = _call(
            fn, (proj, pool_k, pool_v, block_table, positions),
            name="MultiHeadAttentionPagedStep", n_out=3)
        return self.out_proj(out), new_pk, new_pv


class PositionwiseFFN(HybridBlock):
    """FFN(x) = W2 act(W1 x); optional TP sharding (column→row)."""

    def __init__(self, units, hidden_size, activation="gelu", dropout=0.0,
                 tp_axis: Optional[str] = None, dtype="float32"):
        super().__init__()
        if tp_axis:
            from ...parallel.tensor_parallel import (
                ColumnParallelDense, RowParallelDense)

            self.ffn_1 = ColumnParallelDense(hidden_size, axis_name=tp_axis,
                                             flatten=False, in_units=units,
                                             activation=activation, dtype=dtype)
            self.ffn_2 = RowParallelDense(units, axis_name=tp_axis,
                                          flatten=False, in_units=hidden_size,
                                          dtype=dtype)
        else:
            self.ffn_1 = Dense(hidden_size, flatten=False, in_units=units,
                               activation=activation, dtype=dtype)
            self.ffn_2 = Dense(units, flatten=False, in_units=hidden_size,
                               dtype=dtype)
        self.dropout = Dropout(dropout) if dropout else None

    def forward(self, x):
        h = self.ffn_1(x)
        if self.dropout is not None:
            h = self.dropout(h)
        return self.ffn_2(h)


class TransformerEncoderLayer(HybridBlock):
    """Pre-LN transformer layer (the stable-training variant)."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 attention_dropout=0.0, activation="gelu", causal=False,
                 pre_norm=True, tp_axis: Optional[str] = None, dtype="float32"):
        super().__init__()
        self._pre_norm = pre_norm
        self.attn = MultiHeadAttention(units, num_heads,
                                       dropout=attention_dropout,
                                       causal=causal, tp_axis=tp_axis,
                                       dtype=dtype)
        self.ffn = PositionwiseFFN(units, hidden_size, activation=activation,
                                   dropout=dropout, tp_axis=tp_axis, dtype=dtype)
        self.ln1 = LayerNorm(in_channels=units)
        self.ln2 = LayerNorm(in_channels=units)
        self.dropout = Dropout(dropout) if dropout else None

    def forward(self, x, mask=None):
        if self._pre_norm:
            h = self.attn(self.ln1(x), mask=mask)
            if self.dropout is not None:
                h = self.dropout(h)
            x = x + h
            h = self.ffn(self.ln2(x))
            if self.dropout is not None:
                h = self.dropout(h)
            return x + h
        h = self.attn(x, mask=mask)
        if self.dropout is not None:
            h = self.dropout(h)
        x = self.ln1(x + h)
        h = self.ffn(x)
        if self.dropout is not None:
            h = self.dropout(h)
        return self.ln2(x + h)

    def forward_step(self, x, cache_k, cache_v, pos):
        """KV-cache variant of forward (no dropout: decode is inference)."""
        if self._pre_norm:
            h, ck, cv = self.attn.forward_step(self.ln1(x), cache_k,
                                               cache_v, pos)
            x = x + h
            return x + self.ffn(self.ln2(x)), ck, cv
        h, ck, cv = self.attn.forward_step(x, cache_k, cache_v, pos)
        x = self.ln1(x + h)
        return self.ln2(x + self.ffn(x)), ck, cv

    def forward_step_paged(self, x, pool_k, pool_v, block_table, positions,
                           layer):
        """Paged-pool variant of :meth:`forward_step` (no dropout:
        decode is inference): the whole pools and this layer's index."""
        if self._pre_norm:
            h, pk, pv = self.attn.forward_step_paged(
                self.ln1(x), pool_k, pool_v, block_table, positions, layer)
            x = x + h
            return x + self.ffn(self.ln2(x)), pk, pv
        h, pk, pv = self.attn.forward_step_paged(
            x, pool_k, pool_v, block_table, positions, layer)
        x = self.ln1(x + h)
        return self.ln2(x + self.ffn(x)), pk, pv


class TransformerEncoder(HybridBlock):
    """Stack of pre/post-norm self-attention + FFN blocks over npx.multi_head_attention; the flash-attention Pallas kernel backs long sequences."""
    def __init__(self, num_layers, units, hidden_size, num_heads, dropout=0.0,
                 attention_dropout=0.0, activation="gelu", causal=False,
                 pre_norm=True, tp_axis: Optional[str] = None, dtype="float32"):
        super().__init__()
        self._num_layers = num_layers
        for i in range(num_layers):
            setattr(self, f"layer{i}", TransformerEncoderLayer(
                units, hidden_size, num_heads, dropout=dropout,
                attention_dropout=attention_dropout, activation=activation,
                causal=causal, pre_norm=pre_norm, tp_axis=tp_axis, dtype=dtype))
        self.final_ln = LayerNorm(in_channels=units) if pre_norm else None

    def forward(self, x, mask=None):
        for i in range(self._num_layers):
            x = getattr(self, f"layer{i}")(x, mask=mask)
        if self.final_ln is not None:
            x = self.final_ln(x)
        return x

    def forward_step(self, x, cache_k, cache_v, pos):
        """KV-cache decode through the stack. ``cache_k``/``cache_v`` are
        (num_layers, B, H, Lmax, D) stacked ring buffers."""
        from ... import numpy as mxnp

        new_ks, new_vs = [], []
        for i in range(self._num_layers):
            x, ck, cv = getattr(self, f"layer{i}").forward_step(
                x, cache_k[i], cache_v[i], pos)
            new_ks.append(ck)
            new_vs.append(cv)
        if self.final_ln is not None:
            x = self.final_ln(x)
        return x, mxnp.stack(new_ks), mxnp.stack(new_vs)

    def forward_step_paged(self, x, pool_k, pool_v, block_table, positions):
        """Paged-pool decode through the stack. ``pool_k``/``pool_v``
        are the ``(num_layers, NB, bs, H*D')`` block pools sharing ONE
        block table (a block holds one layer's slice; the same block id
        addresses every layer's pool, so splice/free work per sequence,
        not per layer). Every layer is handed the whole pools and its
        index, writes its rows in place and hands the pools on: no
        ``pool[i]``, no ``stack`` — a slice of a pool is a copy of a pool
        (70 MB a layer at GPT-2-large); slices, the re-stack and the
        layout conversions around them were 72% of the decode step
        (PERF.md, PR 27)."""
        for i in range(self._num_layers):
            x, pool_k, pool_v = getattr(self, f"layer{i}").forward_step_paged(
                x, pool_k, pool_v, block_table, positions, i)
        if self.final_ln is not None:
            x = self.final_ln(x)
        return x, pool_k, pool_v
