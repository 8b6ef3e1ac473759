"""A decoder-only language model with no attention layer: every layer is
power retention (``gluon.nn.retention``), so what a request keeps while
it is served is one fixed-size state, not a cache that grows with its
context. The architecture of Manifest AI's Brumby-14B-Base
(``model_type`` ``brumby``): RMSNorm pre-norm, grouped K/V heads with
per-head RMSNorm on q and k, rotary positions, a SiLU-gated FFN, an
untied head.

``serving.LLMEngine`` serves it through the same contract as
``bert._CausalLM`` — ``init_block_pool``, ``decode_step_paged`` and
``cache_geometry`` — with the pools read as slots: ``init_block_pool``
returns ``S (L, slots, Hk, d, Dp)`` and ``z (L, slots, Hk, Dp)``, a lane's
block table has one entry, and prefill runs in chunks of one fixed size
that carry the state (``prefill_chunk_step``).

Not imported by ``mxnet_tpu.gluon.model_zoo``:
``from mxnet_tpu.gluon.model_zoo import brumby``.
"""
from __future__ import annotations

import jax.numpy as jnp

from ...numpy_extension import _call
from ..block import HybridBlock
from ..nn.basic_layers import Dense, Embedding
from ..nn.norm_layers import RMSNorm
from ..nn.retention import RetentionDecoderLayer
from .generation import CacheGeometry

__all__ = ["brumby_like"]

STATE_DTYPE = "float32"


class _RetentionLM(HybridBlock):
    def __init__(self, vocab_size=151936, units=5120, hidden_size=17408,
                 num_layers=40, num_heads=40, num_kv_heads=8, head_dim=128,
                 max_length=32768, rope_theta=1e6, epsilon=1e-6,
                 prefill_chunk=1024, dtype="float32"):
        super().__init__()
        self._num_layers, self._kv_heads = num_layers, num_kv_heads
        self._head_dim, self._max_length = head_dim, max_length
        self._chunk = prefill_chunk
        self.word_embed = Embedding(vocab_size, units, dtype=dtype)
        for i in range(num_layers):
            setattr(self, f"layer{i}", RetentionDecoderLayer(
                units, hidden_size, num_heads, num_kv_heads, head_dim,
                rope_theta, epsilon, dtype))
        self.final_norm = RMSNorm(epsilon=epsilon, in_channels=units)
        self.lm_head = Dense(vocab_size, use_bias=False, flatten=False,
                             in_units=units, dtype=dtype)

    # -- what the cache manager asks ------------------------------------
    def cache_geometry(self, block_size: int) -> CacheGeometry:
        """One slot a request, whatever its length; a context bounded by
        positions alone; prefill in chunks that carry the state; and
        nothing yet that indexes blocks of rows."""
        return CacheGeometry(
            kind="state_slots", blocks_for=lambda tokens: 1,
            max_positions=self._max_length, prefill_chunk=self._chunk,
            cache_dtypes=(STATE_DTYPE,), unsupported={
                "prefix_cache": "a prefix is shared as blocks of rows; a "
                                "state would need a snapshot per prefix",
                "kv_spill": "the spill tier holds blocks of rows",
                "role": "the prefill-to-decode handoff ships blocks of rows",
                "draft_model": "a rejected draft token cannot be taken back "
                               "out of a state",
                "mesh": "the retention kernels are not partitioned and the "
                        "state pools have no sharding rule"})

    def init_block_pool(self, num_blocks, block_size, dtype=STATE_DTYPE):
        """Zeroed state pools with ``num_blocks`` slots:
        ``S (L, NB, Hk, d, Dp)`` and ``z (L, NB, Hk, Dp)``
        (:mod:`mxnet_tpu.ops.retention`). ``block_size`` means nothing to
        a state."""
        from ... import numpy as mxnp
        from ...ops.retention import phi_size

        if dtype != STATE_DTYPE:
            raise ValueError(f"the retention state is {STATE_DTYPE}, "
                             f"not {dtype!r}")
        d, dp = self._head_dim, phi_size(self._head_dim)
        lead = (self._num_layers, num_blocks, self._kv_heads)
        return (mxnp.zeros(lead + (d, dp), dtype=dtype),
                mxnp.zeros(lead + (dp,), dtype=dtype))

    # -- the programs' bodies -------------------------------------------
    def _layers(self):
        return [getattr(self, f"layer{i}") for i in range(self._num_layers)]

    def decode_step_paged(self, token_ids, pool_s, pool_z, block_table,
                          positions):
        """One token per lane: ``token_ids (R, 1)`` at ``positions (R,)``,
        lane ``r``'s slot in ``block_table[r, 0]``. Returns
        ``(logits (R, 1, V), pool_s, pool_z)``."""
        x = self.word_embed(token_ids[:, 0])
        slots = block_table[:, 0]
        for i, layer in enumerate(self._layers()):
            x, pool_s, pool_z = layer.forward_step(
                x, pool_s, pool_z, slots, positions, i)
        logits = self.lm_head(self.final_norm(x))
        return logits.reshape(logits.shape[0], 1, -1), pool_s, pool_z

    def prefill_chunk_step(self, token_ids, pool_s, pool_z, slot, start,
                           n_real):
        """A chunk of one lane: ``token_ids (1, c)`` at positions ``start
        + arange(c)``, the first ``n_real`` of them tokens. Leaves the
        state of the last real token in ``slot`` and returns the logits of
        that token alone, ``(1, V)``: the head never sees the chunk's
        other rows."""
        x = self.word_embed(token_ids[0])
        for i, layer in enumerate(self._layers()):
            x, pool_s, pool_z = layer.forward_chunk(
                x, pool_s, pool_z, slot, start, n_real, i)
        last = _call(
            lambda h, n: jnp.take(h, jnp.reshape(n, (1,)).astype(jnp.int32)
                                  - 1, axis=0),
            (x, n_real), name="LastRealRow")
        return self.lm_head(self.final_norm(last)), pool_s, pool_z

    def forward(self, token_ids):
        """``(B, T)`` token ids -> ``(B, T, V)`` logits: each sequence as
        one chunk from an empty state."""
        from ... import numpy as mxnp

        zero = mxnp.array(jnp.zeros((), jnp.int32))
        count = mxnp.array(jnp.asarray(token_ids.shape[1], jnp.int32))
        out = []
        for b in range(token_ids.shape[0]):
            pool_s, pool_z = self.init_block_pool(1, 0)
            x = self.word_embed(token_ids[b])
            for i, layer in enumerate(self._layers()):
                x, pool_s, pool_z = layer.forward_chunk(
                    x, pool_s, pool_z, zero, zero, count, i)
            out.append(self.lm_head(self.final_norm(x)))
        return mxnp.stack(out)


def brumby_like(**kwargs):
    return _RetentionLM(**kwargs)
