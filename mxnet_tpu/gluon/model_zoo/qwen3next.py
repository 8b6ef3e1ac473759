"""A decoder-only language model of three gated delta-rule layers to every
gated softmax-attention layer, a mixture of experts behind every one: the
architecture of Qwen3-Next-80B-A3B (``model_type`` ``qwen3_next``;
``gluon.nn.qwen3next`` has the blocks). Zero-centred RMSNorm pre-norm,
an untied head.

What a request keeps while it is served is of **two families**: blocks of
K/V rows for the full-attention layers, as many as its tokens fill, and
one fixed-size state (the delta rule's matrices and the convolution's
tail) for the others. ``serving.LLMEngine`` serves it through the same
contract as ``bert._CausalLM`` and ``brumby._RetentionLM`` —
``cache_geometry``, ``init_block_pool``, ``decode_step_paged``,
``prefill_chunk_step`` — with ``CacheGeometry.lane_state`` set: the pools
are ``(K, V, S, tail)``, the first two indexed by blocks through a lane's
table, the last two by the lane's own index.

A model may hold one chip's share of every layer's experts
(``experts_held``, ``first_expert``): the router still scores all
``num_experts`` (:mod:`mxnet_tpu.ops.experts`).

Not imported by ``mxnet_tpu.gluon.model_zoo``:
``from mxnet_tpu.gluon.model_zoo import qwen3next``.
"""
from __future__ import annotations

import jax.numpy as jnp

from ...numpy_extension import _call
from ..block import HybridBlock
from ..nn.basic_layers import Dense, Embedding
from ..nn.qwen3next import Qwen3NextDecoderLayer
from ..parameter import Parameter
from .generation import CacheGeometry

__all__ = ["qwen3next_like"]

STATE_DTYPE = "float32"


class _Qwen3NextLM(HybridBlock):
    def __init__(self, vocab_size=151936, units=2048, num_layers=48,
                 full_attention_interval=4, num_heads=16, num_kv_heads=2,
                 head_dim=256, rotary_dim=64, rope_theta=1e7,
                 linear_key_heads=16, linear_value_heads=32,
                 linear_key_dim=128, linear_value_dim=128, conv_width=4,
                 num_experts=512, experts_per_token=10, expert_size=512,
                 shared_expert_size=512, experts_held=None, first_expert=0,
                 max_length=262144, epsilon=1e-6, prefill_chunk=2048,
                 dtype="float32"):
        super().__init__()
        self._max_length, self._chunk = max_length, prefill_chunk
        self._eps = float(epsilon)
        self._kv_row = num_kv_heads * head_dim
        self._state = (linear_value_heads, linear_key_dim, linear_value_dim)
        attention = dict(num_heads=num_heads, num_kv_heads=num_kv_heads,
                         head_dim=head_dim, rotary_dim=rotary_dim,
                         rope_theta=rope_theta)
        delta = dict(key_heads=linear_key_heads,
                     value_heads=linear_value_heads, key_dim=linear_key_dim,
                     value_dim=linear_value_dim, conv_width=conv_width)
        experts = dict(expert_size=expert_size, num_experts=num_experts,
                       experts_per_token=experts_per_token,
                       experts_held=experts_held, first_expert=first_expert,
                       shared_size=shared_expert_size)
        self.word_embed = Embedding(vocab_size, units, dtype=dtype)
        self._layers = []
        for i in range(num_layers):
            full = (i + 1) % full_attention_interval == 0
            layer = Qwen3NextDecoderLayer(
                full, units, attention if full else delta, experts, epsilon,
                dtype)
            setattr(self, f"layer{i}", layer)
            self._layers.append(layer)
        self._n_full = sum(ly.full_attention for ly in self._layers)
        self._tail = ((conv_width - 1) * self._layers[0].mixer.channels
                      if self._n_full < num_layers else 0,)
        self.final_norm = Parameter("final_norm", shape=(units,),
                                    dtype="float32")
        self.lm_head = Dense(vocab_size, use_bias=False, flatten=False,
                             in_units=units, dtype=dtype)

    # -- what the cache manager asks ------------------------------------
    def cache_geometry(self, block_size: int) -> CacheGeometry:
        """Blocks of ``block_size`` K/V rows, as many as a request's
        tokens fill, and beside them one state a lane; prefill in chunks
        (whole blocks) that write rows and carry the state."""
        if self._chunk % block_size:
            raise ValueError(f"the prefill chunk ({self._chunk}) is not a "
                             f"whole number of blocks of {block_size}")
        state = "a snapshot of the lane's state per prefix would be needed"
        return CacheGeometry(
            kind="kv_blocks", lane_state=True,
            blocks_for=lambda tokens: -(-tokens // block_size),
            max_positions=self._max_length, prefill_chunk=self._chunk,
            cache_dtypes=("bfloat16", "float32"), unsupported={
                "prefix_cache": "a prefix is shared as blocks of rows; "
                                + state,
                "kv_spill": "the spill tier holds blocks of rows; " + state,
                "role": "the prefill-to-decode handoff ships blocks of "
                        "rows, not a state",
                "draft_model": "a rejected draft token cannot be taken back "
                               "out of a state",
                "mesh": "the delta-rule and expert kernels are not "
                        "partitioned and the state pools have no sharding "
                        "rule"})

    def init_block_pool(self, num_blocks, block_size, dtype="bfloat16",
                        state_slots=2):
        """Zeroed pools ``(K, V, S, tail)``: K/V rows ``(Lf, num_blocks,
        block_size, Hkv * D)`` in ``dtype`` for the ``Lf`` full-attention
        layers, and for the ``Ld`` delta-rule layers ``S (Ld, state_slots,
        Hv, dk, dv)`` and the convolution's tail ``(Ld, state_slots, (W -
        1) * channels)`` in float32."""
        from ... import numpy as mxnp

        if dtype not in ("bfloat16", "float32"):
            raise ValueError(f"K/V rows are bfloat16 or float32, not "
                             f"{dtype!r}")
        n_delta = len(self._layers) - self._n_full
        rows = (self._n_full, num_blocks, block_size, self._kv_row)
        return (mxnp.zeros(rows, dtype=dtype), mxnp.zeros(rows, dtype=dtype),
                mxnp.zeros((n_delta, state_slots) + self._state,
                           dtype=STATE_DTYPE),
                mxnp.zeros((n_delta, state_slots) + self._tail,
                           dtype=STATE_DTYPE))

    # -- the programs' bodies -------------------------------------------
    def _head(self, x, counts):
        from ...ops import gated_attention as ga

        eps = self._eps
        x = _call(lambda a, w: ga.rms0(a, w, eps).astype(a.dtype),
                  (x, self.final_norm.data()), name="RMSNorm0")

        def total(*each):           # over the layers: sums, and the max
            c = jnp.stack(each)
            return jnp.stack([c[:, 0].sum(), c[:, 1].sum(), c[:, 2].max(),
                              c[:, 3].sum()]).astype(jnp.int32)

        return self.lm_head(x), _call(total, tuple(counts),
                                      name="ExpertCounts")

    def decode_step_paged(self, token_ids, pool_k, pool_v, pool_s, pool_c,
                          block_table, positions):
        """One token per lane: ``token_ids (R, 1)`` at ``positions (R,)``,
        lane ``r``'s blocks in ``block_table[r]`` and its state in slot
        ``r``. Returns ``(logits (R, 1, V), counts (4,), pool_k, pool_v,
        pool_s, pool_c)``; ``counts`` as in :mod:`mxnet_tpu.ops.experts`,
        over the layers."""
        x = self.word_embed(token_ids[:, 0])
        slots = _call(lambda p: jnp.arange(p.shape[0], dtype=jnp.int32),
                      (positions,), name="LaneSlots")
        counts, full, delta = [], 0, 0
        for layer in self._layers:
            h = layer.normed(x)
            if layer.full_attention:
                h, pool_k, pool_v = layer.mixer.forward_step(
                    h, pool_k, pool_v, block_table, positions, full)
                full += 1
            else:
                h, pool_s, pool_c = layer.mixer.forward_step(
                    h, pool_s, pool_c, slots, delta)
                delta += 1
            x, c = layer.finish(x, h)
            counts.append(c)
        logits, counts = self._head(x, counts)
        return (logits.reshape(logits.shape[0], 1, -1), counts, pool_k,
                pool_v, pool_s, pool_c)

    def _run_chunk(self, tokens, pools, slot, table, start, n_real):
        """The layers over one lane's chunk ``tokens (c,)``: the rows
        after the last layer, every layer's counts and the pools."""
        pool_k, pool_v, pool_s, pool_c = pools
        x = self.word_embed(tokens)
        real = _call(lambda t, n: jnp.arange(t.shape[0]) < jnp.reshape(
            n, ()), (tokens, n_real), name="RealRows")
        counts, full, delta = [], 0, 0
        for layer in self._layers:
            h = layer.normed(x)
            if layer.full_attention:
                h, pool_k, pool_v = layer.mixer.forward_chunk(
                    h, pool_k, pool_v, table, start, full)
                full += 1
            else:
                h, pool_s, pool_c = layer.mixer.forward_chunk(
                    h, pool_s, pool_c, slot, start, n_real, delta)
                delta += 1
            x, c = layer.finish(x, h, real)
            counts.append(c)
        return x, counts, (pool_k, pool_v, pool_s, pool_c)

    def prefill_chunk_step(self, token_ids, pool_k, pool_v, pool_s, pool_c,
                           slot, table, start, n_real):
        """A chunk of one lane: ``token_ids (1, c)`` at positions ``start
        + arange(c)``, the first ``n_real`` of them tokens; the lane's
        blocks in ``table (MB,)``, its state in ``slot``. Writes the
        chunk's K/V rows, leaves the state of the last real token and
        returns the logits of that token alone, ``(1, V)``, before the
        counts and the pools."""
        x, counts, pools = self._run_chunk(
            token_ids[0], (pool_k, pool_v, pool_s, pool_c), slot, table,
            start, n_real)
        last = _call(
            lambda h, n: jnp.take(h, jnp.reshape(n, (1,)).astype(jnp.int32)
                                  - 1, axis=0),
            (x, n_real), name="LastRealRow")
        return (*self._head(last, counts), *pools)

    def forward(self, token_ids):
        """``(B, T)`` token ids -> ``(B, T, V)`` logits: each sequence as
        one chunk (padded to whole sub-chunks of the delta rule) through
        pools of its own."""
        from ... import numpy as mxnp
        from ...ops.gated_delta import SUB

        t = token_ids.shape[1]
        c = -(-t // SUB) * SUB
        zero = mxnp.array(jnp.zeros((), jnp.int32))
        count = mxnp.array(jnp.asarray(t, jnp.int32))
        table = mxnp.array(jnp.zeros((1,), jnp.int32))
        out = []
        for b in range(token_ids.shape[0]):
            pools = self.init_block_pool(2, c, dtype=STATE_DTYPE,
                                         state_slots=1)
            ids = mxnp.array(jnp.pad(token_ids[b]._data, (0, c - t)))
            x, counts, _ = self._run_chunk(ids, pools, zero, table, zero,
                                           count)
            out.append(self._head(x, counts)[0][:t])
        return mxnp.stack(out)


def qwen3next_like(**kwargs):
    return _Qwen3NextLM(**kwargs)
