"""A decoder-only language model whose attention layers are of two kinds,
one *full* layer to every three *window* layers, with a mixture of
experts behind every layer but the leading dense ones: the architecture
of Laguna-S-2.1 (``model_type`` ``laguna``; ``gluon.nn.laguna`` has the
blocks). RMSNorm pre-norm, an untied head.

The kinds differ in their query heads (48 and 72 over 8 K/V heads of
128), in their rotary rule (YaRN on half of a head's values; the plain
rule on all) and in **what a request keeps while it is served**: a full
layer every position's K/V rows, in blocks, as many as its tokens fill; a
window layer the rows of its last ``window`` positions, in a ring a
lane. ``serving.LLMEngine`` serves it through the same contract as
``bert._CausalLM``, ``brumby._RetentionLM`` and
``qwen3next._Qwen3NextLM`` — ``cache_geometry``, ``init_block_pool``,
``decode_step_paged``, ``prefill_chunk_step`` — with
``CacheGeometry.lane_state`` set: the pools are ``(K, V, ring K, ring
V)``, the first two indexed by blocks through a lane's table, the last
two by the lane's own index, and the allocator counts blocks of the full
layers only.

A model may hold one chip's share of every layer's experts
(``experts_held``, ``first_expert``): the router still scores all
``num_experts`` (:mod:`mxnet_tpu.ops.experts`).

Not imported by ``mxnet_tpu.gluon.model_zoo``:
``from mxnet_tpu.gluon.model_zoo import laguna``.
"""
from __future__ import annotations

import jax.numpy as jnp

from ...numpy_extension import _call
from ..block import HybridBlock
from ..nn.basic_layers import Dense, Embedding
from ..nn.laguna import LagunaDecoderLayer
from ..parameter import Parameter
from .generation import CacheGeometry

__all__ = ["laguna_like", "ring_readings"]

FULL, WINDOW = "full_attention", "sliding_attention"


class _LagunaLM(HybridBlock):
    def __init__(self, vocab_size=100352, units=3072, num_layers=48,
                 layer_types=(FULL, WINDOW, WINDOW, WINDOW),
                 heads_per_layer=(48, 72, 72, 72), num_kv_heads=8,
                 head_dim=128, window=512, rope_theta=500000.0,
                 rotary_dim=64, yarn_factor=128.0, yarn_original=8192,
                 yarn_beta_fast=32.0, yarn_beta_slow=1.0,
                 yarn_attention_factor=1.4852030263919618,
                 window_rope_theta=10000.0, window_rotary_dim=128,
                 dense_layers=(0,),
                 dense_size=12288, num_experts=256, experts_per_token=10,
                 expert_size=1024, shared_expert_size=1024,
                 routed_scale=2.5, experts_held=None, first_expert=0,
                 max_length=1048576, epsilon=1e-6, prefill_chunk=1024,
                 dtype="float32"):
        """``layer_types`` and ``heads_per_layer`` are read with the
        layer's index modulo their length (a period, or the published
        lists whole)."""
        super().__init__()
        from ...ops.gated_attention import (RING_BLOCK, rope_frequencies,
                                            yarn_frequencies)

        if window % RING_BLOCK:
            raise ValueError(f"the window ({window}) is not a whole number "
                             f"of a ring's blocks of {RING_BLOCK} rows")
        self._max_length, self._chunk = max_length, prefill_chunk
        self._eps, self._window = float(epsilon), int(window)
        self._kv_row = num_kv_heads * head_dim
        rules = {
            FULL: dict(freq=yarn_frequencies(
                rotary_dim, rope_theta, yarn_factor, yarn_original,
                yarn_beta_fast, yarn_beta_slow),
                rotary_scale=yarn_attention_factor, window=None),
            WINDOW: dict(freq=rope_frequencies(
                window_rotary_dim, window_rope_theta).astype("float32"),
                window=self._window)}
        experts = dict(expert_size=expert_size, num_experts=num_experts,
                       experts_per_token=experts_per_token,
                       routed_scale=routed_scale, experts_held=experts_held,
                       first_expert=first_expert,
                       shared_size=shared_expert_size)
        self.word_embed = Embedding(vocab_size, units, dtype=dtype)
        self._layers = []
        for i in range(num_layers):
            kind = layer_types[i % len(layer_types)]
            mixer = dict(num_heads=heads_per_layer[i % len(heads_per_layer)],
                         num_kv_heads=num_kv_heads, head_dim=head_dim,
                         **rules[kind])
            layer = LagunaDecoderLayer(
                units, mixer, experts,
                dense_size if i in dense_layers else None, epsilon, dtype)
            setattr(self, f"layer{i}", layer)
            self._layers.append(layer)
        self._n_window = sum(ly.mixer.window is not None
                             for ly in self._layers)
        self.final_norm = Parameter("final_norm", shape=(units,),
                                    dtype="float32")
        self.lm_head = Dense(vocab_size, use_bias=False, flatten=False,
                             in_units=units, dtype=dtype)

    # -- what the cache manager asks ------------------------------------
    def cache_geometry(self, block_size: int) -> CacheGeometry:
        """Blocks of ``block_size`` K/V rows for the full layers, as many
        as a request's tokens fill, and beside them a ring of ``window``
        rows a lane for the window layers; prefill in chunks (whole
        blocks) that write rows and leave their last in the ring."""
        if self._chunk % block_size:
            raise ValueError(f"the prefill chunk ({self._chunk}) is not a "
                             f"whole number of blocks of {block_size}")
        ring = "a ring holds a lane's last rows and no prefix's"
        return CacheGeometry(
            kind="kv_blocks", lane_state=True,
            blocks_for=lambda tokens: -(-tokens // block_size),
            max_positions=self._max_length, prefill_chunk=self._chunk,
            cache_dtypes=("bfloat16", "float32"),
            row_layers=(len(self._layers) - self._n_window, self._n_window,
                        self._window),
            unsupported={
                "prefix_cache": "a prefix is shared as blocks of rows; "
                                + ring,
                "kv_spill": "the spill tier holds blocks of rows; " + ring,
                "role": "the prefill-to-decode handoff ships blocks of "
                        "rows, not a ring",
                "draft_model": "a rejected draft token's row cannot be "
                               "taken back out of a ring that has wrapped",
                "mesh": "the expert kernel is not partitioned and the "
                        "rings have no sharding rule"})

    def init_block_pool(self, num_blocks, block_size, dtype="bfloat16",
                        state_slots=2):
        """Zeroed pools ``(K, V, ring K, ring V)`` in ``dtype``: K/V rows
        ``(Lf, num_blocks, block_size, Hkv * D)`` for the ``Lf`` full
        layers, and for the ``Lw`` window layers rings ``(Lw,
        state_slots, window, Hkv * D)``."""
        from ... import numpy as mxnp

        if dtype not in ("bfloat16", "float32"):
            raise ValueError(f"K/V rows are bfloat16 or float32, not "
                             f"{dtype!r}")
        rows = (len(self._layers) - self._n_window, num_blocks, block_size,
                self._kv_row)
        ring = (self._n_window, state_slots, self._window, self._kv_row)
        return tuple(mxnp.zeros(shape, dtype=dtype)
                     for shape in (rows, rows, ring, ring))

    # -- the programs' bodies -------------------------------------------
    def _head(self, x, counts):
        from ...ops import gated_attention as ga

        eps = self._eps
        x = _call(lambda a, w: ga.rms(a, w, eps).astype(a.dtype),
                  (x, self.final_norm.data()), name="RMSNorm")

        def total(*each):           # over the layers: sums, and the max
            c = jnp.stack(each)
            return jnp.stack([c[:, 0].sum(), c[:, 1].sum(), c[:, 2].max(),
                              c[:, 3].sum()]).astype(jnp.int32)

        return self.lm_head(x), _call(total, tuple(counts),
                                      name="ExpertCounts")

    def _run(self, x, pools, step, real=None):
        """The layers over rows ``x``: ``step(mixer, h, pool_k, pool_v,
        layer)`` runs a layer's attention on its own pair of pools at its
        index among the layers of its kind."""
        pools, counts, at = list(pools), [], [0, 0]
        for layer in self._layers:
            kind = int(layer.mixer.window is not None)
            h, pools[2 * kind], pools[2 * kind + 1] = step(
                layer.mixer, layer.normed(x), pools[2 * kind],
                pools[2 * kind + 1], at[kind])
            at[kind] += 1
            x, c = layer.finish(x, h, real)
            if c is not None:
                counts.append(c)
        return x, counts, tuple(pools)

    def decode_step_paged(self, token_ids, pool_k, pool_v, ring_k, ring_v,
                          block_table, positions):
        """One token per lane: ``token_ids (R, 1)`` at ``positions (R,)``,
        lane ``r``'s blocks in ``block_table[r]`` and its rings in slot
        ``r``. Returns ``(logits (R, 1, V), counts (4,), pool_k, pool_v,
        ring_k, ring_v)``; ``counts`` as in :mod:`mxnet_tpu.ops.experts`,
        over the layers."""
        slots = _call(lambda p: jnp.arange(p.shape[0], dtype=jnp.int32),
                      (positions,), name="LaneSlots")

        def step(mixer, h, pk, pv, layer):
            where = block_table if mixer.window is None else slots
            return mixer.forward_step(h, pk, pv, where, positions, layer)

        x, counts, pools = self._run(
            self.word_embed(token_ids[:, 0]),
            (pool_k, pool_v, ring_k, ring_v), step)
        logits, counts = self._head(x, counts)
        return (logits.reshape(logits.shape[0], 1, -1), counts, *pools)

    def _run_chunk(self, tokens, pools, slot, table, start, n_real):
        real = _call(lambda t, n: jnp.arange(t.shape[0]) < jnp.reshape(
            n, ()), (tokens, n_real), name="RealRows")

        def step(mixer, h, pk, pv, layer):
            where = table if mixer.window is None else slot
            return mixer.forward_chunk(h, pk, pv, where, start, n_real,
                                       layer)

        return self._run(self.word_embed(tokens), pools, step, real)

    def prefill_chunk_step(self, token_ids, pool_k, pool_v, ring_k, ring_v,
                           slot, table, start, n_real):
        """A chunk of one lane: ``token_ids (1, c)`` at positions ``start
        + arange(c)``, the first ``n_real`` of them tokens; the lane's
        blocks in ``table (MB,)``, its rings in ``slot``. Writes the
        chunk's K/V rows, leaves its last ``window`` real rows in the
        rings and returns the logits of the last real token alone, ``(1,
        V)``, before the counts and the pools."""
        x, counts, pools = self._run_chunk(
            token_ids[0], (pool_k, pool_v, ring_k, ring_v), slot, table,
            start, n_real)
        last = _call(
            lambda h, n: jnp.take(h, jnp.reshape(n, (1,)).astype(jnp.int32)
                                  - 1, axis=0),
            (x, n_real), name="LastRealRow")
        return (*self._head(last, counts), *pools)

    def forward(self, token_ids):
        """``(B, T)`` token ids -> ``(B, T, V)`` logits: each sequence as
        one chunk through pools of its own."""
        from ... import numpy as mxnp

        t = token_ids.shape[1]
        zero = mxnp.array(jnp.zeros((), jnp.int32))
        count = mxnp.array(jnp.asarray(t, jnp.int32))
        table = mxnp.array(jnp.zeros((1,), jnp.int32))
        out = []
        for b in range(token_ids.shape[0]):
            pools = self.init_block_pool(2, t, dtype="float32",
                                         state_slots=1)
            x, counts, _ = self._run_chunk(token_ids[b], pools, zero, table,
                                           zero, count)
            out.append(self._head(x, counts)[0])
        return mxnp.stack(out)


def laguna_like(**kwargs):
    return _LagunaLM(**kwargs)


def ring_readings(ring_k, ring_v, probes):
    """What a check reads of one lane's rings ``(Lw, window, Hkv * D)``
    out of :meth:`LLMEngine.snapshot_cache`: the rows themselves,
    float32, position ``p`` in row ``p mod window``. The caller's
    ``probes`` ask a state for its answers; rows are read as they are."""
    return ring_k.astype(jnp.float32), ring_v.astype(jnp.float32)
