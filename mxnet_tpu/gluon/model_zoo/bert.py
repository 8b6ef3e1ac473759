"""BERT model family — the SURVEY.md §7 stage-8 stretch target, built
TPU-first: flash-attention encoder layers, bf16-ready, optional Megatron
TP via ``tp_axis``. (The reference kept BERT in gluonnlp; the in-tree
pieces were only the attention primitive ops, transformer.cc:650.)
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as onp

from ... import numpy_extension as npx
from ...ndarray.ndarray import ndarray, _unwrap, _wrap
from ..block import HybridBlock
from ..parameter import Parameter
from .. import nn
from ..nn.transformer import TransformerEncoder

__all__ = ["BERTModel", "BERTForPretraining", "bert_base", "bert_large",
           "gpt_like"]


class BERTModel(HybridBlock):
    """Embeddings (word + position + token-type) → transformer encoder →
    (sequence output, pooled [CLS] output)."""

    def __init__(self, vocab_size=30522, units=768, hidden_size=3072,
                 num_layers=12, num_heads=12, max_length=512,
                 token_types=2, dropout=0.1, tp_axis: Optional[str] = None,
                 dtype="float32"):
        super().__init__()
        self._units = units
        self.word_embed = nn.Embedding(vocab_size, units, dtype=dtype)
        self.token_type_embed = nn.Embedding(token_types, units, dtype=dtype)
        self.pos_embed = Parameter("pos_embed", shape=(max_length, units),
                                   dtype=dtype)
        self.embed_ln = nn.LayerNorm(in_channels=units)
        self.embed_dropout = nn.Dropout(dropout) if dropout else None
        self.encoder = TransformerEncoder(
            num_layers, units, hidden_size, num_heads, dropout=dropout,
            attention_dropout=dropout, pre_norm=False, tp_axis=tp_axis,
            dtype=dtype)
        self.pooler = nn.Dense(units, activation="tanh", flatten=False,
                               in_units=units, dtype=dtype)

    def forward(self, token_ids, token_types=None, valid_length=None):
        b, l = token_ids.shape
        emb = self.word_embed(token_ids)
        if token_types is not None:
            emb = emb + self.token_type_embed(token_types)
        emb = emb + self.pos_embed.data()[:l]
        emb = self.embed_ln(emb)
        if self.embed_dropout is not None:
            emb = self.embed_dropout(emb)
        mask = None
        if valid_length is not None:
            vl = _unwrap(valid_length)
            m = jnp.arange(l)[None, :] < vl[:, None]          # (B, Lk)
            mask = _wrap(m[:, None, None, :])                  # (B,1,1,Lk) bool
        seq = self.encoder(emb, mask=mask)
        pooled = self.pooler(seq[:, 0])
        return seq, pooled


class BERTForPretraining(HybridBlock):
    """MLM head (transform + tied decoder) + NSP head."""

    def __init__(self, bert: BERTModel, vocab_size=30522, dtype="float32"):
        super().__init__()
        self.bert = bert
        units = bert._units
        self.mlm_transform = nn.Dense(units, activation="gelu", flatten=False,
                                      in_units=units, dtype=dtype)
        self.mlm_ln = nn.LayerNorm(in_channels=units)
        self.mlm_bias = Parameter("mlm_bias", shape=(vocab_size,), dtype=dtype,
                                  init="zeros")
        self.nsp = nn.Dense(2, flatten=False, in_units=units, dtype=dtype)

    def forward(self, token_ids, token_types=None, valid_length=None):
        seq, pooled = self.bert(token_ids, token_types, valid_length)
        h = self.mlm_ln(self.mlm_transform(seq))
        # decoder tied to the word embedding (standard BERT weight tying);
        # taped ndarray ops so eager record()/backward() reaches everything
        w = self.bert.word_embed.weight.data()
        logits = h @ w.T + self.mlm_bias.data()
        nsp_logits = self.nsp(pooled)
        return logits, nsp_logits


def bert_base(**kwargs):
    """BERT-base: L12 H768 A12 (the BASELINE stretch-goal config)."""
    cfg = dict(units=768, hidden_size=3072, num_layers=12, num_heads=12)
    cfg.update(kwargs)
    return BERTModel(**cfg)


def bert_large(**kwargs):
    cfg = dict(units=1024, hidden_size=4096, num_layers=24, num_heads=16)
    cfg.update(kwargs)
    return BERTModel(**cfg)


class _CausalLM(HybridBlock):
    """Decoder-only LM (GPT-style): causal flash-attention encoder stack +
    tied LM head — exercises the causal kernel path end to end."""

    def __init__(self, vocab_size=32000, units=768, hidden_size=3072,
                 num_layers=12, num_heads=12, max_length=2048,
                 dropout=0.0, tp_axis: Optional[str] = None, dtype="float32"):
        super().__init__()
        self._units = units
        self.word_embed = nn.Embedding(vocab_size, units, dtype=dtype)
        self.pos_embed = Parameter("pos_embed", shape=(max_length, units),
                                   dtype=dtype)
        self.encoder = TransformerEncoder(
            num_layers, units, hidden_size, num_heads, dropout=dropout,
            attention_dropout=dropout, causal=True, pre_norm=True,
            tp_axis=tp_axis, dtype=dtype)

    def forward(self, token_ids):
        b, l = token_ids.shape
        emb = self.word_embed(token_ids)
        emb = emb + self.pos_embed.data()[:l]
        seq = self.encoder(emb)
        w = self.word_embed.weight.data()
        return seq @ w.T

    def decode_step(self, token_ids, cache_k, cache_v, pos):
        """KV-cache forward of ``token_ids`` (B, T) at absolute positions
        [pos, pos+T). Returns (logits (B, T, V), new_ck, new_cv). Used by
        :func:`mxnet_tpu.gluon.model_zoo.generation.generate`."""
        from ...numpy_extension import _call
        import jax as _jax

        emb = self.word_embed(token_ids)
        pos_table = self.pos_embed.data()
        t = token_ids.shape[1]

        def add_pos(e, table, ps):
            sl = _jax.lax.dynamic_slice(
                table, (ps.astype(jnp.int32), jnp.zeros((), jnp.int32)),
                (t, table.shape[1]))
            return e + sl[None]

        emb = _call(add_pos, (emb, pos_table, pos), name="add_pos_embed")
        seq, ck, cv = self.encoder.forward_step(emb, cache_k, cache_v, pos)
        w = self.word_embed.weight.data()
        return seq @ w.T, ck, cv

    def decode_step_paged(self, token_ids, pool_k, pool_v, block_table,
                          positions):
        """Paged-KV decode of T tokens per lane: ``token_ids`` is
        (R, T) — lane ``r``'s token ``t`` at absolute position
        ``positions[r] + t`` — K/V land in the shared block pools
        through ``block_table`` (R, MB). Returns (logits (R, T, V),
        new_pool_k, new_pool_v). T=1 is the continuous-batching decode
        program (:mod:`mxnet_tpu.serving.llm`); T=K+1 is the speculative
        verify forward; T=suffix-bucket is shared-prefix suffix prefill
        — all static pool/table shapes, so admission and sequence
        growth never retrace."""
        from ...numpy_extension import _call

        emb = self.word_embed(token_ids)
        pos_table = self.pos_embed.data()
        t = token_ids.shape[1]

        def add_pos(e, table, ps):
            # per-lane, per-offset gather (dense decode_step slices ONE
            # shared pos): jnp gather clamps out-of-range lanes — the
            # serving engine bounds positions against the context
            # window on the host
            idx = ps.astype(jnp.int32)[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
            return e + jnp.take(table, idx, axis=0)

        emb = _call(add_pos, (emb, pos_table, positions),
                    name="add_pos_embed_paged")
        seq, pk, pv = self.encoder.forward_step_paged(
            emb, pool_k, pool_v, block_table, positions)
        w = self.word_embed.weight.data()
        return seq @ w.T, pk, pv

    def cache_geometry(self, block_size):
        """Blocks of ``block_size`` K/V rows: a request of ``n`` tokens
        holds ``ceil(n / block_size)`` of them, and the position table
        bounds the context (what ``serving.LLMEngine``'s cache manager
        asks, :class:`~.generation.CacheGeometry`)."""
        from .generation import CacheGeometry

        return CacheGeometry(
            "kv_blocks", lambda tokens: -(-tokens // block_size),
            int(self.pos_embed.shape[0]))

    def init_block_pool(self, num_blocks, block_size, dtype="float32"):
        """Zeroed ``(L, NB, block_size, H*D')`` paged K/V block pools.

        The one pool layout (:func:`~mxnet_tpu.ops.nn.kv_pool_rows`): a
        row per token with every head side by side. At GPT-2 widths a
        row (``H*D`` = 768, 1,280) is a whole multiple of the chip's 128
        lanes, so the engine's buffer, the decode step's row write and
        the kernel's block all use plain row-major and the donated pool
        is updated in place; with the head size (64) innermost the three
        disagreed and every layer converted a whole pool twice.

        The paged analogue of :meth:`init_cache`: pool capacity — not
        ``max_length x max_batch`` — bounds KV memory; a sequence owns
        ``ceil(context / block_size)`` blocks via its block table and
        returns them the moment it finishes. ``dtype="int8"`` stores
        quantized blocks (``D' = D + 4``: each head's values, then its
        4 bitcast scale bytes, see
        :func:`~mxnet_tpu.ops.nn.kv_cache_quantize`)."""
        from ... import numpy as mxnp

        enc = self.encoder
        heads = enc.layer0.attn._heads
        d = enc.layer0.attn._units // heads
        if dtype == "int8":
            from ..nn.transformer import _KV_SCALE_BYTES

            d += _KV_SCALE_BYTES
        shape = (enc._num_layers, num_blocks, block_size, heads * d)
        return mxnp.zeros(shape, dtype=dtype), mxnp.zeros(shape, dtype=dtype)

    def init_cache(self, batch_size, max_length, dtype="float32"):
        """Zeroed (L, B, H, Lmax, D) key/value ring buffers.

        ``dtype="int8"``: quantized cache — values int8 plus a
        per-(batch, head, position) f32 scale bitcast into 4 extra
        feature bytes (halved HBM traffic vs bf16 on the bandwidth-bound
        decode path; see nn.transformer.kv_cache_quantize)."""
        from ... import numpy as mxnp

        enc = self.encoder
        heads = enc.layer0.attn._heads
        d = enc.layer0.attn._units // heads
        if dtype == "int8":
            from ..nn.transformer import _KV_SCALE_BYTES

            d += _KV_SCALE_BYTES
        shape = (enc._num_layers, batch_size, heads, max_length, d)
        return mxnp.zeros(shape, dtype=dtype), mxnp.zeros(shape, dtype=dtype)


def gpt_like(**kwargs):
    return _CausalLM(**kwargs)
