"""Paged-attention decode kernel (the vLLM idea, Pallas-TPU form).

One query token per decode lane attends over its KV history, which
lives in fixed-size blocks scattered across a shared pool and addressed
through a per-lane block table. The table rides the scalar-prefetch
channel (``pltpu.PrefetchScalarGridSpec``): each grid step's BlockSpec
``index_map`` reads ``block_table[lane, j]`` to DMA exactly that pool
block into VMEM — the gather never materializes a dense per-lane cache
in HBM, which is the point: decode reads ``length`` real positions,
not ``max_context``.

Grid: ``(lanes, max_blocks)`` — one lane per program row covering all
heads, online-softmax accumulation over the block axis (the
flash-attention recurrence with block_q == 1). Every block's last two
dims are the array's own (q ``(1, H, D)``, pool ``(1, H, bs, D')``),
which is what the TPU lowering accepts for 64-wide heads. The single
query row per head would underfill the MXU, so scores and the weighted
sum run on the VPU.

int8 pools (the engine default) take the same kernel: a pool row is
``[D int8 values | 4 bitcast f32-scale bytes]``
(:func:`~mxnet_tpu.ops.nn.kv_cache_quantize`), and the kernel
dequantizes INSIDE the block after the DMA — the bandwidth-bound read
moves half the bytes of bf16 and the fast path finally arms for the
default config.

Oracle: the jnp gather path in :func:`mxnet_tpu.ops.nn.paged_attention`
(itself token-identical to the dense cache); the kernel is checked
against it in interpret mode on CPU (``tests/test_llm_serving.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["paged_attention_kernel"]

_NEG_BIG = -1e30  # finite mask (−inf breaks the online-softmax carry)


def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, bs, mb, quantized,
                  sm_scale):
    import jax.experimental.pallas as pl

    from ..nn import kv_cache_dequantize

    r = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_BIG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)              # (H, D)
    if quantized:
        k = kv_cache_dequantize(k_ref[0], jnp.float32)    # (H, bs, D)
        v = kv_cache_dequantize(v_ref[0], jnp.float32)
    else:
        k = k_ref[0].astype(jnp.float32)          # (H, bs, D)
        v = v_ref[0].astype(jnp.float32)
    # one query row per head: the products run on the VPU in f32 (a
    # (1, D) x (D, bs) matmul would fill one MXU row in 128)
    s = jnp.sum(q[:, None, :] * k, axis=-1) * sm_scale       # (H, bs)
    length = len_ref[r]
    pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    # (a bare Python float here is an f64 operand under jax_enable_x64)
    s = jnp.where(pos < length, s, jnp.float32(_NEG_BIG))
    m_prev = m_ref[:, :1]                         # (H, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                        # (H, bs)
    l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
    pv = jnp.sum(p[:, :, None] * v, axis=1)       # (H, D)
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == mb - 1)
    def _finish():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def paged_attention_kernel(q, k_pool, v_pool, block_table, lengths,
                           interpret=None):
    """Block-table decode attention.

    ``q``: (R, H, D) one token per lane; ``k_pool``/``v_pool``:
    (NB, H, bs, D') pools — float pools carry ``D' = D``; int8 pools
    carry ``D' = D + 4`` (the :func:`~mxnet_tpu.ops.nn.kv_cache_quantize`
    bitcast-scale layout) and are dequantized inside the kernel after
    the block DMA; ``block_table``: (R, MB) int32; ``lengths``: (R,)
    int32 valid positions per lane. Returns (R, H, D) in the pool dtype
    (float pools) or ``q``'s dtype (int8 pools). ``interpret=None``
    auto-selects: compiled Mosaic on TPU, the Pallas interpreter
    elsewhere."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    r, h, d = q.shape
    _, _, bs, dp = k_pool.shape
    quantized = k_pool.dtype == jnp.int8
    mb = block_table.shape[1]
    out_dtype = q.dtype if quantized else v_pool.dtype
    bt = block_table.astype(jnp.int32)
    lens = lengths.astype(jnp.int32)
    kernel = functools.partial(
        _paged_kernel, bs=bs, mb=mb, quantized=quantized,
        sm_scale=float(d) ** -0.5)
    # every block's last two dims are the array's own,
    # which is what the TPU lowering takes for D=64 rows and 12 heads.
    # Index maps are traced under jax_enable_x64 (base.py), where a
    # Python 0 becomes an i64 that the lowering refuses: jnp.int32(0)
    def lane_map(i, j, bt_, ln_):
        z = jnp.int32(0)
        return i, z, z

    def block_map(i, j, bt_, ln_):
        z = jnp.int32(0)
        return bt_[i, j], z, z, z

    q_spec = pl.BlockSpec((1, h, d), lane_map)
    pool_spec = pl.BlockSpec((1, h, bs, dp), block_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # block_table, lengths
        grid=(r, mb),
        in_specs=[q_spec, pool_spec, pool_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((h, 128), jnp.float32),   # running max
            pltpu.VMEM((h, 128), jnp.float32),   # running denom
            pltpu.VMEM((h, d), jnp.float32),     # output accumulator
        ],
    )
    # the block axis is a sequential reduction (the scratch accumulators
    # carry across j); lanes are independent
    compiler_params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, h, d), out_dtype),
        compiler_params=compiler_params,
        interpret=interpret,
    )(bt, lens, q, k_pool, v_pool)
