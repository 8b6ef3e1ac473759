"""Paged-attention decode kernel (the vLLM idea, Pallas-TPU form).

One query token per decode lane attends over its KV history, which
lives in fixed-size blocks scattered across a shared pool and addressed
through a per-lane block table. The table rides the scalar-prefetch
channel (``pltpu.PrefetchScalarGridSpec``): each grid step's BlockSpec
``index_map`` reads ``block_table[lane, j]`` to DMA exactly that pool
block into VMEM — the gather never materializes a dense per-lane cache
in HBM, which is the point: decode reads ``length`` real positions,
not ``max_context``.

**The layout.** The kernel takes the WHOLE pool, ``(L, NB, bs, H*D')``
(:func:`~mxnet_tpu.ops.nn.kv_pool_rows`: a row per token, heads side by
side), and the layer as a third prefetched scalar; its block is
``(bs, H*D')`` at ``[layer, block_table[lane, j]]``. Three parties
touch a pool in the decode program — the XLA scatter that stores the
step's rows, this kernel, and the compiler's layout for the donated
parameter and result — and they have to agree, or every layer converts
a whole pool on the way in and out (PR 27: 72% of the decode step).
With the head size (64) innermost they did not: a 64-wide minor
dimension wastes half of the 128 lanes, so XLA moved other axes inside
while Mosaic pins row-major. Rows of ``H*D`` = 768 or 1,280 are whole
multiples of 128 lanes: all three use plain row-major, nothing is
padded, nothing is sliced per layer, and the donated pool is updated in
place.

Grid: ``(lanes, max_blocks)`` — one lane per program row covering all
heads, online-softmax accumulation over the block axis (the
flash-attention recurrence with block_q == 1), accumulators in f32.
Heads are reduced INSIDE the row, without a reshape, through a 0/1
"head of lane" matrix ``E`` ``(HP, H*D)`` (``HP``: heads padded to 128;
``E[h, c] = 1`` where column ``c`` belongs to head ``h``): the scores
of a block are ONE matmul ``k (bs, H*D) x (E * q).T -> (bs, HP)``, the
softmax runs on the small ``(bs, HP)`` tile, and ``p @ E`` spreads each
head's weights back over its ``D`` lanes for the weighted sum of ``v``
on the VPU. bf16 rows go to the MXU as they are, in one pass with f32
sums; what meets them there and has more than bf16's bits (a float32
query, the rescaling factors) crosses in a high and a low bf16 part, so
the arithmetic stays float32's to 2^-17. The chip timed this against a
butterfly of lane rotations, against float32 matmuls in six passes and
against the old per-head layout (PERF.md, PR 27).

int8 pools (the engine default) take the same kernel: a row is, per
head, ``[D int8 values | 4 bitcast f32-scale bytes]``
(:func:`~mxnet_tpu.ops.nn.kv_cache_quantize`), and the kernel
dequantizes INSIDE the block after the DMA, head by head. Their rows
(816, 1,360 bytes) are no multiple of 128, so the in-place property is
the float pools' alone (PERF.md, PR 27).

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


def _rows(ref, heads, quantized, dtype):
    """The block as rows ``(bs, H*D)`` in ``dtype``."""
    if not quantized:
        return ref[...].astype(dtype)
    from ..nn import kv_cache_dequantize

    w = ref[...].astype(jnp.int32)                # (bs, H*D')
    dp = w.shape[-1] // heads
    return jnp.concatenate(
        [kv_cache_dequantize(w[:, h * dp:(h + 1) * dp], dtype)
         for h in range(heads)], axis=-1)


def _paged_kernel(bt_ref, len_ref, layer_ref, q_ref, k_ref, v_ref, o_ref,
                  e_ref, qe_ref, m_ref, l_ref, acc_ref, *, bs, mb, heads,
                  d, quantized, sm_scale, precision, q_parts):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r = pl.program_id(0)
    j = pl.program_id(1)
    mm = e_ref.dtype           # what the MXU is fed: bf16 rows as they are
    f32 = jnp.float32

    def dot(a, b, contract):
        return jax.lax.dot_general(a, b, (contract, ((), ())),
                                   precision=precision,
                                   preferred_element_type=f32)

    def spread(rows):
        """(n, HP) per-head numbers -> (n, H*D): every lane its head's."""
        return dot(jnp.concatenate(rows, axis=0).astype(mm), e_ref[...],
                   ((1,), (0,)))

    def two_parts(x):
        """f32 numbers as a high and a low part that ``mm`` holds, so
        that they cross the MXU to f32 accuracy."""
        hi = x.astype(mm).astype(f32)
        return [hi, x - hi]

    @pl.when(j == 0)
    def _init():
        # once per lane: E, and the query laid out as one row per head
        # (its low part, where it has one, in the rows after the heads)
        row = jax.lax.broadcasted_iota(jnp.int32, e_ref.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, e_ref.shape, 1)

        def of_head(h):
            return (col >= h * d) & (col < h * d + d)

        e_ref[...] = of_head(row).astype(mm)
        parts = two_parts(q_ref[0].astype(f32))[:q_parts]
        qe_ref[...] = sum(
            jnp.where(of_head(row - i * heads), part, f32(0))
            for i, part in enumerate(parts)).astype(mm)
        m_ref[...] = jnp.full_like(m_ref, _NEG_BIG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k = _rows(k_ref, heads, quantized, mm)        # (bs, H*D)
    v = _rows(v_ref, heads, quantized, f32)
    s = dot(k, qe_ref[...], ((1,), (1,)))         # (bs, HP)
    if q_parts == 2:    # lanes [H, 2H) hold the low part's sums: add them
        # (a bare Python int shift is an i64 under jax_enable_x64)
        s = s + pltpu.roll(s, jnp.int32(s.shape[1] - heads), axis=1)
    s = s * sm_scale
    pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    # (a bare Python float here is an f64 operand under jax_enable_x64)
    s = jnp.where(pos < len_ref[r], s, f32(_NEG_BIG))
    m_prev = m_ref[...]                           # (8, HP), rows alike
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:1])                    # (bs, HP)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=0, keepdims=True)
    m_ref[...] = m_new
    # the weights go to the MXU in its own precision (bf16 rows: as the
    # oracle casts them to the values' dtype), the rescaling in two parts
    w = spread([p] + two_parts(alpha))            # (bs + 16, H*D)
    acc_ref[...] = (acc_ref[...] * (w[bs:bs + 8] + w[bs + 8:])
                    + jnp.sum(w[:bs] * v, axis=0, keepdims=True))

    @pl.when(j == mb - 1)
    def _finish():
        inv = spread(two_parts(1.0 / jnp.maximum(l_ref[...], 1e-30)))
        o_ref[0] = (acc_ref[:1] * (inv[:1] + inv[8:9])).astype(o_ref.dtype)


def paged_attention_kernel(q, k_pool, v_pool, block_table, lengths,
                           layer=0, interpret=None):
    """Block-table decode attention.

    ``q``: (R, H, D) one token per lane; ``k_pool``/``v_pool``: the whole
    ``(L, NB, bs, H*D')`` pools in the one pool layout (a row per token,
    heads side by side: a multiple of 128 lanes at GPT-2 widths, which is
    what lets the row scatter, this kernel's block and the donated buffer
    share row-major, module docstring) — float pools carry ``D' = D``;
    int8 pools carry ``D' = D + 4`` per head (the
    :func:`~mxnet_tpu.ops.nn.kv_cache_quantize` bitcast-scale layout)
    and are dequantized inside the kernel after the block DMA;
    ``block_table``: (R, MB) int32; ``lengths``: (R,) int32 valid
    positions per lane; ``layer``: int or () int32, the layer whose
    blocks are read (a prefetched scalar: one compiled kernel serves
    every layer). Returns (R, H, D) in the pool dtype (float pools) or
    ``q``'s dtype (int8 pools). ``interpret=None`` auto-selects:
    compiled Mosaic on TPU, the Pallas interpreter elsewhere."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    r, h, d = q.shape
    bs, hdp = k_pool.shape[2:]
    hd = h * d
    quantized = k_pool.dtype == jnp.int8
    mb = block_table.shape[1]
    out_dtype = q.dtype if quantized else v_pool.dtype
    # bf16 rows feed the MXU as they are, in one pass (HIGHEST on bf16 is
    # a Mosaic reject), and a wider query meets them in two bf16 parts;
    # any other pool is float32 at full precision (six passes)
    native = k_pool.dtype == jnp.bfloat16
    mm = jnp.bfloat16 if native else jnp.float32
    q_parts = 2 if native and q.dtype != jnp.bfloat16 else 1
    hp = -(-h * q_parts // 128) * 128   # the query's rows, in whole lanes
    kernel = functools.partial(
        _paged_kernel, bs=bs, mb=mb, heads=h, d=d, quantized=quantized,
        sm_scale=float(d) ** -0.5, q_parts=q_parts,
        precision=(jax.lax.Precision.DEFAULT if native
                   else jax.lax.Precision.HIGHEST))

    # every block's last two dims are the array's own. Index maps are
    # traced under jax_enable_x64 (base.py), where a Python 0 becomes an
    # i64 that the lowering refuses: jnp.int32(0)
    def lane_map(i, j, bt_, ln_, ly_):
        z = jnp.int32(0)
        return i, z, z

    def block_map(i, j, bt_, ln_, ly_):
        z = jnp.int32(0)
        return ly_[0], bt_[i, j], z, z

    q_spec = pl.BlockSpec((1, 1, hd), lane_map)
    pool_spec = pl.BlockSpec((None, None, bs, hdp), block_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # block_table, lengths, layer
        grid=(r, mb),
        in_specs=[q_spec, pool_spec, pool_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((hp, hd), mm),            # E: head of lane
            pltpu.VMEM((hp, hd), mm),            # E * q: a row per head
            pltpu.VMEM((8, hp), jnp.float32),    # running max
            pltpu.VMEM((8, hp), jnp.float32),    # running denom
            pltpu.VMEM((8, hd), jnp.float32),    # output accumulator
        ],
    )
    # the block axis is a sequential reduction (the scratch accumulators
    # carry across j); lanes are independent
    compiler_params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, 1, hd), out_dtype),
        compiler_params=compiler_params,
        interpret=interpret,
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), q.reshape(r, 1, hd),
      k_pool, v_pool)
    return out.reshape(r, h, d)
