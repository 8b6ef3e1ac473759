"""Fused Pallas decode-step kernels (the FusionStitching direction).

One paged decode step used to launch the whole per-layer kernel zoo:
qkv Dense, quantize, pool scatter, paged-attend, out-proj Dense — each
its own XLA op reading activations back through HBM. Decode is
bytes-bound (4.7% of the HBM roofline on the banked TPU row), so the
launches and intermediate round-trips are pure tax. This module
collapses the per-layer decode hot path into three Pallas launches:

- :func:`fused_qkv_project` — QKV projection + bias + (for int8 pools)
  the per-(token, head) KV quantization fused into ONE kernel; the
  quantized rows come out in the 4-byte bitcast-scale layout
  (:func:`~mxnet_tpu.ops.nn.kv_cache_quantize`) ready to scatter into
  the pool, so K/V never exist unquantized in HBM.
- :func:`~.paged_attention.paged_attention_kernel` — the existing
  scalar-prefetch block-table attend (now int8-capable), with the KV
  write landing in place on the donated pool buffers immediately
  before it.
- :func:`fused_out_project` — out projection + bias in one kernel.

Gate: :func:`fused_decode_armed` — the env knob
``MXNET_TPU_LLM_FUSED_DECODE`` (``1`` arms; unarmed by default, see the
function for why). Oracle: the unfused jnp path in
``MultiHeadAttention.forward_step_paged``, checked in interpret mode on
CPU (``tests/test_llm_serving.py``); the chip's compiler is asked in
``tests/test_chip_compile.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...base import env_str
from ..nn import _KV_SCALE_BYTES, kv_pool_rows

__all__ = ["fused_decode_armed", "fused_decode_step",
           "fused_qkv_project", "fused_out_project"]


# --- gating ----------------------------------------------------------------
def fused_decode_armed() -> bool:
    """Should the paged decode step run the fused Pallas kernels?

    Only when ``MXNET_TPU_LLM_FUSED_DECODE`` asks for it (``1``/``on``;
    the kernels run interpreted off the TPU). Unarmed by default, on
    every backend and for every pool dtype: the one argument for
    arming it on the TPU was a per-launch cost fitted to numbers that
    predate PR 1, :func:`fused_out_project` holds the whole ``(U, U)``
    weight in VMEM and so does not scale with width, and no chip run has
    compared the trio with XLA's own fusion of the same ops (ROADMAP
    S3). Always off inside :func:`~mxnet_tpu.ops.nn.no_pallas` scopes."""
    from ..nn import _pallas_disabled

    if _pallas_disabled.depth:
        return False
    mode = env_str("MXNET_TPU_LLM_FUSED_DECODE", "0").strip().lower()
    return mode in ("1", "on", "true", "yes", "force")


# --- kernel bodies ---------------------------------------------------------
def _qkv_kernel(x_ref, wq_ref, wk_ref, wv_ref, bq_ref, bk_ref, bv_ref,
                q_ref, k_ref, v_ref, *, quantized, precision):
    # the ONE definition of the int8 [values | f32 scale bytes] layout:
    # fusing the oracle's own quantizer into the kernel keeps the
    # interpret-mode parity promise by construction
    from ..nn import kv_cache_quantize

    x = x_ref[...].astype(jnp.float32)            # (N, U)

    def proj(w_ref, b_ref):                       # -> (N, D) f32
        w = w_ref[0].astype(jnp.float32)          # (U, D)
        y = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                                precision=precision,
                                preferred_element_type=jnp.float32)
        return y + b_ref[0].astype(jnp.float32)   # (1, D) broadcast

    q_ref[0] = proj(wq_ref, bq_ref).astype(q_ref.dtype)
    k = proj(wk_ref, bk_ref)
    v = proj(wv_ref, bv_ref)
    if quantized:
        k_ref[0] = kv_cache_quantize(k)
        v_ref[0] = kv_cache_quantize(v)
    else:
        k_ref[0] = k.astype(k_ref.dtype)
        v_ref[0] = v.astype(v_ref.dtype)


def _out_kernel(a_ref, w_ref, b_ref, o_ref, *, precision):
    a = a_ref[...].astype(jnp.float32)            # (N, U)
    w = w_ref[...].astype(jnp.float32)            # (U_out, U_in)
    y = jax.lax.dot_general(a, w, (((1,), (1,)), ((), ())),
                            precision=precision,
                            preferred_element_type=jnp.float32)
    o_ref[...] = (y + b_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


# --- host wrappers ---------------------------------------------------------
def fused_qkv_project(x, w_qkv, b_qkv, *, heads, store_dtype,
                      interpret=None):
    """QKV projection + bias + KV-store conversion in one Pallas kernel.

    ``x``: (N, U) decode activations; ``w_qkv``: (3U, U) Dense weight
    (out, in); ``b_qkv``: (3U,) or None. Returns ``(q, k_store,
    v_store)``: q (N, H, D) in ``x``'s dtype; k/v (N, H, D') already in
    the pool's row encoding — int8 + bitcast scale when ``store_dtype``
    is int8, a plain cast otherwise
    (:func:`~mxnet_tpu.ops.nn.kv_pool_rows` makes pool rows of them).
    Grid: one program per head, over head-major operands — weights
    ``(H, U, D)``, biases ``(H, 1, D)``,
    outputs ``(H, N, D')`` — so that every block's last two dims are
    the array's own, which is what the TPU lowering accepts for 64-wide
    heads; the outputs are transposed to ``(N, H, D')`` outside."""
    import jax.experimental.pallas as pl

    from .flash_attention import _matmul_precision

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, u = x.shape
    d = u // heads
    quantized = jnp.dtype(store_dtype) == jnp.int8
    dp = d + _KV_SCALE_BYTES if quantized else d
    if b_qkv is None:
        b_qkv = jnp.zeros((3 * u,), x.dtype)

    def slab(w):                                  # (U_out, U_in) -> (H, U_in, D)
        return w.reshape(heads, d, u).transpose(0, 2, 1)

    wq, wk, wv = (slab(w_qkv[:u]), slab(w_qkv[u:2 * u]),
                  slab(w_qkv[2 * u:]))
    bq, bk, bv = (b_qkv[:u].reshape(heads, 1, d),
                  b_qkv[u:2 * u].reshape(heads, 1, d),
                  b_qkv[2 * u:].reshape(heads, 1, d))
    kernel = functools.partial(
        _qkv_kernel, quantized=quantized,
        precision=_matmul_precision(x.dtype))

    # index maps are traced under jax_enable_x64 (base.py), where a
    # Python 0 becomes an i64 that the lowering refuses: jnp.int32(0)
    def head_map(h):
        z = jnp.int32(0)
        return h, z, z

    def whole_map(h):
        z = jnp.int32(0)
        return z, z

    w_spec = pl.BlockSpec((1, u, d), head_map)
    b_spec = pl.BlockSpec((1, 1, d), head_map)
    kv_spec = pl.BlockSpec((1, n, dp), head_map)
    q, ks, vs = pl.pallas_call(
        kernel,
        grid=(heads,),
        in_specs=[pl.BlockSpec((n, u), whole_map),
                  w_spec, w_spec, w_spec, b_spec, b_spec, b_spec],
        out_specs=[pl.BlockSpec((1, n, d), head_map), kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct((heads, n, d), x.dtype),
                   jax.ShapeDtypeStruct((heads, n, dp), store_dtype),
                   jax.ShapeDtypeStruct((heads, n, dp), store_dtype)],
        interpret=interpret,
    )(x, wq, wk, wv, bq, bk, bv)
    return tuple(t.transpose(1, 0, 2) for t in (q, ks, vs))


def fused_out_project(attn, w_out, b_out, *, interpret=None):
    """Out projection + bias in one Pallas kernel. ``attn``: (N, U);
    ``w_out``: (U, U) Dense weight (out, in); ``b_out``: (U,) or None.
    Returns (N, U) in ``attn``'s dtype."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .flash_attention import _matmul_precision

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, u = attn.shape
    if b_out is None:
        b_out = jnp.zeros((u,), attn.dtype)
    kernel = functools.partial(_out_kernel,
                               precision=_matmul_precision(attn.dtype))
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        in_specs=[vmem, vmem, vmem],
        out_specs=vmem,
        out_shape=jax.ShapeDtypeStruct((n, u), attn.dtype),
        interpret=interpret,
    )(attn, w_out, b_out.reshape(1, u))


def fused_decode_step(x, w_qkv, b_qkv, w_out, b_out, pool_k, pool_v,
                      block_table, positions, layer, *, heads, units,
                      interpret=None):
    """One attention sublayer's paged decode step through the fused
    kernels: QKV+quantize kernel -> in-place pool scatter (donated
    buffers) -> scalar-prefetch paged-attend kernel -> out-proj kernel.

    ``x``: (R, T, U) at per-lane absolute positions ``positions[r]+t``;
    the whole pools ``(L, NB, bs, H*D')`` and this layer's index, as in
    ``MultiHeadAttention.forward_step_paged``; ``block_table`` (R, MB).
    Returns
    ``(out (R, T, U), new_pool_k, new_pool_v)`` — arithmetic matches
    the unfused jnp path (the interpret-mode oracle)."""
    r, t, u = x.shape
    n = r * t
    bs = pool_k.shape[2]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    q, ks, vs = fused_qkv_project(
        x.reshape(n, u), w_qkv, b_qkv, heads=heads,
        store_dtype=pool_k.dtype, interpret=interpret)
    pos = positions.astype(jnp.int32)
    bt = block_table.astype(jnp.int32)
    abs_pos = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
    blk = jnp.take_along_axis(bt, abs_pos // bs, axis=1).reshape(-1)
    slot = (abs_pos % bs).reshape(-1)
    pool_k = pool_k.at[layer, blk, slot].set(kv_pool_rows(ks))
    pool_v = pool_v.at[layer, blk, slot].set(kv_pool_rows(vs))
    from .paged_attention import paged_attention_kernel

    out = paged_attention_kernel(
        q, pool_k, pool_v, jnp.repeat(bt, t, axis=0),
        (abs_pos + 1).reshape(-1), layer, interpret=interpret)  # (N, H, D)
    o = fused_out_project(out.reshape(n, u).astype(x.dtype), w_out,
                          b_out, interpret=interpret)
    return o.reshape(r, t, u), pool_k, pool_v
