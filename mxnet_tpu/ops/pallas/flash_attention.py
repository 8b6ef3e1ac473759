"""Pallas TPU flash attention — the fused kernel the reference could not
have: its attention was materialized O(L²) interleaved matmuls
(``src/operator/contrib/transformer.cc:650 interleaved_matmul_selfatt_qk``)
plus a separate softmax op. Here the whole QKᵀ→softmax→PV chain runs in one
kernel: K/V blocks stream HBM→VMEM, scores never leave VMEM, and the MXU
sees back-to-back matmuls (the playbook in /opt/skills/guides/pallas_guide.md).

Layout: (batch, heads, seq, head_dim). fp32 online-softmax accumulators
regardless of input dtype (bf16-safe).

Grid: (batch*heads, q_blocks, k_blocks) — the last axis runs sequentially
on TPU, so VMEM scratch (acc, m, l) persists across K blocks of one Q block.

Backward: ``jax.custom_vjp`` whose bwd recomputes attention blockwise
(O(L) memory) — flash-style recompute instead of saving the O(L²) matrix.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def _matmul_precision(dtype):
    """One policy for every kernel matmul, fwd and bwd: bf16 runs at
    native MXU precision (HIGHEST on bf16 is a Mosaic reject; f32
    accumulation comes from preferred_element_type); f32 follows the
    ambient jax_default_matmul_precision (docs/precision.md).

    Mosaic's dot lowering accepts only DEFAULT and HIGHEST — an ambient
    "high" (3-pass bf16) reaching a kernel dot is a compile-time
    NotImplementedError that surfaces at the ENCLOSING jit (observed:
    bert_base/fp32 train bench, 2026-08-02). For f32 inputs "high" maps
    to HIGHEST: accuracy >= what the caller asked for, at 6-pass cost on
    the attention dots only; callers who want the fast path run bf16."""
    if dtype == jnp.bfloat16:
        return jax.lax.Precision.DEFAULT
    amb = jax.config.jax_default_matmul_precision
    return {"highest": jax.lax.Precision.HIGHEST,
            "high": jax.lax.Precision.HIGHEST}.get(amb,
                                                   jax.lax.Precision.DEFAULT)


def _mha_reference(q, k, v, causal: bool, sm_scale: float):
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * sm_scale
    if causal:
        lq, lk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((lq, lk), dtype=bool), k=lk - lq)
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vf).astype(q.dtype)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *rest, sm_scale, causal,
                  block_q, block_k, seq_q, seq_k, n_k, precision):
    # rest = (lse_ref?, acc_ref, m_ref, l_ref): the lse output exists
    # only when the caller saves residuals for a backward — the
    # inference primal skips its HBM writes entirely
    lse_ref = rest[0] if len(rest) == 4 else None
    acc_ref, m_ref, l_ref = rest[-3:]
    import jax.experimental.pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, jnp.float32(_NEG_INF))
        l_ref[:] = jnp.zeros_like(l_ref)

    # causal block skip: K blocks entirely in the future contribute nothing
    # (the other half of the score matrix — this is where flash wins)
    run = True
    if causal:
        run = ki * block_k <= qi * block_q + (block_q - 1) + (seq_k - seq_q)

    @pl.when(run)
    def _compute():
        neg_inf = jnp.float32(_NEG_INF)
        # bf16 inputs feed the MXU natively; accumulation is f32 via
        # preferred_element_type (casting inputs up first would halve MXU rate)
        q = q_ref[0]                                     # (bq, d)
        kt = k_ref[0]                                    # (d, bk) — pre-transposed
        v = v_ref[0]                                     # (bk, d)
        # plain [1]x[0] contraction: Mosaic v5e rejects bf16 rhs-transpose
        s = jax.lax.dot_general(
            q, kt, (((1,), (0,)), ((), ())),
            precision=precision,
            preferred_element_type=jnp.float32) * jnp.float32(sm_scale)

        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = k_pos < seq_k                             # K padding
        mask &= q_pos < seq_q                            # Q padding (rows are discarded anyway)
        if causal:
            mask &= k_pos <= q_pos + (seq_k - seq_q)
        s = jnp.where(mask, s, neg_inf)

        m_prev = m_ref[:, :1]                            # (bq, 1)
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, jnp.float32(0.0))         # fully-masked rows
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            precision=precision,
            preferred_element_type=jnp.float32)          # (bq, d)
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[:] / jnp.where(l == 0.0, jnp.float32(1.0), l)).astype(o_ref.dtype)
        if lse_ref is not None:
            # log-sum-exp per row, saved for the backward (lane-128
            # layout, the same residual layout the official TPU kernel
            # uses); the l==0 guard keeps fully-masked/padded rows at a
            # finite value
            lse_ref[0] = m_ref[:] + jnp.log(
                jnp.where(l_ref[:] == 0.0, jnp.float32(1.0), l_ref[:]))


def _flash_kernel_resident(q_ref, k_ref, v_ref, o_ref, *rest, sm_scale,
                           causal, block_q, block_k, seq_q, seq_k, n_k,
                           precision):
    """Resident-KV forward: one grid program per (bh, q-block), the
    ENTIRE (transposed) K and V for that head delivered to VMEM by the
    BlockSpec, and a STATIC python loop over K chunks inside the kernel.

    Why (round 5, measured 2026-08-02): the streaming kernel's
    (bh, n_q, n_k) grid puts ~0.5 us of math in each of 3072 programs at
    GPT-small shapes (B32 H12 L1024 D64) — per-program overhead made the
    attention op 18x slower than an MLP matmul of equal FLOPs in the
    same window (42 ms vs 11.5 ms fwd+bwd per layer). At d=64 a whole
    head's K is 128 KB — VMEM fits the full K/V up to L~16k, so the k
    loop belongs INSIDE the program: no per-chunk grid overhead, online
    softmax state in plain values (no scratch ref round-trips), and the
    causal skip (pl.when per chunk) still saves the MXU work.
    """
    lse_ref = rest[0] if len(rest) == 4 else None
    acc_ref, m_ref, l_ref = rest[-3:]
    import jax.experimental.pallas as pl

    qi = pl.program_id(1)
    q = q_ref[0]                                         # (bq, d)
    neg_inf = jnp.float32(_NEG_INF)

    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, neg_inf)
    l_ref[:] = jnp.zeros_like(l_ref)

    # softmax in base-2: fold log2(e) into the score scale so
    # p = exp2(s2 - m2) — Mosaic's exp2 is the cheap transcendental and
    # the rescale costs zero extra VPU passes (it rides the existing
    # sm_scale multiply). lse is converted back to natural log at the end.
    LOG2E = 1.4426950408889634
    scale2 = jnp.float32(sm_scale * LOG2E)

    def chunk_body(j, masked):
        """One (bq, bk) K chunk. ``masked`` is a trace-time flag: the
        iota/compare/select mask stack (≈6 VPU passes over the score
        block — HALF this kernel's runtime at d=64, where everything is
        VPU-bound) is emitted only for chunks that can actually contain
        masked lanes: the causal diagonal and the padded tail. Interior
        chunks run mask-free."""
        kt = k_ref[0, :, j * block_k:(j + 1) * block_k]   # (d, bk)
        vj = v_ref[0, j * block_k:(j + 1) * block_k, :]   # (bk, d)
        s2 = jax.lax.dot_general(
            q, kt, (((1,), (0,)), ((), ())),
            precision=precision,
            preferred_element_type=jnp.float32) * scale2
        if masked:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = (k_pos < seq_k) & (q_pos < seq_q)
            if causal:
                mask &= k_pos <= q_pos + (seq_k - seq_q)
            s2 = jnp.where(mask, s2, neg_inf)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, s2.max(axis=-1, keepdims=True))
        p = jnp.exp2(s2 - m_new)
        if masked:
            p = jnp.where(mask, p, jnp.float32(0.0))
        alpha = jnp.exp2(m_prev - m_new)
        l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(vj.dtype), vj, (((1,), (0,)), ((), ())),
            precision=precision,
            preferred_element_type=jnp.float32)
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    shift = seq_k - seq_q
    for j in range(n_k):
        lo = j * block_k                  # chunk's first k position
        hi = (j + 1) * block_k - 1        # chunk's last k position
        pad_chunk = hi >= seq_k           # trace-time: k padding present
        if causal:
            # runtime causal gate: wholly-future chunks are skipped
            # (saves the MXU/VPU half above the diagonal; K/V are
            # resident so the skip costs nothing)
            run = lo <= qi * block_q + (block_q - 1) + shift
            # runtime: does the diagonal cross this chunk for ANY row of
            # this q block? below-diagonal chunks need no causal mask
            diag = hi > qi * block_q + shift
            if pad_chunk:
                pl.when(run)(functools.partial(chunk_body, j, True))
            else:
                pl.when(jnp.logical_and(run, diag))(
                    functools.partial(chunk_body, j, True))
                pl.when(jnp.logical_and(run, jnp.logical_not(diag)))(
                    functools.partial(chunk_body, j, False))
        else:
            # q-padding rows need no mask: their softmax is independent
            # garbage on rows the caller slices away
            chunk_body(j, pad_chunk)

    l = l_ref[:, :1]
    o_ref[0] = (acc_ref[:] / jnp.where(l == 0.0, jnp.float32(1.0), l)
                ).astype(o_ref.dtype)
    if lse_ref is not None:
        # m/l are base-2; natural-log lse = (m2 + log2 l) / log2 e
        lse_ref[0] = (m_ref[:] + jnp.log2(
            jnp.where(l_ref[:] == 0.0, jnp.float32(1.0), l_ref[:]))
        ) / jnp.float32(LOG2E)


# VMEM budget for the resident-KV path: K + V (bf16, double-buffered by
# the pipeline) + q/out blocks + the (bq, bk) f32 score chunk, with
# headroom under the ~16 MB VMEM. Above it, the streaming grid kernel
# keeps correctness at any length.
_RESIDENT_KV_VMEM_BYTES = 8 * 1024 * 1024


def _resident_fits(lk, d, itemsize):
    return 4 * lk * d * itemsize <= _RESIDENT_KV_VMEM_BYTES


# the kernel entry points are jitted: a bare ``pallas_call`` builds a new
# jit wrapper on every call, so an eager caller would compile the kernel
# again each time
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8))
def _flash_forward(q, k, v, causal, sm_scale, block_q, block_k, interpret,
                   save_residuals=False):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, lq, d = q.shape
    lk = k.shape[2]
    bq = min(block_q, lq)
    bk = min(block_k, lk)
    n_q = -(-lq // bq)
    n_k = -(-lk // bk)
    pad_q = n_q * bq - lq
    pad_k = n_k * bk - lk
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else v
    qp = qp.reshape(b * h, n_q * bq, d)
    kp = kp.reshape(b * h, n_k * bk, d).swapaxes(1, 2)  # (bh, d, Lk)
    vp = vp.reshape(b * h, n_k * bk, d)

    precision = _matmul_precision(q.dtype)
    resident = _resident_fits(n_k * bk, d, qp.dtype.itemsize)
    if resident:
        # one program per (bh, q-block); the k loop lives inside the
        # kernel (see _flash_kernel_resident: ~4x fewer, fatter grid
        # programs — the streaming grid was per-program-overhead-bound
        # at moderate L)
        kernel = functools.partial(
            _flash_kernel_resident, sm_scale=sm_scale, causal=causal,
            block_q=bq, block_k=bk, seq_q=lq, seq_k=lk, n_k=n_k,
            precision=precision)
        grid = (b * h, n_q)
        in_specs = [
            pl.BlockSpec((1, bq, d), lambda bh, qi: (bh, qi, jnp.int32(0))),
            pl.BlockSpec((1, d, n_k * bk),
                         lambda bh, qi: (bh, jnp.int32(0), jnp.int32(0))),
            pl.BlockSpec((1, n_k * bk, d),
                         lambda bh, qi: (bh, jnp.int32(0), jnp.int32(0))),
        ]
        out_specs = [
            pl.BlockSpec((1, bq, d), lambda bh, qi: (bh, qi, jnp.int32(0))),
        ]
        if save_residuals:
            out_specs.append(pl.BlockSpec(
                (1, bq, 128), lambda bh, qi: (bh, qi, jnp.int32(0))))
    else:
        kernel = functools.partial(
            _flash_kernel, sm_scale=sm_scale, causal=causal, block_q=bq,
            block_k=bk, seq_q=lq, seq_k=lk, n_k=n_k, precision=precision)
        grid = (b * h, n_q, n_k)
        in_specs = [
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, jnp.int32(0))),
            pl.BlockSpec((1, d, bk), lambda bh, qi, ki: (bh, jnp.int32(0), ki)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, jnp.int32(0))),
        ]
        out_specs = [
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, jnp.int32(0))),
        ]
        if save_residuals:
            out_specs.append(pl.BlockSpec(
                (1, bq, 128), lambda bh, qi, ki: (bh, qi, jnp.int32(0))))
    out_shape = [jax.ShapeDtypeStruct((b * h, n_q * bq, d), q.dtype)]
    if save_residuals:
        out_shape.append(
            jax.ShapeDtypeStruct((b * h, n_q * bq, 128), jnp.float32))
    # resident grid dims are independent programs (PARALLEL lets Mosaic
    # pipeline/reorder them); the streaming grid NEEDS its last dim
    # sequential — the scratch accumulators carry across k programs
    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel") if resident
            else ("parallel", "parallel", "arbitrary"))
    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        compiler_params=compiler_params,
        interpret=interpret,
    )(qp, kp, vp)
    out = res[0].reshape(b, h, n_q * bq, d)[:, :, :lq, :]
    if not save_residuals:
        return out, None
    # (bh, Lpad, 128) lane-broadcast -> (b, h, lq) row values
    lse = res[1][:, :lq, 0].reshape(b, h, lq)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    out, _ = _flash_forward(q, k, v, causal, sm_scale, block_q, block_k,
                            interpret)
    return out


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    out, lse = _flash_forward(q, k, v, causal, sm_scale, block_q, block_k,
                              interpret, save_residuals=True)
    return out, (q, k, v, out, lse)


def _causal_block_mask(q_pos, k_pos, causal, seq_q, seq_k):
    mask = (k_pos < seq_k)[None, :]
    if causal:
        mask = mask & (k_pos[None, :] <= q_pos[:, None] + (seq_k - seq_q))
    return mask  # (lq, bk)


def _bwd_dq_kernel(q_ref, kT_ref, k_ref, vT_ref, g_ref, o_ref, lse_ref,
                   dq_ref, d_scr, dq_scr, *, sm_scale, causal, block_q,
                   block_k, seq_q, seq_k, n_k, precision):
    """dQ = sum_k ds @ K with everything transient in VMEM. Grid
    (bh, q_blocks, k_blocks): K innermost, so dq/D scratch persist across
    the K sweep of one Q block (the forward kernel's accumulator shape)."""
    import jax.experimental.pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    f32 = jnp.float32

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        # D = rowsum(dO * O): recomputed here from the blocks already
        # resident instead of shipping another lane-128 residual
        g = g_ref[0].astype(f32)
        o = o_ref[0].astype(f32)
        d_scr[:] = jnp.broadcast_to(
            jnp.sum(g * o, axis=-1, keepdims=True), d_scr.shape)

    run = True
    if causal:
        run = ki * block_k <= qi * block_q + (block_q - 1) + (seq_k - seq_q)

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        s = jax.lax.dot_general(
            q, kT_ref[0], (((1,), (0,)), ((), ())), precision=precision,
            preferred_element_type=f32) * f32(sm_scale)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = (k_pos < seq_k) & (q_pos < seq_q)
        if causal:
            mask &= k_pos <= q_pos + (seq_k - seq_q)
        p = jnp.where(mask, jnp.exp(s - lse_ref[0][:, :1]), f32(0.0))
        dp = jax.lax.dot_general(
            g_ref[0], vT_ref[0], (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=f32)
        ds = p * (dp - d_scr[:, :1]) * f32(sm_scale)
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=f32)

    @pl.when(ki == n_k - 1)
    def _emit():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(k_ref, v_ref, q_ref, g_ref, qT_ref, gT_ref, oT_ref,
                    lseT_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale,
                    causal, block_q, block_k, seq_q, seq_k, n_q, precision):
    """dK = sum_q ds^T @ Q, dV = sum_q p^T @ dO. Grid (bh, k_blocks,
    q_blocks): Q innermost, so dk/dv scratch persist across the Q sweep
    of one K block. Scores are computed transposed (K rows, Q lanes) so
    every contraction is a plain [1]x[0] — no Mosaic transposed-operand
    patterns."""
    import jax.experimental.pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)
    f32 = jnp.float32

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = True
    if causal:
        run = ki * block_k <= qi * block_q + (block_q - 1) + (seq_k - seq_q)

    @pl.when(run)
    def _compute():
        k = k_ref[0]
        sT = jax.lax.dot_general(
            k, qT_ref[0], (((1,), (0,)), ((), ())), precision=precision,
            preferred_element_type=f32) * f32(sm_scale)      # (bk, bq)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_q), 0)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_q), 1)
        maskT = (k_pos < seq_k) & (q_pos < seq_q)
        if causal:
            maskT &= k_pos <= q_pos + (seq_k - seq_q)
        lse_row = lseT_ref[0][:1, :]                          # (1, bq)
        pT = jnp.where(maskT, jnp.exp(sT - lse_row), f32(0.0))
        dv_scr[:] += jax.lax.dot_general(
            pT.astype(k.dtype), g_ref[0], (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=f32)
        dpT = jax.lax.dot_general(
            v_ref[0], gT_ref[0], (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=f32)  # (bk, bq)
        gT = gT_ref[0].astype(f32)
        oT = oT_ref[0].astype(f32)
        d_row = jnp.sum(gT * oT, axis=0, keepdims=True)       # (1, bq)
        dsT = pT * (dpT - d_row) * f32(sm_scale)
        dk_scr[:] += jax.lax.dot_general(
            dsT.astype(k.dtype), q_ref[0], (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=f32)

    @pl.when(qi == n_q - 1)
    def _emit():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10))
def _flash_bwd_pallas(q, k, v, out, lse, g, causal, sm_scale, block_q,
                      block_k, interpret):
    """Pallas flash backward: dq/dk/dv with all score-sized transients in
    VMEM. Against the scan backward (what interpret mode runs) this
    path removes the dominant cost — every (Lq, bk) s/p/dp/ds tensor
    round-tripping HBM between XLA matmuls."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, lq, d = q.shape
    lk = k.shape[2]
    bq = min(block_q, lq)
    bk = min(block_k, lk)
    n_q = -(-lq // bq)
    n_k = -(-lk // bk)
    pad_q = n_q * bq - lq
    pad_k = n_k * bk - lk

    def padq(a):
        return jnp.pad(a, ((0, 0), (0, 0), (0, pad_q), (0, 0))) \
            if pad_q else a

    def padk(a):
        return jnp.pad(a, ((0, 0), (0, 0), (0, pad_k), (0, 0))) \
            if pad_k else a

    bh = b * h
    qp = padq(q).reshape(bh, n_q * bq, d)
    gp = padq(g).reshape(bh, n_q * bq, d)
    op = padq(out).reshape(bh, n_q * bq, d)
    kp = padk(k).reshape(bh, n_k * bk, d)
    vp = padk(v).reshape(bh, n_k * bk, d)
    kT = kp.swapaxes(1, 2)
    vT = vp.swapaxes(1, 2)
    qT = qp.swapaxes(1, 2)
    gT = gp.swapaxes(1, 2)
    oT = op.swapaxes(1, 2)
    # lane-128 lse for the dq kernel (the official kernel's residual
    # layout); padded q rows get +1e30 so p = exp(s - 1e30) = 0. The dkv
    # kernel reads lse along LANES, so its copy only needs the minimum 8
    # sublanes — not a second full 128-wide broadcast.
    lse_p = jnp.pad(lse.reshape(bh, lq), ((0, 0), (0, pad_q)),
                    constant_values=-_NEG_INF) if pad_q \
        else lse.reshape(bh, lq)
    lse128 = jnp.broadcast_to(lse_p[:, :, None], (bh, n_q * bq, 128))
    lseT = jnp.broadcast_to(lse_p[:, None, :], (bh, 8, n_q * bq))

    precision = _matmul_precision(q.dtype)

    common = dict(sm_scale=sm_scale, causal=causal, block_q=bq,
                  block_k=bk, seq_q=lq, seq_k=lk, precision=precision)
    qspec = pl.BlockSpec((1, bq, d), lambda g0, a, b_: (g0, a, jnp.int32(0)))
    kspec2 = pl.BlockSpec((1, bk, d), lambda g0, a, b_: (g0, b_, jnp.int32(0)))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, n_k=n_k, **common),
        grid=(bh, n_q, n_k),
        in_specs=[
            qspec,                                                   # q
            pl.BlockSpec((1, d, bk), lambda g0, a, b_: (g0, jnp.int32(0), b_)),  # kT
            kspec2,                                                  # k
            pl.BlockSpec((1, d, bk), lambda g0, a, b_: (g0, jnp.int32(0), b_)),  # vT
            qspec,                                                   # g
            qspec,                                                   # o
            pl.BlockSpec((1, bq, 128), lambda g0, a, b_: (g0, a, jnp.int32(0))),  # lse
        ],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((bh, n_q * bq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 128), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(qp, kT, kp, vT, gp, op, lse128)

    kspec = pl.BlockSpec((1, bk, d), lambda g0, a, b_: (g0, a, jnp.int32(0)))
    qspec2 = pl.BlockSpec((1, bq, d), lambda g0, a, b_: (g0, b_, jnp.int32(0)))
    tspec2 = pl.BlockSpec((1, d, bq), lambda g0, a, b_: (g0, jnp.int32(0), b_))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, n_q=n_q, **common),
        grid=(bh, n_k, n_q),
        in_specs=[
            kspec,                                                   # k
            kspec,                                                   # v
            qspec2,                                                  # q
            qspec2,                                                  # g
            tspec2,                                                  # qT
            tspec2,                                                  # gT
            tspec2,                                                  # oT
            pl.BlockSpec((1, 8, bq), lambda g0, a, b_: (g0, jnp.int32(0), b_)),  # lseT
        ],
        out_specs=[kspec, kspec],
        out_shape=[jax.ShapeDtypeStruct((bh, n_k * bk, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, n_k * bk, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
    )(kp, vp, qp, gp, qT, gT, oT, lseT)

    dq = dq.reshape(b, h, n_q * bq, d)[:, :, :lq]
    dk = dk.reshape(b, h, n_k * bk, d)[:, :, :lk]
    dv = dv.reshape(b, h, n_k * bk, d)[:, :, :lk]
    return dq, dk, dv


def _flash_bwd(causal, sm_scale, block_q, block_k, interpret, res, g):
    """Flash backward: ONE blockwise pass over K computing dQ/dK/dV, never
    materializing more than one (Lq, block_k) score block.

    Standard flash-attention-2 backward math: with the lse SAVED by the
    forward kernel (a (b,h,L) f32 residual — saving it deleted the whole
    lse-recompute pass this backward used to run), p = exp(s - lse)
    reconstructs each probability block exactly; ds = p * (dp - D) where
    D = rowsum(dO * O).
    """
    q, k, v, out, lse = res
    b, h, lq, d = q.shape
    lk = k.shape[2]
    # The compiled Pallas backward on the TPU, by fixed rule: blocks
    # capped at 256 (and by the caller's block args), which the chip's
    # compiler accepts (tests/test_chip_compile.py). Interpret mode
    # stays on the scan path below — the Pallas interpreter's python
    # grid loop is for the dedicated kernel unit tests, not every
    # CPU-test backward.
    if not interpret and jax.default_backend() == "tpu":
        return _flash_bwd_pallas(
            q, k, v, out, lse, g, causal, sm_scale,
            min(block_q, 256), min(block_k, 256), False)
    # the XLA-scan backward gets no launch-overhead win from big K blocks
    # (that argument is the Pallas forward grid's); it only pays their
    # memory — s/p/dp/ds transients scale with bk. Cap at 128 regardless
    # of the forward default.
    bk = min(block_k, 128, lk)
    n_k = -(-lk // bk)
    pad = n_k * bk - lk
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else v
    # block-major: (n_k, b, h, bk, d)
    kb = kp.reshape(b, h, n_k, bk, d).transpose(2, 0, 1, 3, 4)
    vb = vp.reshape(b, h, n_k, bk, d).transpose(2, 0, 1, 3, 4)
    # operands stay in the INPUT dtype (bf16 on the training path) with
    # fp32 ACCUMULATION via preferred_element_type — the forward kernel's
    # own numerics. Upcasting operands to f32 (the old code) doubled the
    # HBM bytes of every backward matmul and, under a "highest" ambient
    # precision, turned each one into 6-pass fp32 MXU emulation.
    gq = g.astype(q.dtype)
    f32 = jnp.float32
    q_pos = jnp.arange(lq)
    scale = f32(sm_scale)

    # single pass: accumulate dq; emit dk/dv per block (lse comes from
    # the forward kernel's saved residual)
    D = jnp.einsum("bhqd,bhqd->bhq", gq, out.astype(q.dtype),
                   preferred_element_type=f32)  # rowsum(dO*O)

    def pair_grads(q_blk, g_blk, lse_blk, d_blk, k_blk, v_blk, mask):
        """Gradients of one (q-block, k-block) pair; the flash-2 math."""
        s = jnp.einsum("bhqd,bhkd->bhqk", q_blk, k_blk,
                       preferred_element_type=f32) * scale
        p = jnp.where(mask, jnp.exp(s - lse_blk[..., None]), 0.0)
        pq = p.astype(q.dtype)  # bf16 operand, like the fwd kernel's PV
        dv_p = jnp.einsum("bhqk,bhqd->bhkd", pq, g_blk,
                          preferred_element_type=f32)
        dp = jnp.einsum("bhqd,bhkd->bhqk", g_blk, v_blk,
                        preferred_element_type=f32)
        ds = p * (dp - d_blk[..., None]) * scale
        dsq = ds.astype(q.dtype)  # flash-2: ds in compute dtype
        dq_p = jnp.einsum("bhqk,bhkd->bhqd", dsq, k_blk,
                          preferred_element_type=f32)
        dk_p = jnp.einsum("bhqk,bhqd->bhkd", dsq, q_blk,
                          preferred_element_type=f32)
        return dq_p, dk_p, dv_p

    if not causal:
        # full-q path: biggest einsums, no skippable blocks exist
        def grad_body(dq, blk):
            i, k_blk, v_blk = blk
            mask = _causal_block_mask(q_pos, i * bk + jnp.arange(bk),
                                      causal, lq, lk)
            dq_p, dk_blk, dv_blk = pair_grads(q, gq, lse, D, k_blk, v_blk,
                                              mask)
            return dq + dq_p, (dk_blk, dv_blk)

        dq0 = jnp.zeros((b, h, lq, d), f32)
        dq, (dkb, dvb) = jax.lax.scan(grad_body, dq0,
                                      (jnp.arange(n_k), kb, vb))
    else:
        # causal: block the q axis too and SKIP dead (q, k) pairs via
        # lax.cond — the forward kernel's causal block-skip, mirrored.
        # Without this the backward does ~2x the necessary matmul FLOPs
        # (every pair computed, half fully masked).
        bq = min(128, lq)
        n_q = -(-lq // bq)
        pad_q = n_q * bq - lq
        def qpad(a, fill=0.0):
            return jnp.pad(a, ((0, 0), (0, 0), (0, pad_q)) + ((0, 0),) *
                           (a.ndim - 3), constant_values=fill) if pad_q else a
        # block-major over q: (n_q, b, h, bq, ...)
        qb = qpad(q).reshape(b, h, n_q, bq, d).transpose(2, 0, 1, 3, 4)
        gb = qpad(gq).reshape(b, h, n_q, bq, d).transpose(2, 0, 1, 3, 4)
        # padded q rows: lse=+inf would still give p=0, but 0*inf NaNs in
        # ds; a large finite fill keeps p exactly 0 and ds finite
        lseb = qpad(lse, -_NEG_INF).reshape(b, h, n_q, bq).transpose(2, 0, 1, 3)
        Db = qpad(D).reshape(b, h, n_q, bq).transpose(2, 0, 1, 3)

        def k_body(dqb, blk):
            i, k_blk, v_blk = blk

            def q_body(carry, qblk):
                dk_acc, dv_acc = carry
                qi, q_blk, g_blk, lse_blk, d_blk, dq_prev = qblk
                # pair is live iff its LAST q row can see the k block's
                # first row: ki*bk <= qi*bq + bq-1 + (lk - lq)
                live = i * bk <= qi * bq + (bq - 1) + (lk - lq)

                def compute(_):
                    k_pos = i * bk + jnp.arange(bk)
                    mask = _causal_block_mask(
                        qi * bq + jnp.arange(bq), k_pos, True, lq, lk)
                    dq_p, dk_p, dv_p = pair_grads(
                        q_blk, g_blk, lse_blk, d_blk, k_blk, v_blk, mask)
                    return dq_prev + dq_p, dk_acc + dk_p, dv_acc + dv_p

                def skip(_):
                    return dq_prev, dk_acc, dv_acc

                dq_new, dk_acc, dv_acc = jax.lax.cond(live, compute, skip,
                                                      None)
                return (dk_acc, dv_acc), dq_new

            zero_kd = jnp.zeros((b, h, bk, d), f32)
            (dk_blk, dv_blk), dqb = jax.lax.scan(
                q_body, (zero_kd, zero_kd),
                (jnp.arange(n_q), qb, gb, lseb, Db, dqb))
            return dqb, (dk_blk, dv_blk)

        dqb0 = jnp.zeros((n_q, b, h, bq, d), f32)
        dqb, (dkb, dvb) = jax.lax.scan(k_body, dqb0,
                                       (jnp.arange(n_k), kb, vb))
        dq = dqb.transpose(1, 2, 0, 3, 4).reshape(b, h, n_q * bq, d)
        dq = dq[:, :, :lq]
    dk = dkb.transpose(1, 2, 0, 3, 4).reshape(b, h, n_k * bk, d)[:, :, :lk]
    dv = dvb.transpose(1, 2, 0, 3, 4).reshape(b, h, n_k * bk, d)[:, :, :lk]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


# Default block sizes, by fixed rule. 128x128 blocks make each grid
# program a tiny (128,64)x(64,128) matmul (8192 programs for
# B8/H16/L1024); 256x512 blocks lift arithmetic intensity ~8x per program
# and use ~1.5 MB of the ~16 MB VMEM. The chip's compiler accepts both
# (tests/test_chip_compile.py); which is faster is not measured.
_DEFAULT_BLOCKS = (256, 512)


def flash_attention(
    q, k, v,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """Fused attention over (batch, heads, seq, head_dim) tensors.

    ``interpret=None`` auto-selects: the compiled Mosaic kernel on TPU, the
    Pallas interpreter elsewhere (so CPU tests exercise the same kernel
    logic the TPU runs). Block sizes default to ``_DEFAULT_BLOCKS``.
    """
    if q.ndim != 4:
        raise ValueError(f"expected (b, h, l, d), got {q.shape}")
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q = block_q or _DEFAULT_BLOCKS[0]
    block_k = block_k or _DEFAULT_BLOCKS[1]
    return _flash(q, k, v, causal, float(sm_scale), block_q, block_k, interpret)
