"""``gated_delta_step`` — the gated delta rule's decode step as a kernel
(:mod:`mxnet_tpu.ops.gated_delta` has the equations and the plain
``jax.numpy`` form it is checked against).

One token per lane; bound by bytes: a lane's state in one layer is ``Hv``
matrices of ``dk x dv`` float32 (2,097,152 B at 32 heads of 128 x 128),
read once and written once in place — the pool is aliased in and out, and
the lane's slot and the layer are prefetched scalars. Grid ``(lanes, Hv /
hb)``: a step takes ``hb`` heads' matrices as one block. What a head
needs besides its matrix comes as one ``(8, 128)`` tile laid out by XLA —
rows ``k``, ``q``, ``v``, and ``exp(g)`` and ``beta`` spread over the
lanes — 3% of the state's bytes. The matrix lies with ``dk`` down the
sublanes and ``dv`` along the lanes, so ``S^T k`` and ``S^T q`` are
sublane sums of the matrix scaled by a *column*; the tile is transposed
once a head (XLU) to get ``k`` and ``q`` as columns. The rest is
multiplies and adds on the VPU.

Lowers for the chip with ``jax_enable_x64`` on (index maps return
``jnp.int32``; no Python scalar meets a traced value).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .layer_norm import _auto_interpret

__all__ = ["gated_delta_step"]

F32 = jnp.float32
_VMEM_LIMIT = 32 * 1024 * 1024
# heads a grid step takes. The chip read 8, 16 and 32 alike (1.72, 1.79,
# 1.68 ms a layer of 64 lanes alone: PERF.md, section 6, PR 33): the
# step is not bound by its blocks' size
_HEADS_PER_STEP = 16


def _step_kernel(slot_ref, layer_ref, rows_ref, s_ref, so_ref, o_ref, *,
                 hb):
    import jax.experimental.pallas as pl

    def head(h, _):
        rows = rows_ref[h]                          # (8, d)
        cols = rows.T                               # (d, 8): k, q as columns
        k_col, q_col = cols[:, 0:1], cols[:, 1:2]
        v_row, decay, beta = rows[2:3], rows[3:4], rows[4:5]
        s = s_ref[h] * decay                        # (dk, dv)
        kv = jnp.sum(k_col * s, axis=0, keepdims=True)
        s = s + k_col * (beta * (v_row - kv))
        so_ref[h] = s
        o_ref[pl.ds(h, 1), :] = jnp.sum(q_col * s, axis=0, keepdims=True)
        return _

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(hb), head, jnp.int32(0))


def gated_delta_step(q, k, v, g, beta, pool_s, slots, layer, *,
                     interpret=None):
    """:func:`mxnet_tpu.ops.gated_delta.delta_step_jnp` as a kernel."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    interpret = _auto_interpret(interpret)
    r, hv, d = v.shape
    if pool_s.shape[-2:] != (d, d) or d % 128:
        raise ValueError("gated_delta_step: square heads of a multiple of "
                         f"128 (got {pool_s.shape[-2:]}, values of {d})")
    hb = min(_HEADS_PER_STEP, hv)
    while hv % hb:
        hb -= 1
    rep = hv // k.shape[1]
    spread = jnp.broadcast_to
    rows = jnp.stack(
        [jnp.repeat(k.astype(F32), rep, axis=1),
         jnp.repeat(q.astype(F32), rep, axis=1), v.astype(F32),
         spread(jnp.exp(g.astype(F32))[..., None], (r, hv, d)),
         spread(beta.astype(F32)[..., None], (r, hv, d)),
         *[jnp.zeros((r, hv, d), F32)] * 3], axis=2)    # (R, Hv, 8, d)

    def of_lane(i, j, slot_, layer_):
        z = jnp.int32(0)
        return i, j, z, z

    def state(i, j, slot_, layer_):
        z = jnp.int32(0)
        return layer_[0], slot_[i], j, z, z

    def out(i, j, slot_, layer_):
        return i, j, jnp.int32(0)

    s_spec = pl.BlockSpec((None, None, hb, d, d), state)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                      # slots, layer
        grid=(r, hv // hb),
        in_specs=[pl.BlockSpec((None, hb, 8, d), of_lane), s_spec],
        out_specs=[s_spec, pl.BlockSpec((None, hb, d), out)])
    pool_s, o = pl.pallas_call(
        functools.partial(_step_kernel, hb=hb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(pool_s.shape, pool_s.dtype),
                   jax.ShapeDtypeStruct((r, hv, d), F32)],
        # inputs count the prefetched scalars: 3 is the pool
        input_output_aliases={3: 0},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name="gated_delta_step",
    )(slots.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
      rows, pool_s)
    return o, pool_s
