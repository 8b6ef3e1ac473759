"""``moe_grouped_ffn`` — the held experts' gated FFN over rows sorted by
expert, as one kernel (:mod:`mxnet_tpu.ops.experts` has the layer and the
``lax.ragged_dot`` form this is checked against).

A *visit* is one tile of ``tm`` sorted rows under one expert: the tile's
rows times that expert's three matrices, ``(silu(x Wg) * (x Wu)) Wd``,
kept for the rows that are the expert's. The visits are laid out by XLA
beforehand from the group sizes (``_visits``) and ride the scalar-prefetch
channel: an expert with no row gets no visit, so its 3 x ``U x F``
weights are never fetched — at decode, where 64 tokens touch some 90 of
128 held experts, the fetched weights are the step's floor — and an
expert whose rows straddle two tiles gets two. The grid is the most
visits there can be (tiles + experts - 1); the ones past the last real
visit repeat its block indices, fetch nothing and compute nothing. A
tile's output block stays in VMEM over its consecutive visits, each
writing its own rows; rows of no held expert (the tail of the sort) are
never written and mean nothing.

bf16 operands cross the MXU in one pass with float32 sums; float32 ones
in full. Lowers with ``jax_enable_x64`` on.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .layer_norm import _auto_interpret

__all__ = ["grouped_ffn"]

F32 = jnp.float32
_VMEM_LIMIT = 64 * 1024 * 1024


def _visits(sizes, tm: int, tiles: int):
    """``(expert, tile)`` of every visit, in order, padded to ``tiles +
    experts - 1`` by repeating the last real one; the experts' first and
    one-past-last sorted rows; and the number of real visits."""
    i32 = jnp.int32
    held = sizes.shape[0]
    ends = jnp.cumsum(sizes).astype(i32)
    starts = ends - sizes
    first = starts // tm
    n = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0).astype(i32)
    v_end = jnp.cumsum(n).astype(i32)
    total = v_end[-1]
    v = jnp.arange(tiles + held - 1, dtype=i32)
    v = jnp.minimum(v, jnp.maximum(total - 1, 0))
    gid = jnp.minimum(jnp.searchsorted(v_end, v, side="right"),
                      held - 1).astype(i32)
    tile = jnp.clip(first[gid] + v - (v_end - n)[gid], 0, tiles - 1)
    return gid, tile.astype(i32), starts, ends, jnp.reshape(total, (1,))


def _kernel(gid_ref, tile_ref, start_ref, end_ref, total_ref, x_ref, wg_ref,
            wu_ref, wd_ref, o_ref, *, tm, precision):
    import jax.experimental.pallas as pl

    v = pl.program_id(0)

    def dot(a, b):
        return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                   precision=precision,
                                   preferred_element_type=F32)

    @pl.when(v < total_ref[0])
    def _():
        g, t = gid_ref[v], tile_ref[v]
        row = t * jnp.int32(tm) + jax.lax.broadcasted_iota(
            jnp.int32, (tm, 1), 0)
        mine = (row >= start_ref[g]) & (row < end_ref[g])
        x = x_ref[...]
        h = jax.nn.silu(dot(x, wg_ref[...])) * dot(x, wu_ref[...])
        y = dot(h.astype(x.dtype), wd_ref[...]).astype(o_ref.dtype)
        # the tile's first visit finds whatever the buffer held
        opened = (v == 0) | (tile_ref[jnp.maximum(v - 1, 0)] != t)
        kept = jnp.where(opened, jnp.zeros_like(y), o_ref[...])
        o_ref[...] = jnp.where(mine, y, kept)


def grouped_ffn(rows, sizes, wg, wu, wd, *, interpret=None):
    """:func:`mxnet_tpu.ops.experts.grouped_ffn_jnp` as a kernel; rows
    past ``sum(sizes)`` come back unspecified."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    interpret = _auto_interpret(interpret)
    m, u = rows.shape
    held, _, f = wg.shape
    tm = 256 if m >= 4096 else 128
    if m < tm:
        tm = -(-m // 16) * 16
    tiles = -(-m // tm)
    if tiles * tm != m:
        rows = jnp.pad(rows, ((0, tiles * tm - m), (0, 0)))
    native = rows.dtype == jnp.bfloat16

    def row_map(v, gid, tile, *_):
        return tile[v], jnp.int32(0)

    def weight_map(v, gid, tile, *_):
        z = jnp.int32(0)
        return gid[v], z, z

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(tiles + held - 1,),
        in_specs=[pl.BlockSpec((tm, u), row_map),
                  pl.BlockSpec((None, u, f), weight_map),
                  pl.BlockSpec((None, u, f), weight_map),
                  pl.BlockSpec((None, f, u), weight_map)],
        out_specs=pl.BlockSpec((tm, u), row_map))
    out = pl.pallas_call(
        functools.partial(
            _kernel, tm=tm,
            precision=(jax.lax.Precision.DEFAULT if native
                       else jax.lax.Precision.HIGHEST)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tiles * tm, u), rows.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name="moe_grouped_ffn",
    )(*_visits(sizes.astype(jnp.int32), tm, tiles), rows,
      wg.astype(rows.dtype), wu.astype(rows.dtype), wd.astype(rows.dtype))
    return out[:m]
