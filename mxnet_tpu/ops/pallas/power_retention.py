"""The two power-retention kernels (:mod:`mxnet_tpu.ops.retention` has the
equations, the layout of phi and the plain ``jax.numpy`` forms both are
checked against).

``power_retention_step`` — decode, one token per lane; bound by bytes.
Grid ``(lanes, K/V heads)``; the slot of a lane and the layer are
prefetched scalars, the block is one head's whole state
``(d, Dp)`` = 4.26 MB at heads of 128, read once and written once in
place (the pools are aliased in and out). Everything the block meets is
laid along its lanes by XLA beforehand — phi of the key, phi of the
group's query heads and the gate as rows of ``Dp``, the value spread over
128 lanes — so the kernel is loads, multiplies and adds on the VPU, eight
sublanes at a time: scale by ``g``, add ``v[row] * phi(k)``, store, and
accumulate the row's products with each query head's phi; the lane sums
are taken once per head at the end.

``power_retention_chunk`` — prefill, ``c`` tokens of one lane; bound by
the MXU. Grid ``(K/V heads, c / tq)``: a step takes ``tq`` rows of the
group's query heads and of the key and value. Inside the chunk it is the
quadratic form, ``((q k^T)^2 * decay) v`` against the chunk's keys; for
what came before, phi is built tile by tile in VMEM — two lane rotations,
two products and a select per tile, never a gather, never phi of a chunk
in HBM — and each tile goes to the MXU twice: against the incoming state
(read) and, for the keys, against the weighted values (update). Operands
cross the MXU as bfloat16 with float32 sums, as every other matmul of a
bfloat16 model here; the state itself stays float32. A slot handed to a
new request counts as zero through a prefetched flag, not a ``zeros`` of
the pool.

Both lower for the chip with ``jax_enable_x64`` on (index maps return
``jnp.int32``; no Python scalar meets a traced value).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..retention import EPS, _grouped, phi, phi_size
from .layer_norm import _auto_interpret

__all__ = ["power_retention_step", "power_retention_chunk"]

F32 = jnp.float32
_VMEM_LIMIT = 64 * 1024 * 1024      # of 128 MiB: four 4.26 MB state blocks


def _i32(x):
    return jnp.reshape(x, (1,)).astype(jnp.int32)


# --- decode ----------------------------------------------------------------
def _step_kernel(slot_ref, layer_ref, rows_ref, v_ref, s_ref, z_ref,
                 so_ref, zo_ref, out_ref, acc_ref, *, group, d, tiles):
    import jax.experimental.pallas as pl

    j = pl.program_id(1)
    g_row = rows_ref[7:8, :]                       # (1, Dp): g in every lane
    z_new = g_row * z_ref[pl.ds(j, 1), :] + rows_ref[0:1, :]
    zo_ref[pl.ds(j, 1), :] = z_new

    def rows_of_eight(n, _):
        r0 = pl.multiple_of(n * 8, 8)
        v8 = v_ref[pl.ds(r0, 8), :]                # (8, d): v[row] in all lanes
        acc = [jnp.zeros((8, d), F32) for _ in range(group)]
        for t in range(tiles):
            cs = slice(t * d, (t + 1) * d)
            s = g_row[:, :d] * s_ref[pl.ds(r0, 8), cs] \
                + v8 * rows_ref[0:1, cs]
            so_ref[pl.ds(r0, 8), cs] = s
            for i in range(group):
                acc[i] = acc[i] + s * rows_ref[1 + i:2 + i, cs]
        for i in range(group):
            acc_ref[i, pl.ds(r0, 8), :] = acc[i]
        return _

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(d // 8), rows_of_eight,
                      jnp.int32(0))
    for i in range(group):
        # (d rows of v, d lanes) -> the lane sums as a row of d lanes
        out_ref[i:i + 1, :] = jnp.sum(acc_ref[i].T, axis=0, keepdims=True)
        den = jnp.sum(rows_ref[1 + i:2 + i, :] * z_new, axis=1,
                      keepdims=True)
        out_ref[8 + i:9 + i, :] = jnp.broadcast_to(den, (1, d))


def power_retention_step(q, k, v, lg, pool_s, pool_z, slots, layer,
                         interpret=None):
    """:func:`mxnet_tpu.ops.retention.retention_step_jnp` as a kernel."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    interpret = _auto_interpret(interpret)
    r, hq, d = q.shape
    hk = k.shape[1]
    group, dp = hq // hk, phi_size(d)
    if d != 128 or group > 6:
        raise ValueError("power_retention_step: heads of 128, at most six "
                         f"query heads a K/V head (got {d}, {group})")
    g = jnp.exp(lg.astype(F32))
    rows = jnp.concatenate(
        [phi(k)[:, :, None], _grouped(phi(q), hk),
         jnp.zeros((r, hk, 6 - group, dp), F32),
         jnp.broadcast_to(g[..., None, None], (r, hk, 1, dp))], axis=2)
    v_lanes = jnp.broadcast_to(v.astype(F32)[..., None], (r, hk, d, d))

    def of_lane(i, j, slot_, layer_):
        z = jnp.int32(0)
        return i, j, z, z

    def state(i, j, slot_, layer_):
        z = jnp.int32(0)
        return layer_[0], slot_[i], j, z, z

    def norm(i, j, slot_, layer_):
        z = jnp.int32(0)
        return layer_[0], slot_[i], z, z

    s_spec = pl.BlockSpec((None, None, None, d, dp), state)
    z_spec = pl.BlockSpec((None, None, hk, dp), norm)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                      # slots, layer
        grid=(r, hk),
        in_specs=[pl.BlockSpec((None, None, 8, dp), of_lane),
                  pl.BlockSpec((None, None, d, d), of_lane), s_spec, z_spec],
        out_specs=[s_spec, z_spec,
                   pl.BlockSpec((None, None, 16, d), of_lane)],
        scratch_shapes=[pltpu.VMEM((group, d, d), F32)])
    pool_s, pool_z, out = pl.pallas_call(
        functools.partial(_step_kernel, group=group, d=d, tiles=dp // d),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(pool_s.shape, pool_s.dtype),
                   jax.ShapeDtypeStruct(pool_z.shape, pool_z.dtype),
                   jax.ShapeDtypeStruct((r, hk, 16, d), F32)],
        # inputs count the prefetched scalars: 4, 5 are the pools
        input_output_aliases={4: 0, 5: 1},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name="power_retention_step",
    )(slots.astype(jnp.int32), _i32(layer), rows, v_lanes, pool_s, pool_z)
    num, den = out[:, :, :group], out[:, :, 8:8 + group, :1]
    return (num / (den + EPS)).reshape(q.shape), pool_s, pool_z


# --- prefill ---------------------------------------------------------------
def _phi_tile(x, xs, a, d, lane):
    """Tile ``a`` of phi of the rows of ``x`` (``xs`` is ``x * sqrt 2``):
    the layout of :func:`mxnet_tpu.ops.retention.phi_layout`."""
    from jax.experimental.pallas import tpu as pltpu

    if a == d // 2:
        return x * x
    first = xs[:, a:a + 1] * pltpu.roll(x, jnp.int32(d - 1 - a), axis=1)
    if a == d // 2 - 1:         # the row pairs with itself: padding
        return jnp.where(lane < d - 1 - a, first, F32(0))
    return jnp.where(lane < d - 1 - a, first,
                     xs[:, d - 2 - a:d - 1 - a] * x)


def _chunk_kernel(slot_ref, layer_ref, fresh_ref, q_ref, k_ref, v_ref,
                  gcol_ref, grow_ref, gend_ref, s_ref, z_ref, o_ref, so_ref,
                  zo_ref,
                  num_ref, den_ref, acc_ref, dacc_ref, zs_ref, *, group, tq,
                  c, d,
                  mm):
    import jax.experimental.pallas as pl

    j, rb = pl.program_id(0), pl.program_id(1)
    fresh = fresh_ref[0] != 0
    first = rb == 0
    r0 = pl.multiple_of(rb * tq, tq)
    sqrt2 = F32(2.0 ** 0.5)

    # bf16 operands in one pass (HIGHEST on bf16 is a Mosaic reject,
    # whatever jax_default_matmul_precision says); float32 ones in full
    precision = jax.lax.Precision.DEFAULT if mm == jnp.bfloat16 \
        else jax.lax.Precision.HIGHEST

    def dot(a, b, contract):
        return jax.lax.dot_general(a.astype(mm), b.astype(mm),
                                   (contract, ((), ())), precision=precision,
                                   preferred_element_type=F32)

    gq = gcol_ref[pl.ds(r0, tq), :]                # (tq, d): G[t] per lane
    grow = grow_ref[...]                           # (1, c):  G[s]
    gend = gend_ref[0:1, :]                        # (1, d): G[c - 1]
    carry = jnp.exp(gq)                            # what came before, at t
    left = jnp.exp(gend - gq)                      # row t, at the chunk's end
    end = jnp.exp(gend)

    # inside the chunk: the masked power matrix against the chunk's keys
    t_idx = r0 + jax.lax.broadcasted_iota(jnp.int32, (tq, c), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (tq, c), 1)
    decay = jnp.exp(jnp.where(s_idx <= t_idx, gq[:, :1] - grow,
                              F32(-1e30)))
    k_all, v_all = k_ref[...], v_ref[...]
    for i in range(group):
        rows = slice(i * tq, (i + 1) * tq)
        s = dot(q_ref[i], k_all, ((1,), (1,)))     # (tq, c)
        a = s * s * decay
        num_ref[rows, :] = dot(a, v_all, ((1,), (0,)))
        den_ref[rows, :] = jnp.broadcast_to(
            jnp.sum(a, axis=1, keepdims=True), (tq, d))

    # what came before: phi, a tile at a time, against the state
    q5 = q_ref[...].reshape(group * tq, d)
    kb = k_ref[pl.ds(r0, tq), :]
    qs5, ks = q5 * sqrt2, kb * sqrt2
    vw_t = (v_ref[pl.ds(r0, tq), :] * left).T      # (d, tq)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, d), 1)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    dacc_ref[...] = jnp.zeros_like(dacc_ref)
    # head j's row of the normaliser goes through a scratch, whole: a
    # dynamic row with a static lane slice is refused in one load, and
    # so is a lane slice of a one-row value. Rows: in, out so far, out
    zs_ref[0:1, :] = jnp.where(fresh, F32(0), z_ref[pl.ds(j, 1), :])
    zs_ref[1:2, :] = zo_ref[pl.ds(j, 1), :]
    for a in range(d // 2 + 1):
        cs = slice(a * d, (a + 1) * d)
        pq = _phi_tile(q5, qs5, a, d, lane)        # (group * tq, d)
        pk = _phi_tile(kb, ks, a, d, lane)         # (tq, d)
        s_in = jnp.where(fresh, F32(0), s_ref[:, cs])
        acc_ref[...] += dot(pq, s_in, ((1,), (1,)))
        dacc_ref[...] += pq * zs_ref[0:1, cs]
        so_ref[:, cs] = jnp.where(first, end * s_in, so_ref[:, cs]) \
            + dot(vw_t, pk, ((1,), (0,)))
        zs_ref[2:3, cs] = \
            jnp.where(first, end * zs_ref[0:1, cs], zs_ref[1:2, cs]) \
            + jnp.sum(left * pk, axis=0, keepdims=True)
    zo_ref[pl.ds(j, 1), :] = zs_ref[2:3, :]
    den_before = jnp.sum(dacc_ref[...], axis=1, keepdims=True)
    for i in range(group):
        rows = slice(i * tq, (i + 1) * tq)
        num = num_ref[rows, :] + carry * acc_ref[rows, :]
        den = den_ref[rows, :] + carry * den_before[rows]
        o_ref[i] = num / (den + F32(EPS))


def power_retention_chunk(q, k, v, lg, pool_s, pool_z, slot, layer, fresh,
                          n_real, tq=128, mxu_dtype=jnp.bfloat16,
                          interpret=None):
    """:func:`mxnet_tpu.ops.retention.retention_chunk_jnp` as a kernel.
    ``tq`` rows a grid step (it divides the chunk); ``mxu_dtype`` is what
    the matmuls' operands are rounded to (float32: the oracle's
    arithmetic, for the tests)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    interpret = _auto_interpret(interpret)
    c, hq, d = q.shape
    hk = k.shape[1]
    group, dp = hq // hk, phi_size(d)
    tq = min(tq, c)
    if d != 128 or c % tq or tq % 8:
        raise ValueError("power_retention_chunk: heads of 128 and a chunk "
                         f"of whole row blocks (got {d}, {c}, {tq})")
    real = (jnp.arange(c) < n_real)[:, None]
    lg = jnp.where(real, lg.astype(F32), 0.0)
    k = jnp.where(real[..., None], k.astype(F32), 0.0)
    big_g = jnp.cumsum(lg, axis=0).T                          # (Hk, c)
    qh = _grouped(q.astype(F32), hk).transpose(1, 2, 0, 3)    # (Hk, G, c, d)
    kh, vh = k.transpose(1, 0, 2), v.astype(F32).transpose(1, 0, 2)
    gcol = jnp.broadcast_to(big_g[..., None], (hk, c, d))
    grow = big_g[:, None, :]
    gend = jnp.broadcast_to(big_g[:, -1, None, None], (hk, 8, d))

    def rows_of(j, rb, *_):
        z = jnp.int32(0)
        return j, z, rb, z

    def head(j, rb, *_):
        z = jnp.int32(0)
        return j, z, z

    def state(j, rb, slot_, layer_, fresh_):
        z = jnp.int32(0)
        return layer_[0], slot_[0], j, z, z

    def norm(j, rb, slot_, layer_, fresh_):
        z = jnp.int32(0)
        return layer_[0], slot_[0], z, z

    q_spec = pl.BlockSpec((None, group, tq, d), rows_of)
    kv_spec = pl.BlockSpec((None, c, d), head)
    s_spec = pl.BlockSpec((None, None, None, d, dp), state)
    z_spec = pl.BlockSpec((None, None, hk, dp), norm)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                      # slot, layer, fresh
        grid=(hk, c // tq),
        in_specs=[q_spec, kv_spec, kv_spec, kv_spec,
                  pl.BlockSpec((None, 1, c), head),
                  pl.BlockSpec((None, 8, d), head), s_spec, z_spec],
        out_specs=[q_spec, s_spec, z_spec],
        scratch_shapes=[pltpu.VMEM((group * tq, d), F32)] * 4
        + [pltpu.VMEM((8, dp), F32)])
    o, pool_s, pool_z = pl.pallas_call(
        functools.partial(_chunk_kernel, group=group, tq=tq, c=c, d=d,
                          mm=mxu_dtype),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((hk, group, c, d), F32),
                   jax.ShapeDtypeStruct(pool_s.shape, pool_s.dtype),
                   jax.ShapeDtypeStruct(pool_z.shape, pool_z.dtype)],
        # inputs count the prefetched scalars: 9, 10 are the pools
        input_output_aliases={9: 1, 10: 2},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name="power_retention_chunk",
    )(_i32(slot), _i32(layer), _i32(fresh), qh, kh, vh, gcol, grow, gend,
      pool_s, pool_z)
    return o.transpose(2, 0, 1, 3).reshape(q.shape), pool_s, pool_z
