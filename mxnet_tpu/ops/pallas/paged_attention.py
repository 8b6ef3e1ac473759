"""Paged-attention decode kernel (the vLLM idea, Pallas-TPU form).

One query token per decode lane attends over its KV history, which
lives in fixed-size blocks scattered across a shared pool and addressed
through a per-lane block table. The table, the lanes' lengths and the
layer ride the scalar-prefetch channel (``pltpu.PrefetchScalarGridSpec``)
and the kernel fetches exactly the pool blocks a lane holds into VMEM —
the gather never materializes a dense per-lane cache in HBM, which is
the point: decode reads ``length`` real positions, not ``max_context``.

**The layout.** The kernel takes the WHOLE pool, ``(L, NB, bs, H*D')``
(:func:`~mxnet_tpu.ops.nn.kv_pool_rows`: a row per token, heads side by
side), and the layer as a third prefetched scalar; a block is
``(bs, H*D')`` at ``[layer, block_table[lane, c]]``. Three parties
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

Grid: ``(lanes, ceil(max_blocks / G))`` — a grid step handles a *group*
of ``G`` consecutive blocks of a lane's table, ``G * bs`` positions
(:func:`_group_blocks`: from the block, the table, the row and the pool
dtype; 16 blocks = 256 positions for bf16 rows of 1,280), and a group
wholly past ``lengths[lane]`` is skipped: no compute, nothing fetched.
One block a step was 73,728 grid steps a decode step of GPT-2-large at
0.57 us each whatever the lanes held, 88% of the serving cell's device
time at a ninth of the rows' bandwidth floor (PERF.md, PR 30). Inside a
group it is the flash-attention recurrence with the HEADS as the query
rows: the query is laid out once per lane as one row per head, ``qe``
``(HR, H*D)`` with ``q`` in the head's own columns and zeros elsewhere,
so the scores of all heads over a group are ONE matmul
``qe x k.T -> (HR, G*bs)`` whose weights are the group's K rows as they
lie, the softmax carry ``(m, l)`` is a column per head, and
``p x v -> (HR, H*D)`` accumulates every head's weighted sum over the
whole row, of which the head's own ``D`` columns are read at the end.
The MXU multiplies zeros twenty times over and does not care: it is the
rows' bytes that set the floor. bf16 rows go to the MXU as they are, in
one pass with f32 sums; a float32 query crosses in a high and a low
bf16 part (two rows a head), so the scores stay float32's to 2^-17; the
rescaling of the carry stays on the VPU in float32.

How a group reaches VMEM depends on what Mosaic will slice. Rows that
are whole multiples of 128 lanes (the float pools at GPT-2 widths) are
copied by hand: the pools stay in HBM (``memory_space=ANY``), a group is
``G`` ``make_async_copy`` per pool into one of two slots, and the next
live group's copies — this lane's, or the next lane's first — are
started before this group's compute. A block wholly past the lane's
length is not copied at all; its rows in the slot are masked. Rows that
are no multiple of 128 lanes (int8: 816, 1,360 bytes; toy widths)
cannot be sliced out of an unblocked pool (Mosaic: "Slice shape along
dimension 3 must be aligned to tiling (128)"), so the same pool is
handed to ``pallas_call`` ``G`` times, each under a ``BlockSpec`` whose
``index_map`` reads one table entry, and the automatic pipeline fetches
them; past a lane's last live group the maps repeat that group's
entries and nothing is fetched again. The chip timed both ways on bf16
rows of 1,280: 7.0 against 14.0 ms a decode step (PERF.md, PR 30): the
pipeline pays for every operand at every step, as the old grid did.

A program calls the kernel once a layer, and jax traces and lowers a
``pallas_call``'s body anew at every call: the call is traced once per
shapes (:func:`_traced`) and its equation bound at each site, so 36
layers cost one trace and one lowering (set-up is an end-to-end metric).

int8 pools (the engine default) take the same kernel: a row is, per
head, ``[D int8 values | 4 bitcast f32-scale bytes]``
(:func:`~mxnet_tpu.ops.nn.kv_cache_quantize`), and the kernel
dequantizes the group's rows after the fetch, head by head, and feeds
the MXU float32 at ``HIGHEST`` as it does for float32 pools.

Table entries past a lane's length are never relied on to hold anything
but finite numbers (the engine points them at its trash block).

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


def _rows(x, heads, quantized, dtype):
    """Pool rows ``(n, H*D')`` as values ``(n, H*D)`` in ``dtype``."""
    if not quantized:
        return x.astype(dtype)
    from ..nn import kv_cache_dequantize

    w = x.astype(jnp.int32)
    dp = w.shape[-1] // heads
    return jnp.concatenate(
        [kv_cache_dequantize(w[:, h * dp:(h + 1) * dp], dtype)
         for h in range(heads)], axis=-1)


_GROUP_VMEM = 6 * 2 ** 20     # of a v5e's 16 MiB of scoped VMEM


def _group_blocks(bs, mb, hdp, hd, pool_dtype):
    """``G``: how many of a lane's blocks one grid step handles, from
    the shapes alone: the largest power of two whose rows — two slots
    each of K and V as the pool holds them, and a float32's worth of
    each as the MXU's operands — stay inside ``_GROUP_VMEM``, and no
    more than the table holds. bf16 rows of 1,280: 16 blocks (256
    positions); of 768: 32; float32 rows of 1,280: 8."""
    per_block = bs * (4 * hdp * jnp.dtype(pool_dtype).itemsize + 8 * hd)
    g = 1
    while 2 * g * per_block <= _GROUP_VMEM and g < mb:
        g *= 2
    return g


def _paged_kernel(bt_ref, len_ref, layer_ref, q_ref, *rest, bs, mb, g,
                  heads, d, hr, quantized, sm_scale, precision, q_parts,
                  mm, by_hand, share=1):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    r = pl.program_id(0)
    j = pl.program_id(1)
    n = g * bs                          # positions a group holds
    length = len_ref[r]

    # (scalars below: a bare Python int is an i64 under jax_enable_x64)
    i32 = jnp.int32

    def groups_of(lane):                # live groups; never none (len 0)
        return jnp.maximum((len_ref[lane] + i32(n - 1)) // i32(n), i32(1))

    live = j < groups_of(r)

    if not by_hand:         # the pipeline has fetched G blocks of each pool
        o_ref, qe_ref, m_ref, l_ref, acc_ref = rest[2 * g:]

        def fetched():
            return [_rows(jnp.concatenate([x[...] for x in refs], axis=0),
                          heads, quantized, mm)
                    for refs in (rest[:g], rest[g:2 * g])]
    else:
        (k_hbm, v_hbm, o_ref, qe_ref, m_ref, l_ref, acc_ref, k_buf, v_buf,
         sems, step_ref) = rest

        def copies(lane, grp, slot, do):
            """``do`` (start or wait) the copies of group ``grp`` of
            ``lane`` into ``slot``: of the blocks the lane holds and no
            further — what a slot's other rows held stays, masked. (A
            loop, not ``G`` copies of its body: unrolled, the body took
            five times as long to trace and lower and ran no faster.)"""
            first = grp * i32(g)
            held = jnp.clip((len_ref[lane] + i32(bs - 1)) // i32(bs),
                            i32(1), i32(mb))     # length 0: one block
            layer = layer_ref[0]

            def block(i, _):
                blk = bt_ref[lane, first + i]
                do(pltpu.make_async_copy(
                    k_hbm.at[layer, blk], k_buf.at[slot, i],
                    sems.at[i32(0), slot]))
                do(pltpu.make_async_copy(
                    v_hbm.at[layer, blk], v_buf.at[slot, i],
                    sems.at[i32(1), slot]))

            jax.lax.fori_loop(i32(0), jnp.minimum(held - first, i32(g)),
                              block, None)

        def fetched():
            first = (r == 0) & (j == 0)

            @pl.when(first)
            def _():
                # a slot's rows are masked, never trusted to be finite:
                # what the scratch held before this call may be anything
                k_buf[...] = jnp.zeros_like(k_buf)
                v_buf[...] = jnp.zeros_like(v_buf)
                step_ref[0] = i32(0)

            slot = step_ref[0] % i32(2)
            step_ref[0] = step_ref[0] + i32(1)
            # the next live group, this lane's or the next lane's first
            more = j + i32(1) < groups_of(r)
            nxt_r = jnp.where(more, r, r + i32(1))
            nxt_j = jnp.where(more, j + i32(1), i32(0))

            def start(t, _):
                # t = 1: the next group into the other slot; t = 0, in
                # the call's first step alone: this group into its own
                nxt = t == 1

                @pl.when(~nxt | (nxt_r < pl.num_programs(0)))
                def _():
                    copies(jnp.where(nxt, nxt_r, r), jnp.where(nxt, nxt_j, j),
                           jnp.where(nxt, i32(1) - slot, slot),
                           lambda c: c.start())

            jax.lax.fori_loop(jnp.where(first, i32(0), i32(1)), i32(2),
                              start, None)
            copies(r, j, slot, lambda c: c.wait())
            return [_rows(buf[slot].reshape(n, -1), heads, quantized, mm)
                    for buf in (k_buf, v_buf)]

    def dot(a, b, contract):
        return jax.lax.dot_general(a, b, (contract, ((), ())),
                                   precision=precision,
                                   preferred_element_type=f32)

    def of_head(shape, first_row):
        """0/1: column ``c`` belongs to (the K/V head of) head ``row -
        first_row``."""
        h = jax.lax.broadcasted_iota(jnp.int32, shape, 0) - first_row
        if share > 1:           # ``share`` query heads read one K/V head
            h = h // i32(share)
        col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        return (col >= h * i32(d)) & (col < (h + 1) * i32(d))

    @pl.when(j == 0)
    def _init():
        # once per lane: the query as one row per head (its low part,
        # where it has one, in the rows after the heads' ``hr``)
        q = q_ref[0].astype(f32)
        if share > 1:
            # grouped heads: the block is (H, D), a head a row; lay each
            # row under every K/V head's columns, of_head keeps its own
            q = jnp.concatenate(
                [jnp.pad(q, ((0, hr - heads), (0, 0)))] * (heads // share),
                axis=1)
            q = jnp.concatenate([q] * q_parts, axis=0)
        hi = q.astype(mm).astype(f32)
        qe_ref[...] = sum(
            jnp.where(of_head(qe_ref.shape, i * hr), part, f32(0))
            for i, part in enumerate([hi, q - hi][:q_parts])).astype(mm)
        m_ref[...] = jnp.full_like(m_ref, _NEG_BIG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _group():
        k, v = fetched()                              # (n, H*D) each
        s = dot(qe_ref[...], k, ((1,), (1,)))         # (q_parts*hr, n)
        if q_parts == 2:                              # high + low part
            s = s[:hr] + s[hr:]
        s = s * sm_scale
        pos = j * i32(n) + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # (a bare Python float here is an f64 operand under jax_enable_x64)
        s = jnp.where(pos < length, s, f32(_NEG_BIG))
        m_prev = m_ref[...]                           # (hr, 128), lanes alike
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])                 # (hr, n)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        # the weights go to the MXU in its own precision (bf16 rows: as
        # the oracle casts them to the values' dtype); the rescaling stays
        # on the VPU in float32
        acc_ref[...] = (acc_ref[...] * alpha[:, :1]
                        + dot(p.astype(mm), v, ((1,), (0,))))

    @pl.when(j == pl.num_programs(1) - i32(1))
    def _finish():
        inv = 1.0 / jnp.maximum(l_ref[...], 1e-30)
        own = jnp.where(of_head(acc_ref.shape, 0),
                        acc_ref[...] * inv[:, :1], f32(0))
        if share > 1:           # a head's D columns, a head a row
            o_ref[0] = sum(own[:heads, kv * d:(kv + 1) * d] for kv in range(
                heads // share)).astype(o_ref.dtype)
        else:
            o_ref[0] = jnp.sum(own, axis=0,
                               keepdims=True).astype(o_ref.dtype)


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
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    args = (q, k_pool, v_pool, block_table, lengths,
            jnp.asarray(layer, jnp.int32))
    traced = _traced(tuple((a.shape, jnp.dtype(a.dtype)) for a in args),
                     bool(interpret))
    return jax.core.eval_jaxpr(traced.jaxpr, traced.consts, *args)[0]


@functools.lru_cache(maxsize=None)
def _traced(avals, interpret):
    """The call, traced once per shapes: a program calls the kernel once
    a layer with the same shapes, and tracing its body 36 times was a
    quarter of the serving cell's warm set-up (PERF.md, PR 30). The
    ``pallas_call`` equation is bound anew at every call site, under the
    caller's own name stack."""
    return jax.make_jaxpr(functools.partial(_call, interpret=interpret))(
        *[jax.ShapeDtypeStruct(*a) for a in avals])


def _call(q, k_pool, v_pool, block_table, lengths, layer, *, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, h, d = q.shape
    bs, hdp = k_pool.shape[2:]
    quantized = k_pool.dtype == jnp.int8
    # grouped K/V heads (float pools): a row holds Hkv heads of D, and
    # query head i reads K/V head i // share
    share = 1 if quantized else h * d // hdp
    if not quantized and (share < 1 or h % share or h // share * d != hdp):
        raise ValueError(f"{h} query heads of {d} do not divide over pool "
                         f"rows of {hdp}")
    hd = h // share * d
    mb = block_table.shape[1]
    out_dtype = q.dtype if quantized else v_pool.dtype
    # bf16 rows feed the MXU as they are, in one pass (HIGHEST on bf16 is
    # a Mosaic reject), and a wider query meets them in two bf16 parts;
    # any other pool is float32 at full precision (six passes)
    native = k_pool.dtype == jnp.bfloat16
    mm = jnp.bfloat16 if native else jnp.float32
    q_parts = 2 if native and q.dtype != jnp.bfloat16 else 1
    hr = -(-h // 16) * 16               # a head a row, in whole tiles
    g = _group_blocks(bs, mb, hdp, hd, k_pool.dtype)
    # rows of whole lanes are copied by hand; others Mosaic cannot slice
    # out of an unblocked pool, and its pipeline fetches them (docstring)
    by_hand = hdp % 128 == 0
    kernel = functools.partial(
        _paged_kernel, bs=bs, mb=mb, g=g, heads=h, d=d, hr=hr,
        quantized=quantized, sm_scale=float(d) ** -0.5, q_parts=q_parts,
        mm=mm, by_hand=by_hand, share=share,
        precision=(jax.lax.Precision.DEFAULT if native
                   else jax.lax.Precision.HIGHEST))

    # every block's last two dims are the array's own. Index maps are
    # traced under jax_enable_x64 (base.py), where a Python 0 becomes an
    # i64 that the lowering refuses: jnp.int32(0)
    def lane_map(i, j, bt_, ln_, ly_):
        z = jnp.int32(0)
        return i, z, z

    def block_map(i, j, bt_, ln_, ly_, *, nth):
        # past the lane's last live group: that group again, so nothing
        # is fetched; a column past the table is the table's last
        i32 = jnp.int32
        n = i32(g * bs)
        last = jnp.maximum((ln_[i] + n - i32(1)) // n, i32(1)) - i32(1)
        col = jnp.minimum(jnp.minimum(j, last) * i32(g) + i32(nth),
                          i32(mb - 1))
        return ly_[0], bt_[i, col], i32(0), i32(0)

    # a lane's query and output: one row of all heads, or (grouped
    # heads, whose values share columns) a row a head
    q_shape = (1, 1, hd) if share == 1 else (1, h, d)
    q_spec = pl.BlockSpec(q_shape, lane_map)
    scratch = [
        pltpu.VMEM((q_parts * hr, hd), mm),      # the query, a row a head
        pltpu.VMEM((hr, 128), jnp.float32),      # running max
        pltpu.VMEM((hr, 128), jnp.float32),      # running denom
        pltpu.VMEM((hr, hd), jnp.float32),       # output accumulator
    ]
    if by_hand:
        pools = [pl.BlockSpec(memory_space=pl.ANY)] * 2
        operands = [k_pool, v_pool]
        scratch += [
            pltpu.VMEM((2, g, bs, hdp), k_pool.dtype),   # K rows, 2 slots
            pltpu.VMEM((2, g, bs, hdp), v_pool.dtype),   # V rows
            pltpu.SemaphoreType.DMA((2, 2)),             # [pool, slot]
            pltpu.SMEM((1,), jnp.int32),                 # live steps so far
        ]
    else:
        pools = [pl.BlockSpec((None, None, bs, hdp),
                              functools.partial(block_map, nth=i))
                 for i in range(g)] * 2
        operands = [k_pool] * g + [v_pool] * g
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # block_table, lengths, layer
        grid=(r, -(-mb // g)),
        in_specs=[q_spec] + pools,
        out_specs=q_spec,
        scratch_shapes=scratch,
    )
    # the group axis is a sequential reduction (the scratch accumulators
    # carry across j), and a lane's last group fetches the next lane's
    # first: both axes in order
    compiler_params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r,) + q_shape[1:], out_dtype),
        compiler_params=compiler_params,
        interpret=interpret,
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32),
      q.reshape((r,) + q_shape[1:]), *operands)
    return out.reshape(r, h, d)
