"""Ask the chip's compiler, without the chip.

The TPU compiler is installed in the sandbox and compiles for a chip that
is described, not attached. Interpret-mode tests cannot see what it
refuses (a block shape off the (8, 128) tiling, an i64 index under
``jax_enable_x64``, an unsupported bitcast), so the kernels of the main
path are compiled here at GPT-2-small shapes — units 768, 12 heads of 64,
vocabulary 50,257, sequence 1024 — and so are one whole train step and
one whole decode step of a 2-layer model at that width.

Nothing runs, so nothing here says a word about results or speed. The
topology is described inside a fixture, never at import: only one process
may load the TPU's library, and every xdist worker imports every file.
Keep these tests in this one file.
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as onp
import pytest
from jax.sharding import SingleDeviceSharding

R, H, D, U, V, L = 32, 12, 64, 768, 50257, 1024   # lanes, heads, ...
NB, BS, MB = 2049, 16, 64                          # pool blocks (+trash)


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler here: skip, loudly
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without a chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    """Code that asks ``jax.default_backend()`` still sees the CPU here;
    steer it onto its TPU branch, from the test."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


HLO_DTYPE = {"float32": "f32", "bfloat16": "bf16", "int8": "s8"}


def _compile(fn, args, one_chip, donate=()):
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)
    return jax.jit(fn, donate_argnums=donate).lower(*args).compile()


def _s(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def _pool(dtype, heads=H):
    """One KV pool of the 2-layer ``lm`` in the one pool layout,
    ``(L, NB, bs, H*D')``; ``None`` is the model's own dtype."""
    dp = D + 4 if dtype == "int8" else D
    return _s((2, NB, BS, heads * dp), dtype or "bfloat16")


# --- one case per kernel of the main path ----------------------------------
def _paged(pool_dtype, heads=H, q_dtype="bfloat16"):
    """The paged kernel at GPT-2-small's rows (12 heads: 768 lanes) or
    GPT-2-large's (20: 1,280), under the query a bf16 model hands it:
    float32, since its LayerNorms are float32 (``q_dtype``)."""
    from mxnet_tpu.ops.pallas.paged_attention import paged_attention_kernel

    pool = _pool(pool_dtype, heads)
    return (lambda q, k, v, bt, ln, layer: paged_attention_kernel(
                q, k, v, bt, ln, layer, interpret=False),
            (_s((R, heads, D), q_dtype), pool, pool,
             _s((R, MB), "int32"), _s((R,), "int32"), _s((), "int32")))


def _paged_scratch(fn, args):
    """The VMEM scratch of the program's paged kernels as traced: the
    grid of the first, and the bytes of its scratch by memory space
    (the query's rows, the softmax carry, and two slots of a group's K
    and V rows where the kernel copies them by hand)."""
    calls = []

    def walk(jaxpr):
        for e in jaxpr.eqns:
            if (e.primitive.name == "pallas_call" and "_paged_kernel"
                    in e.params["jaxpr"].debug_info.func_name):
                calls.append(e)
            for sub in jax.core.jaxprs_in_params(e.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    grid = calls[0].params["grid_mapping"]
    sizes = {}
    for v in calls[0].params["jaxpr"].invars[-grid.num_scratch_operands:]:
        space = str(v.aval.memory_space or "vmem")
        if space != "semaphore_mem":
            sizes[space] = (sizes.get(space, 0) + int(onp.prod(v.aval.shape))
                            * jnp.dtype(v.aval.dtype).itemsize)
    return len(calls), tuple(grid.grid), sizes


def _flash(blocks, backward):
    # the module, not the function of the same name the package exports
    fa = importlib.import_module("mxnet_tpu.ops.pallas.flash_attention")
    qkv = _s((8, H, L, D), "bfloat16")

    def fwd(q, k, v):
        return fa._flash(q, k, v, True, D ** -0.5, *blocks, False)

    def fwd_bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    return (fwd_bwd if backward else fwd), (qkv, qkv, qkv)


def _layer_norm(dtype):
    from mxnet_tpu.ops.pallas.layer_norm import fused_layer_norm

    def fwd_bwd(x, g, b):
        return jax.value_and_grad(
            lambda *a: fused_layer_norm(*a, 1e-5, False)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2))(x, g, b)

    return fwd_bwd, (_s((8 * L, U), dtype), _s((U,), dtype), _s((U,), dtype))


def _cross_entropy(dtype):
    from mxnet_tpu.ops.nn import softmax_cross_entropy

    def fwd_bwd(logits, labels):
        return jax.value_and_grad(
            lambda x: softmax_cross_entropy(x, labels)[0]
            .astype(jnp.float32))(logits)

    return fwd_bwd, (_s((8 * L, V), dtype), _s((8 * L,), "int32"))


KERNELS = {
    "paged-float-pools": lambda: _paged("bfloat16"),
    "paged-int8-pools": lambda: _paged("int8"),
    "paged-float-pools-768-f32q": lambda: _paged("bfloat16", 12, "float32"),
    "paged-int8-pools-768-f32q": lambda: _paged("int8", 12, "float32"),
    "paged-float-pools-1280-f32q": lambda: _paged("bfloat16", 20, "float32"),
    "paged-int8-pools-1280-f32q": lambda: _paged("int8", 20, "float32"),
    "paged-float-pools-1280": lambda: _paged("bfloat16", 20),
    "paged-f32-pools-1280-f32q": lambda: _paged("float32", 20, "float32"),
    "flash-fwd-256x512": lambda: _flash((256, 512), False),
    "flash-fwd-128x128": lambda: _flash((128, 128), False),
    "flash-fwd-bwd-256x512": lambda: _flash((256, 512), True),
    "flash-fwd-bwd-128x128": lambda: _flash((128, 128), True),
    "layer-norm-f32": lambda: _layer_norm("float32"),
    "layer-norm-bf16": lambda: _layer_norm("bfloat16"),
    "cross-entropy-f32": lambda: _cross_entropy("float32"),
    "cross-entropy-bf16": lambda: _cross_entropy("bfloat16"),
}


@pytest.mark.parametrize("case", sorted(KERNELS))
def test_kernel_compiles_for_v5e(case, one_chip, no_compile_cache, on_tpu):
    assert jax.config.jax_enable_x64      # base.py's setting is in force
    fn, args = KERNELS[case]()
    compiled = _compile(fn, args, one_chip)
    if case.startswith("cross-entropy"):
        # no kernel since PR 32: the loss and its gradient in one program
        # hold the logits, ``dlogits`` and next to nothing beside them
        mem = compiled.memory_analysis()
        assert "tpu_custom_call" not in compiled.as_text(), case
        assert mem.temp_size_in_bytes < 64 * 2 ** 20, case
        assert mem.output_size_in_bytes < args[0].size * args[
            0].dtype.itemsize + 2 ** 20, case
        return
    assert "tpu_custom_call" in compiled.as_text(), case
    if case.startswith("paged"):
        _, grid, scratch = _paged_scratch(fn, args)
        print(f"{case}: grid {grid}, scratch {scratch}")
        assert onp.prod(grid) <= R * MB // 4, grid     # blocks in groups
    if "fwd-bwd" in case:                 # the Pallas backward, not the scan
        assert compiled.as_text().count("tpu_custom_call") >= 3, case


# --- the loss's two programs, alone ----------------------------------------
def _nv_shaped(text, dtype):
    """The ``pad`` and ``copy`` operations of a compiled program whose
    result has the logits' shape, as it is or padded to whole blocks."""
    shapes = [f" = {HLO_DTYPE[dtype]}[{8 * L},{v}]"
              for v in (V, -(-V // 2048) * 2048)]
    return [line.strip()[:160] for line in text.splitlines()
            if any(sh in line for sh in shapes)
            and (" pad(" in line or " copy(" in line)]


@pytest.mark.parametrize("per_example", [False, True], ids=["sum", "rows"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_loss_forward_program_for_v5e(dtype, per_example, one_chip,
                                      no_compile_cache):
    """The forward rule of ``softmax_cross_entropy`` at the train cell's
    (8,192, 50,257): the program makes nothing the size of the logits, no
    temporary, no output, no padded or transposed copy, whichever way the
    compiler lays its parameter out (for this shape, by columns)."""
    from mxnet_tpu.ops.nn import _ce_forward

    nv = 8 * L * V * jnp.dtype(dtype).itemsize
    compiled = _compile(
        lambda x, lab: _ce_forward(x, lab, per_example),
        (_s((8 * L, V), dtype), _s((8 * L,), "int32")), one_chip)
    mem, text = compiled.memory_analysis(), compiled.as_text()
    print(f"forward {dtype}: temporaries {mem.temp_size_in_bytes / 2**20:.1f}"
          f" MiB, outputs {mem.output_size_in_bytes / 2**20:.3f} MiB")
    assert "tpu_custom_call" not in text
    assert mem.temp_size_in_bytes < 64 * 2**20 < nv
    assert mem.output_size_in_bytes < 2**20
    assert not _nv_shaped(text, dtype)


@pytest.mark.parametrize("g_rows", [1, 8 * L], ids=["sum", "rows"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_loss_backward_program_for_v5e(dtype, g_rows, one_chip,
                                       no_compile_cache):
    """The pullback: one output shaped like the logits, in their dtype,
    and under 64 MiB beside it. The iota, the comparison and the float32
    softmax are fused away, so no second (N, V) array exists."""
    from mxnet_tpu.ops.nn import _ce_backward

    nv = 8 * L * V * jnp.dtype(dtype).itemsize
    g_dtype = dtype if g_rows == 1 else "float32"
    compiled = _compile(
        _ce_backward,
        (_s((8 * L, V), dtype), _s((8 * L,), "int32"),
         _s((8 * L,), "float32"), _s((g_rows,), g_dtype)), one_chip)
    mem, text = compiled.memory_analysis(), compiled.as_text()
    print(f"backward {dtype}: temporaries "
          f"{mem.temp_size_in_bytes / 2**20:.1f} MiB, outputs "
          f"{mem.output_size_in_bytes / 2**20:.1f} MiB of {nv / 2**20:.1f}")
    assert mem.temp_size_in_bytes < 64 * 2**20
    assert nv <= mem.output_size_in_bytes < nv + 2**20
    assert not _nv_shaped(text, dtype)


# --- whole programs, 2 layers at full width --------------------------------
@pytest.fixture(scope="module")
def lm():
    from mxnet_tpu.gluon.model_zoo import bert

    net = bert.gpt_like(vocab_size=V, max_length=L, num_layers=2,
                        dtype="bfloat16")
    net.initialize()
    return net


def test_train_step_program_compiles_for_v5e(lm, one_chip,
                                             no_compile_cache, on_tpu):
    """Forward, loss and backward of the causal LM on a batch of
    8 x 1024: the flash and layer-norm kernels and the loss's two
    programs inside one program, as a hybridized trainer step holds them."""
    import mxnet_tpu as mx
    from mxnet_tpu.ops.nn import softmax_cross_entropy

    fn, params = lm.functionalize(
        mx.np.array(onp.zeros((8, L), onp.int32)), training=True)

    def loss(p, x, labels, key):
        logits, _ = fn(p, x, key=key)
        return softmax_cross_entropy(logits.reshape(-1, V), labels)[0]

    compiled = _compile(
        jax.value_and_grad(loss),
        (params, _s((8, L), "int32"), _s((8 * L,), "int32"),
         _s((2,), "uint32")), one_chip)
    # per layer: flash fwd + dq + dkv, two norms; then the final norm
    assert compiled.as_text().count("tpu_custom_call") >= 2 * 5 + 1


def test_recorded_forward_and_backward_compile_for_v5e(lm, one_chip,
                                                       no_compile_cache,
                                                       on_tpu):
    """The two programs of a hybridized block's recorded call (PR 35) on a
    batch of 8 x 1024: the forward that also returns the pullback's
    residuals, and the backward over them. The backward holds each
    matmul's two transposes and, of the forward, only the two matmuls a
    layer whose results are larger than their operands (QKV, FFN in: the
    policy rebuilds those) — no flash forward, no norm kernel; no weight
    comes back from the forward; and a layer hands over 196 MB: by hand
    4 x 25.2 (the two sums of the residual stream, the two norms'
    outputs) + 25.2 (the flash kernel's ``o``) + 50.3 (its ``lse``,
    padded to 128 lanes) + 21 (the norms' statistics); with two layers
    the embedding's sum and the final norm add 14 MB a layer."""
    import re

    import mxnet_tpu as mx

    x = mx.np.array(onp.zeros((8, L), onp.int32))
    flat, treedef = jax.tree_util.tree_flatten((x,))
    cg = lm._build_cache((x,), flat, treedef, True,
                         lm._ensure_params_ready((x,)))

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = [p.data()._data for _, p in cg.param_list]
    vals = [on_chip(v) for v in params + [x._data, jax.random.PRNGKey(0)]]
    fwd = cg.fwd_res_fn.lower(*vals).compile()
    plan = cg.res_plan
    outs, res = jax.eval_shape(cg.fwd_res_fn, *vals)
    outs = tuple(on_chip(o) for o in outs)
    bwd = cg.bwd_fn.lower([on_chip(r) for r in res], vals, outs,
                          outs).compile()

    fm, bm = fwd.memory_analysis(), bwd.memory_analysis()
    logits = 8 * L * V * 4
    mb = 1 / 2**20
    print(f"recorded forward: outputs {fm.output_size_in_bytes * mb:.1f} MiB "
          f"(logits {logits * mb:.1f} + residuals {plan['nbytes'] * mb:.1f}"
          f", {plan['nbytes'] / 2 / 1e6:.1f} MB a layer), temporaries "
          f"{fm.temp_size_in_bytes * mb:.1f} MiB; backward: arguments "
          f"{bm.argument_size_in_bytes * mb:.1f} MiB, temporaries "
          f"{bm.temp_size_in_bytes * mb:.1f} MiB, outputs "
          f"{bm.output_size_in_bytes * mb:.1f} MiB")
    assert 150e6 < plan["nbytes"] / 2 < 225e6
    assert abs(fm.output_size_in_bytes - logits - plan["nbytes"]) < 2**20
    assert fm.temp_size_in_bytes < 2 * 2**30
    assert bm.temp_size_in_bytes < 2**30

    # every weight is named as the forward's own input, none returned:
    # no copy shaped like a parameter (or its transpose) in the forward
    named = {(k, n) for k, n in plan["src"] if k != "res"}
    assert len(named) >= 2 * 4 + 2 and all(k == "in" for k, _ in named)
    shapes = {tuple(p.shape) for p in params if p.ndim == 2}
    shapes |= {s[::-1] for s in shapes}
    copies = [tuple(int(n) for n in dims.split(","))
              for dims in re.findall(r"\[([\d,]+)\]\{[^}]*\} copy\(",
                                     fwd.as_text())]
    assert copies and not [c for c in copies if c in shapes]

    fwd_text, bwd_text = fwd.as_text(), bwd.as_text()
    matmuls = fwd_text.count(" convolution(")
    assert matmuls == 2 * 4 + 1
    assert bwd_text.count(" convolution(") == 2 * matmuls + 2 * 2
    # forward: flash + two norms a layer, the final norm; backward: the
    # flash kernel's dq and dkv a layer and nothing of the forward
    assert fwd_text.count("tpu_custom_call") == 2 * 3 + 1
    assert "_flash_forward" not in bwd_text
    kernels = re.findall(r'custom-call\(.*tpu_custom_call.*op_name="([^"]*)"',
                         bwd_text)
    assert len(kernels) == 2 * 2
    assert all("_flash_bwd_pallas" in k for k in kernels), kernels


def _pool_report(compiled, pool, label):
    """What the chip's compiler says of a program that is handed both
    pools donated: its temporaries and the two pools, in bytes, and the
    ``copy`` operations whose result is shaped like a pool."""
    pool_bytes = 2 * int(onp.prod(pool.shape)) * pool.dtype.itemsize
    temp = compiled.memory_analysis().temp_size_in_bytes
    dims = ",".join(map(str, pool.shape))
    short = HLO_DTYPE[pool.dtype.name]
    copies = [line.strip()[:160] for line in compiled.as_text().splitlines()
              if f" = {short}[{dims}]" in line and " copy(" in line]
    print(f"{label}: pools {pool_bytes / 2**20:.1f} MiB, temporaries "
          f"{temp / 2**20:.1f} MiB, pool-shaped copies {len(copies)}")
    return temp, pool_bytes, copies


@pytest.mark.parametrize("kv_cache_dtype", ["int8", None])
def test_decode_step_program_compiles_for_v5e(lm, kv_cache_dtype, one_chip,
                                              no_compile_cache, on_tpu):
    """The engine's one decode program at its default geometry (32 lanes,
    2,048 blocks of 16, context 1024), paged kernel inside, **with the
    pools donated** as the engine hands them over off the CPU. For float
    pools this is the guard of the one pool layout (PR 27): rows of
    ``H*D`` lanes let the row scatter, the kernel's block and the donated
    buffer share row-major, so the program needs no temporary and no
    copy shaped like a pool (on ``(L, NB, H, bs, D)`` it needed 1.7 x
    the pools and copied one twice a layer). int8 rows (816 bytes) are no
    multiple of 128 lanes: the case keeps compiling and prints its
    numbers, without a limit."""
    from mxnet_tpu.gluon.model_zoo.generation import paged_decode_program

    run, params = paged_decode_program(
        lm, max_running=R, num_blocks=NB, block_size=BS,
        max_blocks_per_seq=MB, kv_cache_dtype=kv_cache_dtype, donate=True)
    pool = _pool(kv_cache_dtype)
    args = (params, _s((R, 1), "int32"), pool, pool, _s((R, MB), "int32"),
            _s((R,), "int32"), _s((2,), "uint32"))
    compiled = _compile(run._fn, args, one_chip, donate=(2, 3))
    # per layer: two norms and the paged kernel, whatever the pool dtype
    assert compiled.as_text().count("tpu_custom_call") >= 2 * 3
    temp, pools, copies = _pool_report(
        compiled, pool, f"decode, {kv_cache_dtype or 'bf16'} pools")
    if kv_cache_dtype is None:
        assert temp < 0.05 * pools, (temp, pools)
        assert not copies, copies
    n, grid, scratch = _paged_scratch(run._fn, args)
    print(f"paged kernel x {n}: grid {grid}, scratch {scratch}")
    assert n == 2 and scratch["vmem"] < 16 * 2 ** 20, (n, scratch)


@pytest.mark.parametrize("kv_cache_dtype", ["int8", None])
def test_prefill_program_compiles_for_v5e(lm, kv_cache_dtype, one_chip,
                                          no_compile_cache, on_tpu):
    """One prefill-and-splice bucket (512 tokens) under the same rule: a
    donated float pool takes the prompt's blocks in place. The program
    computes the float32 logits of the whole bucket (``Pb x V`` x 4
    bytes, 98 MiB here, of which one row is sampled: they do not grow
    with the pools); what it holds beside them stays under 5% of the
    pools."""
    from mxnet_tpu.gluon.model_zoo.generation import paged_prefill_program

    pb = 512
    run, params = paged_prefill_program(
        lm, prefill_len=pb, num_blocks=NB, block_size=BS,
        kv_cache_dtype=kv_cache_dtype, donate=True)
    pool = _pool(kv_cache_dtype)
    compiled = _compile(
        run._fn,
        (params, _s((1, pb), "int32"), _s((), "int32"), pool, pool,
         _s((pb // BS,), "int32"), _s((2,), "uint32")), one_chip,
        donate=(3, 4))
    temp, pools, copies = _pool_report(
        compiled, pool, f"prefill {pb}, {kv_cache_dtype or 'bf16'} pools")
    if kv_cache_dtype is None:
        assert temp - pb * V * 4 < 0.05 * pools, (temp, pools)
        assert not copies, copies


# --- a model whose cache is a state: the retention programs ----------------
# Brumby-14B-Base's published widths, 2 of its layers, the cell's geometry
B_UNITS, B_FFN, B_HQ, B_HK, B_D, B_V = 5120, 17408, 40, 8, 128, 151936
B_LANES, B_SLOTS, B_CHUNK, B_DP = 16, 17, 1024, 8320


def _retention_kernel(which):
    from mxnet_tpu.ops.pallas import power_retention as pr

    pools = _state_pools()
    if which == "step":
        n, rest = B_LANES, (_s((B_LANES,), "int32"), _s((), "int32"))
        fn = pr.power_retention_step
    else:
        n, rest = B_CHUNK, (_s((), "int32"), _s((), "int32"),
                            _s((), "bool"), _s((), "int32"))
        fn = pr.power_retention_chunk
    return (lambda *a: fn(*a, interpret=False),
            (_s((n, B_HQ, B_D), "float32"), _s((n, B_HK, B_D), "float32"),
             _s((n, B_HK, B_D), "float32"), _s((n, B_HK), "float32"),
             *pools, *rest))


@pytest.mark.parametrize("which", ["step", "chunk"])
def test_retention_kernel_compiles_for_v5e(which, one_chip,
                                           no_compile_cache):
    """The recurrent step (16 lanes) and the chunked form (1,024 tokens)
    at heads of 128, 40 query over 8 K/V heads, with ``jax_enable_x64``
    as the package sets it; each under its own name, so that the trace
    prints it and the roofline readers find it."""
    assert jax.config.jax_enable_x64
    fn, args = _retention_kernel(which)
    text = _compile(fn, args, one_chip, donate=(4, 5)).as_text()
    assert f"%power_retention_{which}" in text and "tpu_custom_call" in text


@pytest.fixture(scope="module")
def retention_lm():
    from mxnet_tpu import initializer as mx_init
    from mxnet_tpu.gluon.model_zoo import brumby

    net = brumby.brumby_like(
        vocab_size=B_V, units=B_UNITS, hidden_size=B_FFN, num_layers=2,
        num_heads=B_HQ, num_kv_heads=B_HK, head_dim=B_D,
        prefill_chunk=B_CHUNK, dtype="bfloat16")
    # nothing runs, so the values mean nothing: zeros, not 2.2 G random
    # draws on the CPU (two minutes)
    net.initialize(mx_init.Zero())
    return net


def _state_report(compiled, pools, label):
    """As ``_pool_report``, for the two state pools: the program's
    temporaries beside the pools' bytes, and every ``copy`` whose result
    is shaped like either pool."""
    pool_bytes = sum(int(onp.prod(p.shape)) * 4 for p in pools)
    temp = compiled.memory_analysis().temp_size_in_bytes
    shaped = [f" = f32[{','.join(map(str, p.shape))}]" for p in pools]
    copies = [line.strip()[:160] for line in compiled.as_text().splitlines()
              if " copy(" in line and any(s in line for s in shaped)]
    print(f"{label}: pools {pool_bytes / 2**20:.1f} MiB, temporaries "
          f"{temp / 2**20:.1f} MiB, pool-shaped copies {len(copies)}")
    return temp, pool_bytes, copies


def _state_pools():
    return (_s((2, B_SLOTS, B_HK, B_D, B_DP), "float32"),
            _s((2, B_SLOTS, B_HK, B_DP), "float32"))


def test_state_decode_program_compiles_for_v5e(retention_lm, one_chip,
                                               no_compile_cache, on_tpu):
    """The engine's one decode program over a model whose cache is a
    state — ``paged_decode_program`` as it stands, the block table one
    slot wide — at the cell's geometry (16 lanes, 17 slots), pools
    donated: the step kernel takes the pools aliased, so the program
    holds no copy shaped like a pool. Its temporaries are stated: the
    rows XLA lays out for the kernel (phi of 48 heads x 16 lanes, 34 MB a
    layer) and the float32 logits of 16 rows."""
    from mxnet_tpu.gluon.model_zoo.generation import paged_decode_program

    run, params = paged_decode_program(
        retention_lm, max_running=B_LANES, num_blocks=B_SLOTS, block_size=16,
        max_blocks_per_seq=1, kv_cache_dtype="float32", donate=True)
    pools = _state_pools()
    compiled = _compile(
        run._fn,
        (params, _s((B_LANES, 1), "int32"), *pools, _s((B_LANES, 1), "int32"),
         _s((B_LANES,), "int32"), _s((2,), "uint32")), one_chip,
        donate=(2, 3))
    assert compiled.as_text().count("%power_retention_step") >= 2
    temp, pool_bytes, copies = _state_report(compiled, pools, "state decode")
    assert not copies, copies
    assert temp < 0.15 * pool_bytes, (temp, pool_bytes)


def test_state_prefill_program_compiles_for_v5e(retention_lm, one_chip,
                                                no_compile_cache, on_tpu):
    """The one chunk-prefill program (1,024 tokens of one lane), pools
    donated and updated in place by the chunk kernel. Its temporaries are
    the chunk's activations (the FFN's two 1,024 x 17,408 float32
    intermediates are 136 MiB) and the logits of ONE row — the head never
    sees the chunk's other rows, which at 151,936 words would be 594 MiB
    of float32."""
    from mxnet_tpu.gluon.model_zoo.generation import state_prefill_program

    run, params = state_prefill_program(
        retention_lm, chunk=B_CHUNK, num_blocks=B_SLOTS, donate=True)
    pools = _state_pools()
    compiled = _compile(
        run._fn,
        (params, _s((1, B_CHUNK), "int32"), _s((), "int32"), _s((), "int32"),
         *pools, _s((), "int32"), _s((2,), "uint32")), one_chip,
        donate=(4, 5))
    assert compiled.as_text().count("%power_retention_chunk") >= 2
    temp, pool_bytes, copies = _state_report(compiled, pools,
                                             "state prefill 1024")
    assert not copies, copies
    assert temp < B_CHUNK * B_V * 4, temp      # no whole-chunk logits


# --- two families of cache in one program: the Qwen3-Next programs ---------
# Qwen3-Next-80B-A3B's published widths, one period of its layers (three
# delta-rule + one full), one chip's 128 of 512 experts, the cell's geometry
Q_LANES, Q_SLOTS, Q_CHUNK, Q_NB, Q_MB, Q_V = 64, 65, 2048, 40961, 1088, 37984


@pytest.mark.parametrize("heads,kv_heads,d,pool_dtype,q_dtype", [
    (16, 2, 256, "bfloat16", "float32"),
    (16, 2, 256, "float32", "float32"),
    (20, 20, 64, "bfloat16", "float32"),
])
def test_paged_kernel_grouped_heads_compiles_for_v5e(
        heads, kv_heads, d, pool_dtype, q_dtype, one_chip, no_compile_cache):
    """The paged kernel with grouped K/V heads — 16 query heads over 2
    K/V heads of 256, a pool row of 512 — beside GPT-2-large's 20 heads
    of 64 in the same call: the rows are whole multiples of 128 lanes in
    both, so both are copied by hand."""
    from mxnet_tpu.ops.pallas.paged_attention import paged_attention_kernel

    pool = _s((2, NB, BS, kv_heads * d), pool_dtype)
    fn = lambda q, k, v, bt, ln, layer: paged_attention_kernel(  # noqa: E731
        q, k, v, bt, ln, layer, interpret=False)
    text = _compile(fn, (_s((R, heads, d), q_dtype), pool, pool,
                         _s((R, MB), "int32"), _s((R,), "int32"),
                         _s((), "int32")), one_chip).as_text()
    assert "tpu_custom_call" in text


@pytest.fixture(scope="module")
def qwen3next_lm():
    from mxnet_tpu import initializer as mx_init
    from mxnet_tpu.gluon.model_zoo import qwen3next

    net = qwen3next.qwen3next_like(
        vocab_size=Q_V, num_layers=4, experts_held=128,
        prefill_chunk=Q_CHUNK, dtype="bfloat16")
    net.initialize(mx_init.Zero())
    return net


def _qwen_pools():
    rows = _s((1, Q_NB, BS, 512), "bfloat16")
    return (rows, rows, _s((3, Q_SLOTS, 32, 128, 128), "float32"),
            _s((3, Q_SLOTS, 3 * 8192), "float32"))


def _four_pool_report(compiled, pools, label):
    """As ``_pool_report``, for the tuple of four pools: the program's
    temporaries, the pools' bytes, and every ``copy`` whose result is
    shaped like any of them."""
    pool_bytes = sum(int(onp.prod(p.shape)) * p.dtype.itemsize for p in pools)
    temp = compiled.memory_analysis().temp_size_in_bytes
    shaped = {f" = {HLO_DTYPE[str(p.dtype)]}[{','.join(map(str, p.shape))}]"
              for p in pools}
    copies = [line.strip()[:160] for line in compiled.as_text().splitlines()
              if " copy(" in line and any(s in line for s in shaped)]
    print(f"{label}: pools {pool_bytes / 2**20:.1f} MiB, temporaries "
          f"{temp / 2**20:.1f} MiB, pool-shaped copies {len(copies)}")
    return temp, pool_bytes, copies


def test_two_family_decode_program_compiles_for_v5e(
        qwen3next_lm, one_chip, no_compile_cache, on_tpu):
    """The engine's one decode program over K/V rows in blocks and a
    state a lane — ``paged_decode_program`` as it stands, four pools
    where the pair stood — at the cell's geometry (64 lanes, 65 slots,
    40,960 blocks), all four donated: the delta-rule kernel takes its
    pool aliased, the K/V rows and the convolution's tails are scattered
    in place, and the program holds no copy shaped like a pool. The
    three kernels print under their names. Temporaries: the 640 sorted
    rows of each expert layer and the float32 logits of 64 rows."""
    from mxnet_tpu.gluon.model_zoo.generation import paged_decode_program

    run, params = paged_decode_program(
        qwen3next_lm, max_running=Q_LANES, num_blocks=Q_NB, block_size=BS,
        max_blocks_per_seq=Q_MB, kv_cache_dtype="bfloat16", donate=True)
    pools = _qwen_pools()
    compiled = _compile(
        run._fn,
        (params, _s((Q_LANES, 1), "int32"), *pools,
         _s((Q_LANES, Q_MB), "int32"), _s((Q_LANES,), "int32"),
         _s((2,), "uint32")), one_chip, donate=(2, 3, 4, 5))
    text = compiled.as_text()
    assert text.count("%gated_delta_step") >= 3
    assert text.count("%moe_grouped_ffn") >= 4
    temp, pool_bytes, copies = _four_pool_report(compiled, pools,
                                                 "two-family decode")
    assert not copies, copies
    assert temp < 0.1 * pool_bytes, (temp, pool_bytes)


def test_two_family_chunk_program_compiles_for_v5e(
        qwen3next_lm, one_chip, no_compile_cache, on_tpu):
    """The one chunk-prefill program (2,048 tokens of one lane): it
    writes the chunk's K/V rows through the lane's table and carries the
    state, all four pools donated and updated in place. No ``(heads,
    chunk, context)`` score array: the attention goes a block of 512 keys
    at a time under a loop (17,408 keys at once would be 2.3 GB; a
    block's scores are ``f32[2,16384,512]``, 64 MiB). Temporaries stated:
    the expert layer's 20,480 sorted rows in and out (2 x 80 MiB), a key
    block's scores and the attention's running rows, the delta rule's
    per-sub-chunk matrices, and the logits of ONE row."""
    from mxnet_tpu.gluon.model_zoo.generation import state_prefill_program

    run, params = state_prefill_program(
        qwen3next_lm, chunk=Q_CHUNK, num_blocks=Q_NB, block_size=BS,
        max_blocks_per_seq=Q_MB, kv_cache_dtype="bfloat16", donate=True)
    pools = _qwen_pools()
    compiled = _compile(
        run._fn,
        (params, _s((1, Q_CHUNK), "int32"), _s((), "int32"), _s((), "int32"),
         *pools, _s((), "int32"), _s((Q_MB,), "int32"), _s((2,), "uint32")),
        one_chip, donate=(4, 5, 6, 7))
    text = compiled.as_text()
    assert text.count("%moe_grouped_ffn") >= 4
    assert "flash" not in text      # the chunk's attention is the XLA loop
    assert f"f32[16,{Q_CHUNK},{Q_MB * BS}]" not in text
    assert f"f32[2,8,{Q_CHUNK},{Q_MB * BS}]" not in text
    temp, pool_bytes, copies = _four_pool_report(compiled, pools,
                                                 "two-family chunk 2048")
    assert not copies, copies
    assert temp < 1.5 * 2 ** 30, temp


# --- window layers that keep a ring beside full layers (PR 36) ---------------
# the cell laguna-serve-backlog-32k's geometry: 24 lanes (+ the trash slot),
# blocks of 16, a table of 2,112 blocks (33,792 positions), chunks of 1,024
G_LANES, G_SLOTS, G_CHUNK, G_NB, G_MB, G_V = 24, 25, 1024, 28673, 2112, 12544


@pytest.fixture(scope="module")
def laguna_lm():
    """The leading dense layer and one period (full, three window layers,
    and the next period's full layer) at the published widths, one chip's
    32 of 256 experts."""
    from mxnet_tpu import initializer as mx_init
    from mxnet_tpu.gluon.model_zoo import laguna

    net = laguna.laguna_like(vocab_size=G_V, num_layers=5, experts_held=32,
                             prefill_chunk=G_CHUNK, dtype="bfloat16")
    net.initialize(mx_init.Zero())
    return net


def _laguna_pools():
    rows = _s((2, G_NB, BS, 1024), "bfloat16")
    ring = _s((3, G_SLOTS, 512, 1024), "bfloat16")
    return rows, rows, ring, ring


def test_ring_decode_program_compiles_for_v5e(
        laguna_lm, one_chip, no_compile_cache, on_tpu):
    """The engine's one decode program over K/V rows in blocks and rings
    of 512 rows a lane, all four pools donated: one paged kernel takes 48
    query rows over a lane's table and 72 over its ring seen as 32 fixed
    blocks (the reshape of a ring is the same bytes: no copy shaped like
    a pool), rows of 1,024 values copied by hand. The expert kernel's
    blocks are 3 x 3,072 x 1,024."""
    from mxnet_tpu.gluon.model_zoo.generation import paged_decode_program

    run, params = paged_decode_program(
        laguna_lm, max_running=G_LANES, num_blocks=G_NB, block_size=BS,
        max_blocks_per_seq=G_MB, kv_cache_dtype="bfloat16", donate=True)
    pools = _laguna_pools()
    compiled = _compile(
        run._fn,
        (params, _s((G_LANES, 1), "int32"), *pools,
         _s((G_LANES, G_MB), "int32"), _s((G_LANES,), "int32"),
         _s((2,), "uint32")), one_chip, donate=(2, 3, 4, 5))
    text = compiled.as_text()
    assert text.count("%moe_grouped_ffn") >= 4
    assert f"bf16[{G_LANES},72,128]" in text and \
        f"bf16[{G_LANES},48,128]" in text
    temp, pool_bytes, copies = _four_pool_report(compiled, pools,
                                                 "ring decode")
    assert not copies, copies
    assert temp < 0.1 * pool_bytes, (temp, pool_bytes)


def test_ring_chunk_program_compiles_for_v5e(
        laguna_lm, one_chip, no_compile_cache, on_tpu):
    """The one chunk-prefill program (1,024 tokens of one lane): a full
    layer's rows through the table and its attention a block of 512 keys
    at a time; a window layer's attention over the ring's rows and its
    own, 256 queries against 768 keys at a time, never ``(72, 1024,
    1536)`` scores at once; all four pools donated and updated in place.
    Temporaries stated: the expert layer's 10,240 sorted rows in and out,
    a key block's scores, the dense layer's 1,024 x 12,288 rows."""
    from mxnet_tpu.gluon.model_zoo.generation import state_prefill_program

    run, params = state_prefill_program(
        laguna_lm, chunk=G_CHUNK, num_blocks=G_NB, block_size=BS,
        max_blocks_per_seq=G_MB, kv_cache_dtype="bfloat16", donate=True)
    pools = _laguna_pools()
    compiled = _compile(
        run._fn,
        (params, _s((1, G_CHUNK), "int32"), _s((), "int32"), _s((), "int32"),
         *pools, _s((), "int32"), _s((G_MB,), "int32"), _s((2,), "uint32")),
        one_chip, donate=(4, 5, 6, 7))
    text = compiled.as_text()
    assert text.count("%moe_grouped_ffn") >= 4
    assert "f32[8,9216,1536]" not in text and "f32[72,1024,1536]" not in text
    temp, pool_bytes, copies = _four_pool_report(compiled, pools,
                                                 "ring chunk 1024")
    assert not copies, copies
    assert temp < 1.5 * 2 ** 30, temp
