"""Softmax cross-entropy: the reference-contract op
(src/operator/loss_binary_op.cc softmax_cross_entropy) as one
``custom_vjp`` of two jitted programs (mxnet_tpu/ops/nn.py: the row
logsumexp and the label's logit forward, ``softmax - onehot`` backward),
and the gluon loss on top of it. Until PR 32 the forward's row reduction
was a Pallas kernel (``fused_lse``); its tests stay, as tests of the
programs that took its place."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, npx
from mxnet_tpu import numpy as np
from mxnet_tpu.ops import nn as ops_nn


def cross_entropy_with_logits(x, lab):
    """The per-row loss, as the gluon loss asks for it."""
    return ops_nn._softmax_ce(x, lab, True)


def fused_lse(x):
    """The forward program's second result: the row lse it saves for the
    pullback, float32 whatever the logits are."""
    return ops_nn._ce_forward(x, jnp.zeros(x.shape[:1], jnp.int32), True)[1]


def _primitives(jaxpr):
    for e in jaxpr.eqns:
        yield e.primitive.name
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _primitives(sub)


def _oracle_nll(x, lab):
    lse = jax.scipy.special.logsumexp(x.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(
        x.astype(jnp.float32), jnp.clip(lab, 0, None)[:, None], -1)[:, 0]
    return jnp.where(lab >= 0, lse - picked, 0.0)


def _lse64(x):
    x = onp.asarray(x.astype(jnp.float32)).astype("float64")
    m = x.max(-1, keepdims=True)
    return (m + onp.log(onp.exp(x - m).sum(-1, keepdims=True)))[:, 0]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n,v", [(7, 129), (64, 1000), (33, 4096),
                                 (100, 1000), (100, 50257)])
def test_fused_lse_matches_scipy(n, v, dtype):
    """The saved lse against float64, at shapes no tile divides too
    (N = 100, V = 50,257 and 1,000)."""
    x = jnp.array(onp.random.randn(n, v).astype("float32") * 4).astype(dtype)
    got = fused_lse(x)
    assert got.shape == (n,) and got.dtype == jnp.float32
    onp.testing.assert_allclose(onp.asarray(got), _lse64(x),
                                rtol=1e-5, atol=1e-5)


def test_fused_lse_pads_nothing():
    """Neither program pads or gathers the logits, and no Pallas call is
    left in them: elementwise passes and row reductions only, which XLA
    runs on the array as the chip lays it out."""
    x, lab = jnp.zeros((100, 1000)), jnp.zeros((100,), jnp.int32)
    fwd = set(_primitives(jax.make_jaxpr(
        lambda a, b: ops_nn._ce_forward(a, b, False))(x, lab).jaxpr))
    bwd = set(_primitives(jax.make_jaxpr(ops_nn._ce_backward)(
        x, lab, jnp.zeros((100,)), jnp.ones((1,))).jaxpr))
    for found in (fwd, bwd):
        assert not found & {"pad", "gather", "pallas_call", "transpose",
                            "copy"}, found
    assert "reduce_max" in fwd and "exp" in fwd and "exp" in bwd


def test_kernel_forward_backward_oracle():
    n, v = 45, 777
    x = jnp.array(onp.random.randn(n, v).astype("float32") * 3)
    lab = jnp.array(onp.random.randint(0, v, (n,)).astype("int32"))
    lab = lab.at[3].set(-1)  # ignore-index row
    got = cross_entropy_with_logits(x, lab)
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(_oracle_nll(x, lab)),
                                rtol=1e-5, atol=1e-5)
    g = jax.grad(lambda z: cross_entropy_with_logits(z, lab).sum())(x)
    gr = jax.grad(lambda z: _oracle_nll(z, lab).sum())(x)
    onp.testing.assert_allclose(onp.asarray(g), onp.asarray(gr),
                                rtol=1e-4, atol=1e-5)
    # ignored row gets zero gradient
    assert float(jnp.abs(g[3]).max()) == 0.0


def test_kernel_bf16():
    n, v = 16, 512
    x32 = onp.random.randn(n, v).astype("float32")
    x = jnp.array(x32).astype(jnp.bfloat16)
    lab = jnp.array(onp.random.randint(0, v, (n,)).astype("int32"))
    got = cross_entropy_with_logits(x, lab)
    want = _oracle_nll(jnp.array(x32).astype(jnp.bfloat16), lab)
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                rtol=2e-2, atol=2e-2)


def test_npx_op_reference_contract():
    """shape-(1,) sum with the 1e-8 clamp, loss_binary_op-inl.h:44-57."""
    n, v = 12, 50
    data = np.array(onp.random.randn(n, v).astype("float32"))
    label = np.array(onp.random.randint(0, v, (n,)).astype("float32"))
    out = npx.softmax_cross_entropy(data, label)
    assert out.shape == (1,)
    logits = onp.asarray(data)
    lse = onp.log(onp.exp(logits).sum(-1))
    nll = lse - logits[onp.arange(n), onp.asarray(label).astype(int)]
    onp.testing.assert_allclose(onp.asarray(out)[0], nll.sum(), rtol=1e-4)
    # clamp: a certain-wrong row contributes at most -log(1e-8)
    data2 = np.array(onp.full((1, 3), 0.0, "float32"))
    data2[0, 0] = 200.0
    out2 = npx.softmax_cross_entropy(data2, np.array([2.0]))
    onp.testing.assert_allclose(onp.asarray(out2)[0], -onp.log(1e-8),
                                rtol=1e-5)


def test_npx_op_autograd():
    n, v = 9, 21
    data = np.array(onp.random.randn(n, v).astype("float32"))
    label = np.array(onp.random.randint(0, v, (n,)).astype("int32"))
    data.attach_grad()
    with autograd.record():
        loss = npx.softmax_cross_entropy(data, label, per_example=True).sum()
    loss.backward()
    x = jnp.array(onp.asarray(data))
    lab = jnp.array(onp.asarray(label))
    want = jax.grad(lambda z: _oracle_nll(z, lab).sum())(x)
    onp.testing.assert_allclose(onp.asarray(data.grad), onp.asarray(want),
                                rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("shape", [(8, 10), (4, 6, 10)])
def test_gluon_loss_fused_path_parity(shape):
    """The fused sparse path must equal the log_softmax+pick path."""
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss

    pred = np.array(onp.random.randn(*shape).astype("float32"))
    label = np.array(onp.random.randint(0, shape[-1], shape[:-1]).astype("float32"))
    fused = SoftmaxCrossEntropyLoss()(pred, label)
    manual = -npx.pick(npx.log_softmax(pred, axis=-1), label, axis=-1)
    if manual.ndim > 1:
        manual = np.mean(manual, axis=tuple(range(1, manual.ndim)))
    onp.testing.assert_allclose(onp.asarray(fused), onp.asarray(manual),
                                rtol=1e-5, atol=1e-6)


def test_gluon_loss_fused_path_grad_and_weighting():
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss

    n, v = 6, 11
    pred = np.array(onp.random.randn(n, v).astype("float32"))
    label = np.array(onp.random.randint(0, v, (n,)).astype("float32"))
    sw = np.array(onp.random.rand(n).astype("float32"))
    pred.attach_grad()
    with autograd.record():
        loss = SoftmaxCrossEntropyLoss(weight=0.5)(pred, label, sw).sum()
    loss.backward()
    x = jnp.array(onp.asarray(pred))
    lab = jnp.array(onp.asarray(label)).astype(jnp.int32)
    w = jnp.array(onp.asarray(sw)) * 0.5

    def ref(z):
        return (_oracle_nll(z, lab) * w).sum()

    onp.testing.assert_allclose(onp.asarray(pred.grad),
                                onp.asarray(jax.grad(ref)(x)),
                                rtol=1e-4, atol=1e-5)


def test_gluon_loss_nonlast_axis_still_works():
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss

    pred = np.array(onp.random.randn(5, 7, 3).astype("float32"))
    label = np.array(onp.random.randint(0, 7, (5, 3)).astype("float32"))
    got = SoftmaxCrossEntropyLoss(axis=1)(pred, label)
    manual = -npx.pick(npx.log_softmax(pred, axis=1), label, axis=1)
    manual = np.mean(manual, axis=tuple(range(1, manual.ndim)))
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(manual),
                                rtol=1e-5, atol=1e-6)


def test_hybridized_block_with_fused_loss():
    """The fused op must be trace-transparent (jit inside hybridize)."""
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn

    net = nn.Dense(13)
    net.initialize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    x = np.array(onp.random.randn(4, 8).astype("float32"))
    y = np.array(onp.random.randint(0, 13, (4,)).astype("float32"))
    eager = loss_fn(net(x), y)
    net.hybridize()
    traced = loss_fn(net(x), y)
    onp.testing.assert_allclose(onp.asarray(eager), onp.asarray(traced),
                                rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n,v", [(100, 1000), (12, 129), (9, 131),
                                 (300, 2049), (257, 128)])
def test_fused_lse_block_tile_alignment(n, v):
    """Shapes off the (8, 128) tiles: for 8<N<256 with N%8!=0 or
    128<V<2048 with V%128!=0 the kernel's raw min() block was unaligned
    (advisor finding). The programs that replaced it have no blocks; the
    shapes stay as cases, and the result must be exact at each."""
    x = jnp.array(onp.random.randn(n, v).astype("float32") * 4)
    got = fused_lse(x)
    want = jax.scipy.special.logsumexp(x, axis=-1)
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                rtol=1e-5, atol=1e-5)


def test_fused_lse_chosen_blocks_are_tile_multiples(monkeypatch):
    """White-box: on the TPU backend too the op chooses no block, because
    it calls no kernel: the same two programs whatever the backend, the
    mesh or ``no_pallas`` say."""
    def programs():
        return str(jax.make_jaxpr(jax.value_and_grad(
            lambda z: ops_nn.softmax_cross_entropy(
                z, jnp.zeros((100,), jnp.int32))[0]))(jnp.zeros((100, 1000))))

    here = programs()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops_nn._tpu_kernels_selected()
    assert programs() == here and "pallas_call" not in here
    with ops_nn.no_pallas():
        assert programs() == here


def test_sum_mode_clamp_is_value_only():
    """Reference backward (loss_binary_op-inl.h:85-106) is softmax-onehot
    unconditionally: the 1e-8 forward floor must NOT zero dlogits on
    confidently-wrong rows (advisor finding — those rows need gradient
    the most)."""
    v = 5
    data = np.array(onp.zeros((1, v), "float32"))
    data[0, 0] = 200.0  # confidently wrong: NLL ≈ 200 >> -log(1e-8)
    label = np.array([2.0])
    data.attach_grad()
    with autograd.record():
        out = npx.softmax_cross_entropy(data, label)
    out.backward()
    g = onp.asarray(data.grad)
    # softmax-onehot: ~ +1 at the argmax, -1 at the true label
    assert g[0, 0] > 0.9 and g[0, 2] < -0.9, g
    # forward still clamped
    onp.testing.assert_allclose(onp.asarray(out)[0], -onp.log(1e-8),
                                rtol=1e-5)


def test_gluon_fused_loss_preserves_pred_dtype():
    """bf16 pred → bf16 loss, as the old log_softmax+pick path returned
    (advisor finding: user-visible dtype change in AMP loops)."""
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss

    pred = np.array(onp.random.randn(4, 9).astype("float32")).astype("bfloat16")
    label = np.array(onp.random.randint(0, 9, (4,)).astype("float32"))
    out = SoftmaxCrossEntropyLoss()(pred, label)
    assert str(out.dtype) == "bfloat16"


def test_sum_mode_clamp_handles_masked_label_inf_nll():
    """A label landing on a -inf (masked) logit makes nll=+inf — exactly
    the p=0 case the 1e-8 floor exists for. The value-only clamp must
    return the finite cap, not NaN (review finding: a straight-through
    `nll + sg(min-nll)` form evaluates inf-inf=NaN)."""
    data = np.array(onp.zeros((2, 4), "float32"))
    data[0, 1] = -onp.inf  # masked vocab entry
    label = np.array([1.0, 2.0])  # row 0's label IS the masked entry
    out = npx.softmax_cross_entropy(data, label)
    val = float(onp.asarray(out)[0])
    assert onp.isfinite(val), val
    # row0 contributes the cap, row1 the ordinary NLL over its 4 classes
    expect = -onp.log(1e-8) + onp.log(4.0)
    onp.testing.assert_allclose(val, expect, rtol=1e-5)


def _oracle64(x, lab, per_example, g):
    """Value and dlogits in float64 numpy: nothing of the op's code."""
    lse = _lse64(x)
    x = onp.asarray(x.astype(jnp.float32)).astype("float64")
    lab = onp.asarray(lab)
    keep = lab >= 0
    rows = onp.arange(len(lab))
    nll = onp.where(keep, lse - x[rows, onp.clip(lab, 0, None)], 0.0)
    onehot = onp.zeros_like(x)
    onehot[rows[keep], lab[keep]] = 1.0
    gr = onp.where(keep, onp.broadcast_to(onp.asarray(g, "float64"),
                                          lab.shape), 0.0)
    dx = (onp.exp(x - lse[:, None]) - onehot) * gr[:, None]
    if per_example:
        return nll, dx
    return onp.minimum(nll, -onp.log(1e-8)).sum(keepdims=True), dx


@pytest.mark.parametrize("per_example", [False, True], ids=["sum", "rows"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n,v", [(100, 1000), (100, 50257)])
def test_ragged_value_and_grad_float64_oracle(n, v, dtype, per_example):
    """Both return forms at shapes no tile divides, with ignored rows:
    value and gradient against float64, the gradient in the logits'
    dtype."""
    rng = onp.random.RandomState(n + v)
    x = jnp.array(rng.randn(n, v).astype("float32") * 3).astype(dtype)
    lab = rng.randint(0, v, (n,)).astype("int32")
    lab[[3, n - 1]] = -1
    lab[5] = v - 1
    lab = jnp.array(lab)
    g = (rng.rand(n).astype("float32") + 0.5 if per_example
         else onp.array([1.5], "float32"))
    out, pull = jax.vjp(
        lambda z: ops_nn._softmax_ce(z, lab, per_example), x)
    (dx,) = pull(jnp.array(g).astype(out.dtype))
    want, want_dx = _oracle64(x, lab, per_example, g)
    assert out.dtype == (jnp.float32 if per_example else x.dtype)
    assert out.shape == ((n,) if per_example else (1,))
    assert dx.dtype == x.dtype and dx.shape == x.shape
    # bf16: the sum is rounded once on the way out (8 bits), and dlogits
    # likewise; the row losses stay float32 whatever the logits are
    rtol = 1e-5 if dtype == "float32" or per_example else 1e-2
    onp.testing.assert_allclose(onp.asarray(out.astype(jnp.float32)), want,
                                rtol=rtol, atol=1e-4)
    onp.testing.assert_allclose(
        onp.asarray(dx.astype(jnp.float32)), want_dx,
        rtol=1e-4 if dtype == "float32" else 1e-2, atol=1e-6)
    assert float(jnp.abs(dx[3].astype(jnp.float32)).max()) == 0.0


class _Compiles:
    """Backend compiles by jax's own monitoring events, as
    ``chipbench.harness.Compiles`` counts them."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


@pytest.fixture(scope="module")
def compiles():
    from jax._src import monitoring

    counter = _Compiles()
    yield counter
    monitoring.unregister_event_duration_listener(counter._on)


@pytest.mark.parametrize("per_example", [False, True], ids=["sum", "rows"])
def test_eager_op_is_two_programs(per_example, compiles):
    """``apply_op`` jits nothing, so what the eager op launches under
    ``record()`` and ``backward()`` is what its two rules are: one
    program forward and one backward that touch (N, V). Programs whose
    shapes hold no V (the head gradient, the label's cast) are warm from
    a first pass at another V; a repeat at the same shapes compiles
    nothing."""
    n = 24

    def step(v):
        data = np.array(onp.random.randn(n, v).astype("float32"))
        label = np.array(onp.random.randint(0, v, (n,)).astype("int32"))
        data.attach_grad()
        c0 = compiles.n
        with autograd.record():
            loss = npx.softmax_cross_entropy(data, label,
                                             per_example=per_example)
        loss.wait_to_read()
        c1 = compiles.n
        loss.backward()
        data.grad.wait_to_read()
        return c1 - c0, compiles.n - c1, data.grad

    step(301)
    fwd, bwd, grad = step(523)
    assert 1 <= fwd <= 2 and bwd == 1, (fwd, bwd)
    assert grad.shape == (n, 523) and str(grad.dtype) == "float32"
    assert step(523)[:2] == (0, 0)


def test_rules_are_the_two_jitted_programs():
    """The forward rule calls the forward program once and the pullback
    the backward program once; the residuals are the caller's logits and
    labels and the row lse, nothing else shaped (N, V)."""
    from unittest import mock

    x = jnp.array(onp.random.randn(10, 37).astype("float32"))
    lab = jnp.arange(10, dtype=jnp.int32)
    with mock.patch.object(ops_nn, "_ce_forward",
                           wraps=ops_nn._ce_forward) as fwd, \
            mock.patch.object(ops_nn, "_ce_backward",
                              wraps=ops_nn._ce_backward) as bwd:
        out, pull = jax.vjp(
            lambda z: ops_nn.softmax_cross_entropy(z, lab), x)
        assert (fwd.call_count, bwd.call_count) == (1, 0)
        pull(jnp.ones_like(out))
        assert (fwd.call_count, bwd.call_count) == (1, 1)
    res = jax.tree_util.tree_leaves(pull)
    assert sum(r.shape == x.shape for r in res if hasattr(r, "shape")) == 1
    assert all(isinstance(f, type(jax.jit(lambda: 0)))
               for f in (ops_nn._ce_forward, ops_nn._ce_backward))


def test_hybridized_loss_inlines_the_two_programs():
    """Inside an outer ``jit`` (a hybridized block, a functionalized step)
    the two inner programs are part of the caller's: value and gradient
    agree with the eager op's, and both are traced under the one call."""
    x = jnp.array(onp.random.randn(12, 65).astype("float32"))
    lab = jnp.array(onp.random.randint(-1, 65, (12,)).astype("int32"))

    def loss(z):
        return ops_nn.softmax_cross_entropy(z, lab)[0]

    eager = jax.value_and_grad(loss)(x)
    traced = jax.jit(jax.value_and_grad(loss))(x)
    for a, b in zip(eager, traced):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=1e-6, atol=1e-7)
    text = str(jax.make_jaxpr(jax.value_and_grad(loss))(x))
    assert "_ce_forward" in text and "_ce_backward" in text
