"""Fused softmax cross-entropy: the Pallas single-pass lse kernel
(mxnet_tpu/ops/pallas/cross_entropy.py), the reference-contract op
(src/operator/loss_binary_op.cc softmax_cross_entropy), and the gluon
loss fused path. The kernel itself runs in Pallas interpreter mode on
CPU so the suite exercises the same logic the TPU compiles."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, npx
from mxnet_tpu import numpy as np
from mxnet_tpu.ops.pallas.cross_entropy import (cross_entropy_with_logits,
                                                fused_lse)


def _oracle_nll(x, lab):
    lse = jax.scipy.special.logsumexp(x.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(
        x.astype(jnp.float32), jnp.clip(lab, 0, None)[:, None], -1)[:, 0]
    return jnp.where(lab >= 0, lse - picked, 0.0)


@pytest.mark.parametrize("n,v", [(7, 129), (64, 1000), (33, 4096)])
def test_fused_lse_matches_scipy(n, v):
    x = jnp.array(onp.random.randn(n, v).astype("float32") * 4)
    got = fused_lse(x, interpret=True)
    want = jax.scipy.special.logsumexp(x, axis=-1)
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                rtol=1e-5, atol=1e-5)


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


@pytest.mark.parametrize("n,v", [(100, 1000), (12, 129), (9, 131)])
def test_fused_lse_block_tile_alignment(n, v):
    """Block sizes must round to Mosaic tile multiples (8 rows × 128
    lanes): for 8<N<256 with N%8!=0 or 128<V<2048 with V%128!=0 the raw
    min() block was unaligned — a hard Mosaic reject on TPU (advisor
    finding). The rounding must also keep the result exact."""
    x = jnp.array(onp.random.randn(n, v).astype("float32") * 4)
    got = fused_lse(x, interpret=True)
    want = jax.scipy.special.logsumexp(x, axis=-1)
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                rtol=1e-5, atol=1e-5)


def test_fused_lse_chosen_blocks_are_tile_multiples():
    """White-box: bn % 8 == 0 and bv % 128 == 0 for unaligned inputs."""
    import jax.experimental.pallas as pl
    from unittest import mock

    from mxnet_tpu.ops.pallas import cross_entropy as ce

    seen = {}
    real_call = pl.pallas_call

    def spy(kernel, *a, **kw):
        spec = kw["in_specs"][0]
        seen["block"] = tuple(spec.block_shape)
        return real_call(kernel, *a, **kw)

    with mock.patch.object(pl, "pallas_call", side_effect=spy):
        ce.fused_lse(jnp.zeros((100, 1000)), interpret=True)
    bn, bv = seen["block"]
    assert bn % 8 == 0 and bv % 128 == 0, seen["block"]


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
