"""npx op tail: magic-code reshape, CTC loss (brute-force path oracle),
activation/special functions (reference src/operator parity)."""
import itertools

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd

npx = mx.npx


def test_reshape_magic_codes():
    """The reference matrix_op.cc Reshape doc examples."""
    assert npx.reshape(mx.np.zeros((2, 3, 4)), (6, 1, -1)).shape == (6, 1, 4)
    assert npx.reshape(mx.np.zeros((2, 3, 4)), (3, -1, 2)).shape == (3, 4, 2)
    assert npx.reshape(mx.np.zeros((2, 3, 4)), (-1,)).shape == (24,)
    # 0: copy dimension
    assert npx.reshape(mx.np.zeros((2, 3, 4)), (4, 0, 2)).shape == (4, 3, 2)
    # -2: copy all remaining
    assert npx.reshape(mx.np.zeros((2, 3, 4)), (-2,)).shape == (2, 3, 4)
    assert npx.reshape(mx.np.zeros((2, 3, 4)), (2, -2)).shape == (2, 3, 4)
    # -3: merge two consecutive dims
    assert npx.reshape(mx.np.zeros((2, 3, 4)), (-3, 4)).shape == (6, 4)
    assert npx.reshape(mx.np.zeros((2, 3, 4)), (0, -3)).shape == (2, 12)
    # -4: split a dim
    assert npx.reshape(mx.np.zeros((2, 3, 4)), (-4, 1, 2, -2)).shape \
        == (1, 2, 3, 4)
    assert npx.reshape(mx.np.zeros((2, 3, 4)), (2, -4, -1, 3, 4)).shape \
        == (2, 1, 3, 4)
    # reverse: codes applied right-to-left (reference doc example)
    assert npx.reshape(mx.np.zeros((10, 5, 4)), (-1, 0), reverse=True).shape \
        == (50, 4)
    assert npx.reshape(mx.np.zeros((10, 5, 4)), (-1, 0)).shape == (40, 5)


def test_activation_tail_oracles():
    x = onp.linspace(-3, 3, 13).astype(onp.float32)
    a = mx.np.array(x)
    sig = 1 / (1 + onp.exp(-x))
    onp.testing.assert_allclose(npx.silu(a).asnumpy(), x * sig, rtol=1e-5)
    onp.testing.assert_allclose(npx.swish(a).asnumpy(), x * sig, rtol=1e-5)
    sp = onp.log1p(onp.exp(-onp.abs(x))) + onp.maximum(x, 0)
    onp.testing.assert_allclose(npx.mish(a).asnumpy(), x * onp.tanh(sp),
                                rtol=1e-4, atol=1e-5)
    onp.testing.assert_allclose(npx.log_sigmoid(a).asnumpy(), onp.log(sig),
                                rtol=1e-4, atol=1e-5)
    onp.testing.assert_allclose(
        npx.hard_sigmoid(a).asnumpy(), onp.clip(0.2 * x + 0.5, 0, 1),
        rtol=1e-6)
    pos = onp.abs(x) + 0.5
    onp.testing.assert_allclose(npx.rsqrt(mx.np.array(pos)).asnumpy(),
                                1 / onp.sqrt(pos), rtol=1e-5)
    onp.testing.assert_allclose(npx.rcbrt(mx.np.array(pos)).asnumpy(),
                                1 / onp.cbrt(pos), rtol=1e-5)
    from scipy.special import digamma as ref_digamma

    onp.testing.assert_allclose(npx.digamma(mx.np.array(pos)).asnumpy(),
                                ref_digamma(pos), rtol=1e-4)


def test_smooth_l1_and_softmax_ce():
    x = onp.array([-2.0, -0.5, 0.0, 0.5, 2.0], onp.float32)
    out = npx.smooth_l1(mx.np.array(x), scalar=1.0).asnumpy()
    ref = onp.where(onp.abs(x) < 1, 0.5 * x * x, onp.abs(x) - 0.5)
    onp.testing.assert_allclose(out, ref, rtol=1e-6)

    logits = onp.random.RandomState(0).randn(4, 7).astype(onp.float32)
    labels = onp.array([1, 0, 6, 3], onp.float32)
    got = float(npx.softmax_cross_entropy(mx.np.array(logits),
                                          mx.np.array(labels)))
    e = onp.exp(logits - logits.max(-1, keepdims=True))
    logp = onp.log(e / e.sum(-1, keepdims=True))
    ref = -sum(logp[i, int(labels[i])] for i in range(4))
    onp.testing.assert_allclose(got, ref, rtol=1e-5)


def _ctc_bruteforce(logits, label):
    """Sum path probabilities over ALL alignments that collapse to label
    (blank=0). logits (T, C) for one sequence."""
    T, C = logits.shape
    e = onp.exp(logits - logits.max(-1, keepdims=True))
    p = e / e.sum(-1, keepdims=True)

    def collapse(path):
        out = []
        prev = None
        for s in path:
            if s != prev and s != 0:
                out.append(s)
            prev = s
        return tuple(out)

    total = 0.0
    for path in itertools.product(range(C), repeat=T):
        if collapse(path) == tuple(label):
            prob = 1.0
            for t, s in enumerate(path):
                prob *= p[t, s]
            total += prob
    return -onp.log(total)


def test_ctc_loss_matches_bruteforce():
    rng = onp.random.RandomState(1)
    T, B, C = 5, 2, 3
    data = rng.randn(T, B, C).astype(onp.float32)
    label = onp.array([[1, 2], [2, 1]], onp.int32)
    losses = npx.ctc_loss(mx.np.array(data), mx.np.array(label)).asnumpy()
    for i in range(B):
        ref = _ctc_bruteforce(data[:, i], label[i])
        onp.testing.assert_allclose(losses[i], ref, rtol=1e-4, atol=1e-4)


def test_ctc_loss_variable_lengths():
    rng = onp.random.RandomState(2)
    T, B, C = 6, 2, 4
    data = rng.randn(T, B, C).astype(onp.float32)
    label = onp.array([[1, 2], [3, 0]], onp.int32)  # row 1 has length 1
    losses = npx.ctc_loss(
        mx.np.array(data), mx.np.array(label),
        data_lengths=mx.np.array(onp.array([4, 6], onp.int32)),
        label_lengths=mx.np.array(onp.array([2, 1], onp.int32))).asnumpy()
    ref0 = _ctc_bruteforce(data[:4, 0], [1, 2])
    ref1 = _ctc_bruteforce(data[:6, 1], [3])
    onp.testing.assert_allclose(losses[0], ref0, rtol=1e-4, atol=1e-4)
    onp.testing.assert_allclose(losses[1], ref1, rtol=1e-4, atol=1e-4)


def test_ctc_loss_is_differentiable():
    rng = onp.random.RandomState(3)
    data = mx.np.array(rng.randn(4, 1, 3).astype(onp.float32))
    label = mx.np.array(onp.array([[1, 2]], onp.int32))
    data.attach_grad()
    with autograd.record():
        loss = npx.ctc_loss(data, label).sum()
    loss.backward()
    g = data.grad.asnumpy()
    assert onp.isfinite(g).all() and onp.abs(g).sum() > 0


def test_ctc_loss_empty_target():
    """label_length 0: loss is the all-blank path only (review-found
    negative-index wraparound)."""
    rng = onp.random.RandomState(4)
    T, C = 3, 3
    data = rng.randn(T, 1, C).astype(onp.float32)
    loss = npx.ctc_loss(
        mx.np.array(data), mx.np.array(onp.array([[1, 2]], onp.int32)),
        label_lengths=mx.np.array(onp.array([0], onp.int32))).asnumpy()
    e = onp.exp(data[:, 0] - data[:, 0].max(-1, keepdims=True))
    p = e / e.sum(-1, keepdims=True)
    ref = -onp.log(onp.prod(p[:, 0]))  # all-blank path
    onp.testing.assert_allclose(loss[0], ref, rtol=1e-4)


def test_gluon_ctc_loss_matches_bruteforce():
    """gluon.loss.CTCLoss (NTC layout) against the same path oracle,
    including the empty-target guard."""
    from mxnet_tpu import gluon

    rng = onp.random.RandomState(5)
    pred = rng.randn(2, 4, 3).astype(onp.float32)  # (N, T, C)
    label = onp.array([[1, 2], [2, 0]], onp.int32)
    loss_fn = gluon.loss.CTCLoss()
    out = loss_fn(mx.np.array(pred), mx.np.array(label),
                  None, mx.np.array(onp.array([2, 1], onp.int32))).asnumpy()
    ref0 = _ctc_bruteforce(pred[0], [1, 2])
    ref1 = _ctc_bruteforce(pred[1], [2])
    onp.testing.assert_allclose(out[0], ref0, rtol=1e-4, atol=1e-4)
    onp.testing.assert_allclose(out[1], ref1, rtol=1e-4, atol=1e-4)
    # empty target: all-blank path NLL exactly once
    out0 = loss_fn(mx.np.array(pred[:1]), mx.np.array(label[:1]),
                   None, mx.np.array(onp.array([0], onp.int32))).asnumpy()
    e = onp.exp(pred[0] - pred[0].max(-1, keepdims=True))
    p = e / e.sum(-1, keepdims=True)
    onp.testing.assert_allclose(out0[0], -onp.log(onp.prod(p[:, 0])),
                                rtol=1e-4)


# ---- round-3 surface-diff tail: npx samplers, dlpack, nonzero,
# constraint_check, ReflectionPad2D, Append/AsList, HybridCompose ----

def test_npx_bernoulli_prob_logit():
    mx.npx.seed(3)
    b = mx.npx.bernoulli(prob=0.25, size=(4000,))
    assert 0.2 < float(b.asnumpy().mean()) < 0.3
    bl = mx.npx.bernoulli(logit=mx.np.array([-20.0, 20.0]))
    assert bl.asnumpy().tolist() == [0.0, 1.0]
    with pytest.raises(mx.MXNetError):
        mx.npx.bernoulli(prob=0.5, logit=0.0)
    with pytest.raises(mx.MXNetError):
        mx.npx.bernoulli()


def test_npx_sampler_n_batch_shape():
    u = mx.npx.uniform_n(low=mx.np.array([0.0, 100.0]),
                         high=mx.np.array([1.0, 101.0]), batch_shape=(3,))
    assert u.shape == (3, 2)
    vals = u.asnumpy()
    assert (vals[:, 0] < 2).all() and (vals[:, 1] > 99).all()
    n = mx.npx.normal_n(loc=0.0, scale=1e-6, batch_shape=(4, 2))
    assert n.shape == (4, 2) and abs(float(n.asnumpy().mean())) < 1e-3
    # no batch_shape -> broadcast shape alone
    assert mx.npx.normal_n(loc=mx.np.zeros((5,))).shape == (5,)


def test_npx_nonzero_and_constraint_check():
    nz = mx.npx.nonzero(mx.np.array([[1, 0], [0, 3]]))
    assert nz.asnumpy().tolist() == [[0, 0], [1, 1]]
    assert str(nz.dtype) == "int64"
    ok = mx.npx.constraint_check(mx.np.array([True, True]), "nope")
    assert bool(ok.asnumpy())
    with pytest.raises(mx.MXNetError, match="sigma must be positive"):
        mx.npx.constraint_check(mx.np.array([True, False]),
                                "sigma must be positive")


def test_dlpack_torch_roundtrip():
    torch = pytest.importorskip("torch")
    t = torch.arange(6, dtype=torch.float32).reshape(2, 3)
    a = mx.npx.from_dlpack(t)
    assert a.shape == (2, 3)
    onp.testing.assert_allclose(a.asnumpy(), t.numpy())
    cap = mx.npx.to_dlpack_for_read(mx.np.array([1.0, 2.0]))
    back = torch.utils.dlpack.from_dlpack(cap)
    onp.testing.assert_allclose(back.numpy(), [1.0, 2.0])
    # write variant exists and matches (immutability documented)
    cap2 = mx.npx.to_dlpack_for_write(mx.np.array([3.0]))
    assert float(torch.utils.dlpack.from_dlpack(cap2)[0]) == 3.0


def test_reflection_pad2d_torch_oracle():
    torch = pytest.importorskip("torch")
    x = onp.random.rand(2, 3, 5, 5).astype("float32")
    out = mx.gluon.nn.ReflectionPad2D(2)(mx.np.array(x))
    ref = torch.nn.ReflectionPad2d(2)(torch.tensor(x)).numpy()
    onp.testing.assert_allclose(out.asnumpy(), ref)
    assert mx.gluon.nn.ReflectionPad2D(0)(mx.np.array(x)).shape == x.shape


def test_batchify_append_aslist():
    from mxnet_tpu.gluon.data import batchify
    out = batchify.Append()([[1, 2, 3, 4], [4, 5, 6], [8, 2]])
    assert [o.shape for o in out] == [(1, 4), (1, 3), (1, 2)]
    flat = batchify.Append(expand=False)([[1, 2]])
    assert flat[0].shape == (2,)
    g = batchify.Group(batchify.Stack(), batchify.AsList())
    data, texts = g([([1, 2], "a"), ([3, 4], "b")])
    assert data.shape == (2, 2) and texts == ["a", "b"]


def test_hybrid_compose_traces():
    from mxnet_tpu.gluon.data.vision import transforms as T
    img = onp.random.randint(0, 255, (16, 16, 3)).astype("uint8")
    stages = [T.ToTensor(), T.Normalize([0.5] * 3, [0.2] * 3),
              T.Cast("float32")]
    hc = T.HybridCompose(stages)
    want = T.Compose(stages)(img)
    got_eager = hc(mx.np.array(img))
    hc.hybridize()
    got_jit = hc(mx.np.array(img))
    onp.testing.assert_allclose(got_eager.asnumpy(), onp.asarray(want),
                                atol=1e-6)
    onp.testing.assert_allclose(got_jit.asnumpy(), onp.asarray(want),
                                atol=1e-6)
