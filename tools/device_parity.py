#!/usr/bin/env python
"""Device-parity sweep: run a curated op set on the CURRENT backend and
check every result against a host-side numpy oracle.

This is the reference's ``check_consistency`` pattern
(``python/mxnet/test_utils.py:1428``: same symbol across devices,
outputs cross-checked) turned into a bankable artifact: the CI suite
proves correctness on the 8-virtual-device CPU mesh; this proves the
same ops are CORRECT ON REAL TPU SILICON — latency tables can't show
that. ``benchmark/results_parity_tpu.json`` is a result of it that
predates PR 1.

CLI:
    python tools/device_parity.py [--output out.json] [--cpu]
Exit code 0 iff every check passes.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as onp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# NumPy-tight oracle comparisons need exact fp32 matmuls; the package
# default is the one-pass MXU precision (docs/precision.md), so this
# harness opts in to the 6-pass emulation explicitly.
os.environ.setdefault("MXNET_MATMUL_PRECISION", "highest")


def _cases():
    """(name, mx_fn(mx) -> array, oracle() -> np array, rtol, atol)."""
    rng = onp.random.RandomState(0)
    A = rng.uniform(-1, 1, (32, 48)).astype(onp.float32)
    B = rng.uniform(-1, 1, (48, 16)).astype(onp.float32)
    P = rng.uniform(0.1, 0.9, (32, 48)).astype(onp.float32)
    X4 = rng.uniform(-1, 1, (4, 8, 10, 10)).astype(onp.float32)
    W4 = rng.uniform(-0.3, 0.3, (16, 8, 3, 3)).astype(onp.float32)
    V = rng.uniform(-2, 2, (256,)).astype(onp.float32)
    IDX = rng.randint(0, 32, (10,)).astype(onp.int32)
    S = rng.randn(16, 16).astype(onp.float32)
    PD = (S @ S.T + 16 * onp.eye(16)).astype(onp.float32)

    import scipy.signal as sps

    def conv_oracle():
        out = onp.zeros((4, 16, 10, 10), onp.float32)
        xp = onp.pad(X4, ((0, 0), (0, 0), (1, 1), (1, 1)))
        for n in range(4):
            for o in range(16):
                acc = onp.zeros((10, 10), onp.float64)
                for c in range(8):
                    acc += sps.correlate2d(xp[n, c], W4[o, c], mode="valid")
                out[n, o] = acc
        return out

    def softmax_oracle(x, axis=-1):
        e = onp.exp(x - x.max(axis=axis, keepdims=True))
        return e / e.sum(axis=axis, keepdims=True)

    cases = [
        ("add", lambda mx: mx.np.array(A) + mx.np.array(A),
         lambda: A + A, 1e-6, 1e-6),
        ("matmul", lambda mx: mx.np.dot(mx.np.array(A), mx.np.array(B)),
         lambda: A @ B, 1e-5, 1e-5),
        ("einsum", lambda mx: mx.np.einsum(
            "ij,jk->ik", mx.np.array(A), mx.np.array(B)),
         lambda: onp.einsum("ij,jk->ik", A, B), 1e-5, 1e-5),
        # transcendentals: the TPU evaluates exp/log/tanh with polynomial
        # approximations that are NOT IEEE-correctly-rounded like numpy's
        # libm (measured on v5e: exp∘log roundtrip 9.9e-5 abs, tanh
        # 1.9e-5 abs). Gates sit ~5x above the measured error — loose
        # enough for the hardware's documented accuracy class, tight
        # enough that a wrong-formula bug (>1e-3) still fails.
        ("exp_log", lambda mx: mx.np.log(mx.np.exp(mx.np.array(A))),
         lambda: A, 1e-3, 5e-4),
        ("tanh", lambda mx: mx.np.tanh(mx.np.array(A)),
         lambda: onp.tanh(A), 1e-4, 1e-4),
        ("erf", lambda mx: mx.npx.erf(mx.np.array(A)),
         lambda: __import__("scipy.special", fromlist=["erf"]).erf(A),
         1e-5, 1e-6),
        ("sum_axis", lambda mx: mx.np.sum(mx.np.array(A), axis=0),
         lambda: A.sum(axis=0), 1e-5, 1e-5),
        ("mean", lambda mx: mx.np.mean(mx.np.array(A)),
         lambda: A.mean(), 1e-6, 1e-6),
        ("var", lambda mx: mx.np.var(mx.np.array(A), axis=1),
         lambda: A.var(axis=1), 1e-5, 1e-6),
        ("cumsum", lambda mx: mx.np.cumsum(mx.np.array(V)),
         lambda: onp.cumsum(V), 1e-4, 1e-4),
        ("sort", lambda mx: mx.np.sort(mx.np.array(V)),
         lambda: onp.sort(V), 0, 0),
        ("argsort", lambda mx: mx.np.argsort(mx.np.array(V)),
         lambda: onp.argsort(V), 0, 0),
        ("take", lambda mx: mx.np.take(mx.np.array(A), mx.np.array(IDX),
                                       axis=0),
         lambda: onp.take(A, IDX, axis=0), 1e-6, 1e-6),
        ("softmax", lambda mx: mx.npx.softmax(mx.np.array(A), axis=-1),
         lambda: softmax_oracle(A), 1e-5, 1e-6),
        ("log_softmax", lambda mx: mx.npx.log_softmax(
            mx.np.array(A), axis=-1),
         lambda: onp.log(softmax_oracle(A)), 1e-4, 1e-5),
        ("layer_norm", lambda mx: mx.npx.layer_norm(
            mx.np.array(A), mx.np.ones((48,)), mx.np.zeros((48,))),
         lambda: (A - A.mean(-1, keepdims=True))
         / onp.sqrt(A.var(-1, keepdims=True) + 1e-5), 1e-4, 1e-4),
        ("convolution", lambda mx: mx.npx.convolution(
            mx.np.array(X4), mx.np.array(W4), num_filter=16, pad=1,
            no_bias=True),
         conv_oracle, 1e-4, 1e-4),
        ("pooling_max", lambda mx: mx.npx.pooling(
            mx.np.array(X4), kernel=(2, 2), pool_type="max",
            stride=(2, 2)),
         lambda: X4.reshape(4, 8, 5, 2, 5, 2).max(axis=(3, 5)),
         1e-6, 1e-6),
        ("batch_norm_eval", lambda mx: mx.npx.batch_norm(
            mx.np.array(X4), mx.np.ones((8,)), mx.np.zeros((8,)),
            mx.np.zeros((8,)), mx.np.ones((8,))),
         lambda: X4, 1e-4, 1e-4),
        ("cholesky", lambda mx: mx.np.linalg.cholesky(mx.np.array(PD)),
         lambda: onp.linalg.cholesky(PD), 1e-4, 1e-4),
        ("svd_singular_values", lambda mx: mx.np.linalg.svd(
            mx.np.array(S))[1],
         lambda: onp.linalg.svd(S)[1], 1e-4, 1e-4),
        ("solve", lambda mx: mx.np.linalg.solve(
            mx.np.array(PD), mx.np.array(S)),
         lambda: onp.linalg.solve(PD, S), 1e-3, 1e-3),
        ("rfft_mag", lambda mx: mx.np.abs(mx.np.fft.rfft(mx.np.array(V))),
         lambda: onp.abs(onp.fft.rfft(V)), 1e-3, 1e-3),
        ("sigmoid", lambda mx: mx.npx.sigmoid(mx.np.array(A)),
         lambda: 1 / (1 + onp.exp(-A)), 1e-6, 1e-6),
        ("gelu", lambda mx: mx.npx.gelu(mx.np.array(A)),
         lambda: 0.5 * A * (1 + onp.tanh(
             0.7978845608028654 * (A + 0.044715 * A ** 3))), 1e-4, 1e-4),
        ("where", lambda mx: mx.np.where(
            mx.np.array(P) > 0.5, mx.np.array(A), mx.np.array(-A)),
         lambda: onp.where(P > 0.5, A, -A), 1e-6, 1e-6),
        ("clip_grad_chain", lambda mx: _grad_chain(mx, A),
         lambda: 2.0 * onp.clip(A, -0.5, 0.5)
         * (onp.abs(A) <= 0.5), 1e-5, 1e-5),
        ("one_hot", lambda mx: mx.npx.one_hot(mx.np.array(IDX), depth=32),
         lambda: onp.eye(32, dtype=onp.float32)[IDX], 0, 0),
        ("topk_values", lambda mx: mx.npx.topk(
            mx.np.array(A), k=5, ret_typ="value"),
         lambda: -onp.sort(-A, axis=-1)[:, :5], 1e-6, 1e-6),
        ("flash_vs_naive_attention", lambda mx: _flash(mx),
         lambda: _naive_attention_oracle(), 2e-3, 2e-3),
    ]
    return cases


_QKV = None


def _qkv():
    global _QKV
    if _QKV is None:
        rng = onp.random.RandomState(3)
        _QKV = [rng.uniform(-1, 1, (2, 4, 128, 32)).astype(onp.float32)
                for _ in range(3)]
    return _QKV


def _flash(mx):
    from mxnet_tpu.ops.pallas.flash_attention import flash_attention

    q, k, v = (mx.np.array(x) for x in _qkv())  # (b, h, l, d)
    return flash_attention(q._data, k._data, v._data, causal=True)


def _naive_attention_oracle():
    q, k, v = _qkv()  # (b, h, l, d)
    d = q.shape[-1]
    s = (q @ k.transpose(0, 1, 3, 2)) / onp.sqrt(d)
    l_ = q.shape[2]
    mask = onp.tril(onp.ones((l_, l_), bool))
    s = onp.where(mask, s, -1e30)
    e = onp.exp(s - s.max(-1, keepdims=True))
    p = e / e.sum(-1, keepdims=True)
    return p @ v  # (b, h, l, d)


def _grad_chain(mx, A):
    from mxnet_tpu import autograd

    x = mx.np.array(A)
    x.attach_grad()
    with autograd.record():
        loss = (mx.np.clip(x, -0.5, 0.5) ** 2).sum()
    loss.backward()
    return x.grad


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--output", default=None)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    import mxnet_tpu as mx

    dev = jax.devices()[0]
    results = {}
    failed = []
    backend_errors = []
    for name, fn, oracle, rtol, atol in _cases():
        try:
            raw = fn(mx)
            got = onp.asarray(raw.asnumpy() if hasattr(raw, "asnumpy")
                              else raw)
            want = onp.asarray(oracle())
            max_abs = float(onp.max(onp.abs(got - want)))
            ok = bool(onp.allclose(got, want, rtol=rtol, atol=atol))
            results[name] = {"ok": ok, "max_abs_err": round(max_abs, 8)}
            if not ok:
                failed.append(name)
            print(f"[parity] {name}: {'OK' if ok else 'FAIL'} "
                  f"(max_abs {max_abs:.2e})", file=sys.stderr)
        except Exception as e:  # noqa: BLE001 — record, keep sweeping
            # a crash inside the backend/compiler is a different finding
            # than a numeric miscompare: the op never produced a value
            # (observed once: a compiler SIGABRT on SVD). Keep them
            # in separate buckets so a compiler outage can't masquerade
            # as a framework-correctness failure (or vice versa).
            results[name] = {"ok": False, "backend_error": repr(e)[:200]}
            backend_errors.append(name)
            print(f"[parity] {name}: BACKEND ERROR {e!r}", file=sys.stderr)
    out = {"device": dev.platform,
           "device_kind": getattr(dev, "device_kind", ""),
           "passed": len(results) - len(failed) - len(backend_errors),
           "total": len(results),
           "failed": failed,
           "backend_errors": backend_errors,
           "results": results}
    text = json.dumps(out, indent=2)
    print(text)
    if args.output:
        with open(args.output, "w") as f:
            f.write(text + "\n")
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
