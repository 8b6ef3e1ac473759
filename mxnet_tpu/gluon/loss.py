"""Loss functions (reference ``python/mxnet/gluon/loss.py``)."""
from __future__ import annotations

import numpy as onp

from .. import numpy as np
from .. import numpy_extension as npx
from ..ndarray.ndarray import ndarray
from .block import HybridBlock

__all__ = [
    "Loss",
    "L2Loss",
    "L1Loss",
    "HuberLoss",
    "HingeLoss",
    "SquaredHingeLoss",
    "LogisticLoss",
    "SigmoidBinaryCrossEntropyLoss",
    "SigmoidBCELoss",
    "SoftmaxCrossEntropyLoss",
    "SoftmaxCELoss",
    "KLDivLoss",
    "CTCLoss",
    "TripletLoss",
    "PoissonNLLLoss",
    "CosineEmbeddingLoss",
]


def _apply_weighting(loss, weight=None, sample_weight=None):
    if sample_weight is not None:
        loss = loss * sample_weight
    if weight is not None:
        loss = loss * weight
    return loss


def _reshape_like(pred, label):
    if pred.shape != label.shape:
        label = label.reshape(pred.shape)
    return label


class Loss(HybridBlock):
    def __init__(self, weight=None, batch_axis=0):
        super().__init__()
        self._weight = weight
        self._batch_axis = batch_axis

    def __repr__(self):
        return f"{type(self).__name__}(batch_axis={self._batch_axis}, w={self._weight})"


class L2Loss(Loss):
    def __init__(self, weight=1.0, batch_axis=0):
        super().__init__(weight, batch_axis)

    def forward(self, pred, label, sample_weight=None):
        label = _reshape_like(pred, label)
        loss = np.square(label - pred)
        loss = _apply_weighting(loss, self._weight / 2, sample_weight)
        return np.mean(loss, axis=tuple(range(1, loss.ndim)))


class L1Loss(Loss):
    def __init__(self, weight=None, batch_axis=0):
        super().__init__(weight, batch_axis)

    def forward(self, pred, label, sample_weight=None):
        label = _reshape_like(pred, label)
        loss = np.abs(label - pred)
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return np.mean(loss, axis=tuple(range(1, loss.ndim)))


class HuberLoss(Loss):
    def __init__(self, rho=1, weight=None, batch_axis=0):
        super().__init__(weight, batch_axis)
        self._rho = rho

    def forward(self, pred, label, sample_weight=None):
        label = _reshape_like(pred, label)
        loss = np.abs(label - pred)
        loss = np.where(
            loss > self._rho,
            loss - 0.5 * self._rho,
            (0.5 / self._rho) * np.square(loss),
        )
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return np.mean(loss, axis=tuple(range(1, loss.ndim)))


class HingeLoss(Loss):
    def __init__(self, margin=1, weight=None, batch_axis=0):
        super().__init__(weight, batch_axis)
        self._margin = margin

    def forward(self, pred, label, sample_weight=None):
        label = _reshape_like(pred, label)
        loss = np.maximum(self._margin - pred * label, np.zeros_like(pred))
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return np.mean(loss, axis=tuple(range(1, loss.ndim)))


class SquaredHingeLoss(Loss):
    def __init__(self, margin=1, weight=None, batch_axis=0):
        super().__init__(weight, batch_axis)
        self._margin = margin

    def forward(self, pred, label, sample_weight=None):
        label = _reshape_like(pred, label)
        loss = np.square(np.maximum(self._margin - pred * label, np.zeros_like(pred)))
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return np.mean(loss, axis=tuple(range(1, loss.ndim)))


class LogisticLoss(Loss):
    def __init__(self, weight=None, batch_axis=0, label_format="signed"):
        super().__init__(weight, batch_axis)
        self._label_format = label_format

    def forward(self, pred, label, sample_weight=None):
        label = _reshape_like(pred, label)
        if self._label_format == "signed":
            label = (label + 1.0) / 2.0
        # stable softplus form: log(1+e^p) - p*l = max(p,0) - p*l + log1p(e^-|p|)
        loss = (
            np.maximum(pred, np.zeros_like(pred))
            - pred * label
            + np.log1p(np.exp(-np.abs(pred)))
        )
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return np.mean(loss, axis=tuple(range(1, loss.ndim)))


class SigmoidBinaryCrossEntropyLoss(Loss):
    def __init__(self, from_sigmoid=False, weight=None, batch_axis=0):
        super().__init__(weight, batch_axis)
        self._from_sigmoid = from_sigmoid

    def forward(self, pred, label, sample_weight=None, pos_weight=None):
        label = _reshape_like(pred, label)
        if not self._from_sigmoid:
            if pos_weight is None:
                loss = np.maximum(pred, np.zeros_like(pred)) - pred * label + np.log1p(np.exp(-np.abs(pred)))
            else:
                log_weight = 1 + (pos_weight - 1) * label
                loss = (
                    pred
                    - pred * label
                    + log_weight * (np.log1p(np.exp(-np.abs(pred))) + np.maximum(-pred, np.zeros_like(pred)))
                )
        else:
            eps = 1e-12
            if pos_weight is None:
                loss = -(np.log(pred + eps) * label + np.log(1.0 - pred + eps) * (1.0 - label))
            else:
                loss = -(
                    np.log(pred + eps) * label * pos_weight
                    + np.log(1.0 - pred + eps) * (1.0 - label)
                )
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return np.mean(loss, axis=tuple(range(1, loss.ndim)))


SigmoidBCELoss = SigmoidBinaryCrossEntropyLoss


class SoftmaxCrossEntropyLoss(Loss):
    """reference loss.py SoftmaxCrossEntropyLoss (sparse or dense labels)."""

    def __init__(self, axis=-1, sparse_label=True, from_logits=False,
                 weight=None, batch_axis=0):
        super().__init__(weight, batch_axis)
        self._axis = axis
        self._sparse_label = sparse_label
        self._from_logits = from_logits

    def forward(self, pred, label, sample_weight=None):
        axis = self._axis if self._axis >= 0 else pred.ndim + self._axis
        if (self._sparse_label and not self._from_logits
                and axis == pred.ndim - 1):
            # fused path: lse - picked from two jitted programs instead of
            # materializing log_softmax over the class axis; out-of-range
            # labels clip, matching npx.pick's default mode on the old path
            n_cls = pred.shape[-1]
            nll = npx.softmax_cross_entropy(
                pred.reshape(-1, n_cls),
                np.clip(label.reshape(-1), 0, n_cls - 1), per_example=True)
            # per_example NLL is f32; the old log_softmax+pick path kept
            # pred's dtype (e.g. bf16) — preserve that output contract
            loss = nll.reshape(label.shape).astype(pred.dtype)
            loss = _apply_weighting(loss, self._weight, sample_weight)
            return np.mean(loss, axis=tuple(range(1, loss.ndim))) if loss.ndim > 1 else loss
        if not self._from_logits:
            pred = npx.log_softmax(pred, axis=self._axis)
        if self._sparse_label:
            loss = -npx.pick(pred, label, axis=self._axis)
        else:
            label = _reshape_like(pred, label)
            loss = -np.sum(pred * label, axis=self._axis)
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return np.mean(loss, axis=tuple(range(1, loss.ndim))) if loss.ndim > 1 else loss


SoftmaxCELoss = SoftmaxCrossEntropyLoss


class KLDivLoss(Loss):
    def __init__(self, from_logits=True, axis=-1, weight=None, batch_axis=0):
        super().__init__(weight, batch_axis)
        self._from_logits = from_logits
        self._axis = axis

    def forward(self, pred, label, sample_weight=None):
        if not self._from_logits:
            pred = npx.log_softmax(pred, axis=self._axis)
        loss = label * (np.log(label + 1e-12) - pred)
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return np.mean(loss, axis=tuple(range(1, loss.ndim)))


class CTCLoss(Loss):
    """Connectionist temporal classification (reference loss.py CTCLoss over
    src/operator/nn/ctc_loss.cc). Forward-algorithm in log space via scan."""

    def __init__(self, layout="NTC", label_layout="NT", weight=None):
        super().__init__(weight, 0)
        self._layout = layout
        self._label_layout = label_layout

    def forward(self, pred, label, pred_lengths=None, label_lengths=None, sample_weight=None):
        import jax
        import jax.numpy as jnp
        from ..ops.dispatch import apply_op
        from ..ndarray.ndarray import _wrap, _unwrap

        if self._layout == "TNC":
            pred = pred.swapaxes(0, 1)  # -> NTC
        blank = 0  # the reference's ctc_loss blank-label convention

        def ctc(logits, labels, in_len, lab_len):
            # logits (N,T,C) log-probs; labels (N,L)
            logp = jax.nn.log_softmax(logits, axis=-1)
            N, T, C = logp.shape
            L = labels.shape[1]
            S = 2 * L + 1
            ext = jnp.full((N, S), blank, jnp.int32)
            ext = ext.at[:, 1::2].set(labels.astype(jnp.int32))
            neg_inf = -1e30
            alpha = jnp.full((N, S), neg_inf)
            alpha = alpha.at[:, 0].set(logp[:, 0, blank])
            alpha = alpha.at[:, 1].set(
                jnp.where(lab_len > 0, logp[jnp.arange(N), 0, ext[:, 1]], neg_inf)
            )

            same = jnp.concatenate(
                [jnp.full((N, 2), True), ext[:, 2:] == ext[:, :-2]], axis=1
            )

            def step(alpha, t):
                a_shift1 = jnp.concatenate([jnp.full((N, 1), neg_inf), alpha[:, :-1]], axis=1)
                a_shift2 = jnp.concatenate([jnp.full((N, 2), neg_inf), alpha[:, :-2]], axis=1)
                a_shift2 = jnp.where(same, neg_inf, a_shift2)
                merged = jnp.logaddexp(jnp.logaddexp(alpha, a_shift1), a_shift2)
                emit = jnp.take_along_axis(logp[:, t, :], ext, axis=1)
                new_alpha = merged + emit
                new_alpha = jnp.where(t < in_len[:, None], new_alpha, alpha)
                return new_alpha, None

            alpha, _ = jax.lax.scan(step, alpha, jnp.arange(1, T))
            end = 2 * lab_len.astype(jnp.int32)
            last = jnp.take_along_axis(alpha, end[:, None], axis=1)[:, 0]
            last2 = jnp.take_along_axis(
                alpha, jnp.maximum(end - 1, 0)[:, None], axis=1
            )[:, 0]
            # empty target: only the all-blank path counts once (end-1
            # would clamp back onto s=0 and double-count it)
            last2 = jnp.where(lab_len > 0, last2, neg_inf)
            return -jnp.logaddexp(last, last2)

        N, T, _ = pred.shape
        if pred_lengths is None:
            pred_lengths = np.full((N,), T, dtype="int32")
        if label_lengths is None:
            label_lengths = np.full((N,), label.shape[1], dtype="int32")
        loss = apply_op(
            ctc, (pred, label, pred_lengths, label_lengths), name="CTCLoss"
        )
        return _apply_weighting(loss, self._weight, sample_weight)


class TripletLoss(Loss):
    def __init__(self, margin=1, weight=None, batch_axis=0):
        super().__init__(weight, batch_axis)
        self._margin = margin

    def forward(self, pred, positive, negative, sample_weight=None):
        positive = _reshape_like(pred, positive)
        negative = _reshape_like(pred, negative)
        loss = np.sum(np.square(positive - pred) - np.square(negative - pred),
                      axis=tuple(range(1, pred.ndim)))
        loss = np.maximum(loss + self._margin, np.zeros_like(loss))
        return _apply_weighting(loss, self._weight, sample_weight)


class PoissonNLLLoss(Loss):
    def __init__(self, weight=None, from_logits=True, batch_axis=0, compute_full=False):
        super().__init__(weight, batch_axis)
        self._from_logits = from_logits
        self._compute_full = compute_full

    def forward(self, pred, target, sample_weight=None, epsilon=1e-08):
        target = _reshape_like(pred, target)
        if self._from_logits:
            loss = np.exp(pred) - target * pred
        else:
            loss = pred - target * np.log(pred + epsilon)
        if self._compute_full:
            stirling = target * np.log(target + epsilon) - target + 0.5 * np.log(2 * target * onp.pi + epsilon)
            stirling = np.where(target <= 1, np.zeros_like(stirling), stirling)
            loss = loss + stirling
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return np.mean(loss)


class CosineEmbeddingLoss(Loss):
    def __init__(self, weight=None, batch_axis=0, margin=0):
        super().__init__(weight, batch_axis)
        self._margin = margin

    def forward(self, input1, input2, label, sample_weight=None):
        # _reshape_like returns the reshaped SECOND argument (label-side);
        # assigning it to input1 made this loss compute cos(x2, x2) == 1
        input2 = _reshape_like(input1, input2)
        cos = np.sum(input1 * input2, axis=-1) / (
            np.linalg.norm(input1, axis=-1) * np.linalg.norm(input2, axis=-1) + 1e-12
        )
        label = label.reshape(cos.shape)
        loss = np.where(
            label == 1, 1.0 - cos, np.maximum(np.zeros_like(cos), cos - self._margin)
        )
        return _apply_weighting(loss, self._weight, sample_weight)


class SDMLLoss(Loss):
    """Smoothed Deep Metric Learning loss (reference loss.py:934,
    Bonadiman et al. 2019): each row of ``x2`` is the positive for the
    same row of ``x1``; the rest of the minibatch acts as negatives. KL
    between the softmax of negative pairwise distances and a smoothed
    identity label matrix."""

    def __init__(self, smoothing_parameter=0.3, weight=1.0, batch_axis=0):
        super().__init__(weight, batch_axis)
        self.kl_loss = KLDivLoss(from_logits=True)
        self.smoothing_parameter = smoothing_parameter

    def _compute_distances(self, x1, x2):
        x1_ = np.expand_dims(x1, 1)
        x2_ = np.expand_dims(x2, 0)
        return np.sum((x1_ - x2_) ** 2, axis=2)

    def _compute_labels(self, batch_size):
        gold = np.eye(batch_size)
        p = self.smoothing_parameter
        return gold * (1 - p) + (1 - gold) * p / (batch_size - 1)

    def forward(self, x1, x2):
        batch_size = x1.shape[0]
        labels = self._compute_labels(batch_size)
        distances = self._compute_distances(x1, x2)
        log_probabilities = npx.log_softmax(-distances, axis=1)
        # kl_loss averages over the row; scale back (reference :1042)
        return self.kl_loss(log_probabilities, labels) * batch_size
