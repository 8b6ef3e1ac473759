"""Training "traffic": a rotation of distinct token batches from the seed.

Parameters (``traffic/<name>.json``): ``batch``, ``seq``,
``distinct_batches``. Every seed gives the same shapes; only the tokens
differ."""
from __future__ import annotations

import numpy as onp


def batches(params: dict, vocab: int, seed: int) -> list:
    """``distinct_batches`` pairs of host arrays: tokens (batch, seq) and
    next-token labels (batch * seq,), the last position of each sequence
    carrying the loss's ignore index -1."""
    rng = onp.random.RandomState(seed % 2**32)
    b, s = int(params["batch"]), int(params["seq"])
    out = []
    for _ in range(int(params["distinct_batches"])):
        x = rng.randint(0, vocab, (b, s)).astype(onp.int32)
        labels = onp.concatenate(
            [x[:, 1:], onp.full((b, 1), -1, onp.int32)], axis=1)
        out.append((x, labels.reshape(-1)))
    return out
