"""Operations and bytes from shapes — the benchmark's own arithmetic.

Nothing here measures: every function turns sizes into the operations or
bytes the *algorithm* needs (recomputation is never counted), and
``peaks`` turns a ``device_kind`` into the published peaks. A roofline
share is ``floor_seconds(...) / measured seconds``.
"""
from __future__ import annotations

import json
import os


def sizes(config: dict) -> dict:
    """gpt_like's keyword arguments from a configuration file's published
    keys (the names of the model's own ``config.json``)."""
    units = int(config["n_embd"])
    return dict(vocab_size=int(config["vocab_size"]), units=units,
                hidden_size=int(config.get("n_inner") or 4 * units),
                num_layers=int(config["n_layer"]),
                num_heads=int(config["n_head"]),
                max_length=int(config["n_positions"]))


def peaks(device_kind: str, path: str | None = None) -> dict:
    """The published peaks of ``device_kind``. A device that is not in the
    table is an error, never a default."""
    path = path or os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"device_kind {device_kind!r} is not in {path}: add its "
            f"published peaks with their source (known: {sorted(table)})")
    return table[device_kind]


def matmul_params(sz: dict) -> int:
    """Weights that take part in a matrix multiplication per token: the
    blocks' four projections and two FFN matrices, and the tied head once
    (the embedding lookup is a gather, not a matmul)."""
    d, f = sz["units"], sz["hidden_size"]
    return sz["num_layers"] * (4 * d * d + 2 * d * f) + sz["vocab_size"] * d


def attention_flops_fwd(sz: dict, seq: int) -> float:
    """Forward causal-attention operations per token at sequence length
    ``seq``: QK^T and PV are 2*d operations per (query, key) pair each,
    and a query sees (seq + 1) / 2 keys on average."""
    return sz["num_layers"] * 4.0 * sz["units"] * (seq + 1) / 2.0


def train_flops_per_token(sz: dict, seq: int) -> float:
    """Forward + backward = 3 x forward; forward = 2 per matmul weight
    plus causal attention."""
    return 3.0 * (2.0 * matmul_params(sz) + attention_flops_fwd(sz, seq))


def flash_fwd(batch: int, heads: int, seq: int, head_dim: int,
              itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) of one causal flash-attention forward call:
    two matmuls over the lower triangle; Q, K, V read and O written."""
    pairs = batch * heads * seq * (seq + 1) / 2.0
    return 4.0 * head_dim * pairs, 4.0 * batch * heads * seq * head_dim \
        * itemsize


def flash_bwd(batch: int, heads: int, seq: int, head_dim: int,
              itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) of the backward: five matmuls over the
    triangle (S again, dP, dV, dQ, dK); Q, K, V, O, dO read, dQ, dK, dV
    written. The S recomputation is what the algorithm itself does, so it
    counts; a kernel that recomputes more than once gets no credit."""
    pairs = batch * heads * seq * (seq + 1) / 2.0
    return 10.0 * head_dim * pairs, 8.0 * batch * heads * seq * head_dim \
        * itemsize


def kv_block_bytes(heads: int, head_dim: int, block_size: int,
                   kv_dtype: str) -> int:
    """Bytes of one block of one layer's K *and* V rows. int8 rows carry
    4 scale bytes on the feature axis (``ops.nn.kv_cache_quantize``)."""
    row = head_dim + 4 if kv_dtype == "int8" else \
        head_dim * (4 if kv_dtype == "float32" else 2)
    return 2 * heads * block_size * row


def floor_seconds(ops: float, nbytes: float, peak: dict,
                  int8: bool = False) -> tuple[float, str]:
    """The least time the chip could take and which bound sets it."""
    t_ops = ops / ((peak["int8_tops"] if int8 else peak["bf16_tflops"])
                   * 1e12)
    t_bytes = nbytes / (peak["hbm_gbps"] * 1e9)
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "bandwidth")
