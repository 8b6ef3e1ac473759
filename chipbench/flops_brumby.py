"""Operations and bytes of the ``brumby_like`` model from its shapes — the
arithmetic of ``flops.py`` for a model whose layers keep a state
(``mxnet_tpu.ops.retention``). Nothing here measures; every count is of
what the *algorithm* needs, never of what a kernel happens to do: phi is
counted at its exact size ``d (d + 1) / 2`` (8,256 for heads of 128), not
at the 8,320 the pools hold, and a chunk that starts a request reads no
state, because there is none yet.
"""
from __future__ import annotations

import numpy as onp


def sizes(config: dict) -> dict:
    """``brumby_like``'s keyword arguments from a configuration file's
    published keys (the names of the model's own ``config.json``)."""
    return dict(
        vocab_size=int(config["vocab_size"]),
        units=int(config["hidden_size"]),
        hidden_size=int(config["intermediate_size"]),
        num_layers=int(config["num_hidden_layers"]),
        num_heads=int(config["num_attention_heads"]),
        num_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        max_length=int(config["max_position_embeddings"]),
        rope_theta=float(config["rope_theta"]),
        epsilon=float(config["rms_norm_eps"]))


def overrides(config: dict, sz: dict, seed: int) -> dict:
    """Parameters the benchmark does not draw from normal(0, std): the
    gate's bias. With ``Wg`` ~ normal(0, 0.02) and no offset the gates sit
    near 1/2 and a state forgets in a few tokens, so no check could see a
    wrong carry between chunks. Each layer's K/V heads get half-lives
    log-spaced over ``assumed_values.gate_half_life_tokens`` (64 to 8,192:
    64, 128, ... 8,192 for eight heads), in an order the seed draws per
    layer: ``bg = log(g / (1 - g))``, ``g = 2 ** (-1 / half-life)``."""
    lo, hi = config["assumed_values"]["gate_half_life_tokens"]
    rng = onp.random.RandomState((seed + 3) % 2**32)
    half_life = onp.exp(onp.linspace(onp.log(lo), onp.log(hi),
                                     sz["num_kv_heads"]))
    g = 2.0 ** (-1.0 / half_life)
    bias = onp.log(g / (1.0 - g))
    return {f"layer{i}.retention.g_proj.bias":
            bias[rng.permutation(len(bias))].astype(onp.float32)
            for i in range(sz["num_layers"])}


def phi_exact(sz: dict) -> int:
    d = sz["head_dim"]
    return d * (d + 1) // 2


def layer_params(sz: dict) -> dict:
    """The parameters of one layer, by part."""
    u, f, d = sz["units"], sz["hidden_size"], sz["head_dim"]
    hq, hk = sz["num_heads"], sz["num_kv_heads"]
    return {"q": u * hq * d, "k": u * hk * d, "v": u * hk * d,
            "gate": u * hk, "gate_bias": hk, "o": hq * d * u,
            "ffn": 3 * u * f, "norms": 2 * u + 2 * d}


def layer_matmul_params(sz: dict) -> int:
    """Weights of a layer that take part in a matrix multiplication."""
    p = layer_params(sz)
    return sum(p[k] for k in ("q", "k", "v", "gate", "o", "ffn"))


def matmul_params(sz: dict, head: bool = True) -> int:
    """Per token: every layer's matmul weights and, where the token's
    logits are computed, the untied head (the embedding is a gather). A
    prompt's tokens pass no head but the last."""
    return sz["num_layers"] * layer_matmul_params(sz) \
        + (sz["vocab_size"] * sz["units"] if head else 0)


def state_bytes(sz: dict) -> int:
    """Float32 bytes of one request's state in one layer: ``S`` and ``z``
    for every K/V head, phi at its exact size."""
    d = sz["head_dim"]
    return sz["num_kv_heads"] * (phi_exact(sz) * d + phi_exact(sz)) * 4


def retention_step(sz: dict, tokens: int) -> tuple[float, float]:
    """(operations, bytes) of ``tokens`` decoded tokens: per token and
    layer, every K/V head's state is read and written once (the floor is
    these bytes), every query head reads it with phi(q) and every K/V
    head adds ``v phi(k)^T``: ``2 * D * d`` operations each."""
    d, big_d = sz["head_dim"], phi_exact(sz)
    ops = 2.0 * big_d * d * (sz["num_heads"] + sz["num_kv_heads"])
    return (tokens * sz["num_layers"] * ops,
            tokens * sz["num_layers"] * 2.0 * state_bytes(sz))


def retention_chunk(sz: dict, chunks) -> float:
    """Operations of the chunked form over ``chunks``, an iterable of
    ``(start, tokens)``: the position a chunk starts at and its real
    tokens. Per layer: every key's ``v phi(k)^T`` into the state; phi(q)
    against the incoming state for every query head, unless the chunk
    starts the request; and inside the chunk, ``q . k`` and the weighted
    ``v`` (``2 * d`` each) for every pair ``s <= t``."""
    d, big_d = sz["head_dim"], phi_exact(sz)
    hq, hk = sz["num_heads"], sz["num_kv_heads"]
    ops = 0.0
    for start, n in chunks:
        ops += n * hk * 2.0 * big_d * d
        if start:
            ops += n * hq * 2.0 * big_d * d
        ops += hq * 4.0 * d * n * (n + 1) / 2.0
    return sz["num_layers"] * ops
