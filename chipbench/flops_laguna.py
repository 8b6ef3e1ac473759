"""Operations and bytes of the ``laguna_like`` model from its shapes — the
arithmetic of ``flops.py`` for a model whose attention layers are of two
kinds (full layers that keep every position's K/V rows, window layers
that keep the last ``window``), with a leading dense layer and a mixture
of experts of which one chip's share is held. Nothing here measures;
every count is of what the *algorithm* needs: live rows, not blocks or a
ring's whole length; an expert's weights where a token really reached it
(``flops_qwen3next.expert_work`` over the program's own counters, which
reads ``sizes["units"]`` and ``sizes["expert_size"]``).
"""
from __future__ import annotations

import numpy as onp

from chipbench.flops_qwen3next import expert_params  # noqa: F401  (3 U F)

FULL = "full_attention"


def sizes(config: dict) -> dict:
    """``laguna_like``'s keyword arguments from a configuration file's
    published keys (the names of the model's own ``config.json``). The
    router keeps the published count of experts; ``num_experts`` in a
    file that lists it under ``reduced`` is how many are held here. The
    per-layer lists are the published ones, cut to the layers that are
    kept."""
    published = config.get("published", {})
    n, head = int(config["num_hidden_layers"]), int(config["head_dim"])
    full = config["rope_parameters"]["full_attention"]
    window = config["rope_parameters"]["sliding_attention"]
    if full["rope_type"] != "yarn" or window["rope_type"] != "default":
        raise ValueError("laguna_like turns a full layer by YaRN and a "
                         "sliding layer by the plain rule")
    sparse = config["mlp_layer_types"][:n]
    dense = tuple(i for i, kind in enumerate(sparse) if kind == "dense")
    if dense != tuple(i for i in config["mlp_only_layers"] if i < n):
        raise ValueError("mlp_layer_types and mlp_only_layers disagree")
    return dict(
        vocab_size=int(config["vocab_size"]),
        units=int(config["hidden_size"]), num_layers=n,
        layer_types=tuple(config["layer_types"][:n]),
        heads_per_layer=tuple(
            int(h) for h in config["num_attention_heads_per_layer"][:n]),
        num_kv_heads=int(config["num_key_value_heads"]), head_dim=head,
        window=int(config["sliding_window"]),
        rope_theta=float(full["rope_theta"]),
        rotary_dim=int(round(float(full["partial_rotary_factor"]) * head)),
        yarn_factor=float(full["factor"]),
        yarn_original=int(full["original_max_position_embeddings"]),
        yarn_beta_fast=float(full["beta_fast"]),
        yarn_beta_slow=float(full["beta_slow"]),
        yarn_attention_factor=float(full["attention_factor"]),
        window_rope_theta=float(window["rope_theta"]),
        window_rotary_dim=int(round(
            float(window["partial_rotary_factor"]) * head)),
        dense_layers=dense, dense_size=int(config["intermediate_size"]),
        num_experts=int(published.get("num_experts", config["num_experts"])),
        experts_per_token=int(config["num_experts_per_tok"]),
        expert_size=int(config["moe_intermediate_size"]),
        shared_expert_size=int(config["shared_expert_intermediate_size"]),
        routed_scale=float(config["moe_routed_scaling_factor"]),
        experts_held=int(config["num_experts"]),
        first_expert=int(config.get("first_expert", 0)),
        max_length=int(config["max_position_embeddings"]),
        epsilon=float(config["rms_norm_eps"]))


def overrides(config: dict, sz: dict, seed: int) -> dict:
    """Parameters the benchmark does not draw from normal(0, std), the
    same in program and reference: the RMSNorm weights, ``1 +
    normal(0, assumed_values.norm_spread)``. A plain weight drawn around
    zero would put every layer's input near nothing, and a weight of
    exactly one would hide a norm that ignores it."""
    spread = float(config["assumed_values"]["norm_spread"])
    rng = onp.random.RandomState((seed + 3) % 2**32)

    def norm():
        return (1.0 + spread * rng.randn(sz["units"])).astype(onp.float32)

    out = {"final_norm": norm()}
    for i in range(sz["num_layers"]):
        out[f"layer{i}.input_norm"] = norm()
        out[f"layer{i}.post_norm"] = norm()
    return out


# --- the layers --------------------------------------------------------------
def is_full(sz: dict, i: int) -> bool:
    return sz["layer_types"][i % len(sz["layer_types"])] == FULL


def heads(sz: dict, i: int) -> int:
    return sz["heads_per_layer"][i % len(sz["heads_per_layer"])]


def layers_of(sz: dict, full: bool) -> list:
    """The indices of the full (or the window) layers."""
    return [i for i in range(sz["num_layers"]) if is_full(sz, i) == full]


def kind_heads(sz: dict, full: bool) -> int:
    """Query heads of a layer of that kind (one number a kind)."""
    found = {heads(sz, i) for i in layers_of(sz, full)}
    if len(found) != 1:
        raise ValueError(f"layers of one kind with {sorted(found)} heads")
    return found.pop()


# --- parameters --------------------------------------------------------------
def mixer_params(sz: dict, i: int) -> int:
    """Layer ``i``'s attention: q, k, v, the head-wise gate, o."""
    u, d = sz["units"], sz["head_dim"]
    h, hk = heads(sz, i), sz["num_kv_heads"]
    return 2 * u * h * d + 2 * u * hk * d + u * h


def layer_params(sz: dict, i: int) -> int:
    """Layer ``i`` outside its routed experts: mixer, two norms, and the
    dense FFN or the router and the shared expert."""
    u = sz["units"]
    ffn = 3 * u * sz["dense_size"] if i in sz["dense_layers"] else \
        u * sz["num_experts"] + 3 * u * sz["shared_expert_size"]
    return mixer_params(sz, i) + 2 * u + ffn


def matmul_params(sz: dict, head: bool = True) -> int:
    """Weights outside the routed experts that meet a token in a matrix
    multiplication: every layer's but its norms, and, where the token's
    logits are taken, the untied head (the embedding is a gather)."""
    u = sz["units"]
    return sum(layer_params(sz, i) - 2 * u
               for i in range(sz["num_layers"])) \
        + (sz["vocab_size"] * u if head else 0)


def weight_bytes(sz: dict, itemsize: int = 2) -> dict:
    """Bytes of the weights by part: the held experts, the layers outside
    them, embedding + head."""
    sparse = sz["num_layers"] - len(sz["dense_layers"])
    return {
        "experts": sparse * sz["experts_held"] * expert_params(sz) * itemsize,
        "layers": sum(layer_params(sz, i)
                      for i in range(sz["num_layers"])) * itemsize,
        "embedding_head": 2 * sz["vocab_size"] * sz["units"] * itemsize}


# --- the cache ---------------------------------------------------------------
def kv_token_bytes(sz: dict, itemsize: int = 2) -> int:
    """Bytes of one token's K and V rows in one layer of either kind."""
    return 2 * sz["num_kv_heads"] * sz["head_dim"] * itemsize


def ring_bytes(sz: dict, itemsize: int = 2) -> int:
    """Bytes of one lane's rings over all window layers."""
    return len(layers_of(sz, False)) * sz["window"] \
        * kv_token_bytes(sz, itemsize)


def rows_held(sz: dict, positions) -> tuple[int, int]:
    """(rows the full layers hold, rows the window layers hold) for
    requests that have absorbed ``positions``: every position a full
    layer, the last ``window`` a window layer."""
    positions = [int(p) for p in positions]
    return (len(layers_of(sz, True)) * sum(positions),
            len(layers_of(sz, False))
            * sum(min(p, sz["window"]) for p in positions))


# --- attention's own work ----------------------------------------------------
def attention_decode(sz: dict, contexts, full: bool) -> tuple[float, float]:
    """(operations, bytes) of one kind's attention for decoded tokens
    whose contexts (positions attended in a full layer, the token's own
    included) are ``contexts``: ``q . k`` and the weighted ``v`` per query
    head and live row; every live row's K and V read once. A window layer
    has ``min(context, window)`` live rows."""
    live = float(sum(contexts) if full else
                 sum(min(c, sz["window"]) for c in contexts))
    n = len(layers_of(sz, full))
    return (n * 4.0 * kind_heads(sz, full) * sz["head_dim"] * live,
            n * kv_token_bytes(sz) * live)


def attention_chunks(sz: dict, chunks, full: bool) -> float:
    """Operations of one kind's attention over prefilled ``chunks``, an
    iterable of ``(start, tokens)``: token ``t`` of a chunk sees ``start
    + t + 1`` positions in a full layer and the last ``window`` of them
    in a window layer."""
    w = sz["window"]
    if full:
        pairs = sum(n * start + n * (n + 1) / 2.0 for start, n in chunks)
    else:
        pairs = 0.0
        for start, n in chunks:
            ramp = min(max(w - start, 0), n)    # tokens that see < w
            pairs += ramp * start + ramp * (ramp + 1) / 2.0 + (n - ramp) * w
    return len(layers_of(sz, full)) * 4.0 * kind_heads(sz, full) \
        * sz["head_dim"] * pairs
