"""Operations and bytes of the ``qwen3next_like`` model from its shapes —
the arithmetic of ``flops.py`` for a model of gated delta-rule layers,
gated softmax-attention layers and a mixture of experts of which one
chip's share is held. Nothing here measures; every count is of what the
*algorithm* needs: an expert's weights are counted where a token really
reached it (the program's own counters, never an expectation where a
counter exists), the delta rule at its recurrent form's operations
whatever the chunked form spends on its triangular systems, a lane's
state once read and once written.
"""
from __future__ import annotations

import numpy as onp


def sizes(config: dict) -> dict:
    """``qwen3next_like``'s keyword arguments from a configuration file's
    published keys (the names of the model's own ``config.json``). The
    router keeps the published count of experts; ``num_experts`` in a
    file that lists it under ``reduced`` is how many are held here."""
    published = config.get("published", {})
    head = int(config["head_dim"])
    return dict(
        vocab_size=int(config["vocab_size"]),
        units=int(config["hidden_size"]),
        num_layers=int(config["num_hidden_layers"]),
        full_attention_interval=int(config["full_attention_interval"]),
        num_heads=int(config["num_attention_heads"]),
        num_kv_heads=int(config["num_key_value_heads"]),
        head_dim=head,
        rotary_dim=int(round(float(config["partial_rotary_factor"]) * head)),
        rope_theta=float(config["rope_theta"]),
        linear_key_heads=int(config["linear_num_key_heads"]),
        linear_value_heads=int(config["linear_num_value_heads"]),
        linear_key_dim=int(config["linear_key_head_dim"]),
        linear_value_dim=int(config["linear_value_head_dim"]),
        conv_width=int(config["linear_conv_kernel_dim"]),
        num_experts=int(published.get("num_experts", config["num_experts"])),
        experts_per_token=int(config["num_experts_per_tok"]),
        expert_size=int(config["moe_intermediate_size"]),
        shared_expert_size=int(config["shared_expert_intermediate_size"]),
        experts_held=int(config["num_experts"]),
        first_expert=int(config.get("first_expert", 0)),
        max_length=int(config["max_position_embeddings"]),
        epsilon=float(config["rms_norm_eps"]))


def is_full(sz: dict, i: int) -> bool:
    return (i + 1) % sz["full_attention_interval"] == 0


def full_layers(sz: dict) -> int:
    return sum(is_full(sz, i) for i in range(sz["num_layers"]))


def delta_layers(sz: dict) -> int:
    return sz["num_layers"] - full_layers(sz)


def overrides(config: dict, sz: dict, seed: int) -> dict:
    """Parameters the benchmark does not draw from normal(0, std), the
    same in program and reference (both read the parameter):

    * ``a_log``, ``dt_bias`` per value head, so that a layer's heads have
      half-lives log-spaced over ``assumed_values.half_life_tokens`` (16
      to 4,096) in an order the seed draws: ``dt_bias = 1`` and ``A_log =
      log(ln 2 / (half-life * softplus(1)))``. At the published
      initialisation most heads forget within a token, and no check
      could see a wrong carry between chunks.
    * the convolution's taps normal(0, 1/2): at 0.02 the delta-rule
      layers' values, and with them the layers, would be nothing beside
      the residual.
    * the zero-centred norm weights normal(0, ``norm_spread``) and the
      delta rule's output norm ``1 +`` that: enough spread that a weight
      ignored, or a ``1 +`` dropped, shows."""
    values = config["assumed_values"]
    lo, hi = values["half_life_tokens"]
    spread = float(values["norm_spread"])
    rng = onp.random.RandomState((seed + 3) % 2**32)
    hv = sz["linear_value_heads"]
    half_life = onp.exp(onp.linspace(onp.log(lo), onp.log(hi), hv))
    a_log = onp.log(onp.log(2.0) / (half_life * onp.log1p(onp.e)))
    channels = 2 * sz["linear_key_heads"] * sz["linear_key_dim"] \
        + hv * sz["linear_value_dim"]

    def norm(n):
        return (spread * rng.randn(n)).astype(onp.float32)

    out = {"final_norm": norm(sz["units"])}
    for i in range(sz["num_layers"]):
        pre = f"layer{i}."
        out[pre + "input_norm"] = norm(sz["units"])
        out[pre + "post_norm"] = norm(sz["units"])
        if is_full(sz, i):
            out[pre + "mixer.q_norm"] = norm(sz["head_dim"])
            out[pre + "mixer.k_norm"] = norm(sz["head_dim"])
            continue
        out[pre + "mixer.a_log"] = a_log[rng.permutation(hv)].astype(
            onp.float32)
        out[pre + "mixer.dt_bias"] = onp.ones((hv,), onp.float32)
        out[pre + "mixer.out_norm"] = 1.0 + norm(sz["linear_value_dim"])
        out[pre + "mixer.conv"] = (0.5 * rng.randn(
            sz["conv_width"], channels)).astype(onp.float32)
    return out


# --- parameters --------------------------------------------------------------
def expert_params(sz: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * sz["units"] * sz["expert_size"]


def expert_bytes(sz: dict, itemsize: int = 2) -> int:
    return expert_params(sz) * itemsize


def mixer_params(sz: dict, full: bool) -> int:
    u = sz["units"]
    if full:
        h, hk, d = sz["num_heads"], sz["num_kv_heads"], sz["head_dim"]
        return u * 2 * h * d + 2 * u * hk * d + h * d * u + 2 * d
    qk = sz["linear_key_heads"] * sz["linear_key_dim"]
    hv, dv = sz["linear_value_heads"], sz["linear_value_dim"]
    return (u * (2 * qk + 2 * hv * dv) + u * 2 * hv
            + sz["conv_width"] * (2 * qk + hv * dv) + 2 * hv + dv
            + hv * dv * u)


def layer_params(sz: dict, full: bool) -> int:
    """A layer outside its routed experts: mixer, router, shared expert
    and its gate, the two norms."""
    u = sz["units"]
    return (mixer_params(sz, full) + u * sz["num_experts"]
            + 3 * u * sz["shared_expert_size"] + u + 2 * u)


def matmul_params(sz: dict, head: bool = True) -> int:
    """Weights outside the routed experts that meet a token in a matrix
    multiplication: every layer's projections, router, shared expert and
    gate, and, where the token's logits are taken, the untied head (the
    embedding is a gather; norms, taps and gates' vectors are not
    matmuls)."""
    u = sz["units"]
    qk = sz["linear_key_heads"] * sz["linear_key_dim"]
    hv, dv = sz["linear_value_heads"], sz["linear_value_dim"]
    h, hk, d = sz["num_heads"], sz["num_kv_heads"], sz["head_dim"]
    shared = u * sz["num_experts"] + 3 * u * sz["shared_expert_size"] + u
    delta = u * (2 * qk + 2 * hv * dv) + u * 2 * hv + hv * dv * u
    full = u * 2 * h * d + 2 * u * hk * d + h * d * u
    return (delta_layers(sz) * (delta + shared)
            + full_layers(sz) * (full + shared)
            + (sz["vocab_size"] * u if head else 0))


# --- the experts -------------------------------------------------------------
def expected_touched(sz: dict, tokens: int) -> float:
    """Held experts of one layer that ``tokens`` tokens reach, if routing
    were uniform: the expectation the sizing starts from. A reader counts
    with the program's counters instead."""
    miss = (1.0 - sz["experts_per_token"] / sz["num_experts"]) ** tokens
    return sz["experts_held"] * (1.0 - miss)


def expert_work(sz: dict, assignments: float, touched: float) -> tuple:
    """(operations, bytes) of the routed experts for ``assignments``
    (token, held expert) pairs over ``touched`` (layer, held expert)
    pairs: 2 operations a weight and assignment; a touched expert's
    weights read once."""
    return (2.0 * assignments * expert_params(sz),
            float(touched) * expert_bytes(sz))


# --- the mixers' own work ---------------------------------------------------
def state_bytes(sz: dict) -> int:
    """Float32 bytes of one lane's delta-rule matrices in one layer."""
    return sz["linear_value_heads"] * sz["linear_key_dim"] \
        * sz["linear_value_dim"] * 4


def tail_bytes(sz: dict) -> int:
    """Float32 bytes of one lane's convolution tail in one layer."""
    qk = sz["linear_key_heads"] * sz["linear_key_dim"]
    return (sz["conv_width"] - 1) * (
        2 * qk + sz["linear_value_heads"] * sz["linear_value_dim"]) * 4


def kv_token_bytes(sz: dict, itemsize: int = 2) -> int:
    """Bytes of one token's K and V rows in one full-attention layer."""
    return 2 * sz["num_kv_heads"] * sz["head_dim"] * itemsize


def delta_step(sz: dict, tokens: int) -> tuple[float, float]:
    """(operations, bytes) of the delta rule over ``tokens`` tokens, one
    at a time: per token, layer and value head the matrix is decayed,
    read with k, corrected by a rank-one term and read with q — 7
    operations an entry — and, decoding, read and written once."""
    per = delta_layers(sz) * state_bytes(sz)
    return tokens * per / 4 * 7.0, tokens * 2.0 * per


def attention_decode(sz: dict, contexts) -> tuple[float, float]:
    """(operations, bytes) of the full layers' attention for decoded
    tokens whose contexts (positions attended, the token's own included)
    are ``contexts``: ``q . k`` and the weighted ``v`` per query head and
    position; every position's K and V rows read once."""
    total = float(sum(contexts))
    h, d = sz["num_heads"], sz["head_dim"]
    return (full_layers(sz) * 4.0 * h * d * total,
            full_layers(sz) * kv_token_bytes(sz) * total)


def attention_chunks(sz: dict, chunks) -> float:
    """Operations of the full layers' attention over prefilled
    ``chunks``, an iterable of ``(start, tokens)``: token ``t`` of a
    chunk sees ``start + t + 1`` positions."""
    h, d = sz["num_heads"], sz["head_dim"]
    pairs = sum(n * start + n * (n + 1) / 2.0 for start, n in chunks)
    return full_layers(sz) * 4.0 * h * d * pairs
