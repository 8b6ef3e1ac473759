"""``mixed_attn_step_mfu.*`` — layer: the step as a whole (decode and
prefill programs) of a model whose attention layers are of two kinds.

Counted as ``serve_step_mfu`` is, with ``flops_laguna``: every token the
engine decoded or prefilled in the window, times 2 per matmul weight
outside the routed experts (the head only where logits are taken: every
decoded token, a prompt's last); the routed experts' for the assignments
the program **counted** on held experts; and the attention of both kinds
— a full layer's over each token's whole context, a window layer's over
the last ``window`` positions of it — over the window and the chip's bf16
peak of ``peaks.json``, in percent. The share of the whole step: a
kernel's own share is its roofline metric. Without a device trace (a CPU
rehearsal) nothing is reported."""
from chipbench import flops, flops_laguna as counts
from chipbench.layers import _laguna


def read(result, trace, ctx):
    if trace is None:
        return None
    sz, (t0, t1) = result["sizes"], result["window"]
    found = _laguna.chunks(t0, t1)
    contexts = _laguna.decoded_contexts(result, t0, t1)
    opened, closed = (result[k]["counters"].get("moe_assignments")
                      for k in ("stats_open", "stats_close"))
    if not found or not contexts or closed is None:
        return None
    prefilled = sum(n for _, n in found)
    prompts = sum(1 for start, _ in found if start == 0)
    ops = len(contexts) * 2.0 * counts.matmul_params(sz) \
        + prefilled * 2.0 * counts.matmul_params(sz, head=False) \
        + prompts * 2.0 * sz["vocab_size"] * sz["units"] \
        + 2.0 * (closed - (opened or 0)) * counts.expert_params(sz) \
        + sum(counts.attention_decode(sz, contexts, full)[0]
              + counts.attention_chunks(sz, found, full)
              for full in (True, False))
    peak = flops.peaks(ctx.devices[0].device_kind)
    return 100.0 * ops / (t1 - t0) \
        / (peak["bf16_tflops"] * 1e12 * len(ctx.devices))
