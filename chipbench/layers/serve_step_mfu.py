"""``serve_step_mfu.*`` — layer: the step as a whole (decode and prefill
programs) of a model with routed experts.

Every token the engine decoded or prefilled in the window, times the
operations the model needs for it: 2 per matmul weight outside the routed
experts (the head only where logits are taken: every decoded token, a
prompt's last); the routed experts' for the assignments the program
**counted** on held experts (``stats()["counters"]["moe_assignments"]``,
2 x 3 x units x expert width each); the mixers' own — the delta rule a
token and the full layers' attention over each token's context
(``flops_qwen3next``) — over the window and the chip's bf16 peak of
``peaks.json``, in percent. Decoded tokens are the benchmark's
``on_token`` stamps, prefilled ones the engine's ``llm.prefill.chunk``
spans. It is the share of the whole step: a kernel's own share is its
roofline metric. A utilisation is a device number: without a device trace
(a CPU rehearsal) nothing is reported."""
from chipbench import flops, flops_qwen3next as counts
from chipbench.layers import _q3next


def read(result, trace, ctx):
    if trace is None:
        return None
    sz, (t0, t1) = result["sizes"], result["window"]
    found = _q3next.chunks(t0, t1)
    contexts = _q3next.decoded_contexts(result, t0, t1)
    opened, closed = (result[k]["counters"].get("moe_assignments")
                      for k in ("stats_open", "stats_close"))
    if not found or not contexts or closed is None:
        return None
    prefilled = sum(n for _, n in found)
    prompts = sum(1 for start, _ in found if start == 0)
    ops = len(contexts) * 2.0 * counts.matmul_params(sz) \
        + prefilled * 2.0 * counts.matmul_params(sz, head=False) \
        + prompts * 2.0 * sz["vocab_size"] * sz["units"] \
        + counts.expert_work(sz, closed - (opened or 0), 0)[0] \
        + counts.delta_step(sz, len(contexts) + prefilled)[0] \
        + counts.attention_decode(sz, contexts)[0] \
        + counts.attention_chunks(sz, found)
    peak = flops.peaks(ctx.devices[0].device_kind)
    return 100.0 * ops / (t1 - t0) \
        / (peak["bf16_tflops"] * 1e12 * len(ctx.devices))
