"""``serve_mfu.*`` — layer: the step as a whole (decode and prefill
programs).

Every token the engine decoded or prefilled in the window, times the
operations the model needs for it — 2 per matmul weight (the head only
where logits are taken: every decoded token, a prompt's last) plus the
retention's own (``flops_brumby.retention_step`` a decoded token,
``retention_chunk`` the prefilled chunks) — over the window and the chip's
bf16 peak of ``peaks.json``, in percent. Decoded tokens are the
benchmark's ``on_token`` stamps, prefilled ones the engine's
``llm.prefill.chunk`` spans. It is the share of the whole step: a
kernel's own share is its roofline metric. A utilisation is a device
number: without a device trace (a CPU rehearsal) nothing is reported."""
from chipbench import flops, flops_brumby
from chipbench.layers import _retention


def read(result, trace, ctx):
    if trace is None:
        return None
    sz, (t0, t1) = result["sizes"], result["window"]
    found = _retention.chunks(t0, t1)
    tokens = _retention.decoded(result, t0, t1)
    if not found or not tokens:
        return None
    prompts = sum(1 for start, _ in found if start == 0)
    ops = tokens * 2.0 * flops_brumby.matmul_params(sz) \
        + flops_brumby.retention_step(sz, tokens)[0] \
        + sum(n for _, n in found) * 2.0 \
        * flops_brumby.matmul_params(sz, head=False) \
        + prompts * 2.0 * sz["vocab_size"] * sz["units"] \
        + flops_brumby.retention_chunk(sz, found)
    peak = flops.peaks(ctx.devices[0].device_kind)
    return 100.0 * ops / (t1 - t0) \
        / (peak["bf16_tflops"] * 1e12 * len(ctx.devices))
