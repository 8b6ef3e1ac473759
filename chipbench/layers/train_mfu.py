"""``train_mfu`` — layer: the train step as a whole.

``flops.train_flops_per_token`` (forward + backward, recomputation not
counted) times the traced run's own tokens per second, over the chips'
bf16 peak from ``peaks.json``, in percent. A utilisation is a device
number: without a device trace (a CPU rehearsal) nothing is reported."""
from chipbench import flops


def read(result, trace, ctx):
    if trace is None:
        return None
    peak = flops.peaks(ctx.devices[0].device_kind)
    seq = result["batch"][1]
    rate = result["end_to_end"]["train_tokens_per_s"]
    return 100.0 * flops.train_flops_per_token(result["sizes"], seq) * rate \
        / (peak["bf16_tflops"] * 1e12 * len(ctx.devices))
