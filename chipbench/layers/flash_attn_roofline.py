"""``flash_attn_roofline.*`` — layer: kernels (ops/pallas/flash_attention.py).

The least time the chip could take for the flash-attention calls the
algorithm needs (``flops.flash_fwd`` / ``flash_bwd`` against ``peaks.json``:
the larger of operations over peak and bytes over bandwidth) over the time
the program's flash kernels took on the device, in percent.

What is needed comes from the host and the sizes, never from the trace: one
forward and one backward per layer for every step the benchmark enqueued
inside the traced stretch (its ``dispatch`` spans; the stretch ends after
the final sync, so each of those steps ran to its end inside it, and the
tail of the step before them that the stretch may catch makes the share a
little lower, never higher). So a program that fuses, splits or repeats
kernels changes the share only through the time it takes. The time is that
of every flash kernel, found by the names the trace prints (looked at by
hand, PR 25: ``%_flash_forward``, ``%jvp_jit__flash_forward__``,
``%transpose_jvp_jit__flash_bwd_pallas___``); the patterns are data, and
nothing in the program names its kernels yet (PERF.md, tracing)."""
from chipbench import flops, trace_reduce

KERNELS = ("_flash_forward", "_flash_bwd_pallas")


def read(result, trace, ctx):
    if trace is None:
        return None
    took = trace_reduce.matching(trace["by_name"], KERNELS)
    steps = len(ctx.spans.durations("dispatch", *result["trace_span"]))
    if not took or not steps:
        return None
    peak = flops.peaks(ctx.devices[0].device_kind)
    sz, (batch, seq) = result["sizes"], result["batch"]
    heads = sz["num_heads"]
    args = (batch, heads, seq, sz["units"] // heads)
    floor = steps * sz["num_layers"] * (
        flops.floor_seconds(*flops.flash_fwd(*args), peak)[0]
        + flops.floor_seconds(*flops.flash_bwd(*args), peak)[0])
    return 100.0 * floor / took
