"""``retention_chunk_roofline.*`` — layer: kernels
(ops/pallas/power_retention.py, ``power_retention_chunk``).

Operations of the chunked form (``flops_brumby.retention_chunk``: phi at
its exact size, the pairs ``s <= t`` inside a chunk, no read of a state
that does not exist yet) for the real prompt tokens of the chunks the
engine prefilled while the trace ran (its ``llm.prefill.chunk`` spans:
``start``, ``tokens``), over the MXU's bf16 peak of ``peaks.json``, over
the kernel's time in the trace, in percent. The kernel's operands cross
the MXU as bfloat16 with float32 sums, so the bf16 peak is the one that
bounds it; padding rows, the 64 padding entries of phi and the masked half
of the power matrix are work the kernel does and the count leaves out."""
from chipbench import flops, flops_brumby
from chipbench.layers import _retention


def read(result, trace, ctx):
    if trace is None:
        return None
    took = _retention.kernel_seconds(trace, "power_retention_chunk")
    found = _retention.chunks(*result["trace_span"])
    if not took or not found:
        return None
    ops = flops_brumby.retention_chunk(result["sizes"], found)
    peak = flops.peaks(ctx.devices[0].device_kind)
    return 100.0 * ops / (peak["bf16_tflops"] * 1e12) / took
