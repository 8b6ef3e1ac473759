"""``moe_experts_roofline.*`` — layer: kernels (ops/pallas/moe_ffn.py,
``moe_grouped_ffn``).

The grouped expert FFN has to read the three matrices of every held
expert that a token reached, once per program call and layer, and to
spend 2 operations a weight on every (token, held expert) assignment.
Both are **counted by the program** on the device (``ops.experts``: the
engine puts them into the args of ``llm.decode.fetch`` and
``llm.prefill.chunk``), never expected and never read off the trace's
events. Floor = the larger of touched experts x ``expert_bytes`` over the
HBM bandwidth and assignments x 2 x ``expert_params`` over the bf16 peak
of ``peaks.json``, summed over the traced stretch; share = floor / the
kernel's time in the trace, in percent. (The bound is taken on the sums:
decode calls are bound by bytes, a full chunk by neither alone.)"""
from chipbench import flops, flops_qwen3next as counts
from chipbench.layers import _q3next


def read(result, trace, ctx):
    if trace is None:
        return None
    took = _q3next.kernel_seconds(trace, "moe_grouped_ffn")
    hit, touched = _q3next.expert_counts(*result["trace_span"])
    if not took or not touched:
        return None
    ops, nbytes = counts.expert_work(result["sizes"], hit, touched)
    peak = flops.peaks(ctx.devices[0].device_kind)
    return 100.0 * flops.floor_seconds(ops, nbytes, peak)[0] / took
