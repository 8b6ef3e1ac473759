"""``delta_step_roofline.*`` — layer: kernels (ops/pallas/gated_delta.py,
``gated_delta_step``).

The recurrent step has to read and write the delta rule's matrices of
every lane that decoded, once per delta-rule layer, and nothing else of
size: bytes = 2 x ``flops_qwen3next.state_bytes`` (2,097,152 B at 32
heads of 128 x 128) x delta-rule layers x the tokens decoded while the
trace ran, counted by the host from its own ``on_token`` stamps. Floor =
bytes over the HBM bandwidth of ``peaks.json`` (7 operations an entry are
far under the compute bound); share = floor / the kernel's time in the
trace, in percent. The kernel also steps the lanes that carry no request:
their bytes are not counted, so an engine with empty lanes reads lower,
as it should."""
from chipbench import flops, flops_qwen3next as counts
from chipbench.layers import _q3next


def read(result, trace, ctx):
    if trace is None:
        return None
    took = _q3next.kernel_seconds(trace, "gated_delta_step")
    tokens = len(_q3next.decoded_contexts(result, *result["trace_span"]))
    if not took or not tokens:
        return None
    _, nbytes = counts.delta_step(result["sizes"], tokens)
    peak = flops.peaks(ctx.devices[0].device_kind)
    return 100.0 * flops.floor_seconds(0.0, nbytes, peak)[0] / took
