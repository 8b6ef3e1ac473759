"""``retention_step_roofline.*`` — layer: kernels
(ops/pallas/power_retention.py, ``power_retention_step``).

The recurrent step has to read and write the state of every lane that
decoded, once per layer, and nothing else of size: bytes = 2 x
``flops_brumby.state_bytes`` (phi at its exact 8,256, not the 8,320 the
pools hold) x layers x the tokens decoded while the trace ran, counted by
the host from its own ``on_token`` stamps. Floor = bytes over the HBM
bandwidth of ``peaks.json`` (its operations, 13 a state element, are far
under the compute bound); share = floor / the kernel's time in the trace,
in percent. The kernel also steps the lanes that carry no request (they
point at the trash slot): their bytes are not counted, so an engine with
empty lanes reads lower, as it should."""
from chipbench import flops, flops_brumby
from chipbench.layers import _retention


def read(result, trace, ctx):
    if trace is None:
        return None
    took = _retention.kernel_seconds(trace, "power_retention_step")
    tokens = _retention.decoded(result, *result["trace_span"])
    if not took or not tokens:
        return None
    _, nbytes = flops_brumby.retention_step(result["sizes"], tokens)
    peak = flops.peaks(ctx.devices[0].device_kind)
    return 100.0 * flops.floor_seconds(0.0, nbytes, peak)[0] / took
