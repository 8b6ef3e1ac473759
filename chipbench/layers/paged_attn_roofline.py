"""``paged_attn_roofline.*`` — layer: kernels (ops/pallas/paged_attention.py).

The decode kernel only has to read the K and V rows of the blocks its
lanes really hold. Bytes: for every token decoded while the trace ran, the
blocks of its context (``ceil((prompt + index) / block_size)``) times
``flops.kv_block_bytes`` times the layers; floor = bytes over the HBM
bandwidth of ``peaks.json`` (the kernel's operations are far under the
compute bound). Share = floor / the kernel's time in the trace, in
percent. A decode step that straddles an end of the traced stretch is
counted by its tokens and only partly by its kernels: with steps of
0.1-0.5 s in a stretch of 8 s that is a few percent of the value.

The kernel is found by the name the trace prints, looked at by hand
(PR 25): ``%run bf16[32,20,64] custom-call`` — nothing names the kernel, so
it prints under the name of the jitted function that holds it,
``generation.paged_decode_program``'s ``run``, and is its only custom call
of that name (the norms print ``%_run_norm``). The pattern is data."""
from chipbench import flops

KERNEL = ("%run ", "custom-call")        # starts with, ends with


def read(result, trace, ctx):
    if trace is None:
        return None
    took = sum(v for k, v in trace["by_name"].items()
               if k.startswith(KERNEL[0]) and k.endswith(KERNEL[1]))
    lo, hi = result["trace_span"]
    bs = result["block_size"]
    blocks = sum(-(-(len(s.prompt) + i) // bs)
                 for s in result["sent"]
                 for i, t in enumerate(s.times) if i and lo <= t < hi)
    if not took or not blocks:
        return None
    sz = result["sizes"]
    heads = sz["num_heads"]
    nbytes = blocks * sz["num_layers"] * flops.kv_block_bytes(
        heads, sz["units"] // heads, bs, result["kv_dtype"])
    peak = flops.peaks(ctx.devices[0].device_kind)
    return 100.0 * flops.floor_seconds(0.0, nbytes, peak)[0] / took
