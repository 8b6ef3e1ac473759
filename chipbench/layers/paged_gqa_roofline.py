"""``paged_gqa_roofline.*`` — layer: kernels
(ops/pallas/paged_attention.py, grouped K/V heads).

The decode kernel only has to read the K and V rows its lanes' contexts
hold: for every token decoded while the trace ran, its context (prompt +
tokens before it + itself) times ``flops_qwen3next.kv_token_bytes``
(2,048 B at 2 K/V heads of 256 in bf16) times the full-attention layers.
Floor = bytes over the HBM bandwidth of ``peaks.json``; share = floor /
the kernel's time in the trace, in percent. Rows, not blocks: a lane's
last block is read whole but only its live rows are needed.

The kernel carries no name and prints under that of the jitted function
that holds it, ``generation.paged_decode_program``'s ``run``
(``paged_attn_roofline.py``, whose pattern this is; that reader counts
with ``flops.py``'s GPT-2 sizes, a row of heads x units / heads)."""
from chipbench import flops, flops_qwen3next as counts
from chipbench.layers import _q3next
from chipbench.layers.paged_attn_roofline import KERNEL


def read(result, trace, ctx):
    if trace is None:
        return None
    took = sum(v for k, v in trace["by_name"].items()
               if k.startswith(KERNEL[0]) and k.endswith(KERNEL[1]))
    contexts = _q3next.decoded_contexts(result, *result["trace_span"])
    if not took or not contexts:
        return None
    _, nbytes = counts.attention_decode(result["sizes"], contexts)
    if result["kv_dtype"] == "float32":
        nbytes *= 2
    peak = flops.peaks(ctx.devices[0].device_kind)
    return 100.0 * flops.floor_seconds(0.0, nbytes, peak)[0] / took
