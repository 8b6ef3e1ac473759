"""What the ``.laguna`` readers share: ``_q3next.py``'s functions (the
chunks prefilled and the contexts decoded in an interval, the expert
layers' counts), and the paged kernel's time in the trace **by the kind
of layer that called it**.

The paged kernel carries no name and prints under that of the jitted
function that holds it (``paged_attn_roofline.py``); what tells a window
layer's call from a full layer's is the shape the call prints, its
result's ``[lanes, query heads, head size]``: ``%run bf16[24,72,128]
custom-call`` over a ring, ``%run bf16[24,48,128] custom-call`` over a
table. The readers rely on those shapes, from the cell's own sizes; a
model whose two kinds had one head count could not be told apart so and
its readers report nothing."""
from chipbench import flops_laguna as counts
from chipbench.layers._q3next import (chunks, decoded_contexts,  # noqa: F401
                                      expert_counts)
from chipbench.layers.paged_attn_roofline import KERNEL


def paged_seconds(trace, result, full: bool):
    """Seconds of the paged kernel's calls from layers of one kind, or
    None where the kinds cannot be told apart."""
    sz = result["sizes"]
    mine, other = counts.kind_heads(sz, full), counts.kind_heads(sz, not full)
    if mine == other:
        return None
    tag = f"[{result['lanes']},{mine},{sz['head_dim']}]"
    return sum(v for k, v in trace["by_name"].items()
               if k.startswith(KERNEL[0]) and k.endswith(KERNEL[1])
               and tag in k)


def paged_roofline(result, trace, ctx, full: bool):
    """Floor over time of one kind's decode attention: the live rows'
    bytes (``flops_laguna.attention_decode``) of every token decoded
    while the trace ran, over the HBM bandwidth of ``peaks.json``, over
    that kind's kernel time, in percent."""
    from chipbench import flops

    if trace is None:
        return None
    took = paged_seconds(trace, result, full)
    contexts = decoded_contexts(result, *result["trace_span"])
    if not took or not contexts:
        return None
    _, nbytes = counts.attention_decode(result["sizes"], contexts, full)
    if result["kv_dtype"] == "float32":
        nbytes *= 2
    peak = flops.peaks(ctx.devices[0].device_kind)
    return 100.0 * flops.floor_seconds(0.0, nbytes, peak)[0] / took
