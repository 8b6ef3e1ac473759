"""``paged_window_roofline.*`` — layer: kernels
(ops/pallas/paged_attention.py over a lane's ring).

A window layer's decode attention only has to read the rows that are
live in the lane's ring: ``min(context, window)`` of them for every token
decoded while the trace ran, times ``flops_laguna.kv_token_bytes`` times
the window layers — never the ring's whole length where a lane has not
filled it. Floor = bytes over the HBM bandwidth; share = floor / the time
of the kernel's calls that print a window layer's query heads
(``_laguna.py``), in percent."""
from chipbench.layers import _laguna


def read(result, trace, ctx):
    return _laguna.paged_roofline(result, trace, ctx, full=False)
