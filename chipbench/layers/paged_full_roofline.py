"""``paged_full_roofline.*`` — layer: kernels
(ops/pallas/paged_attention.py over a lane's table of blocks).

A full layer's decode attention only has to read the K and V rows of the
positions its lanes' contexts hold: for every token decoded while the
trace ran, its context (prompt + tokens before it + itself) times
``flops_laguna.kv_token_bytes`` (4,096 B at 8 K/V heads of 128 in bf16)
times the full layers. Floor = bytes over the HBM bandwidth; share = floor
/ the time of the kernel's calls that print a full layer's query heads
(``_laguna.py``), in percent. Rows, not blocks."""
from chipbench.layers import _laguna


def read(result, trace, ctx):
    return _laguna.paged_roofline(result, trace, ctx, full=True)
