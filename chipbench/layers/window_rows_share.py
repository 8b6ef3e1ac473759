"""``window_rows_share.*`` — layer: cache manager (serving/kv_cache.py).

The K/V rows the cache holds for live requests over the rows a cache that
kept every position in every layer would hold for them: mean over the
window's ticks of ``(kv_rows_full + kv_rows_window) / (positions x all
layers)``, from the two gauges the engine puts into the args of its
``llm.tick`` spans (``positions`` is ``kv_rows_full`` over the full
layers). 100 where no layer forgets; about 29 at 10k positions with 3
full layers, 9 window layers and a window of 512. A program without the
gauges gives nothing to read."""
from chipbench import flops_laguna as counts
from chipbench.layers import _program_spans


def read(result, trace, ctx):
    sz = result["sizes"]
    full = len(counts.layers_of(sz, True))
    shares = []
    for _, _, _, args in _program_spans.rows(result, "llm.tick"):
        held = args.get("kv_rows_full")
        if held:
            every = held / full * sz["num_layers"]
            shares.append((held + args["kv_rows_window"]) / every)
    return 100.0 * sum(shares) / len(shares) if shares else None
