"""``prefill_time_share.*`` — layer: scheduler (``_admit_locked``: one
whole-prompt prefill per request inside the tick).

Sum of the engine's ``prefill_ms`` over the window / the window, in
percent: the share of the scheduler's time in which every decoding lane
waits for somebody's prompt."""
from chipbench.layers._stats import delta


def read(result, trace, ctx):
    n, total = delta(result, "prefill_ms")
    t0, t1 = result["window"]
    return 100.0 * total / 1e3 / (t1 - t0) if n else None
