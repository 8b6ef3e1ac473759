"""``prefill_ms_per_ktok.*`` — layer: model step (prefill programs).

Sum of the engine's ``prefill_ms`` over the window per thousand prompt
tokens prefilled in it (requests whose first token fell inside the
window; a prompt is padded to its bucket, the padding is not counted)."""
from chipbench.layers._stats import delta


def read(result, trace, ctx):
    n, total = delta(result, "prefill_ms")
    t0, t1 = result["window"]
    tokens = sum(len(s.prompt) for s in result["sent"]
                 if s.times and t0 <= s.times[0] < t1)
    return total / (tokens / 1e3) if n and tokens else None
