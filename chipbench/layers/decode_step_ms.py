"""``decode_step_ms.*`` — layer: model step (decode program).

Mean of the engine's own ``decode_step_ms`` over the window: each
observation times one launch of the decode program up to the host's copy
of the next tokens (``serving/llm.py`` ``_decode_step``)."""
from chipbench.layers._stats import delta


def read(result, trace, ctx):
    n, total = delta(result, "decode_step_ms")
    return total / n if n else None
