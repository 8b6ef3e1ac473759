"""``prefill_chunk_ms.*`` — layer: model step (decode and prefill
programs).

Median of the window's ``llm.prefill.chunk`` spans, in milliseconds: one
launch of the chunk program up to the host's copy of its token
(``serving/llm.py`` ``_chunk_prefill`` waits for every chunk), whatever
the share of the chunk that is padding."""
import statistics

from chipbench import harness
from chipbench.layers._program_spans import rows, seconds


def read(result, trace, ctx):
    found = rows(result, "llm.prefill.chunk")
    if not found:
        return None
    real = sum(r[3].get("tokens", 0) for r in found)
    pad = sum(r[3].get("pad", 0) for r in found)
    harness.note(f"llm.prefill.chunk: {len(found)} chunks, {real} tokens "
                 f"and {pad} rows of padding ({100 * pad / (real + pad):.1f}%)")
    return statistics.median(seconds(found)) * 1e3
