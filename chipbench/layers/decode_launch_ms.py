"""``decode_launch_ms.*`` — layer: model step (decode and prefill
programs).

Median of the window's ``llm.decode.launch`` spans, in milliseconds: the
host's time to hand the decode program its arguments and get futures
back. ``decode_step_ms`` less this is ``llm.decode.fetch``, the host
waiting for the chip."""
import statistics

from chipbench import harness
from chipbench.layers._program_spans import rows, seconds


def read(result, trace, ctx):
    took = seconds(rows(result, "llm.decode.launch"))
    if not took:
        return None
    fetch = seconds(rows(result, "llm.decode.fetch"))
    harness.note(f"llm.decode.fetch: median {statistics.median(fetch) * 1e3:.3f}"
                 f" ms of {len(fetch)}; llm.decode.launch: {len(took)}")
    return statistics.median(took) * 1e3
