"""``call_idle_ms.*`` — layer: device.

Over the traced stretch (``result["trace_span"]``, the profiler's window on
the host's clock): the device's idle (``trace["window_s"] -
trace["busy_s"]``) less the host gaps between program calls (``_calls``)
clipped to the stretch, over the calls whose first ``llm.upload`` starts in
it, in milliseconds. This is the device's idle inside a call: the upload
and the dispatch before the chip starts, and after it ends the runtime's
notice and the copy of the result. A run without a device trace (a CPU
rehearsal) reports nothing. The notes hold the stretch's idle against the
mean whole host gap plus this, times the calls (the two differ by the
calls and gaps cut by the stretch's ends), and the call's tail: this less
a decode call's median upload and dispatch (first ``llm.upload`` to the end
of ``llm.decode.launch``)."""
import statistics

from chipbench import harness
from chipbench.layers import _calls


def read(result, trace, ctx):
    lo, hi = result.get("trace_span") or (None, None)
    if trace is None or lo is None or hi is None:
        return None
    found = _calls.ring(result["window"][0], float("inf"))
    every = _calls.calls(found)
    inside = [c for c in every if lo <= c[0] < hi]
    if not inside:
        return None
    between = _calls.gaps(every)
    idle_s = trace["window_s"] - trace["busy_s"]
    per_call = (idle_s - _calls.clipped(between, lo, hi)) / len(inside)
    whole = [e - s for s, e in between if lo <= s and e <= hi]
    launches = {r[3]["id"]: r for r in found if r[0] == "llm.decode.launch"}
    head = [launches[ups[0][3]["parent"]][2] - start
            for start, _, ups in inside
            if ups[0][3].get("parent") in launches]
    head_s = statistics.median(head) if head else 0.0
    harness.note(
        f"calls: {len(inside)} in the traced stretch of "
        f"{trace['window_s']:.4f} s, idle {idle_s:.4f} s; (mean whole host "
        f"gap {statistics.mean(whole) * 1e3 if whole else 0:.4f} + "
        f"{per_call * 1e3:.4f} ms) x calls = "
        f"{((statistics.mean(whole) if whole else 0) + per_call) * len(inside):.4f}"
        f" s; decode upload + dispatch {head_s * 1e3:.4f} ms, tail "
        f"{(per_call - head_s) * 1e3:.4f} ms")
    return per_call * 1e3
