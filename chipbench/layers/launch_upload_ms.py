"""``launch_upload_ms.*`` — layer: model step (decode and prefill
programs).

The median over the window's decode steps of the time their
``llm.upload`` spans take (two in a speculative round), in milliseconds:
the host putting the step's arrays (tokens, block table, positions) on the
device before the program is dispatched. The notes give the bytes a step
puts; the dispatch (``llm.decode.launch`` less its uploads); and the
uploads of prefill chunks (under ``llm.prefill.chunk``) and of whole-prompt
prefills (under ``llm.prefill``)."""
import statistics

from chipbench import harness
from chipbench.layers._calls import UPLOAD
from chipbench.layers._program_spans import rows

UNDER = ("llm.decode.launch", "llm.prefill.chunk", "llm.prefill")


def read(result, trace, ctx):
    found = rows(result)
    parents = {r[3]["id"]: r for r in found if r[0] in UNDER}
    by_parent: dict = {}
    for r in found:
        if r[0] == UPLOAD and r[3].get("parent") in parents:
            by_parent.setdefault(r[3]["parent"], []).append(r)
    steps = {n: [] for n in UNDER}
    for pid, ups in by_parent.items():
        parent = parents[pid]
        steps[parent[0]].append((parent[2] - parent[1],
                                 sum(u[2] - u[1] for u in ups),
                                 sum(u[3].get("bytes", 0) for u in ups)))
    decode = steps["llm.decode.launch"]
    if not decode:
        return None

    def med(values):
        return f"{statistics.median(values) * 1e3:.4f} ms" if values \
            else "none"

    harness.note(
        f"llm.upload: {len(decode)} decode steps of "
        f"{statistics.median(b for _, _, b in decode):.0f} bytes, dispatch "
        f"(launch less upload) {med([t - u for t, u, _ in decode])}; "
        + "; ".join(f"under {n}: {len(steps[n])} of "
                    f"{med([u for _, u, _ in steps[n]])}, "
                    f"{max((b for _, _, b in steps[n]), default=0)} bytes"
                    for n in UNDER[1:]))
    return statistics.median(u for _, u, _ in decode) * 1e3
