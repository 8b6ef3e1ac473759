"""``host_gap_ms.*`` — layer: serving.LLMEngine scheduler.

For consecutive program calls inside the window (``_calls``): the median
time from the end of one call's ``llm.result`` to the start of the next
call's first ``llm.upload``, in milliseconds. This is the host's time
with nothing queued on the chip. The notes give the gaps' mean and count;
the median time of a gap spent in ``llm.emit``, ``llm.sweep``, admission
(``llm.admit`` outside its prefill) and the turn between two ``llm.tick``
rows; the window's longest tick with its children and the time between
them; and the ring's dropped rows (``tracing.buffer().dropped``), which
must be 0 for the rows to hold every call."""
import statistics

from chipbench import harness
from chipbench.layers import _calls
from chipbench.layers._program_spans import rows

PARTS = ("llm.emit", "llm.sweep", "llm.admit")


def longest_tick(found) -> str:
    ticks = [r for r in found if r[0] == "llm.tick"]
    if not ticks:
        return "no llm.tick"
    name, start, end, args = max(ticks, key=lambda r: r[2] - r[1])
    kids = sorted((r for r in found if r[3].get("parent") == args["id"]),
                  key=lambda r: r[1])
    at, parts = start, []
    for kid in kids:
        parts.append(f"({(kid[1] - at) * 1e3:.3f}) {kid[0]} "
                     f"{(kid[2] - kid[1]) * 1e3:.3f}")
        at = kid[2]
    parts.append(f"({(end - at) * 1e3:.3f})")
    return (f"longest llm.tick {(end - start) * 1e3:.3f} ms, its children "
            "in ms with the time before each in brackets: "
            + ", ".join(parts))


def read(result, trace, ctx):
    found = rows(result)
    between = _calls.gaps(_calls.calls(found))
    if not between:
        return None
    took = [e - s for s, e in between]
    medians = {}
    for name in PARTS:
        cover = _calls.Cover(found, name)
        medians[name] = statistics.median(cover(s, e) for s, e in between)
    tick = _calls.Cover(found, "llm.tick")
    medians["between ticks"] = statistics.median(
        e - s - tick(s, e) for s, e in between)
    from mxnet_tpu.telemetry import tracing

    harness.note(
        f"host gaps: {len(took)}, mean {statistics.mean(took) * 1e3:.4f} "
        f"ms; median in a gap of " + ", ".join(
            f"{n} {v * 1e3:.4f}" for n, v in medians.items())
        + f" ms; {longest_tick(found)}; ring rows dropped "
        f"{tracing.buffer().dropped}")
    return statistics.median(took) * 1e3
