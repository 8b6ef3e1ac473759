"""``tick_host_share.*`` — layer: serving.LLMEngine scheduler.

Over the window's ``llm.tick`` spans (``_tick_locked``): the tick's time
less the time under ``llm.prefill``, ``llm.decode.launch`` and
``llm.decode.fetch`` — the spans in which the host hands the chip a
program or waits for it — over the tick's time, in percent. What is left
is the scheduler's own: sweep, admission bookkeeping, the per-lane token
loop (``llm.emit``) and the tick's self time."""
from chipbench import harness
from chipbench.layers._program_spans import rows, seconds, under

MODEL = ("llm.prefill", "llm.decode.launch", "llm.decode.fetch")


def read(result, trace, ctx):
    found = rows(result)
    ticks = [r for r in found if r[0] == "llm.tick"]
    total = sum(seconds(ticks))
    if not total:
        return None
    inside = under(found, ticks)
    by_name: dict = {}
    for r in inside:
        by_name[r[0]] = by_name.get(r[0], 0.0) + r[2] - r[1]
    harness.note(f"llm.tick: {len(ticks)} ticks, {total:.3f} s; under them, "
                 "% of their time: " + ", ".join(
                     f"{n} {100 * t / total:.2f}" for n, t in sorted(
                         by_name.items(), key=lambda kv: -kv[1])))
    return 100.0 * (total - sum(by_name.get(n, 0.0) for n in MODEL)) / total
