"""``lane_fill.*`` — layer: serving.LLMEngine scheduler.

Tokens the decode program produced in the window (the benchmark's
``on_token`` events that are not a request's first token, which prefill
produces) over decode steps in the window (``stats()`` counters) times
``max_running``: the mean share of lanes that carried a request in a
decode step, in percent."""


def read(result, trace, ctx):
    t0, t1 = result["window"]
    decoded = sum(1 for s in result["sent"] for t in s.times[1:]
                  if t0 <= t < t1)
    steps = result["stats_close"]["counters"]["decode_steps"] \
        - result["stats_open"]["counters"]["decode_steps"]
    if not steps:
        return None
    return 100.0 * decoded / (steps * result["lanes"])
