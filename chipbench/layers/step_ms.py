"""``step_ms.*`` — layer: the train step as a whole.

The median time from one step's start to the next one's over the window,
in milliseconds (the benchmark's own timestamps, host clock). It stands
beside ``train_tokens_per_s``, which is taken over all the steps and all
the time of the window: a run in which the host stalled for a second
reads lower there and the same here."""
import statistics


def read(result, trace, ctx):
    return statistics.median(result["steps"]) * 1e3 if result["steps"] \
        else None
