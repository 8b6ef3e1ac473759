"""``dispatch_ms.*`` — layer: gluon.Trainer + autograd (host dispatch).

The host time to enqueue one train step: the benchmark's span from handing
over the batch and entering ``autograd.record()`` to the return of
``trainer.step()``, with no sync inside. Median over the window's steps
that did not end in a loss fetch, in milliseconds. Source: the benchmark's
own spans (host clock); reported from the traced run."""
import statistics


def read(result, trace, ctx):
    lo, hi = result["window"]
    d = ctx.spans.durations("dispatch", lo, hi)
    return statistics.median(d) * 1e3 if d else None
