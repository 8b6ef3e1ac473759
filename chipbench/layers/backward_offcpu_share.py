"""``backward_offcpu_share.*`` — layer: gluon.Trainer + autograd (host
dispatch).

Over the window's ``autograd.backward`` spans: the sum of (duration less
the thread's CPU time inside the span, ``cpu_us``) over the sum of their
durations, in percent — how much of ``backward()`` the host thread spent
off the CPU. A lower bound on "blocked in a call": near 0 the host is
computing (Python overhead), near 100 it waits."""
from chipbench.layers._program_spans import rows, seconds


def read(result, trace, ctx):
    found = rows(result, "autograd.backward")
    total = sum(seconds(found))
    if not total:
        return None
    on_cpu = sum(args["cpu_us"] for _, _, _, args in found) / 1e6
    return 100.0 * (total - on_cpu) / total
