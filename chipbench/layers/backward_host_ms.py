"""``backward_host_ms.*`` — layer: gluon.Trainer + autograd (host dispatch).

Median duration of the program's ``autograd.backward`` span
(``ops/dispatch.backward``: the whole reverse sweep and the write of the
leaf gradients) over the window's steps, in milliseconds: the host's time
in ``loss.backward()``, from inside. Its ``by_op`` argument says under
which tape node the time lies (the notes of a traced run print it)."""
import statistics

from chipbench import harness
from chipbench.layers._program_spans import rows, seconds


def read(result, trace, ctx):
    found = rows(result, "autograd.backward")
    if not found:
        return None
    by_op: dict = {}
    for _, _, _, args in found:
        for op, (wall, calls) in args.get("by_op", {}).items():
            acc = by_op.setdefault(op, [0.0, 0])
            acc[0] += wall
            acc[1] += calls
    harness.note("autograd.backward by_op, ms a step [wall, calls]: "
                 + ", ".join(f"{op} [{w / len(found):.2f}, "
                             f"{n / len(found):g}]"
                             for op, (w, n) in sorted(
                                 by_op.items(), key=lambda kv: -kv[1][0])))
    return statistics.median(seconds(found)) * 1e3
