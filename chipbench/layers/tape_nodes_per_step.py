"""``tape_nodes_per_step.*`` — layer: gluon.Trainer + autograd (host
dispatch).

Median of the ``nodes`` argument of the window's ``autograd.backward``
spans: the operations recorded on the tape in one step (a hybridized
block is one), each of them one or more programs enqueued forward and
again backward. A count, not a time."""
import statistics

from chipbench.layers._program_spans import rows


def read(result, trace, ctx):
    nodes = [args["nodes"] for _, _, _, args in
             rows(result, "autograd.backward")]
    return statistics.median(nodes) if nodes else None
