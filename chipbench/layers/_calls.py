"""The serving scheduler's program calls, from the program's own spans
(``serving/llm.py``): each program is handed its host arrays inside an
``llm.upload`` (arg ``bytes``) and its result reaches the host inside an
``llm.result``. A call runs from the start of its first upload (a
speculative round, or a prefill with a draft model, uploads twice) to the
end of the result that follows; between two calls the chip has nothing
queued and the time is the host's. A program from before these spans
gives no calls, and a reader that finds none reports nothing."""
import bisect

UPLOAD, RESULT = "llm.upload", "llm.result"


def ring(lo_s: float, hi_s: float) -> list:
    """Every complete span of the ring inside ``[lo_s, hi_s]``
    (``time.perf_counter``), as ``_program_spans.rows`` gives them."""
    from mxnet_tpu.telemetry import tracing

    read = getattr(tracing, "rows", None)
    return read(lo_s, hi_s) if read else []


def calls(found) -> list:
    """``(start_s, end_s, uploads)`` of each call in ``found``, in time
    order. A result with no upload before it (its call began before the
    rows do) is no call; uploads with no result after them neither."""
    out, ups = [], []
    for row in sorted((r for r in found if r[0] in (UPLOAD, RESULT)),
                      key=lambda r: r[1]):
        if row[0] == UPLOAD:
            ups.append(row)
        elif ups:
            out.append((ups[0][1], row[2], ups))
            ups = []
    return out


def gaps(found_calls) -> list:
    """``(start_s, end_s)`` from each call's end to the next one's start."""
    return [(a[1], b[0]) for a, b in zip(found_calls, found_calls[1:])]


def clipped(intervals, lo: float, hi: float) -> float:
    """Seconds of ``intervals`` inside ``[lo, hi]``."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in intervals)


class Cover:
    """Seconds of an interval under the rows called ``name``: spans of
    one name on the scheduler's thread follow each other and never
    overlap, so the rows are searched by their starts."""

    def __init__(self, found, name: str):
        self.rows = sorted((s, e) for n, s, e, _ in found if n == name)
        self.starts = [s for s, _ in self.rows]

    def __call__(self, lo: float, hi: float) -> float:
        i = max(0, bisect.bisect_left(self.starts, lo) - 1)
        j = bisect.bisect_left(self.starts, hi)
        return clipped(self.rows[i:j], lo, hi)
