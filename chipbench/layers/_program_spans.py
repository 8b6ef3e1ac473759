"""The program's own spans inside the window: the rows of the one ring of
``mxnet_tpu.telemetry.tracing`` (``rows(lo_s, hi_s, name)``: complete
spans inside a ``time.perf_counter`` interval, which is the clock of
``result["window"]``), as ``(name, start_s, end_s, args)``. They need no
device trace, so their readers report on a CPU rehearsal too. A program
from before the spans (no ``tracing.rows``) gives no rows, and a reader
that finds none reports nothing."""


def rows(result, name=None) -> list:
    from mxnet_tpu.telemetry import tracing

    read = getattr(tracing, "rows", None)
    return read(*result["window"], name) if read else []


def seconds(found) -> list:
    return [end - start for _, start, end, _ in found]


def under(found, ancestors) -> list:
    """Those of ``found`` with one of ``ancestors`` (rows) above them, by
    the ``parent`` ids the spans carry."""
    by_id = {r[3]["id"]: r for r in found}
    top = {r[3]["id"] for r in ancestors}

    def has(row):
        parent = row[3].get("parent")
        while parent is not None and parent not in top:
            parent = by_id.get(parent, ("", 0, 0, {}))[3].get("parent")
        return parent is not None

    return [r for r in found if has(r)]
