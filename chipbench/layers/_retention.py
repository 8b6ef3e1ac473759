"""What the two retention readers and ``serve_mfu`` share: the kernels'
time in the trace by the names the kernels carry, and the chunks the
engine prefilled in an interval, from its ``llm.prefill.chunk`` spans.

A kernel of ``ops/pallas/power_retention.py`` is a ``pallas_call`` with a
``name=``, so its operation prints as ``%power_retention_step`` /
``%power_retention_chunk`` (``trace_reduce.short_name`` keeps the name
without its number). A program without the kernels or the spans (the
parent of the PR that brought them) gives nothing to read, and a reader
that finds nothing reports nothing."""


def kernel_seconds(trace, name: str) -> float:
    return sum(v for k, v in trace["by_name"].items()
               if k.startswith("%" + name) and k.endswith("custom-call"))


def chunks(lo_s: float, hi_s: float) -> list:
    """``(start position, real tokens)`` of every ``llm.prefill.chunk``
    span that lies inside ``[lo_s, hi_s]`` (``time.perf_counter``)."""
    from mxnet_tpu.telemetry import tracing

    read = getattr(tracing, "rows", None)
    found = read(lo_s, hi_s, "llm.prefill.chunk") if read else []
    return [(int(args["start"]), int(args["tokens"]))
            for _, _, _, args in found if "start" in args]


def decoded(result, lo_s: float, hi_s: float) -> int:
    """Tokens the decode program handed out in the interval: the
    benchmark's ``on_token`` stamps but each request's first, which its
    prefill produced."""
    return sum(1 for s in result["sent"] for t in s.times[1:]
               if lo_s <= t < hi_s)
