"""What the ``.q3next`` readers share: the kernels' time in the trace by
the names the kernels carry and the chunks prefilled in an interval
(``_retention.py``'s two functions), and what the engine counted in an
interval, from the args of its own spans.

``gated_delta_step`` and ``moe_grouped_ffn`` are ``pallas_call``s with a
``name=``, so their operations print as ``%gated_delta_step`` /
``%moe_grouped_ffn`` (``trace_reduce.short_name`` keeps the name without
its number); the paged kernel has none and prints under the name of the
jitted function that holds it (``paged_attn_roofline.py``). The expert
layers' counts are made on the device and land, with the token, in the
args of ``llm.decode.fetch`` and ``llm.prefill.chunk``. A program without
the kernels or the counts (the parent of the PR that brought them) gives
nothing to read, and a reader that finds nothing reports nothing."""
from chipbench.layers._retention import chunks, kernel_seconds  # noqa: F401


def _rows(lo_s: float, hi_s: float, name: str) -> list:
    from mxnet_tpu.telemetry import tracing

    read = getattr(tracing, "rows", None)
    return read(lo_s, hi_s, name) if read else []


def expert_counts(lo_s: float, hi_s: float) -> tuple[int, int]:
    """(assignments on held experts, held experts touched summed over
    layers and program calls) inside ``[lo_s, hi_s]``."""
    hit = touched = 0
    for name in ("llm.decode.fetch", "llm.prefill.chunk"):
        for _, _, _, args in _rows(lo_s, hi_s, name):
            hit += int(args.get("moe_assignments", 0))
            touched += int(args.get("moe_experts_touched", 0))
    return hit, touched


def decoded_contexts(result, lo_s: float, hi_s: float) -> list:
    """The context (positions attended, its own included) of every token
    the decode program handed out in the interval: the benchmark's
    ``on_token`` stamps but each request's first, which its prefill
    produced. Token ``i`` of an answer was computed from the prompt and
    the ``i`` tokens before it."""
    return [len(s.prompt) + i for s in result["sent"]
            for i, t in enumerate(s.times) if i and lo_s <= t < hi_s]
