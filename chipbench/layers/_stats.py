"""Differences of ``LLMEngine.stats()`` summaries between the window's
first and last instant: a summary's ``count`` and ``mean`` are exact
(``telemetry.registry``), so ``count * mean`` is the sum."""


def delta(result, key: str) -> tuple[int, float]:
    """(observations, their sum in the summary's unit) inside the window."""
    a, b = result["stats_open"][key], result["stats_close"][key]
    return (b["count"] - a["count"],
            b["count"] * b["mean"] - a["count"] * a["mean"])
