"""``closed``: ``callers`` callers, each sending its next request when the
last is answered, with no think time — agents and pipelines of bounded
concurrency. A slow system receives less load."""

OPEN_LOOP = False


def first_wave(params: dict, lanes: int) -> int:
    return int(params["callers"])


def plan(params: dict, seed: int):
    return None


def due(plan, params: dict, now_s: float, submitted: int, in_flight: int,
        lanes: int) -> int:
    return max(0, int(params["callers"]) - in_flight)
