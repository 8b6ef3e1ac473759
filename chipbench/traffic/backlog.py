"""``backlog``: ``waiting`` requests always wait in the queue, topped up as
requests finish — offline generation, where only throughput is felt."""

OPEN_LOOP = False


def first_wave(params: dict, lanes: int) -> int:
    """Requests sent at once at the start; each is cut short at random so
    that the lanes fall out of step (see the serve runner)."""
    return lanes


def plan(params: dict, seed: int):
    return None


def due(plan, params: dict, now_s: float, submitted: int, in_flight: int,
        lanes: int) -> int:
    return max(0, lanes + int(params["waiting"]) - in_flight)
