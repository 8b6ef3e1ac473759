"""``poisson``: an open loop. Requests are due on a schedule drawn from the
seed whatever the system does: ``rate_per_s`` on average, and, with
``burst`` > 1, in groups of that many at one instant. Every seed gets the
same set of gaps — the exponential distribution's quantiles — in another
order. A request's latency counts from when it was *due*, so a stall is
charged to the requests it delays; the runner reports how late the
generator ran."""

import numpy as onp

OPEN_LOOP = True
GAPS = 4096


def first_wave(params: dict, lanes: int) -> int:
    return 0


def plan(params: dict, seed: int):
    """Due times in seconds from the start of traffic, ascending."""
    burst = int(params.get("burst", 1))
    mean_gap = burst / float(params["rate_per_s"])
    q = (onp.arange(GAPS) + 0.5) / GAPS
    gaps = -mean_gap * onp.log1p(-q)
    rng = onp.random.RandomState((seed + 1) % 2**32)
    times = onp.cumsum(rng.permutation(gaps))
    return onp.repeat(times, burst)


def due(plan, params: dict, now_s: float, submitted: int, in_flight: int,
        lanes: int) -> int:
    if submitted >= len(plan):
        raise RuntimeError("the poisson schedule ran out: raise GAPS")
    return int(onp.searchsorted(plan, now_s, side="right")) - submitted
