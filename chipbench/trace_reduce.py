"""From a profiler trace (``.xplane.pb``) to busy/idle, time by operation
name and the longest idle gaps.

Two halves. ``read_xplane`` turns the file into plain tuples (it needs
nothing but jax). ``reduce`` is pure Python over those tuples, so that a
hand-written event list checks it (``tests/chipbench_tests``).

What a v5e trace holds (looked at by hand, PR 25): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` has one event per executed
HLO operation, named by its whole HLO text (``short_name`` cuts it down),
and whose line ``XLA Modules`` has one per program; the
host's threads are lines of ``/host:CPU``, and a
``jax.profiler.TraceAnnotation`` is an event on its thread's line. All
planes share one clock (nanoseconds).

``python3 -m chipbench.trace_reduce <file-or-dir>`` prints what a file
holds, for looking at one by hand.
"""
from __future__ import annotations

import glob
import os
import re
import sys
from collections import defaultdict

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "chipbench."


def short_name(text: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO line,
    ``%fusion.10 = bf16[50257,768]{1,0:T(8,128)} fusion(...operands...)``.
    Keep the instruction's name without its number, the first output
    shape and ``custom-call`` where it is one (a Pallas kernel):
    ``%_flash_forward f32[96,1024,64] custom-call``. Operand names are
    dropped, so that a pattern matches a kernel and not its consumers."""
    lhs, sep, rhs = text.partition(" = ")
    if not sep:
        return text
    shape = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rhs)
    return " ".join(filter(None, (
        re.sub(r"\.\d+$", "", lhs), shape and shape.group(1),
        "custom-call" if " custom-call(" in rhs else "")))


def find_xplane(directory: str) -> str | None:
    """The newest ``.xplane.pb`` under a ``start_trace`` directory."""
    found = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def read_xplane(path: str) -> dict:
    """``{"device": {plane: [(name, start_ns, dur_ns)]}, "host":
    [(name, start_ns, dur_ns)]}`` — the device's operations per chip and
    the benchmark's own host spans (``chipbench.*``)."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    device: dict = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device[plane.name] = [
                        (short_name(e.name), float(e.start_ns),
                         float(e.duration_ns)) for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    return {"device": device, "host": host}


def busy_union(events, lo: float, hi: float) -> tuple[float, list]:
    """Nanoseconds of [lo, hi] covered by any event, and the uncovered
    gaps as ``(start, end)`` — events clipped to the window."""
    spans = sorted((max(s, lo), min(s + d, hi)) for _, s, d in events
                   if s + d > lo and s < hi)
    busy, gaps, at = 0.0, [], lo
    for s, e in spans:
        if s > at:
            gaps.append((at, s))
        if e > at:
            busy += e - max(s, at)
            at = e
    if hi > at:
        gaps.append((at, hi))
    return busy, gaps


def span_at(host, lo: float, hi: float) -> str:
    """The benchmark's span that covers most of [lo, hi]; the innermost
    (shortest) wins a tie. ``"(no span)"`` where none overlaps."""
    best, best_key = "(no span)", (0.0, 0.0)
    for name, s, d in host:
        cover = min(s + d, hi) - max(s, lo)
        if cover > 0 and (cover, -d) > best_key:
            best, best_key = name, (cover, -d)
    return best


def reduce(events: dict, window_span: str | None = None):
    """Busy and idle over the traced window, averaged over the chips.

    The window is the host span named ``window_span`` where the trace has
    one (the benchmark wraps its traced stretch in it), otherwise from the
    first device event's start to the last one's end. Returns ``None`` for
    a trace with no device operation (a CPU run)."""
    device = {k: v for k, v in events["device"].items() if v}
    if not device:
        return None
    host = events["host"]
    win = [(s, s + d) for name, s, d in host if name == window_span]
    if win:
        lo, hi = win[0]
    else:
        lo = min(s for evs in device.values() for _, s, _ in evs)
        hi = max(s + d for evs in device.values() for _, s, d in evs)
    busy_ns, by_name, gaps = 0.0, defaultdict(float), []
    for evs in device.values():
        b, g = busy_union(evs, lo, hi)
        busy_ns += b
        for name, s, d in evs:
            if s + d > lo and s < hi:
                by_name[name] += (min(s + d, hi) - max(s, lo))
        gaps.extend(g)
    n = len(device)
    gap_by_span = defaultdict(float)
    for s, e in gaps:
        gap_by_span[span_at(host, s, e)] += e - s
    return {
        "chips": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "by_name": {k: v / n / 1e9 for k, v in by_name.items()},
        "idle_by_span": {k: v / n / 1e9 for k, v in gap_by_span.items()},
    }


def matching(by_name: dict, patterns) -> float:
    """Sum of ``by_name``'s values (seconds) over operations
    whose printed name contains any of ``patterns``."""
    return sum(v for k, v in by_name.items()
               if any(p in k for p in patterns))


def breakdown(red: dict, top: int = 10) -> dict:
    """The contract's ``breakdown``: the device operations that took most
    time, and the device's idle time by what the host was doing (the
    benchmark's innermost span over each gap), summed per span."""
    ops = sorted(red["by_name"].items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(red["idle_by_span"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}


def dump(path: str) -> None:
    """Print planes, lines, event counts and the first events with their
    stats — for reading a trace by hand."""
    import jax

    if os.path.isdir(path):
        path = find_xplane(path) or path
    data = jax.profiler.ProfileData.from_file(path)
    print(f"{path}: {os.path.getsize(path)} bytes")
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r}: {len(evs)} events")
            total = defaultdict(float)
            for e in evs:
                total[e.name] += e.duration_ns
            for name, ns in sorted(total.items(), key=lambda kv: -kv[1])[:25]:
                print(f"      {ns / 1e6:10.3f} ms  {name}")
            for e in evs[:3]:
                print("      first:", e.name, e.start_ns, e.duration_ns,
                      {k: str(v)[:120] for k, v in list(e.stats)[:12]})


if __name__ == "__main__":
    dump(sys.argv[1])
