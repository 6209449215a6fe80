"""The device's idle time in a traced window, credited to the engine's spans.

The engine's spans reach the JAX profiler's trace as host events named
``fm.<span>`` (``repro.observability.trace``), on the line of the thread
that did the work and on the profiler's clock, the clock of the device's
operations.  Over the window of the ``bench.op<i>`` annotations
(``bench/trace_reduce.py``), every nanosecond in which the first device
ran no operation is credited to the innermost ``fm.*`` event open at that
instant on the line or lines that carry the annotations (the compute
thread), or to ``none``:

    idle_by_span    {"fm.fetch": s, "fm.plan": s, ..., "none": s}; the
                    values sum to the window's length less the first
                    device's busy time

``sweep`` computes it from plain tuples: the device's busy intervals and
the host events, each named before any ``#``, where a TraceMe's
arguments would follow.  Reading them from a trace is left to
``trace_reduce``, which already walks both.
"""
from __future__ import annotations

from bench import trace_reduce

SPAN_PREFIX = "fm."
NONE = "none"


def idle_intervals(busy, lo, hi):
    """The gaps of the merged, sorted ``busy`` intervals inside [lo, hi)."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def sweep(busy, lo, hi, events) -> dict:
    """Idle time of [lo, hi) by the innermost event open at each instant.

    ``busy``: (start, end) device intervals; ``events``: (start, end,
    name) host events, in any order.  The innermost of the events open at
    an instant is the one that started last (of two that started together,
    the one that ends first).  Returns {name: idle time}, ``none`` where no
    event was open; the values sum to hi - lo less the busy union."""
    idle = idle_intervals(trace_reduce._union(busy), lo, hi)
    if not idle:
        return {}
    evs = sorted((s, e, name) for s, e, name in events if e > s)
    cuts = {x for s, e in idle for x in (s, e)}
    cuts.update(x for s, e, _ in evs for x in (s, e) if lo < x < hi)
    points = sorted(cuts)
    out: dict = {}
    active, nxt, gap = [], 0, 0
    for a, b in zip(points, points[1:]):
        while gap < len(idle) and idle[gap][1] <= a:
            gap += 1
        if gap == len(idle):
            break
        while nxt < len(evs) and evs[nxt][0] <= a:
            active.append(evs[nxt])
            nxt += 1
        active = [ev for ev in active if ev[1] > a]
        if not idle[gap][0] <= a < idle[gap][1]:
            continue
        name = (max(active, key=lambda ev: (ev[0], -ev[1]))[2]
                if active else NONE)
        out[name] = out.get(name, 0) + (b - a)
    return out
