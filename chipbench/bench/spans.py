"""The program's own host spans (``obs.stage`` names, which the program
also writes to the profiler as annotations) in a flattened trace.

Spans are found by name alone: every Python thread's annotations may
land on trace lines of one name, so a line tells no thread apart.
"""

from __future__ import annotations

import bisect
import math

from bench import trace

#: One per serve flush: ``execute_batch`` under the flush worker.
FLUSH = "flush.execute"


def named(events, name: str, lo: float = -math.inf, hi: float = math.inf
          ) -> list[tuple[float, float]]:
    """Sorted (start, end) ns of the host spans called ``name`` that
    start inside [lo, hi)."""
    return sorted((s, s + d) for plane, _, n, s, d in events
                  if n == name and not trace.is_device(plane)
                  and lo <= s < hi)


def ms_per_flush(ctx, names) -> float | None:
    """Milliseconds of the spans called ``names`` that start in the
    traced window, over the serve flushes that start in it; None without
    a trace or without a flush span (a program that lacks the spans)."""
    tr = ctx["trace"]
    if tr is None:
        return None
    lo, hi = tr["window"]
    flushes = len(named(tr["events"], FLUSH, lo, hi))
    if not flushes:
        return None
    ns = sum(e - s for name in names
             for s, e in named(tr["events"], name, lo, hi))
    return ns / 1e6 / flushes


def self_ms(ctx, parent: str, child: str) -> float | None:
    """Mean self time in ms of the ``parent`` spans that start in the
    traced window: each one's duration less that of the ``child`` spans
    it holds. A child belongs to the latest-starting parent that
    contains it. None without a trace or without a parent span."""
    tr = ctx["trace"]
    if tr is None:
        return None
    lo, hi = tr["window"]
    parents = named(tr["events"], parent)
    inside = [lo <= s < hi for s, _ in parents]
    if not any(inside):
        return None
    starts = [s for s, _ in parents]
    ns = sum(e - s for (s, e), i in zip(parents, inside) if i)
    for s, e in named(tr["events"], child):
        j = bisect.bisect_right(starts, s) - 1
        while j >= 0 and parents[j][1] < e:
            j -= 1
        if j >= 0 and inside[j]:
            ns -= e - s
    return ns / 1e6 / sum(inside)
