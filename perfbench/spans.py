"""Arithmetic on the program's host spans, for the readers of the serving
front end's layer.

The program's ``obs.trace`` spans reach the profiler's host plane through
the ``xla=True`` bridge (``tracing.Trace.host``) with a name, a start and
an end, and no thread or attributes; so a child span is told from its
parent by lying inside it in time.  In the read cell only the flush
thread runs reads while the window is open, so every ``serve.score`` or
``serve.predict`` inside a ``serve.flush`` is that flush's read.
"""
from __future__ import annotations

import bisect
from typing import List, Sequence

from perfbench import tracing
from perfbench.tracing import Interval, Trace

FLUSH = "serve.flush"
#: the device read inside a flush (``ScoringFrontend._execute``)
READS = ("serve.score", "serve.predict")


def intervals(trace: Trace, win: Interval,
              names: Sequence[str]) -> List[Interval]:
    """The host spans named one of ``names``, clipped to the window,
    sorted by start."""
    return sorted(tracing.clip(((e.start_ns, e.end_ns) for e in trace.host
                                if e.name in names), win))


def host_ns(outer: Sequence[Interval],
            inner: Sequence[Interval]) -> List[int]:
    """For each of ``outer``, its length less the part covered by the
    spans of ``inner`` (sorted by start) that lie wholly inside it."""
    starts = [a for a, _ in inner]
    out = []
    for lo, hi in outer:
        i = bisect.bisect_left(starts, lo)
        kids = []
        while i < len(inner) and inner[i][0] < hi:
            if inner[i][1] <= hi:
                kids.append(inner[i])
            i += 1
        out.append((hi - lo) - sum(b - a for a, b in tracing.union(kids)))
    return out


def flush_host_ms(trace: Trace, win: Interval):
    """Mean host time of a flush in the window, in ms: each
    ``serve.flush`` less its device read, or None without flushes."""
    flushes = intervals(trace, win, (FLUSH,))
    if not flushes:
        return None
    per = host_ns(flushes, intervals(trace, win, READS))
    return sum(per) * 1e-6 / len(per)


def flushes(trace: Trace, win: Interval) -> int:
    return len(intervals(trace, win, (FLUSH,)))
