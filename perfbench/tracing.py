"""Reduction of a JAX profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
events; everything else works on those events, so the arithmetic can be
tested on a synthetic trace:

* busy: the union of the intervals in which an operation ran on a device,
  inside the traced window (averaged over the devices);
* per-program device time: the executions of each compiled program, by
  its jit name;
* idle gaps: the complement of busy in the window, each gap put down to
  the innermost host span (a program span or one of the harness's) that
  was open at its middle.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]      # [start_ns, end_ns)

#: host events that are spans: dotted names such as ``stream.ingest_chunk``
#: (the program's spans) and ``perfbench.window`` (the harness's)
_SPAN_NAME = re.compile(r"^[A-Za-z_][\w-]*(\.[\w-]+)+$")

WINDOW_SPAN = "perfbench.window"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: int
    end_ns: int


@dataclasses.dataclass
class Trace:
    """``devices`` maps a device plane to its op events and its program
    events; ``host`` holds the host's named events (all threads)."""
    devices: Dict[str, Dict[str, List[Event]]]
    host: List[Event]


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {}
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    lines[line.name] = _events(line.events)
            if lines:
                devices[plane.name] = {
                    "ops": lines.get("XLA Ops", lines.get("XLA Modules")),
                    "programs": lines.get("XLA Modules",
                                          lines.get("XLA Ops"))}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line.events))
    return Trace(devices=devices, host=host)


def _events(events) -> List[Event]:
    out = []
    for e in events:
        start = int(e.start_ns)
        out.append(Event(e.name, start, start + int(e.duration_ns)))
    return out


def window(trace: Trace, name: str = WINDOW_SPAN) -> Interval:
    """The traced window: the host span the harness opened around it."""
    spans = [e for e in trace.host if e.name == name]
    if not spans:
        raise ValueError(f"no host span {name!r} in the trace")
    w = max(spans, key=lambda e: e.end_ns - e.start_ns)
    return w.start_ns, w.end_ns


def clip(intervals: Iterable[Interval], win: Interval) -> List[Interval]:
    lo, hi = win
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Disjoint, sorted union of intervals."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def complement(busy: Sequence[Interval], win: Interval) -> List[Interval]:
    """The idle gaps of a disjoint sorted ``busy`` inside ``win``."""
    gaps, t = [], win[0]
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if win[1] > t:
        gaps.append((t, win[1]))
    return gaps


def busy_ns(trace: Trace, win: Interval) -> float:
    """Device busy time in the window, averaged over the devices."""
    if not trace.devices:
        return 0.0
    tot = 0
    for lines in trace.devices.values():
        iv = union(clip(((e.start_ns, e.end_ns) for e in lines["ops"]),
                        win))
        tot += sum(b - a for a, b in iv)
    return tot / len(trace.devices)


def programs(trace: Trace, win: Interval) -> Dict[str, Tuple[float, int]]:
    """{program name: (device seconds, executions)} in the window, summed
    over the devices.  Names lose a trailing ``(id)`` or ``.n`` suffix."""
    out: Dict[str, Tuple[float, int]] = {}
    for lines in trace.devices.values():
        for e in lines["programs"]:
            iv = clip([(e.start_ns, e.end_ns)], win)
            if not iv:
                continue
            key = program_name(e.name)
            s, n = out.get(key, (0.0, 0))
            out[key] = (s + (iv[0][1] - iv[0][0]) * 1e-9, n + 1)
    return out


def program_name(raw: str) -> str:
    name = re.sub(r"\(\d+\)$", "", raw.strip())
    return re.sub(r"\.\d+$", "", name)


def program_events(trace: Trace, win: Interval,
                   patterns: Sequence[str]) -> List[Interval]:
    """Executions, inside the window, of programs whose name contains one
    of ``patterns``, sorted by start."""
    out = []
    for lines in trace.devices.values():
        for e in lines["programs"]:
            if any(p in e.name for p in patterns):
                out.extend(clip([(e.start_ns, e.end_ns)], win))
    return sorted(out)


def matching_seconds(progs: Dict[str, Tuple[float, int]],
                     patterns: Sequence[str]) -> float:
    return sum(s for name, (s, _) in progs.items()
               if any(p in name for p in patterns))


def idle_between(trace: Trace, win: Interval,
                 executions: Sequence[Interval]) -> float:
    """Device idle ns between consecutive ``executions`` (sorted), on the
    first device: the part of each interval from one end to the next
    start in which no operation ran."""
    if len(executions) < 2 or not trace.devices:
        return 0.0
    lines = next(iter(trace.devices.values()))
    busy = union(clip(((e.start_ns, e.end_ns) for e in lines["ops"]), win))
    tot = 0
    for (_, end), (start, _) in zip(executions, executions[1:]):
        if start > end:
            tot += sum(b - a for a, b in complement(
                clip(busy, (end, start)), (end, start)))
    return float(tot)


def idle_gaps(trace: Trace, win: Interval) -> List[Tuple[str, float, int]]:
    """[(host span, idle seconds, gaps)], idle time on the first device
    grouped by the innermost span open at each gap's middle, largest
    first.  ``(no span)`` collects gaps with no span open."""
    if not trace.devices:
        return []
    lines = next(iter(trace.devices.values()))
    busy = union(clip(((e.start_ns, e.end_ns) for e in lines["ops"]), win))
    spans = sorted((e for e in trace.host if _SPAN_NAME.match(e.name)
                    and e.name != WINDOW_SPAN and e.end_ns > e.start_ns),
                   key=lambda e: e.start_ns)
    acc: Dict[str, List[float]] = {}
    open_spans: List[Event] = []
    nxt = 0
    for a, b in complement(busy, win):        # gaps come sorted
        mid = (a + b) // 2
        while nxt < len(spans) and spans[nxt].start_ns <= mid:
            open_spans.append(spans[nxt])
            nxt += 1
        open_spans = [s for s in open_spans if s.end_ns > mid]
        best: Optional[Event] = min(
            open_spans, key=lambda s: s.end_ns - s.start_ns, default=None)
        key = best.name if best is not None else "(no span)"
        tot = acc.setdefault(key, [0.0, 0])
        tot[0] += (b - a) * 1e-9
        tot[1] += 1
    return sorted(((k, v[0], v[1]) for k, v in acc.items()),
                  key=lambda r: -r[1])
