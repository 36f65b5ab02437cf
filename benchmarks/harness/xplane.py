"""From a profiler trace (``.xplane.pb``) to three things per chip: the
seconds in which an operation ran (the union of the operations' intervals),
the idle share of the traced window, and the operations ranked by the time
they took.  The interval arithmetic is pure and tested on synthetic events;
only ``reduce_file`` touches jax.
"""
from __future__ import annotations

import heapq
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[int, int]                  # [start, end) in ns
Event = Tuple[str, int, int]                # name, start ns, duration ns

# the lines of a device plane whose events are operations on the chip
OP_LINES = ("XLA Ops",)
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Overlapping, nested and touching intervals merged, in order."""
    out: List[Interval] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def busy_ns(events: Iterable[Event],
            window: Optional[Interval] = None) -> int:
    """Nanoseconds covered by at least one event, cut to ``window``."""
    spans = []
    for _, start, dur in events:
        a, b = start, start + dur
        if window is not None:
            a, b = max(a, window[0]), min(b, window[1])
        spans.append((a, b))
    return sum(b - a for a, b in union(spans))


def idle_share(busy: float, window: float) -> Optional[float]:
    """1 - busy / window (both in one unit); nothing where there is no
    window."""
    return None if window <= 0 else 1.0 - busy / window


def rank_ops(events: Iterable[Event], top: int = 10) -> List[Tuple[str, float]]:
    """Operations by total duration, longest first, in seconds."""
    total: Dict[str, int] = {}
    for name, _, dur in events:
        total[name] = total.get(name, 0) + dur
    ranked = sorted(total.items(), key=lambda kv: (-kv[1], kv[0]))
    return [(name[:160], ns / 1e9) for name, ns in ranked[:top]]


def gaps(events: Iterable[Event], window: Interval,
         top: Optional[int] = 10) -> List[Interval]:
    """The longest intervals of ``window`` in which no event ran (all of
    them where ``top`` is None)."""
    covered = union((max(s, window[0]), min(s + d, window[1]))
                    for _, s, d in events)
    out, at = [], window[0]
    for a, b in covered:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if window[1] > at:
        out.append((at, window[1]))
    return sorted(out, key=lambda g: g[0] - g[1])[:top]


@dataclass
class Reduction:
    window_s: float
    busy_s: Dict[int, float] = field(default_factory=dict)      # by chip id
    ops: Dict[int, List[Tuple[str, float]]] = field(default_factory=dict)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    def mean_busy_s(self) -> Optional[float]:
        if not self.busy_s:
            return None
        return sum(self.busy_s.values()) / len(self.busy_s)


def newest_trace(trace_dir: str) -> Optional[str]:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


# host annotations kept from a trace: the benchmark's own and the program's
# lexical layer spans.  Only the benchmark's span the traced window.
BENCH_SPANS = "bench."
PROGRAM_SPANS = "brpc."


def attribute_gaps(idle: List[Interval], host: List[Event],
                    top: int = 10) -> List[Tuple[str, float]]:
    """Idle seconds by what the host was doing: each gap goes to the
    innermost annotation that covers the gap's middle (the one that began
    last and is still open) among the program's ``brpc.*`` layer spans, so
    that a gap names the layer the chip waited for; to the innermost
    ``bench.*`` annotation only where no span of the program is open.  One
    sweep over the sorted starts with the open spans on a heap: a cover is
    found however many events began since."""
    opening = sorted(host, key=lambda e: e[1])
    open_spans: Dict[str, List[Tuple[int, int, str]]] = {
        PROGRAM_SPANS: [], BENCH_SPANS: []}      # heaps of (-start, end, name)
    by_what: Dict[str, int] = {}
    nxt = 0
    for a, b in sorted(idle, key=lambda g: g[0] + g[1]):
        t = (a + b) // 2
        while nxt < len(opening) and opening[nxt][1] <= t:
            name, s, d = opening[nxt]
            nxt += 1
            kind = PROGRAM_SPANS if name.startswith(PROGRAM_SPANS) \
                else BENCH_SPANS
            heapq.heappush(open_spans[kind], (-s, s + d, name))
        what = "no benchmark span open"
        for kind in (PROGRAM_SPANS, BENCH_SPANS):
            heap = open_spans[kind]
            while heap and heap[0][1] <= t:     # ended: it covers no later
                heapq.heappop(heap)             # middle either
            if heap:
                what = heap[0][2]
                break
        by_what[what] = by_what.get(what, 0) + (b - a)
    ranked = sorted(by_what.items(), key=lambda kv: (-kv[1], kv[0]))
    return [(w, ns / 1e9) for w, ns in ranked[:top]]


def traced_window(per_chip: Dict[int, List[Event]],
                  host: List[Event]) -> Optional[Interval]:
    """The span from the first to the last event on any of the chips'
    operation lines and the benchmark's own host annotations (``bench.*``);
    the program's (``brpc.*``) are kept for the idle gaps' names and widen
    no window, so no metric that divides by it moves with them."""
    everything = [e for ev in per_chip.values() for e in ev] \
        + [e for e in host if e[0].startswith(BENCH_SPANS)]
    if not everything:
        return None
    return (min(e[1] for e in everything),
            max(e[1] + e[2] for e in everything))


def reduce_file(path: str, chips: List[int]) -> Reduction:
    """Busy seconds and ranked operations of each chip over the traced
    window, and the busiest chip's idle gaps by what the host was doing."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    per_chip: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in chips:
            ev = per_chip.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name in OP_LINES:
                    ev.extend((e.name, int(e.start_ns), int(e.duration_ns))
                              for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events
                            if e.name.startswith((BENCH_SPANS,
                                                  PROGRAM_SPANS)))
    window = traced_window(per_chip, host)
    if window is None:
        return Reduction(window_s=0.0)
    red = Reduction(window_s=(window[1] - window[0]) / 1e9)
    for chip, ev in per_chip.items():
        red.busy_s[chip] = busy_ns(ev, window) / 1e9
        red.ops[chip] = rank_ops(ev)
    if red.busy_s:
        busiest = max(red.busy_s, key=red.busy_s.get)
        red.idle_gaps = attribute_gaps(
            gaps(per_chip[busiest], window, top=None), host)
    return red


def describe_file(path: str) -> List[str]:
    """Planes, their lines and how many events each holds: what to look at
    by hand before trusting ``reduce_file`` on a new kind of trace."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = list(line.events)
            names = sorted({e.name for e in events})[:6]
            out.append(f"{plane.name} | {line.name} | {len(events)} events "
                       f"| e.g. {names}")
    return out
