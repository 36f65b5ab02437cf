"""The general readers that a ``metrics/<name>.json`` can name under
``reader.kind``.  A reader takes the view of a run and the metric's own
parameters and returns a number, or ``None`` where it finds nothing to read:
the harness then leaves the metric out of the line.  A metric whose reading
needs code of its own has a ``metrics/<name>.py`` with ``read(view, how)``.
"""
from __future__ import annotations

import math
import statistics
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

from .driver import Window
from .xplane import Reduction, idle_share


@dataclass
class View:
    """A run as the metric readers see it."""
    window: Window
    reduction: Optional[Reduction]      # of the traced slice, on a chip
    peaks: Optional[Dict[str, Any]]     # of this device kind

    def good_calls(self, within: Optional[Tuple[int, int]] = None):
        """Correct calls, all of the window or those that ended inside
        ``within`` (ns on the host's clock)."""
        for c in self.window.calls():
            if c[4] and (within is None or within[0] <= c[1] <= within[1]):
                yield c


def overlap_count(calls, within: Tuple[int, int]) -> float:
    """Calls counted by the share of each call's own time that lies inside
    ``within``: a call that is half inside counts a half, so a slice of
    the window holds slice ÷ latency calls per caller whatever calls
    straddle its two ends."""
    n = 0.0
    for c in calls:
        inside = min(c[1], within[1]) - max(c[0], within[0])
        if inside > 0:
            n += inside / max(1, c[1] - c[0])
    return n


def percentile(sorted_values: List[float], q: float) -> float:
    """The value at rank ceil(q*n) (nearest rank): a sample that was
    measured, never an interpolation."""
    n = len(sorted_values)
    rank = max(1, math.ceil(q * n - 1e-9))
    return sorted_values[min(n, rank) - 1]


def calls_rate(view: View, how) -> Optional[float]:
    n = sum(1 for _ in view.good_calls())
    return n / view.window.seconds if n else None


def bytes_rate(view: View, how) -> Optional[float]:
    """Request and reply attachment bytes of correct calls per second."""
    b = sum(2 * c[3] for c in view.good_calls())
    return b / view.window.seconds / how.get("divide_by", 1) if b else None


def latency_percentile(view: View, how) -> Optional[float]:
    """Over ALL calls of the window, failed ones included at the time they
    took."""
    lat = sorted((c[1] - c[0]) / 1e6 for c in view.window.calls())
    return percentile(lat, how["q"]) if lat else None


def setup_seconds(view: View, how) -> Optional[float]:
    return view.window.setup_s


def span_median(view: View, how) -> Optional[float]:
    """Median, in ms, of ``from`` -> ``to`` over the traced slice's calls.
    ``call_entry`` and ``reply_ready`` are the client's own stamps."""
    spans, within = view.window.spans, view.window.trace_slice_ns
    if spans is None:
        return None
    client = {c[5]: c for c in view.good_calls(within)}

    def at(boundary: str, key: str) -> Optional[int]:
        if boundary == "call_entry":
            return client[key][0]
        if boundary == "reply_ready":
            return client[key][1]
        return spans.at.get(boundary, {}).get(key)

    gaps = []
    for key in client:
        a, b = at(how["from"], key), at(how["to"], key)
        if a is not None and b is not None:
            gaps.append((b - a) / 1e6)
    return statistics.median(gaps) if gaps else None


def counter_per_call(view: View, how) -> Optional[float]:
    n = sum(1 for _ in view.good_calls())
    return view.window.counters[how["counter"]] / n if n else None


def compiles_in_window(view: View, how) -> Optional[float]:
    return float(view.window.compiles_in_window)


def _chip(view: View, which: str) -> Optional[int]:
    red = view.reduction
    if red is None or not red.busy_s:
        return None
    if which == "busiest":
        return max(red.busy_s, key=red.busy_s.get)
    return view.window.caller_device.id if which == "caller" else None


def trace_busy_ms_per_call(view: View, how) -> Optional[float]:
    """The chip's busy share of the trace's own window over the calls per
    second of the host-clock slice stamped around the trace.  The two
    windows differ by the profiler's start and stop (some tens of ms), so
    each rate is taken over its own window and no call is counted against
    seconds it did not run in."""
    chip = _chip(view, how["chip"])
    red, within = view.reduction, view.window.trace_slice_ns
    if chip is None or chip not in red.busy_s or not within \
            or red.window_s <= 0:
        return None
    n = overlap_count(view.good_calls(), within)
    if not n:
        return None
    calls_per_s = n / ((within[1] - within[0]) / 1e9)
    return red.busy_s[chip] / red.window_s * 1e3 / calls_per_s


def trace_idle_pct(view: View, how) -> Optional[float]:
    chip = _chip(view, how["chip"])
    if chip is None:
        return None
    share = idle_share(view.reduction.busy_s[chip], view.reduction.window_s)
    return None if share is None else 100.0 * share


KINDS: Dict[str, Callable[[View, Dict[str, Any]], Optional[float]]] = {
    f.__name__: f for f in (
        calls_rate, bytes_rate, latency_percentile, setup_seconds,
        span_median, counter_per_call, compiles_in_window,
        trace_busy_ms_per_call, trace_idle_pct)}


def read(metric, view: View) -> Optional[float]:
    if metric.module is not None:
        return metric.module.read(view, metric.reader)
    return KINDS[metric.reader["kind"]](view, metric.reader)
