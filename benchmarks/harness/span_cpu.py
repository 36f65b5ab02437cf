"""Readers of the CPU time in the program's layer spans (``cpu_ns``: what a
lexical span's own thread ran between its two ends, by
``time.thread_time_ns()``; the span's length less it is what the thread
waited — for the interpreter lock, for a device, for a core).

The rules are ``program_spans``': a per-call figure takes the spans that lie
in the traced slice, one that straddles an end of it by the share of its
length inside, over the calls counted by their overlap with the slice; a
figure a span is of the spans that ended in the slice.  A record with no CPU
reading (``cpu_ns`` -1: stamped, a hand-over's wait, finished on another
thread, or made by a program older than the field) is left out, and with no
reading at all a reader returns ``None``: the line then lacks the metric.
"""
from __future__ import annotations

import statistics
from typing import List, Optional, Tuple

from .program_spans import _calls, _records
from .readers import View


def _readings(view: View, name: str) -> List[Tuple[int, int, int]]:
    """(start_ns, end_ns, cpu_ns) of the slice's records called ``name``
    that carry a CPU reading."""
    return [(r.start_ns, r.end_ns, r.cpu_ns) for r in _records(view, name)
            if getattr(r, "cpu_ns", -1) >= 0]


def _per_call_ms(view: View, name: str, off: bool) -> Optional[float]:
    found = _readings(view, name)
    n = _calls(view) if found else 0
    if not n:
        return None
    lo, hi = view.window.trace_slice_ns
    total = 0.0
    for a, b, cpu in found:
        part = b - a - cpu if off else cpu
        total += part * (min(b, hi) - max(a, lo)) / max(1, b - a)
    return total / 1e6 / n


def cpu_per_call_ms(view: View, name: str) -> Optional[float]:
    """What the spans' threads ran inside the slice, over its calls."""
    return _per_call_ms(view, name, off=False)


def offcpu_per_call_ms(view: View, name: str) -> Optional[float]:
    """What the spans' threads did not run inside the slice (length less
    CPU time), over its calls."""
    return _per_call_ms(view, name, off=True)


def cpu_of_median_ms(view: View, name: str) -> Optional[float]:
    """The CPU time of the median span: the median length of the spans that
    ended inside the slice times the share of their lengths that was CPU
    time.  Not the median of ``cpu_ns``: on the chip machine's host the
    thread CPU clock moves in steps of 10 ms, so a span shorter than that
    reads 0 or 10 ms and a median of such readings is one of the two; the
    share over all of the slice's spans is what such a clock can give, and
    times the median length it stays beside ``program_spans.median_ms`` of
    the same span, which a mean (a few long spans carry it) does not."""
    found = _readings(view, name)
    if not found:
        return None
    lo, hi = view.window.trace_slice_ns
    done = [(b - a, cpu) for a, b, cpu in found if lo <= b <= hi]
    length = sum(d for d, _ in done)
    if not length:
        return None
    return statistics.median(d for d, _ in done) / 1e6 \
        * sum(cpu for _, cpu in done) / length
