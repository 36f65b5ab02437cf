"""Readers of the program's own layer spans (``brpc_tpu/rpc/span.py``: the
client call, the server stages, the ici plane's window pieces, the device
poller), recorded by the program while the profiler session of a traced run
is on, on the ``perf_counter_ns`` clock of the harness's own stamps.

Each reader takes the spans of one name that lie in the traced slice and
returns a number, or ``None`` where there is no such span: a program that
records none (the parent of the PR that brought the spans), a layer the cell
does not drive, a run that was not traced.
"""
from __future__ import annotations

import statistics
from typing import List, Optional, Tuple

from .readers import View, overlap_count


def _records(view: View, name: str) -> list:
    """The program's records called ``name`` that lie at least partly inside
    the traced slice."""
    within = view.window.trace_slice_ns
    if not within:
        return []
    from brpc_tpu.rpc import span as program
    read = getattr(program, "layer_spans", None)
    if read is None:
        return []
    return read(within[0], within[1], name)


def spans(view: View, name: str) -> List[Tuple[int, int]]:
    """(start_ns, end_ns) of those records."""
    return [(r.start_ns, r.end_ns) for r in _records(view, name)]


def median_ms(view: View, name: str) -> Optional[float]:
    """Median length of the spans that ended inside the slice."""
    within = view.window.trace_slice_ns
    done = [(b - a) / 1e6 for a, b in spans(view, name)
            if within[0] <= b <= within[1]]
    return statistics.median(done) if done else None


def self_ms(view: View, name: str, child: str) -> Optional[float]:
    """Median of the spans' own time: a span's length less that of the spans
    called ``child`` that it caused, over the spans that ended inside the
    slice."""
    within = view.window.trace_slice_ns
    inside = {}
    for c in _records(view, child):
        inside[c.cause_id] = inside.get(c.cause_id, 0) \
            + c.end_ns - c.start_ns
    own = [(r.end_ns - r.start_ns - inside.get(r.span_id, 0)) / 1e6
           for r in _records(view, name)
           if within[0] <= r.end_ns <= within[1]]
    return statistics.median(own) if own else None


def _calls(view: View) -> float:
    """Correct calls of the slice, each by the share of it that lies inside:
    the rule ``trace_busy_ms_per_call`` uses."""
    return overlap_count(view.good_calls(), view.window.trace_slice_ns)


def per_call_ms(view: View, name: str,
                zero_beside: Optional[str] = None) -> Optional[float]:
    """The spans' time inside the slice over the slice's calls.  With no
    span of the name it is ``None``, or 0.0 where ``zero_beside`` names a
    span that WAS recorded (no writer stalled, though pieces were cut)."""
    found = spans(view, name)
    if not found and not (zero_beside and spans(view, zero_beside)):
        return None
    n = _calls(view)
    if not n:
        return None
    lo, hi = view.window.trace_slice_ns
    inside = sum(min(b, hi) - max(a, lo) for a, b in found)
    return inside / 1e6 / n


def per_call_count(view: View, name: str) -> Optional[float]:
    """Spans over calls, a span that straddles an end of the slice counted
    by the share of it inside, as the calls are."""
    found = spans(view, name)
    n = _calls(view) if found else 0
    if not n:
        return None
    lo, hi = view.window.trace_slice_ns
    return sum((min(b, hi) - max(a, lo)) / max(1, b - a)
               for a, b in found) / n
