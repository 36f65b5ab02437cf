"""How far a fan-out's sub-calls ran side by side: the sum of its sub-calls'
lifetimes over the fan-out's own time, the median over the fan-outs that
ended in the traced slice.  1.0 = one after another, the width = all of them
side by side from the first moment to the last.

A sub-call's lifetime is read from the fan-out layer's own spans: from the
start of its ``brpc.fanout.issue`` (sub-call ``i`` is the ``i``-th issue
under its ``brpc.fanout``) to the end of the ``brpc.fanout.merge`` that
folded its reply (``n`` = its index; the finalize has ``m`` = 1 and is left
out).  Its ``brpc.call`` span cannot say it: an asynchronous call's ends when
``call_method`` returns, which is before the reply.
"""
import statistics

from benchmarks.harness import program_spans


def read(view, reader):
    within = view.window.trace_slice_ns
    parents = [r for r in program_spans._records(view, reader["parent"])
               if within[0] <= r.end_ns <= within[1]]
    if not parents:
        return None
    issues, merged = {}, {}
    for r in program_spans._records(view, reader["issue"]):
        issues.setdefault(r.cause_id, []).append(r.start_ns)
    for r in program_spans._records(view, reader["merge"]):
        if r.m == 0:
            merged.setdefault(r.cause_id, {})[r.n] = r.end_ns
    shares = []
    for p in parents:
        began = sorted(issues.get(p.span_id, []))
        ended = merged.get(p.span_id, {})
        alive = sum(ended[i] - began[i] for i in ended if i < len(began))
        if alive and p.end_ns > p.start_ns:
            shares.append(alive / (p.end_ns - p.start_ns))
    return statistics.median(shares) if shares else None
