"""The comparison that decides ``correct``: what the timed path delivered
against the plain reference, and the route it took against the
configuration's guarantees.  Every number has a limit of its own; the run is
correct when each number keeps its limit.

Held on every operation of the window, as its client returned
(``driver.Caller``, the same for every client): it did not fail, the reply
attachment is whole and all of it on the device, every block of it is resident
on the caller's chip, and the reply's message answers this operation's key.
Held on a sample drawn from the seed, once the window has
closed and the payload sets are freed: the reply's bytes and its message
(where a method computes one) equal what ``reference/<Method>.py`` works out
from the block that ``reference/payload.py`` regenerates on the host.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from . import counters, traffic
from .driver import Window
from .loader import reference_module
from ..reference import payload


def attachment_bytes(att) -> np.ndarray:
    """A reply attachment as one host uint8 array.  A device block that
    several references point into is read back once."""
    parts = []
    read_back: Dict[int, np.ndarray] = {}
    for i in range(att.backing_block_num()):
        r = att.backing_block(i)
        data = r.block.data
        if hasattr(data, "devices"):
            if id(data) not in read_back:
                read_back[id(data)] = \
                    np.asarray(data).reshape(-1).view(np.uint8)
            host = read_back[id(data)]
            parts.append(host[r.offset:r.offset + r.length])
        else:
            parts.append(np.frombuffer(
                r.block.host_view(r.offset, r.length), np.uint8))
    return np.concatenate(parts) if parts else np.zeros(0, np.uint8)


def _limit(value, rule: str, limit) -> Dict[str, Any]:
    ok = value <= limit if rule == "<=" else value >= limit
    return {"value": value, "rule": rule, "limit": limit, "ok": bool(ok)}


def compare(window: Window):
    """(each number compared, with its limit, in the order it is printed;
    how many sampled calls the reference refused)."""
    wl = window.cell.workload
    specs = traffic.set_specs(wl)
    faults: Dict[str, int] = {}
    for log in window.logs:
        for k, v in log.faults.items():
            faults[k] = faults.get(k, 0) + v
    out: Dict[str, Dict[str, Any]] = {}
    for kind in ("failed_calls", "short_replies", "misplaced_replies",
                 "misordered_replies"):
        out[kind] = _limit(faults.get(kind, 0), "<=", 0)

    compared = byte_mismatches = message_mismatches = wrong_calls = 0
    due = 0         # what the reservoirs must hold: the sample, or every call
    for log in window.logs:
        for mix in range(len(wl["mix"])):
            good = sum(1 for c in log.calls if c[4] and c[2] == mix)
            due += min(wl["sample_per_thread"], good)
        for kept in log.sampled.values():
            for s in kept:
                spec = specs[s.call.set_name]
                request = payload.block(window.seed, spec.set_id,
                                        s.call.block, spec.block_bytes)
                want, message = reference_module(s.call.method).expected(
                    request, s.key)
                got = attachment_bytes(s.attachment)
                if got.shape != want.shape:
                    differ = max(got.size, want.size)
                else:
                    differ = int(np.count_nonzero(got != want))
                byte_mismatches += differ
                message_mismatches += int(s.message != message)
                wrong_calls += int(differ > 0 or s.message != message)
                compared += 1
                s.attachment = None         # the reply's device blocks go
    out["byte_mismatches"] = _limit(byte_mismatches, "<=", 0)
    out["message_mismatches"] = _limit(message_mismatches, "<=", 0)
    out["replies_compared"] = _limit(compared, ">=", max(1, due))

    n_calls = sum(1 for _ in window.calls())
    out["second_route_events"] = _limit(
        sum(window.counters[k] for k in counters.second_route(window.cell)),
        "<=", 0)
    for r in wl.get("route", []):
        per_call = window.counters[r["counter"]] / n_calls if n_calls else 0.0
        out[f"{r['counter']}_per_call"] = _limit(
            per_call, ">=", r["per_call_min"])
    return out, wrong_calls


def verdict(numbers: Dict[str, Dict[str, Any]]) -> bool:
    return all(n["ok"] for n in numbers.values())


def lines(numbers: Dict[str, Dict[str, Any]]) -> List[str]:
    return [f"check {name}: {n['value']} {n['rule']} {n['limit']} "
            f"{'ok' if n['ok'] else 'NOT OK'}" for name, n in numbers.items()]
