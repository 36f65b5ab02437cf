"""The program's own counts, read into one flat dict.  Route checks and
count metrics name a key of it.  The table below is closed; a cell that needs
a count which is not in it names further modules in its workload file
(``"counters": ["<name>", ...]``), each a ``counters/<name>.py`` with ``KEYS``
and ``snapshot(servers) -> {key: count}``: read with the table before and
after the window, so that a key of theirs is a window delta like any other.
"""
from __future__ import annotations

from typing import Dict, Iterable, List

from .loader import BenchmarkError, counter_module

# every key of the table (``plane_<k>`` for each key of
# ``device_plane.plane().stats()``): what a counter module may not give again
TABLE_KEYS = frozenset((
    "native_requests", "native_fused_dispatched", "ici_bytes",
    "ici_device_bytes", "plane_transfers", "plane_bytes_sent",
    "plane_bytes_recv", "plane_fallbacks", "plane_build_failures",
    "plane_match_timeouts", "plane_pending_sends",
    "plane_program_cache_hits", "plane_program_cache_misses",
    "plane_sliced_in_program", "device_completions",
    "device_completion_failures", "relocate_failures"))


def snapshot(servers: List) -> Dict[str, int]:
    """The closed table."""
    from brpc_tpu.bthread.device_waiter import DeviceEventDispatcher
    from brpc_tpu.ici import device_plane, native_plane
    from brpc_tpu.ici.transport import ici_transport_stats
    out: Dict[str, int] = {}
    natives = [s._native_ici for s in servers if s._native_ici is not None]
    out["native_requests"] = sum(n.requests() for n in natives)
    out["native_fused_dispatched"] = sum(n.fused_dispatched for n in natives)
    out["ici_bytes"], out["ici_device_bytes"] = ici_transport_stats()
    for k, v in device_plane.plane().stats().items():
        out[f"plane_{k}"] = v
    disp = DeviceEventDispatcher.instance()
    out["device_completions"] = sum(disp.stats().values())
    out["device_completion_failures"] = disp.failures()
    out["relocate_failures"] = native_plane._g_relocate_failures.get_value()
    return out


def read(servers: List, more: Iterable[str] = ()) -> Dict[str, int]:
    """The table, and the keys of the counter modules named in ``more``."""
    out = snapshot(servers)
    for name in more:
        for k, v in counter_module(name).snapshot(servers).items():
            if k in out:
                raise BenchmarkError(
                    f"counters/{name}.py gives {k!r}, which is read already")
            out[k] = v
    return out


# the counters that the configurations' ``single_route`` guarantee holds at
# zero over a run
SECOND_ROUTE = ("plane_fallbacks", "plane_build_failures",
                "plane_match_timeouts", "device_completion_failures",
                "relocate_failures")


def second_route(cell) -> List[str]:
    """What ``single_route`` holds at zero in this cell: the table's keys
    above, and those its configuration lists besides
    (``second_route_counters``: keys of the cell's counter modules)."""
    return list(SECOND_ROUTE) + list(
        cell.config.get("second_route_counters", []))


def delta(before: Dict[str, int], after: Dict[str, int]) -> Dict[str, int]:
    return {k: after[k] - before.get(k, 0) for k in after}
