"""The program's own counts, read into one flat dict.  Route checks and
count metrics name a key of it.  A metric that needs a count which is not
here reads the program from its own ``metrics/<name>.py``."""
from __future__ import annotations

from typing import Dict, List


def snapshot(servers: List) -> Dict[str, int]:
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


# the counters that the configurations' ``single_route`` guarantee holds at
# zero over a run
SECOND_ROUTE = ("plane_fallbacks", "plane_build_failures",
                "plane_match_timeouts", "device_completion_failures",
                "relocate_failures")


def delta(before: Dict[str, int], after: Dict[str, int]) -> Dict[str, int]:
    return {k: after[k] - before.get(k, 0) for k in after}
