"""Bulk payload routing — ONE table deciding which data plane carries a
payload: payload class × size × peer capability × plane health.

Before this module the selection logic was smeared across call sites
(``_encode_data``'s host flush, its device-chunk ladder, and
``stream.py``'s threshold check each re-derived eligibility).  The
table centralizes the *ordering* decision and the per-route counters;
the *mechanics* (send, claim, degrade, revive) stay with each plane.
This is the scoped seam toward ROADMAP item 5's unified payload router:
a new plane is added by teaching ``candidates`` one clause, not by
touching every encode site.

Routes, fastest first for same-host pairs:

  shm     mmap'd ring segment (``native/fabric.cpp`` nshm): one sender
          copy into shared memory, ZERO receiver copies, no syscalls on
          the byte path — the third bulk tier
  bulk    the dedicated per-pair socket conn (UDS same-host / TCP
          cross-host): syscall + kernel copy each way
  xfer    jax transfer-server pull (device payloads; on TPU pods the
          premapped HBM DMA path)
  inline  bytes ride the control channel frame itself

The sequenced device plane (kind 4) is NOT a row here: it is an SPMD
program both processes enter, not a byte mover, and is consulted before
this table by ``_encode_data``.

Per-route observability: ``rpc_fabric_route_<route>_frames`` /
``_bytes`` Adders, where the ``bulk`` row splits into ``uds``/``tcp``
by how the socket's bulk conn was actually dialed.
"""
from __future__ import annotations

from typing import List

from ..butil import debug_sync as _dbg
from ..butil import flags as _flags

SHM = "shm"
BULK = "bulk"
XFER = "xfer"
INLINE = "inline"

# payload classes (thresholds differ; preserved from the pre-table code)
HOST = "host"          # joined host byte blobs (kind 0/3/6)
DEVICE = "device"      # device-array payloads (kind 1/2/5)
STREAM = "stream"      # stream DATA frames (FRAME_DATA_BULK/_SHM)

# label -> (frames Adder, bytes Adder).  Publish-only dict: entries are
# created exactly once under _counters_lock, READS are lock-free
# (dict.get is GIL-atomic and nothing is ever removed or replaced) —
# the PR-8 device-ref-registry discipline, because record() sits on the
# per-frame fast path.
_counters_lock = _dbg.make_lock("ici.route._counters_lock")
_counters = {}


def candidates(sock, cls: str, nbytes: int) -> List[str]:
    """Ordered candidate routes for one payload on ``sock``.  The caller
    tries them in order; a route that fails mid-frame degrades its plane
    and falls through to the next — nothing is committed to the control
    stream until a route accepted the bytes.

    Small payloads skip the descriptor planes entirely (below the
    class threshold the descriptor + claim round trip costs more than
    the inline copy); oversized-for-the-ring payloads skip shm without
    degrading it."""
    if cls == HOST:
        if nbytes < _flags.get_flag("ici_fabric_bulk_host_min"):
            return [INLINE]
    elif cls == STREAM:
        if nbytes < _flags.get_flag("ici_stream_bulk_threshold"):
            return [INLINE]
    out: List[str] = []
    if sock.plane_usable(SHM, nbytes):
        out.append(SHM)
    if sock.plane_usable(BULK, nbytes):
        out.append(BULK)
    if cls == DEVICE and sock.plane_usable(XFER, nbytes):
        out.append(XFER)
    out.append(INLINE)
    return out


def _counter_pair(label: str):
    """(frames, bytes) Adder pair for ``label`` — the publish-once /
    read-lock-free discipline in ONE place (dict.get is GIL-atomic and
    entries are only ever added; the module lock guards creation)."""
    pair = _counters.get(label)
    if pair is None:
        with _counters_lock:
            pair = _counters.get(label)
            if pair is None:
                from .. import bvar
                pair = _counters[label] = (
                    bvar.Adder(name=f"rpc_fabric_route_{label}_frames"),
                    bvar.Adder(name=f"rpc_fabric_route_{label}_bytes"))
    return pair


def record(sock, route: str, nbytes: int, frames: int = 1) -> None:
    """Count ``frames`` frame(s) on ``route``; the ``bulk`` row is
    labeled by the transport the socket's bulk conn actually uses
    (uds/tcp).  This sits on the per-frame fast path — see
    _counter_pair for the lock discipline."""
    if route == BULK:
        label = "uds" if getattr(sock, "_bulk_is_uds", False) else "tcp"
    else:
        label = route
    pair = _counter_pair(label)
    pair[0] << frames
    pair[1] << nbytes


def record_shm_stripe(stripe: int, nbytes: int, frames: int = 1) -> None:
    """Per-stripe shm accounting (``rpc_fabric_route_shm_stripe_<i>_
    frames/bytes``) — the route-assertion surface for the striped
    plane: a striped transfer is proven striped by these counters, not
    assumed.  Only the striped path records here (1-stripe planes keep
    the plain ``shm`` row, byte-identical to PR 10)."""
    pair = _counter_pair(f"shm_stripe_{stripe}")
    pair[0] << frames
    pair[1] << nbytes


def route_stats() -> dict:
    """Snapshot {label: {frames, bytes}} for /ici and the tools."""
    with _counters_lock:
        items = list(_counters.items())
    return {label: {"frames": f.get_value(), "bytes": b.get_value()}
            for label, (f, b) in items}


# ---- the unified plane-health event family (ici/plane_health.py) -------
#
# One taxonomy for EVERY data plane's health transitions:
# ``rpc_fabric_plane_<name>_<event>`` where event is ``down`` (UP ->
# DOWN, counted once per transition), ``reprobe`` (one revival attempt
# — a prober dial or a lapsed timer latch), ``revived`` (back UP), and
# ``ramp`` (the breaker's half-open gate cleared by real traffic after
# a revival).  Emitted ONLY by the PlaneHealth engine, so /vars shows
# the same four verbs for bulk, shm, device, xfer, and collective.
# Same publish-once/read-lock-free discipline as _counter_pair.

_plane_events = {}


def record_plane(name: str, event: str, n: int = 1) -> None:
    """Count one plane-health event (``down``/``reprobe``/``revived``/
    ``ramp``) for plane ``name``."""
    label = f"{name}_{event}"
    adder = _plane_events.get(label)
    if adder is None:
        with _counters_lock:
            adder = _plane_events.get(label)
            if adder is None:
                from .. import bvar
                adder = _plane_events[label] = bvar.Adder(
                    name=f"rpc_fabric_plane_{label}")
    adder << n


def plane_stats() -> dict:
    """Snapshot {``<plane>_<event>``: count} for /ici's ``planes``
    block and the chaos-matrix assertions."""
    with _counters_lock:
        items = list(_plane_events.items())
    return {label: a.get_value() for label, a in items}


# ---- the COLLECTIVE route (channels/collective_fanout.py) --------------
#
# Not a byte mover, so it is not a row in candidates(): a compiled
# fan-out is an SPMD program every participant enters, selected by the
# plane's own screen BEFORE any per-member RPC is issued.  What the
# table owns is its observability — the selected/degraded/revived
# event counters (per degrade reason), same publish-once/read-lock-free
# discipline as the byte-route pair above.  Event Adders are named
# ``rpc_fabric_route_collective_<event>[_<reason>]`` so they surface in
# /vars alongside the byte-route counters.

_events = {}


def record_collective(event: str, reason: str = "", n: int = 1) -> None:
    """Count one collective-route event (``selected``, ``degraded``,
    ``revived``, ``ineligible``, ``member_entries``, ...) with an
    optional reason suffix."""
    label = f"collective_{event}" + (f"_{reason}" if reason else "")
    adder = _events.get(label)
    if adder is None:
        with _counters_lock:
            adder = _events.get(label)
            if adder is None:
                from .. import bvar
                adder = _events[label] = bvar.Adder(
                    name=f"rpc_fabric_route_{label}")
    adder << n


def collective_stats() -> dict:
    """Snapshot {event_label: count} for /ici and the tools' route
    assertions."""
    with _counters_lock:
        items = list(_events.items())
    return {label: a.get_value() for label, a in items}
