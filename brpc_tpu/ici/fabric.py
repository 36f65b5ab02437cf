"""Multi-controller ici://: cross-process handshake + device data plane.

Reference analogue (SURVEY.md §3.5, src/brpc/rdma/rdma_endpoint.h:37-108):
RdmaEndpoint forms a connection with an out-of-band TCP handshake that
exchanges GID/QPN, then moves payloads over verbs with an explicit-ACK
window, freeing send buffers only on CQ completion.  The TPU translation:

  * **Out-of-band channel** — the JAX coordination service
    (jax.distributed): each process publishes its fabric contact info
    (control TCP address, transfer-server address, owned device ids) under
    a well-known KV key; peers resolve it with a blocking get.  This is
    the GID/QPN exchange.
  * **Control plane** — a plain TCP connection per socket pair carries
    protocol bytes (frames, meta — small) plus the window bookkeeping
    (CREDIT) and transfer completions (PULLED — the CQ-completion
    analogue).
  * **Data plane** — DEVICE payloads never ride the control TCP: the
    sender stages arrays on its jax.experimental.transfer server under a
    uuid (``await_pull``) and ships only a descriptor; the receiver pulls
    straight into its local device memory (on TPU pods this is a
    DMA-style fetch, the RDMA-READ model).  Source blocks stay pinned
    until the peer's PULLED ack — the rdma_endpoint.cpp:926 discipline.
  * **Flow control** — same credit window as the in-process IciSocket
    (rdma_endpoint.cpp:771): at most ``ici_socket_window_bytes``
    unconsumed bytes per socket; CREDIT frames replenish on consume.

Addressing: ``ici://k`` is position k in the GLOBAL jax.devices() list
(identical in every process); ownership is ``devices[k].process_index``.
``connect_any(ep)`` routes in-process targets through the zero-copy
IciSocket and remote ones through a FabricSocket transparently, so
Server/Channel code is identical single- or multi-controller.
"""
from __future__ import annotations

import ctypes
import itertools
import json
import os as _os
import socket as _pysocket
import struct
import threading
import time
from typing import Dict, List, Optional, Tuple

from ..butil import flags as _flags
from ..butil import debug_sync as _dbg
from ..butil import logging as log
from ..butil.iobuf import IOBuf, IOPortal, DEVICE
from ..rpc import errors
from ..rpc import fault_injection as _fi
from ..rpc import rpc_dump as _rdump
from ..rpc.socket import Socket
from . import device_plane as _dp
from . import plane_health as _ph
from . import route as _route
from .transport import CreditWindow, OrderedDelivery

_KV_PREFIX = "brpc_tpu/fabric/"

# Data-plane selection for cross-process payloads.  The native bulk plane
# (native/fabric.cpp: uuid-tagged frames over a dedicated TCP connection,
# synchronous-send custody) measured ~2.3 GB/s on a 1-core loopback host
# where the jax transfer-server pull path measured 0.23 GB/s serial /
# 0.5 GB/s pipelined.  On real TPU pods the transfer server is the
# premapped HBM->HBM DMA path that never stages through the host — set
# this flag False there to route device payloads over it instead.
_flags.define_flag("ici_fabric_bulk", True,
                   "cross-process fabric device payloads ride the native "
                   "bulk plane (False: jax transfer-server DMA pulls)")
# Host byte-blobs at least this large also ride the bulk plane (below it
# the descriptor + claim round trip costs more than the inline copy).
_flags.define_flag("ici_fabric_bulk_host_min", 64 * 1024,
                   "min host-chunk bytes routed over the bulk plane",
                   _flags.positive_integer)
# Bulk-plane payload delivery semantics.  True (default): a received
# device payload is delivered as a HOST-RESIDENT array zero-copied over
# the native receive buffer — the reference's RDMA contract exactly
# (rdma delivers into registered HOST memory, rdma_endpoint.cpp:926; the
# application moves bytes to the accelerator when it uses them, which on
# TPU pods is the H2D DMA stage).  False: eagerly device_put on arrival,
# paying a host->device copy before the read event fires — the
# in-process IciSocket's "resident before read" semantics, at ~2x the
# per-byte CPU on CPU-backend fabrics where the "device" is the host.
_flags.define_flag("ici_fabric_host_delivery", True,
                   "deliver fabric bulk payloads host-resident (False: "
                   "eager device_put before the read event)")
# Failure semantics.  A fabric socket is NOT terminal (the reference's
# resilience doctrine, src/brpc/socket.cpp SetFailed/HealthCheck): when
# its control channel dies, in-flight correlation ids fail fast and the
# endpoint is handed to rpc/health_check.py, which probes with
# exponential backoff + jitter until a reconnect (a fresh HELLO/bulk
# handshake under a NEW versioned socket id) can succeed.
_flags.define_flag("ici_fabric_health_check", True,
                   "hand failed fabric endpoints to the health checker "
                   "for backoff-probed revival")
# How long a bulk claim tolerates descriptor/payload skew between the
# control and bulk connections before declaring the bytes lost.  Chaos
# tests shrink this so a dropped bulk frame resolves quickly.
_flags.define_flag("ici_bulk_claim_timeout_s", 60.0,
                   "max seconds a bulk claim waits for its frame")
# Same-host SHARED-MEMORY ring tier (native/fabric.cpp nshm): when both
# ends of a fabric pair run on one host and both advertise the "shm"
# capability, the dialing side creates an mmap'd /dev/shm segment at
# handshake (two SPSC rings, one per direction) and payloads >= the
# bulk thresholds move through it — ONE sender copy into shared memory,
# ZERO receiver copies (claims are zero-copy views into the ring,
# retired on release: consume-to-release credit), no syscalls on the
# byte path, futex doorbells for wakeups.  Only the (uuid, len)
# slot-descriptor rides the control channel (kinds 5/6 + stream
# FRAME_DATA_SHM).  Death (segment kill, peer crash mid-slot, mapping
# failure) degrades to the UDS/TCP bulk tier through the same PR-2
# machinery and revives in the background (the shm revival handshake).
_flags.define_flag("ici_fabric_shm", True,
                   "same-host fabric pairs add the mmap ring bulk tier "
                   "(False: UDS/TCP bulk only)")
_flags.define_flag("ici_shm_ring_bytes", 32 * 1024 * 1024,
                   "per-direction shm ring capacity per socket pair",
                   _flags.positive_integer)
_flags.define_flag("ici_shm_send_timeout_s", 20.0,
                   "max seconds an shm ring send waits for space before "
                   "the plane is declared dead")
# STRIPED shm (ISSUE 12): on multi-core hosts the segment holds N
# independent SPSC ring pairs (per-stripe futex doorbells and locks) so
# concurrent sender/claimer threads stop serializing on one ring — the
# single-core shm plane is copy-count-bounded near 2x, and stripes are
# how the remaining headroom is reached when there are cores to use.
# The descriptor carries its stripe in the uuid's top byte; frames of
# one STREAM share a stripe (affinity by stream id) so per-stream
# ordering is decided by one ring, while unary bulk frames round-robin.
# Health stays plane-wide: one dead stripe degrades the whole plane
# IN-FRAME exactly like the single ring.  0 = auto (1 on a 1-core
# host — the v1 single-ring layout, byte-identical to PR 10 — else
# min(4, cores)).
_flags.define_flag("ici_shm_stripes", 0,
                   "SPSC ring-pair stripes per shm segment (0 = auto: "
                   "1 on 1-core hosts, else min(4, host cores))")

_SHM_STRIPE_SHIFT = 56          # stripe id rides the uuid's top byte


def _resolve_shm_stripes() -> int:
    n = int(_flags.get_flag("ici_shm_stripes"))
    if n <= 0:
        cores = _os.cpu_count() or 1
        return 1 if cores <= 1 else min(4, cores)
    return min(n, 64)
# Cross-process device plane: device payloads cross through the
# SEQUENCED xproc plane — every transfer (both directions) is assigned a
# slot in one total order agreed over the control channel
# (CollectiveSequencer), and each side's single executor enters it at
# that slot.  On backends with multi-controller collectives (TPU pods)
# the transfer is a compiled XLA program both processes enter (shard_map
# + ppermute over the 2-device submesh — the SPMD
# contract); elsewhere the bytes ride the native bulk plane under the
# SAME sequencer (ici_device_plane_xproc_compiled=auto — this repo's CPU
# jaxlib raises "Multiprocess computations aren't implemented on the CPU
# backend").  Eligibility still requires the master ici_device_plane
# flag and its platform gate (TPU by default; host meshes opt in via
# ici_device_plane_host_mesh).  A failed/refused post degrades to
# bulk/inline in the same frame and the plane re-probes after
# ici_device_plane_retry_s.
_flags.define_flag("ici_device_plane_xproc", True,
                   "route cross-process device payloads through the "
                   "sequenced device plane (compiled collectives on TPU "
                   "pods, bulk-carried under the same total order "
                   "elsewhere)")
_flags.define_flag("ici_device_plane_retry_s", 2.0,
                   "seconds a degraded fabric device plane waits before "
                   "re-probing")

_u8p = ctypes.POINTER(ctypes.c_uint8)


class _NativeBufOwner:
    """Releases a native receive buffer when the last numpy view over
    it is collected (chained via the view's base -> ctypes array ->
    ._owner).  The exactly-once release for zero-copy host delivery;
    ``release_fn`` is the plane's release entry point — the socket
    tier's ``brpc_tpu_fab_buf_release`` (recycles into the conn's
    buffer pool, frees when the conn is gone) or the shm tier's
    ``brpc_tpu_shm_release`` (retires the ring slot)."""

    __slots__ = ("_release", "_conn", "_ptr", "_len")

    def __init__(self, release_fn, conn, ptr, length):
        self._release, self._conn, self._ptr = release_fn, conn, ptr
        self._len = length

    def __del__(self):
        try:
            self._release(self._conn, self._ptr, self._len)
        except Exception:
            pass


def _ShmBufOwner(lib, conn, ptr, length):
    """Owner for an shm ring slot: releasing retires it — the
    consume-to-release credit return; the ring space becomes reusable
    for the producer only now, and after the conn closed the LAST
    release also unmaps the segment (the native side defers the munmap
    exactly for this).  Same exactly-once discipline as the socket
    tier's buffers, so it IS that owner with the shm release symbol."""
    return _NativeBufOwner(lib.brpc_tpu_shm_release, conn, ptr, length)


class _ShmOversize(Exception):
    """The frame can never fit this ring — route it elsewhere without
    degrading the (healthy) shm plane."""


def _bulk_lib():
    """The native core, when present and the bulk plane is enabled."""
    if not _flags.get_flag("ici_fabric_bulk"):
        return None
    from ..butil import native as _native
    return _native.load()

# control-channel frame types
_F_HELLO = 1       # json: {target_dev, client_dev, pid}
_F_HELLO_OK = 2
_F_HELLO_ERR = 3
_F_DATA = 4        # chunk list: host bytes + device descriptors
_F_CREDIT = 5      # u64 consumed bytes
_F_PULLED = 6      # u64 uuid — receiver finished pulling (CQ completion)
_F_FIN = 7
# bulk-plane degradation + revival (self-healing; the control channel
# stays the source of truth so every transition is ORDERED relative to
# the descriptors that reference the bulk plane).  Consecutive ops:
# DOWN (sender observed death; peer degrades too), REESTABLISH (json
# {bulk_key} — client re-parked a conn), OK (server claimed + attached
# it), ERR (claim failed/refused; client backs off, retries).
_F_BULK_DOWN, _F_BULK_REESTABLISH, _F_BULK_OK, _F_BULK_ERR = 8, 9, 10, 11
# connectionless liveness probe (rpc/health_check.py): answers whether a
# server is listening at ici://target WITHOUT creating a fabric socket
_F_PING = 12              # u32 target_dev
_F_PING_OK = 13
_F_PING_ERR = 14
# lame-duck announcement (rpc/server.py drain): the sender is draining —
# the receiver pulls the endpoint from its LBs NOW (no probe-timeout
# wait), stops issuing new work on this socket (logoff), and hands the
# endpoint to the health checker for revival after the restart.  Older
# peers ignore unknown frame types, so GOODBYE is compatible both ways.
_F_GOODBYE = 15
# device-plane total order (CollectiveSequencer): the socket's order
# master (server side) assigns every cross-process transfer a dense seq;
# a client-side send goes out with seq -1 in its kind-4 descriptor and
# receives its assignment in this frame (u64 uuid, i64 seq)
_F_DPLANE_SEQ = 16
# shm ring degradation + revival (mirrors the bulk row above, same
# consecutive DOWN/REESTABLISH/OK/ERR ops — REESTABLISH carries json
# {shm_seg}, a fresh segment for the server to attach + unlink): the
# control channel stays the source of truth so every transition is
# ORDERED relative to the kind-5/6 and FRAME_DATA_SHM descriptors that
# reference the ring.  Older peers ignore unknown frame types.
_F_SHM_DOWN, _F_SHM_REESTABLISH, _F_SHM_OK, _F_SHM_ERR = 17, 18, 19, 20
# read-loop dispatch for the two self-healing planes rides ONE table
# (op index = ftype - the plane's DOWN base, relying on the consecutive
# numbering above): {ftype: (plane, op)} with op 0..3 =
# down/reestablish/ok/err — see FabricSocket._on_plane_frame
_PLANE_FRAMES = {b + i: (w, i)
                 for w, b in (("bulk", _F_BULK_DOWN), ("shm", _F_SHM_DOWN))
                 for i in range(4)}
# Compiled collective fan-out announce (channels/collective_fanout.py):
# the fan-out client is the order master — it commits a fan-out group at
# a dense seq and announces it over each remote member's control channel
# (FIFO per member, so every member observes the client's order); the
# member accepts (PARKING the SPMD entry until the client's commit) or
# refuses with a reason, and a refusal/timeout degrades the client's
# collective route in-call.  Two-phase: only after EVERY member accepted
# does the client send GO — an accepted member must never enter a
# program a degraded client will not join (its serial entry runner
# would wedge on the rendezvous forever); parked entries expire on the
# announce timeout.  Older peers ignore unknown frame types; the client
# then degrades on the announce timeout — compatible both ways.
_F_COLL_CALL = 21    # json: {method, seq, devices, mapping, merge,
                     #        shape, dtype, uuid}
_F_COLL_OK = 22      # json: {uuid, pid} — member accepted + parked entry
_F_COLL_ERR = 23     # json: {uuid, pid, reason} — refused, degrade
_F_COLL_GO = 24      # json: {uuid} — commit: the parked entry runs
# Clock alignment (ici/clock.py) deliberately adds NO frame type: the
# NTP-style exchange piggybacks on the HELLO/HELLO_OK handshake (the
# client's wall t0 rides the HELLO json; HELLO_OK echoes it with the
# server's wall), so the chaos suite's deterministic control-frame
# counting — and the read loop — never see it.  The dialing side derives
# the peer offset ± RTT/2; since every pod-scope stitch query DIALS its
# members (client-side sockets), the querier always holds an estimate.

_HDR = struct.Struct("<BI")          # type, body length


def _send_frame(sock: _pysocket.socket, ftype: int, body: bytes) -> None:
    sock.sendall(_HDR.pack(ftype, len(body)) + body)


def _recv_exact(sock: _pysocket.socket, n: int) -> Optional[bytes]:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            return None
        buf += chunk
    return bytes(buf)


def _recv_frame(sock: _pysocket.socket) -> Optional[Tuple[int, bytes]]:
    hdr = _recv_exact(sock, _HDR.size)
    if hdr is None:
        return None
    ftype, length = _HDR.unpack(hdr)
    body = _recv_exact(sock, length) if length else b""
    if length and body is None:
        return None
    return ftype, body


class FabricNode:
    """Per-process fabric runtime: transfer server + control listener +
    the coordination-service registry."""

    _instance: Optional["FabricNode"] = None
    _lock = threading.Lock()

    # fablint guarded-state contract
    _GUARDED_BY = {
        "_xfer_conns": "_xfer_lock",
        "_next_uuid": "_uuid_lock",
    }

    def __init__(self):
        self.process_id = -1
        self.num_processes = 0
        self._kv = None
        self._xfer_server = None
        self._xfer_conns: Dict[int, object] = {}      # pid -> TransferConnection
        self._xfer_lock = _dbg.make_lock("FabricNode._xfer_lock")
        self._ctrl_listener: Optional[_pysocket.socket] = None
        self.ctrl_addr = ""
        self._uuid_lock = _dbg.make_lock("FabricNode._uuid_lock")
        self._next_uuid = 1
        self._peers: Dict[int, dict] = {}             # pid -> contact info
        self._accept_thread: Optional[threading.Thread] = None
        self._shutdown = False
        self._bulk_lib = None                         # native core handle
        self._bulk_listener = 0                       # fab listener handle
        self.bulk_addr = ""
        self.bulk_uds = ""
        self.host_ip = ""
        # same-host shm ring tier: probed at start (a denied /dev/shm
        # just leaves the capability un-advertised — clean degrade)
        self._shm_ok = False
        self._shm_nonce = _os.urandom(4).hex()
        self._shm_lib = None

    # ---- lifecycle -----------------------------------------------------
    @classmethod
    def instance(cls) -> Optional["FabricNode"]:
        with cls._lock:
            return cls._instance

    @classmethod
    def initialize(cls, coordinator_address: Optional[str] = None,
                   num_processes: Optional[int] = None,
                   process_id: Optional[int] = None,
                   host_ip: Optional[str] = None) -> "FabricNode":
        """Join the fabric.  Calls jax.distributed.initialize when the
        coordination service isn't up yet (the reference's equivalent is
        whatever launched the processes); then performs the handshake
        publication.  Idempotent per process.

        ``host_ip`` is the address PUBLISHED to peers; None (default)
        derives it from the route to the coordinator, so multi-host
        fabrics don't hand out 127.0.0.1 (ADVICE r2 finding)."""
        with cls._lock:
            if cls._instance is not None:
                return cls._instance
            node = FabricNode()
            node._start(coordinator_address, num_processes, process_id,
                        host_ip)
            cls._instance = node
            # deterministic pre-exit shutdown ordering: quiesce every
            # fabric reader thread (Python control readers AND native
            # bulk readers) before interpreter/static teardown can race
            # them — the exit-abort class of flake
            import atexit
            atexit.register(cls._atexit_quiesce)
            return node

    @classmethod
    def _atexit_quiesce(cls) -> None:
        with cls._lock:
            node = cls._instance
        if node is not None:
            try:
                node.quiesce()
            except Exception:
                pass

    def _start(self, coordinator_address, num_processes, process_id,
               host_ip) -> None:
        import jax
        from jax._src import distributed
        if distributed.global_state.client is None:
            jax.distributed.initialize(coordinator_address,
                                       num_processes=num_processes,
                                       process_id=process_id)
        self._kv = distributed.global_state.client
        self.process_id = distributed.global_state.process_id
        self.num_processes = distributed.global_state.num_processes
        if host_ip is None:
            host_ip = self._derive_host_ip(
                coordinator_address
                or getattr(distributed.global_state, "coordinator_address",
                           None))
        # data plane: transfer server (explicit TCP transport addresses —
        # the same-host "local" bulk transport is not usable in sandboxed
        # containers, and TCP is the portable choice; on real pods the
        # premapped DMA path takes over).  OPTIONAL: older jax builds
        # ship no jax.experimental.transfer at all — the fabric then
        # rides the native bulk plane for every payload (device refs
        # included), or inlines d2h bytes on the control channel when
        # that is missing too, and publishes no "xfer" contact.
        try:
            from jax.experimental import transfer
        except ImportError:
            transfer = None
        if transfer is not None:
            backend = jax.local_devices()[0].client
            self._xfer_server = transfer.start_transfer_server(
                backend, f"{host_ip}:0", [f"{host_ip}:0"])
        # control plane listener
        self._ctrl_listener = _pysocket.socket()
        self._ctrl_listener.setsockopt(_pysocket.SOL_SOCKET,
                                       _pysocket.SO_REUSEADDR, 1)
        self._ctrl_listener.bind((host_ip, 0))
        self._ctrl_listener.listen(64)
        self.ctrl_addr = "%s:%d" % self._ctrl_listener.getsockname()
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="fabric_accept", daemon=True)
        self._accept_thread.start()
        # bulk data plane (native/fabric.cpp) — optional: peers fall back
        # to transfer-server pulls when either side lacks it
        self.host_ip = host_ip
        lib = _bulk_lib()
        if lib is not None:
            port_out = ctypes.c_int()
            uds_out = ctypes.create_string_buffer(108)
            lh = lib.brpc_tpu_fab_listen(host_ip.encode(),
                                         ctypes.byref(port_out),
                                         uds_out, 108)
            if lh:
                self._bulk_lib = lib
                self._bulk_listener = lh
                self.bulk_addr = f"{host_ip}:{port_out.value}"
                self.bulk_uds = uds_out.value.decode()
        # shm ring capability probe: can this process create, map, and
        # unlink a segment?  A sandbox that denies /dev/shm fails here
        # once and the capability simply is not advertised — peers then
        # keep the socket bulk tier, byte-for-byte the old behavior.
        if lib is not None and hasattr(lib, "brpc_tpu_shm_create") \
                and _flags.get_flag("ici_fabric_shm"):
            probe = f"brpc_tpu_shm_probe.{_os.getpid()}"
            lib.brpc_tpu_shm_unlink(probe.encode())
            ph = lib.brpc_tpu_shm_create(probe.encode(), 64 * 1024)
            if ph:
                lib.brpc_tpu_shm_unlink(probe.encode())
                lib.brpc_tpu_shm_close(ph)
                self._shm_ok = True
                self._shm_lib = lib
        # the handshake publication (GID/QPN analogue)
        info = {
            "ctrl": self.ctrl_addr,
            "devices": [i for i, d in enumerate(jax.devices())
                        if d.process_index == self.process_id],
        }
        if self._xfer_server is not None:
            info["xfer"] = self._xfer_server.address()
        if self.bulk_addr:
            info["bulk"] = self.bulk_addr
            if self.bulk_uds:
                # same-host peers dial the abstract unix plane instead
                # (~3x loopback TCP bandwidth); "host" disambiguates
                # same-host from same-address-on-another-host
                info["bulk_uds"] = self.bulk_uds
                info["host"] = self.host_ip
        if self._shm_ok:
            # shm capability key: same-host peers (matching "host") may
            # hand us a segment name at HELLO; mixed-version or
            # shm-less peers never see an shm descriptor (we only bind
            # the ring when BOTH ends acked it)
            info["shm"] = 1
            info["host"] = self.host_ip
        if _flags.get_flag("ici_device_plane"):
            # device-plane capability advert (both ends must hold it:
            # one-sided entry into an SPMD program would hang forever).
            # Version 3 = sequenced AND traced kind-4 descriptors
            # (<IqQQ> src+seq+trace_id+parent_span_id, plus the
            # _F_DPLANE_SEQ assignment frame), advertised under a NEW
            # key so the treat-as-plane-less rule holds in BOTH
            # directions across every version pair: a v1/v2 peer checks
            # "dplane"/"dplane2" (absent here — it never sends its
            # narrower descriptors at us) and we check "dplane3"
            # (absent on v1/v2 — we never send <IqQQ> at it).
            info["dplane3"] = 3
        self._kv.key_value_set(_KV_PREFIX + str(self.process_id),
                               json.dumps(info))
        log.info("fabric: process %d/%d up ctrl=%s xfer=%s devices=%s",
                 self.process_id, self.num_processes, info["ctrl"],
                 info.get("xfer", "<unavailable>"), info["devices"])

    @staticmethod
    def _derive_host_ip(coordinator_address: Optional[str]) -> str:
        """The IP this host uses to reach the coordinator — the address
        peers can reach US on (every fabric member reaches the
        coordinator by construction).  A UDP connect never sends a
        packet; it just resolves the route."""
        if coordinator_address:
            host, sep, port = coordinator_address.rpartition(":")
            if not sep:                    # no port at all: 'hostname'
                host, port = coordinator_address, ""
            host = host.strip("[]")        # IPv6 '[::1]:1234' form
            s = _pysocket.socket(_pysocket.AF_INET, _pysocket.SOCK_DGRAM)
            try:
                # ValueError too: '[::]' or a port-less 'host:path' form
                # must fall back, not crash FabricNode.initialize
                s.connect((host, int(port) if port.isdigit() else 1))
                return s.getsockname()[0]
            except (OSError, ValueError):
                pass
            finally:
                s.close()
        return "127.0.0.1"

    def shutdown(self) -> None:
        self._shutdown = True
        try:
            if self._ctrl_listener is not None:
                self._ctrl_listener.close()
        except Exception:
            pass
        if self._bulk_listener and self._bulk_lib is not None:
            self._bulk_lib.brpc_tpu_fab_listener_close(self._bulk_listener)
            self._bulk_listener = 0

    def quiesce(self) -> None:
        """Close the listeners, sever every live fabric socket's control
        conn and JOIN its reader, then close+join every native bulk
        conn/listener reader (brpc_tpu_fab_quiesce).  After this returns
        no fabric thread is running, so exit-time teardown (CPython
        finalization, C++ static destructors) has nothing to race."""
        self.shutdown()
        t = self._accept_thread
        if t is not None and t.is_alive() \
                and t is not threading.current_thread():
            t.join(2.0)     # accept() returns once the listener closed
        try:
            from ..rpc.socket import list_sockets
            for s in list(list_sockets()):
                if isinstance(s, FabricSocket):
                    s.quiesce_reader()
        except Exception:
            pass
        lib = self._bulk_lib
        if lib is None:
            try:
                lib = _bulk_lib()
            except Exception:
                lib = None
        if lib is not None and hasattr(lib, "brpc_tpu_fab_quiesce"):
            try:
                lib.brpc_tpu_fab_quiesce()
            except Exception:
                pass

    # ---- registry ------------------------------------------------------
    def peer_info(self, pid: int, timeout_ms: int = 60000) -> dict:
        info = self._peers.get(pid)
        if info is None:
            raw = self._kv.blocking_key_value_get(_KV_PREFIX + str(pid),
                                                  timeout_ms)
            info = json.loads(raw)
            self._peers[pid] = info
        return info

    @staticmethod
    def device_owner(device_id: int) -> int:
        import jax
        return jax.devices()[device_id].process_index

    def xfer_connection(self, pid: int):
        # the dial happens OUTSIDE _xfer_lock: peer_info is a blocking
        # KV get (up to 60s on a slow-starting peer) and connect is a
        # network round trip — holding the lock across either would
        # stall every OTHER peer's transfer path behind one laggard
        # (fablint blocking-under-lock finding).  Two racing dialers
        # both connect; the loser's conn is dropped (same keep-first
        # contract as the device-plane program cache).
        with self._xfer_lock:
            conn = self._xfer_conns.get(pid)
        if conn is not None:
            return conn
        if self._xfer_server is None:
            raise ConnectionError(
                "transfer server unavailable in this jax build "
                "(jax.experimental.transfer missing)")
        conn = self._xfer_server.connect(self.peer_info(pid)["xfer"])
        with self._xfer_lock:
            kept = self._xfer_conns.setdefault(pid, conn)
        if kept is not conn:
            # lost the dial race: release OUR conn, it is a live
            # transfer-server resource, not a GC-able cache entry
            closer = getattr(conn, "close", None)
            if closer is not None:
                try:
                    closer()
                except Exception:
                    pass
        return kept

    def next_uuid(self) -> int:
        with self._uuid_lock:
            u = (self.process_id + 1) << 40 | self._next_uuid
            self._next_uuid += 1
            return u

    def stage(self, uuid: int, arrays: List) -> None:
        self._xfer_server.await_pull(uuid, arrays)

    # ---- server side ---------------------------------------------------
    def _accept_loop(self) -> None:
        while not self._shutdown:
            try:
                conn, _ = self._ctrl_listener.accept()
            except OSError:
                return
            # fablint: thread-quiesced(per-connection; exits when the handshake completes or refuses and the conn closes)
            threading.Thread(target=self._handshake_server, args=(conn,),
                             name="fabric_handshake", daemon=True).start()

    def _handshake_server(self, conn: _pysocket.socket) -> None:
        # every exit that does not hand `bulk_h` to a FabricSocket must
        # release the client's parked bulk connection — each failed
        # handshake (e.g. the retry-until-server-up startup race) would
        # otherwise leak one fd + reader thread in the native pending
        # map, under a key no one will ever claim (review finding)
        bulk_h = 0
        bulk_key = None
        shm_h = 0
        try:
            conn.setsockopt(_pysocket.IPPROTO_TCP, _pysocket.TCP_NODELAY, 1)
            fr = _recv_frame(conn)
            if fr is not None and fr[0] == _F_PING:
                # liveness probe: reply and close, no socket is created
                (target,) = struct.unpack("<I", fr[1])
                from .transport import _listeners, _listeners_lock
                with _listeners_lock:
                    up = target in _listeners
                _send_frame(conn, _F_PING_OK if up else _F_PING_ERR, b"")
                conn.close()
                return
            if fr is None or fr[0] != _F_HELLO:
                conn.close()
                return
            hello = json.loads(fr[1])
            bulk_key = hello.get("bulk_key")
            target = hello["target_dev"]
            plan = _fi.fabric_active()
            if plan is not None and plan.on_hello():
                _send_frame(conn, _F_HELLO_ERR, b"injected hello refusal")
                conn.close()
                self._reap_parked_bulk(bulk_key)
                return
            from .transport import _listeners, _listeners_lock
            with _listeners_lock:
                listener = _listeners.get(target)
            if listener is None:
                _send_frame(conn, _F_HELLO_ERR,
                            f"no server at ici://{target}".encode())
                conn.close()
                self._reap_parked_bulk(bulk_key)
                return
            # bulk plane binding: the client connected its bulk conn
            # BEFORE sending HELLO, so the claim usually returns at once.
            # A client that advertised a key it never connected must get
            # HELLO_ERR, not a silently bulk-less socket — it will send
            # bulk descriptors we could never resolve.
            if bulk_key:
                if self._bulk_listener and self._bulk_lib is not None:
                    bulk_h = self._bulk_lib.brpc_tpu_fab_accept(
                        self._bulk_listener, bulk_key.encode(),
                        15_000_000)
                if not bulk_h:
                    _send_frame(conn, _F_HELLO_ERR,
                                b"bulk plane binding failed")
                    conn.close()
                    return
            # shm ring tier: attach the segment the client created and
            # unlink it (the mapping outlives the name; a later crash
            # leaks nothing).  Attach failure is SOFT — the client only
            # binds its end on our explicit ack, so a missing ack
            # degrades the pair to the socket bulk tier cleanly.
            shm_name = hello.get("shm_seg")
            if shm_name and self._shm_ok and self._shm_lib is not None \
                    and _flags.get_flag("ici_fabric_shm"):
                refused = plan is not None and plan.on_shm_handshake()
                if not refused:
                    shm_h = self._shm_lib.brpc_tpu_shm_attach(
                        shm_name.encode())
                    if shm_h:
                        self._shm_lib.brpc_tpu_shm_unlink(
                            shm_name.encode())
            sock = FabricSocket(conn, local_dev=target,
                                remote_dev=hello["client_dev"],
                                peer_pid=hello["pid"], node=self)
            sock._attach_bulk(self._bulk_lib, bulk_h)
            bulk_h = 0                       # custody passed to the socket
            if shm_h:
                sock._attach_shm(self._shm_lib, shm_h)
                shm_h = 0
            sock.is_server_side = True
            # on_accept attaches the messenger BEFORE any frame can be
            # read — a reader that fires first would drain the input
            # event with no messenger and drop the first request
            listener.on_accept(sock)
            # clock-alignment piggyback (ici/clock.py): echo the
            # client's wall t0 with OUR wall stamp — the client bounds
            # our offset by its HELLO round trip.  Empty for old peers
            # unless the shm ack needs carrying.
            ok = {}
            if "wall_us" in hello:
                ok = {"t0": hello["wall_us"],
                      "wall_us": time.time_ns() // 1000}
            if sock.shm_bound():
                ok["shm"] = True
            ok_body = json.dumps(ok).encode() if ok else b""
            _send_frame(conn, _F_HELLO_OK, ok_body)
            sock.start_io()
        except Exception as e:
            log.error("fabric handshake failed: %s", e)
            try:
                conn.close()
            except Exception:
                pass
            if bulk_h and self._bulk_lib is not None:
                self._bulk_lib.brpc_tpu_fab_conn_close(bulk_h)
            else:
                self._reap_parked_bulk(bulk_key)
            if shm_h and self._shm_lib is not None:
                self._shm_lib.brpc_tpu_shm_close(shm_h)

    # A refused handshake's parked bulk conn is reaped with a short
    # NONZERO claim wait: the client dialed the bulk plane before sending
    # HELLO, but the acceptor thread may not have read the <klen><key>
    # binding header yet — a zero-timeout claim would miss that conn and
    # leak its fd + reader thread in Listener::pending forever (ADVICE r5).
    # 2 s comfortably covers the header race; the reap runs on the
    # per-handshake daemon thread, so the wait blocks no one else.
    _REAP_CLAIM_US = 2_000_000

    def _reap_parked_bulk(self, bulk_key: Optional[str]) -> None:
        """Claim-and-close a bulk conn the client parked for a handshake
        that is now being refused."""
        if not bulk_key or not self._bulk_listener \
                or self._bulk_lib is None:
            return
        h = self._bulk_lib.brpc_tpu_fab_accept(
            self._bulk_listener, bulk_key.encode(), self._REAP_CLAIM_US)
        if h:
            self._bulk_lib.brpc_tpu_fab_conn_close(h)

    # ---- client side ---------------------------------------------------
    def dial_bulk(self, peer_pid: int
                  ) -> Tuple[int, Optional[str], object, bool]:
        """Dial the peer's bulk listener and park a fresh conn under a
        unique key: (handle, key, lib, is_uds).  (0, None, lib, False)
        when either end lacks the native plane.  Shared by the initial
        connect and the degradation-recovery re-establishment path."""
        lib = _bulk_lib()
        bulk_h, bulk_key, is_uds = 0, None, False
        info = self.peer_info(peer_pid)
        if lib is not None and info.get("bulk"):
            bhost, _, bport = info["bulk"].rpartition(":")
            bulk_key = f"{self.process_id}:{self.next_uuid():x}"
            # same host -> abstract unix plane (measured ~3x loopback
            # TCP bandwidth); cross-host or failed -> TCP plane
            if info.get("bulk_uds") and info.get("host") == self.host_ip:
                bulk_h = lib.brpc_tpu_fab_connect_uds(
                    info["bulk_uds"].encode(), bulk_key.encode())
                is_uds = bool(bulk_h)
            if not bulk_h:
                bulk_h = lib.brpc_tpu_fab_connect(
                    bhost.encode(), int(bport), bulk_key.encode())
            if not bulk_h:
                bulk_key = None
        return bulk_h, bulk_key, lib, is_uds

    def shm_peer_ok(self, peer_pid: int) -> bool:
        """Both ends hold the shm capability AND share this host.  The
        flag is re-checked at CONNECT time (not just at the start-time
        probe) so a tool pinning the tier off after the node joined —
        rpc_press --bulk-plane uds — takes effect on every later
        socket."""
        if not self._shm_ok or not _flags.get_flag("ici_fabric_shm"):
            return False
        try:
            info = self.peer_info(peer_pid)
        except Exception:
            return False
        return bool(info.get("shm")) and info.get("host") == self.host_ip

    def create_shm_segment(self) -> Tuple[int, Optional[str], object]:
        """Create a fresh ring segment as the dialing side: (handle,
        name, lib); (0, None, None) when shm is unavailable.  The name
        rides the control channel (HELLO or the shm revival frame); the
        ATTACHING side unlinks after mapping, so the /dev/shm entry
        lives only for the handshake round trip."""
        if not self._shm_ok or self._shm_lib is None:
            return 0, None, None
        # /dev/shm is ONE namespace for the whole host, and the jax
        # process index repeats in every pod on it (and in every pytest
        # worker): the OS pid tells pods apart, the per-node nonce tells
        # a recycled pid from the dead process that leaked an entry
        name = (f"brpc_tpu_shm.{_os.getpid()}.{self._shm_nonce}."
                f"{self.next_uuid():x}")
        stripes = _resolve_shm_stripes()
        if stripes > 1 and hasattr(self._shm_lib, "brpc_tpu_shm_create2"):
            # striped v2 segment (multi-core hosts): the attacher reads
            # the stripe count from the header, no hello change needed
            h = self._shm_lib.brpc_tpu_shm_create2(
                name.encode(),
                int(_flags.get_flag("ici_shm_ring_bytes")), stripes)
        else:
            h = self._shm_lib.brpc_tpu_shm_create(
                name.encode(), int(_flags.get_flag("ici_shm_ring_bytes")))
        if not h:
            return 0, None, None
        return h, name, self._shm_lib

    def drop_shm_segment(self, h: int, name: Optional[str]) -> None:
        """Abandon a created-but-never-acked segment: close the handle
        and remove the directory entry (the attach never happened, so
        nobody else unlinked it)."""
        if self._shm_lib is None:
            return
        if h:
            self._shm_lib.brpc_tpu_shm_close(h)
        self.unlink_shm_segment(name)

    def unlink_shm_segment(self, name: Optional[str]) -> None:
        """The CREATOR's unlink, run on every exit of a handshake — the
        acked one too: the attacher unlinks after mapping, but an
        attacher killed between the two would leave the entry behind
        for the life of the host.  Idempotent (ENOENT is success)."""
        if name and self._shm_lib is not None:
            self._shm_lib.brpc_tpu_shm_unlink(name.encode())

    def ping(self, target_dev: int, timeout: float = 1.0) -> bool:
        """Probe whether ici://target_dev is served by its owner process,
        without creating a fabric socket — the health checker's
        reachability test for cross-process endpoints."""
        try:
            owner = self.device_owner(target_dev)
            info = self.peer_info(owner, timeout_ms=int(timeout * 1000))
            host, _, port = info["ctrl"].rpartition(":")
            with _pysocket.create_connection((host, int(port)),
                                             timeout=timeout) as conn:
                conn.settimeout(timeout)
                _send_frame(conn, _F_PING, struct.pack("<I", target_dev))
                fr = _recv_frame(conn)
                return fr is not None and fr[0] == _F_PING_OK
        except (OSError, ValueError, KeyError):
            return False

    def connect(self, target_dev: int, client_dev: int) -> "FabricSocket":
        owner = self.device_owner(target_dev)
        info = self.peer_info(owner)
        host, _, port = info["ctrl"].rpartition(":")
        conn = _pysocket.create_connection((host, int(port)), timeout=30)
        conn.setsockopt(_pysocket.IPPROTO_TCP, _pysocket.TCP_NODELAY, 1)
        # bulk plane: dial the peer's bulk listener FIRST so the key is
        # already parked when the control HELLO names it (both ends must
        # have the native core; either missing -> transfer-server path)
        bulk_h, bulk_key, lib, bulk_uds = self.dial_bulk(owner)
        # shm ring tier: create the segment BEFORE the HELLO that names
        # it; the server attaches during the handshake and unlinks, so
        # the /dev/shm entry lives only for this round trip.  Bound to
        # the socket only on an explicit ack — a refusing/older server
        # never sees an shm descriptor.
        shm_h, shm_name, shm_lib = (0, None, None)
        if self.shm_peer_ok(owner):
            shm_h, shm_name, shm_lib = self.create_shm_segment()
        hello = {"target_dev": target_dev, "client_dev": client_dev,
                 "pid": self.process_id,
                 # clock-alignment piggyback: our wall at HELLO send;
                 # the HELLO_OK echo + server wall bounds the peer
                 # offset by this round trip (±RTT/2, ici/clock.py)
                 "wall_us": time.time_ns() // 1000}
        if bulk_key:
            hello["bulk_key"] = bulk_key
        if shm_name:
            hello["shm_seg"] = shm_name
        t0_mono = time.monotonic_ns()
        try:
            _send_frame(conn, _F_HELLO, json.dumps(hello).encode())
            fr = _recv_frame(conn)
        except OSError:
            # a reset/timeout mid-handshake must not strand the already
            # -registered native bulk conn (fd + reader thread held by
            # the process-global registry — review finding) nor the
            # created-but-unattached shm segment
            conn.close()
            if bulk_h:
                lib.brpc_tpu_fab_conn_close(bulk_h)
            self.drop_shm_segment(shm_h, shm_name)
            raise
        if fr is None or fr[0] != _F_HELLO_OK:
            msg = fr[1].decode() if fr else "connection closed"
            conn.close()
            if bulk_h:
                lib.brpc_tpu_fab_conn_close(bulk_h)
            self.drop_shm_segment(shm_h, shm_name)
            raise ConnectionRefusedError(f"fabric: {msg}")
        echo = {}
        if fr[1]:
            try:
                echo = json.loads(fr[1])
            except ValueError:
                echo = {}
        if "wall_us" in echo:
            try:
                rtt_us = max(0, (time.monotonic_ns() - t0_mono) // 1000)
                # +1: a 0 bound would claim perfection no measurement
                # can prove
                from . import clock as _clock
                _clock.record(
                    owner,
                    echo["wall_us"] - (echo["t0"] + rtt_us / 2.0),
                    rtt_us / 2.0 + 1.0)
            except (ValueError, KeyError, TypeError):
                pass          # old peer / malformed echo: no estimate
        sock = FabricSocket(conn, local_dev=client_dev,
                            remote_dev=target_dev, peer_pid=owner, node=self)
        if bulk_h:
            sock._bulk_is_uds = bulk_uds
            sock._attach_bulk(lib, bulk_h)
        if shm_h:
            if echo.get("shm"):
                sock._attach_shm(shm_lib, shm_h)
                self.unlink_shm_segment(shm_name)
            else:
                # server did not ack (older peer, refused, or attach
                # failed): the segment must not leak
                self.drop_shm_segment(shm_h, shm_name)
        sock.start_io()
        return sock


class CollectiveSequencer:
    """Direction-spanning total order for one socket pair's device-plane
    transfers — the pod-scale sequencer that closes the PR-3 open item
    (docs/PARITY.md: per-direction executors ordered each direction's
    collectives but let the two directions interleave differently on the
    two processes, a guaranteed SPMD ordering mismatch under
    bidirectional load).

    One sequencer replaces both per-direction executors, agreed over the
    serial control channel:

      * the socket's SERVER side is the order master: it assigns a dense
        sequence number to EVERY transfer, both directions — its own
        sends at encode time, the client's sends the moment their
        descriptor arrives on the control read loop (before anything
        executes);
      * a master-side send carries its seq inside the kind-4 descriptor;
        a client-side send goes out with seq -1 and receives its
        assignment via an ``_F_DPLANE_SEQ`` control frame;
      * each side runs ONE executor thread admitting transfers strictly
        in seq order, so both processes enter transfer k's collective
        only after both executed 0..k-1 — the total order is the
        master's assignment order, identical on both ends regardless of
        how the directions interleaved.

    Progress: at the lowest unexecuted seq, the sender half never waits
    on executor progress of the peer (a compiled collective parks inside
    the XLA runtime until the peer joins; the bulk-carried leg's send is
    a plain write), so the receiver half's wait always resolves —
    lockstep advance, no deadlock.

    The assignment stream is valid for exactly one socket incarnation
    (seqs restart at 0 with each fresh HELLO under a new socket id);
    ``epoch`` records the pod epoch at creation for observability —
    "epoch-ordered" means every incarnation's order is anchored to the
    membership epoch it was created under."""

    def __init__(self, sock: "FabricSocket", master: bool,
                 epoch: int = 0):
        import collections
        self.sock = sock
        self.master = master
        self.epoch = epoch
        self._cv = threading.Condition(
            _dbg.make_lock("CollectiveSequencer._lock"))
        self._next_assign = 0            # master's assignment counter
        self._next_exec = 0              # both sides' execution cursor
        self._ready: Dict[int, object] = {}        # seq -> transfer
        self._unassigned: Dict[int, object] = {}   # uuid -> parked send
        self._closed = False
        # uuids in execution order (bounded; the cross-process order-
        # equality assertions in tests/test_pod.py read this)
        self.executed = collections.deque(maxlen=4096)
        # fablint: thread-quiesced(close() sets _closed and notifies; the run loop fails leftovers and exits)
        self._thread = threading.Thread(
            target=self._run_loop,
            name=f"fabric_dplane_seq_{sock.remote_dev}", daemon=True)
        self._thread.start()

    def submit_local(self, t) -> Optional[int]:
        """Admit a transfer THIS side is sending.  Returns the seq to
        encode into the descriptor — the assignment (master) or -1
        (client, parked until the master's _F_DPLANE_SEQ) — or None when
        the sequencer is closed (the caller fails the transfer and falls
        back in-frame)."""
        with self._cv:
            if self._closed:
                return None
            if self.master:
                seq = self._next_assign
                self._next_assign += 1
                self._ready[seq] = t
                self._cv.notify_all()
            else:
                self._unassigned[t.uuid] = t
                seq = -1
        if seq >= 0:
            _dp.plane().annotate_transfer(t, f"seq assigned {seq}")
        else:
            _dp.plane().annotate_transfer(
                t, "seq parked (awaiting master assignment)")
        return seq

    def submit_remote(self, t, seq: int) -> None:
        """Admit a transfer the PEER is sending (its kind-4 descriptor
        just arrived on the control read loop).  The master assigns an
        unassigned (-1) descriptor NOW and tells the peer — control-read
        ordering makes the assignment deterministic."""
        assign = None
        with self._cv:
            if self._closed:
                _dp.plane().fail_transfer(
                    t, "sequencer closed before execution")
                return
            if seq < 0:
                if not self.master:
                    # protocol violation: only the master assigns
                    _dp.plane().fail_transfer(
                        t, "unassigned descriptor at non-master")
                    return
                seq = assign = self._next_assign
                self._next_assign += 1
            self._ready[seq] = t
            self._cv.notify_all()
        _dp.plane().annotate_transfer(t, f"seq assigned {seq}")
        if assign is not None:
            try:
                self.sock._ctrl_send(_F_DPLANE_SEQ,
                                     struct.pack("<Qq", t.uuid, assign))
            except OSError:
                pass     # control death tears the whole socket down

    def on_assignment(self, uuid: int, seq: int) -> None:
        """Client side: the master's _F_DPLANE_SEQ for one of our parked
        sends — the transfer becomes executable at ``seq``."""
        with self._cv:
            t = self._unassigned.pop(uuid, None)
            if t is None:
                return
            if self._closed:
                # close() already ran: the run loop's leftover sweep can
                # no longer see this transfer (we just popped it), so
                # fail it here or the source pin leaks forever
                _dp.plane().fail_transfer(
                    t, "sequencer closed before execution")
                return
            self._ready[seq] = t
            self._cv.notify_all()
        _dp.plane().annotate_transfer(t, f"seq assigned {seq} "
                                         "(master reply)")

    def _run_loop(self) -> None:
        leftovers: List = []
        while True:
            with self._cv:
                while not self._closed \
                        and self._next_exec not in self._ready:
                    self._cv.wait(0.5)
                if self._closed:
                    leftovers = (list(self._ready.values())
                                 + list(self._unassigned.values()))
                    self._ready.clear()
                    self._unassigned.clear()
                    break
                t = self._ready.pop(self._next_exec)
                self._next_exec += 1
            self._execute(t)
        for t in leftovers:
            # teardown: everything still queued/parked can never execute
            # — fail it so completions fire and source pins release
            _dp.plane().fail_transfer(
                t, "socket torn down before execution")

    def _execute(self, t) -> None:
        sock = self.sock
        if sock.failed or sock._peer_gone():
            _dp.plane().fail_transfer(t, "socket failed before execution")
            return
        _dp.plane().annotate_transfer(
            t, "seq admit queue_wait_us="
               f"{(time.monotonic_ns() - t.posted_ns) // 1000}")
        plan = _fi.fabric_active()
        if plan is not None:
            plan.on_plane_op(sock, "device")    # SLOW chaos injector
        try:
            if _dp.xproc_compiled_ok():
                _dp.plane().execute_remote(t)
            else:
                sock._dplane_execute_bulk(t)
            self.executed.append(t.uuid)
        except Exception as e:
            # the transfer is already failed (completion signaled with
            # an error — delivery/claim paths observe it); latch the
            # plane so later frames keep bulk/inline
            sock._device_plane_down(f"execution failed: {e}")

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()

    def describe(self) -> dict:
        with self._cv:
            return {"master": self.master, "epoch": self.epoch,
                    "assigned": self._next_assign,
                    "executed": self._next_exec,
                    "queued": len(self._ready),
                    "awaiting_assignment": len(self._unassigned)}


class FabricSocket(CreditWindow, OrderedDelivery, Socket):
    """Cross-process ici socket: control TCP + transfer-server pulls,
    with the same credit window as the in-process IciSocket."""

    # fablint guarded-state contract: the bulk-plane handle swap
    # commutes under _bulk_lock (the PR-2 review-finding class),
    # staging under _staged_lock, inbox + credit batching under
    # _inbox_lock, device-plane executors under _dplane_lock.
    # The cumulative bulk byte counters are written by concurrent
    # writer threads (multiple streams share one socket) and so live
    # under _bulk_lock too.  Health/revival STATE (down flags, revival
    # wanted/running, the device re-probe latch) lives in the per-plane
    # PlaneHealth records (ici/plane_health.py, its own guard map) —
    # the bulk/shm records share _bulk_lock and the device record
    # shares _dplane_lock, so the old commute guarantees still hold.
    _GUARDED_BY = {
        "_bulk": "_bulk_lock",
        "_blib": "_bulk_lock",
        "_bulk_epoch": "_bulk_lock",
        "_reestab_pending": "_bulk_lock",
        "bulk_bytes_sent": "_bulk_lock",
        "bulk_bytes_claimed": "_bulk_lock",
        "_shm": "_bulk_lock",
        "_shm_dead": "_bulk_lock",
        "_shmlib": "_bulk_lock",
        "_shm_epoch": "_bulk_lock",
        "_shm_ring_bytes": "_bulk_lock",
        "_shm_stripes": "_bulk_lock",
        "_shm_dead_stripes": "_bulk_lock",
        "_shm_reestab_pending": "_bulk_lock",
        "shm_bytes_sent": "_bulk_lock",
        "shm_bytes_claimed": "_bulk_lock",
        "_staged": "_staged_lock",
        "_inbox": "_inbox_lock",
        "_consumed_unacked": "_inbox_lock",
        "_dplane_seq": "_dplane_lock",
        "_dplane_closed": "_dplane_lock",
    }

    def __init__(self, conn: _pysocket.socket, local_dev: int,
                 remote_dev: int, peer_pid: int, node: FabricNode,
                 window_bytes: Optional[int] = None):
        from .mesh import IciMesh
        mesh = IciMesh.default()
        super().__init__(remote_side=mesh.endpoint(remote_dev))
        self.local_side = mesh.endpoint(local_dev)
        self.local_dev = local_dev
        self.remote_dev = remote_dev
        self.peer_pid = peer_pid
        self.node = node
        self._conn = conn
        self._conn_wlock = _dbg.make_lock("FabricSocket._conn_wlock")
        self._inbox = IOBuf()
        self._inbox_lock = _dbg.make_lock("FabricSocket._inbox_lock")
        self.read_chunk_hint = 1 << 26    # _do_read cuts, never allocates
        # input events run the parse loop INLINE on the delivering thread
        # (the control read loop for host frames): a tasklet spawn +
        # park/wake per frame measured ~1/3 of the per-frame fixed cost
        # on the streaming tier.  Order-sensitive stream frames are
        # consumed inside the parse loop as always; full RPC messages
        # are queued to tasklets (queue_last_message) so user handlers
        # can never stall the control channel's CREDIT/PULLED processing.
        self.queue_last_message = True
        self._consumed_unacked = 0     # credits not yet returned (batched)
        self._peer_closed = False      # reader-visible EOF (ordered)
        self._conn_dead = False        # writer-visible death (immediate)
        self._fin_code = 0             # peer's close code (FIN body)
        self._init_window(window_bytes)
        self._init_delivery()
        self._staged: Dict[int, Tuple] = {}    # uuid -> (src_block, array)
        self._staged_lock = _dbg.make_lock("FabricSocket._staged_lock")
        self._reader: Optional[threading.Thread] = None
        self._bulk = 0                         # native bulk conn handle
        self._blib = None
        # bulk-plane self-healing state.  _bulk_lock guards the handle
        # swap (degrade/re-attach race writers and the read loop);
        # the cumulative counters survive re-attachment so tests can
        # assert threshold routing was actually restored.
        self._bulk_lock = _dbg.make_lock("FabricSocket._bulk_lock")
        self._bulk_epoch = 0                   # attachments so far
        self.bulk_bytes_sent = 0               # cumulative, across epochs
        self.bulk_bytes_claimed = 0
        self._reestab_pending: Optional[Tuple] = None   # (lib, handle)
        self._reestab_ok = False
        self._reestab_evt = threading.Event()
        # shm ring tier (same-host peers; bound only after BOTH ends
        # acked the segment at handshake).  Shares _bulk_lock: the two
        # bulk planes' handle swaps commute under one lock and every
        # writer already holds it on this path.
        self._shm = 0                          # native shm conn handle
        self._shm_dead = 0                     # retired ring, claim-only
        self._shmlib = None
        self._shm_epoch = 0                    # attachments so far
        self._shm_ring_bytes = 0               # per-direction capacity
        self._shm_stripes = 1                  # ring pairs in the segment
        self._shm_dead_stripes = 1             # stripes of the retired ring
        # round-robin stripe cursor for unary bulk frames (streams pin
        # a stripe by affinity instead); itertools.count is GIL-atomic
        self._shm_rr = itertools.count().__next__
        self.shm_bytes_sent = 0                # cumulative, across epochs
        self.shm_bytes_claimed = 0
        self._bulk_is_uds = False              # route-counter label only
        self._shm_peer = node.shm_peer_ok(peer_pid)
        self._shm_reestab_pending: Optional[Tuple] = None  # (lib, h, name)
        self._shm_reestab_ok = False
        self._shm_reestab_evt = threading.Event()
        # kind-1 transfer-server staging needs the module on BOTH ends:
        # ours to stage, the peer's to pull.  A peer whose jax build
        # lacks jax.experimental.transfer publishes no "xfer" contact —
        # staging to it would fail its first pull, so such pairs use the
        # inline d2h fallback instead (review finding)
        self._xfer_usable = (node._xfer_server is not None
                             and "xfer" in node.peer_info(peer_pid))
        # cross-process device plane (kind-4): sequenced transfers both
        # processes execute in ONE agreed total order (CollectiveSequencer
        # — compiled collectives on capable backends, bulk-carried
        # elsewhere).  Down-latched on failure with a timed re-probe.
        # Capability advert version 3 = sequenced + traced descriptors
        # (<IqQQ>) under the "dplane3" key; older peers' narrower wire
        # formats are not spoken anymore, and they never send at us
        # either (they key on "dplane"/"dplane2", which v3 no longer
        # publishes).
        self._dplane_peer = \
            node.peer_info(peer_pid).get("dplane3", 0) >= 3
        self._dplane_lock = _dbg.make_lock("FabricSocket._dplane_lock")
        self._dplane_seq: Optional[CollectiveSequencer] = None   # lazy
        self._dplane_closed = False
        self.dplane_bytes_sent = 0         # cumulative, for tests/builtin
        self.dplane_bytes_recv = 0
        self.dplane_fallbacks = 0
        # ---- plane-health records (ici/plane_health.py) ----------------
        # ONE shared engine owns every plane's UP/DOWN/REESTABLISHING
        # bookkeeping, revival policy, and the unified
        # rpc_fabric_plane_* counters; this socket keeps only the
        # MECHANICS (dial, handshake payloads, teardown, native alive
        # probes).  bulk/shm records share _bulk_lock with the handle
        # swap — the instant-death suppression needs health flags and
        # handles deciding under ONE lock hold — and the device record
        # shares _dplane_lock with the sequencer state.
        def _gone():
            return self.failed or self._peer_gone()

        self._plane_bulk = _ph.register_plane(
            "bulk", self._bulk_lock,
            probe=lambda n: bool(self._bulk_alive()),
            gate=lambda: not (self.is_server_side or _gone()),
            prober=self._bulk_revive_attempt,
            attached=lambda: bool(self._bulk),
            dead=_gone,
            thread_name="fabric_bulk_revive",
            seed=self.id ^ 0x5DEECE66D)
        self._plane_shm = _ph.register_plane(
            "shm", self._bulk_lock,
            probe=self.shm_route_usable,
            gate=lambda: not (self.is_server_side or _gone()
                              or not self._shm_peer),
            prober=self._shm_revive_attempt,
            attached=lambda: bool(self._shm),
            dead=_gone,
            thread_name="fabric_shm_revive",
            seed=self.id ^ 0x73686D)
        self._plane_device = _ph.register_plane(
            "device", self._dplane_lock,
            retry_s=lambda: _flags.get_flag("ici_device_plane_retry_s"),
            on_reprobe=lambda: log.info(
                "fabric %s: device plane re-probing", self.remote_side))
        self._plane_xfer = _ph.register_plane(
            "xfer", _dbg.make_lock("FabricSocket._xfer_plane_lock"),
            probe=lambda n: self._xfer_usable,
            retry_s=lambda: _flags.get_flag("ici_device_plane_retry_s"))
        self._planes = {"bulk": self._plane_bulk, "shm": self._plane_shm,
                        "device": self._plane_device,
                        "xfer": self._plane_xfer}

    def _attach_bulk(self, lib, handle: int) -> None:
        """Bind the native bulk data-plane connection (both ends hold one
        fab conn per fabric socket pair; 0 = transfer-server fallback).
        Re-attachment (bulk revival) closes any stale handle and bumps
        the epoch; chaos plans get to poison the fresh conn here."""
        old = 0
        with self._bulk_lock:
            old, self._bulk = self._bulk, handle
            self._blib = lib
            if handle:
                self._bulk_epoch += 1
        if old and lib is not None:
            lib.brpc_tpu_fab_conn_close(old)
        if handle:
            if hasattr(lib, "brpc_tpu_fab_set_peer"):
                # per-pair plane registry: the /ici page and pod
                # observability aggregate native planes by peer pid
                lib.brpc_tpu_fab_set_peer(handle, self.peer_pid)
            plan = _fi.fabric_active()
            if plan is not None:
                plan.on_bulk_attach(self, lib, handle)
            # an INITIAL attach finds the record UP and counts nothing;
            # a re-attach flips DOWN/REESTABLISHING back to UP and arms
            # the breaker's half-open ramp
            self._plane_bulk.revived()

    # ---- bulk-plane degradation + revival ------------------------------
    # Bulk death with a LIVE control channel no longer kills the socket:
    # the handle is dropped (writers route inline / via the transfer
    # server from the next frame on), the peer is told via the plane
    # down-notify frame, and the client side re-establishes in the
    # background.  The STATE machine — down/reestablishing flags,
    # exponential backoff + jitter, instant-death suppression, the
    # unified counters — lives in the shared PlaneHealth engine
    # (ici/plane_health.py); this socket supplies the MECHANICS: one
    # dial-and-handshake attempt (_bulk_revive_attempt) whose fresh
    # parked conn is bound through the revival handshake on the control
    # channel, whose serial ordering guarantees no descriptor can
    # reference the new conn before both ends attached it.

    def bulk_epoch(self) -> int:
        with self._bulk_lock:
            return self._bulk_epoch

    def _bulk_alive(self) -> int:
        """The bulk handle when usable, else 0.  A handle whose native
        conn died is degraded HERE — at a frame boundary, before any
        descriptor references it, which is what lets an in-progress
        stream fall back inline instead of stranding a descriptor whose
        bytes can never arrive."""
        with self._bulk_lock:
            h, lib = self._bulk, self._blib
        if not h:
            return 0
        if lib.brpc_tpu_fab_alive(h):
            return h
        self._bulk_plane_down("bulk conn dead at frame boundary")
        return 0

    def bulk_plane_failed(self) -> None:
        """Receiver-side hook (rpc/stream.py): a bulk claim failed.  The
        affected stream is failed by the caller; the SOCKET survives —
        only the bulk plane degrades and revival begins."""
        self._bulk_plane_down("bulk claim failed")

    def shm_plane_failed(self) -> None:
        """Receiver-side hook (rpc/stream.py): an shm claim failed —
        same socket-survives contract as bulk_plane_failed."""
        self._shm_plane_down("shm claim failed")

    def _bulk_plane_down(self, reason: str, notify: bool = True) -> None:
        with self._bulk_lock:
            h, self._bulk = self._bulk, 0
            lib = self._blib
        if not h:
            return                      # already degraded / never bound
        if lib is not None:
            lib.brpc_tpu_fab_conn_close(h)
        log.warning("fabric %s: bulk plane down (%s) — inline fallback "
                    "engaged", self.remote_side, reason)
        self._plane_bulk.mark_down(reason)
        if notify:
            self._plane_notify_down("bulk")
        # client side only (the engine's gate enforces it): ensure a
        # revival loop is running, at most one at a time
        self._plane_bulk.kick()

    def _plane_notify_down(self, which: str) -> None:
        """Tell the peer the plane died so it degrades too; the
        receiving side degrades with notify=False (no echo ping-pong)."""
        if self._peer_gone():
            return
        try:
            self._ctrl_send(
                _F_BULK_DOWN if which == "bulk" else _F_SHM_DOWN, b"")
        except OSError:
            pass

    def _bulk_revive_attempt(self) -> bool:
        """ONE re-dial + handshake attempt, run by the engine's backoff
        loop: dial a fresh conn, park it pending, and ask the server to
        claim it; the attach itself happens on the read loop
        (_on_bulk_reply) so descriptor ordering holds."""
        h, key, lib, is_uds = self.node.dial_bulk(self.peer_pid)
        if not h:
            return False
        self._bulk_is_uds = is_uds
        self._reestab_evt.clear()
        self._reestab_ok = False
        with self._bulk_lock:
            self._reestab_pending = (lib, h)
        try:
            self._ctrl_send(_F_BULK_REESTABLISH,
                            json.dumps({"bulk_key": key}).encode())
            ok = self._reestab_evt.wait(5.0) and self._reestab_ok
        except OSError:
            ok = False
        if ok:
            log.info("fabric %s: bulk plane re-established (epoch %d)",
                     self.remote_side, self.bulk_epoch())
            return True
        # timed out / refused: reclaim the pending handle unless the
        # read loop already attached it
        with self._bulk_lock:
            pending, self._reestab_pending = self._reestab_pending, None
        if pending is not None:
            lib.brpc_tpu_fab_conn_close(h)
        return False

    def _on_bulk_reestablish(self, req: dict) -> None:
        """Server side: claim the conn the client re-parked on our bulk
        listener and attach it; runs on the control read loop so the
        attach is ordered BEFORE any descriptor that will use it."""
        key = req.get("bulk_key")
        node = self.node
        ok = False
        plan = _fi.fabric_active()
        if plan is not None and plan.on_bulk_handshake(self):
            node._reap_parked_bulk(key)          # refuse deterministically
        elif key and node._bulk_listener and node._bulk_lib is not None:
            h = node._bulk_lib.brpc_tpu_fab_accept(
                node._bulk_listener, key.encode(), 2_000_000)
            if h:
                self._attach_bulk(node._bulk_lib, h)
                ok = True
        try:
            self._ctrl_send(_F_BULK_OK if ok else _F_BULK_ERR, b"")
        except OSError:
            pass

    def _on_bulk_reply(self, ok: bool) -> None:
        """Client side: _F_BULK_OK/_F_BULK_ERR from the server.  The
        attach happens HERE on the read loop — a descriptor following
        BULK_OK on the serial control channel then always finds the new
        handle bound."""
        with self._bulk_lock:
            pending, self._reestab_pending = self._reestab_pending, None
        if ok and pending is not None:
            self._attach_bulk(*pending)
        elif pending is not None:
            pending[0].brpc_tpu_fab_conn_close(pending[1])
            ok = False
        self._reestab_ok = ok and pending is not None
        self._reestab_evt.set()

    # ---- shm ring tier: attach / degrade / revive ----------------------
    # Mirrors the bulk-plane self-healing above: ring death with a live
    # control channel degrades to the socket bulk tier (route table),
    # the peer is told via the plane down-notify frame, and the CLIENT
    # side (the end that created the original segment) re-creates one
    # in the background — the same shared PlaneHealth engine drives the
    # state/backoff, this socket supplies one create-and-handshake
    # attempt (_shm_revive_attempt) whose serial control ordering
    # guarantees no kind-5/6 descriptor can reference the new ring
    # before both ends attached it.

    def _attach_shm(self, lib, handle: int) -> None:
        """Bind the shm ring pair (0 = no shm tier).  Re-attachment
        closes any stale handle and bumps the epoch; chaos plans get to
        poison the fresh ring here."""
        old = 0
        ring_bytes = 0
        stripes = 1
        if handle:
            st = (ctypes.c_uint64 * 6)()
            if lib.brpc_tpu_shm_stats(handle, st, 6) == 6:
                ring_bytes = int(st[5])
            if hasattr(lib, "brpc_tpu_shm_stripes"):
                stripes = int(lib.brpc_tpu_shm_stripes(handle)) or 1
        with self._bulk_lock:
            old, self._shm = self._shm, handle
            self._shmlib = lib
            if handle:
                self._shm_epoch += 1
                self._shm_ring_bytes = ring_bytes
                self._shm_stripes = stripes
        if old and lib is not None:
            lib.brpc_tpu_shm_close(old)
        if handle:
            plan = _fi.fabric_active()
            if plan is not None:
                plan.on_shm_attach(self, lib, handle)
            # initial attach: no-op (record UP); re-attach: revival
            self._plane_shm.revived()

    def shm_bound(self) -> bool:
        with self._bulk_lock:
            return bool(self._shm)

    def shm_epoch(self) -> int:
        with self._bulk_lock:
            return self._shm_epoch

    def _shm_alive(self) -> int:
        """The shm handle when usable, else 0 — death is detected HERE,
        at a frame boundary, before any descriptor references the ring
        (the same degradation discipline as _bulk_alive)."""
        with self._bulk_lock:
            h, lib = self._shm, self._shmlib
        if not h:
            return 0
        if lib.brpc_tpu_shm_alive(h):
            return h
        self._shm_plane_down("shm ring dead at frame boundary")
        return 0

    def shm_route_usable(self, nbytes: int) -> bool:
        """Route-table health/capability probe: a live ring the payload
        is GUARANTEED to fit (an oversize payload skips shm WITHOUT
        degrading it — the ring is healthy, just small).  The bound is
        half the ring: a frame over ring/2 can land at a wrap position
        where remainder + footprint exceeds the ring and never fits no
        matter how far the consumer drains (the native send returns -3
        there — kept as the belt under this screen)."""
        with self._bulk_lock:
            h, ring = self._shm, self._shm_ring_bytes
        if not h:
            return False
        if ring and nbytes + 48 > ring // 2:
            return False
        return bool(self._shm_alive())

    def _shm_plane_down(self, reason: str, notify: bool = True) -> None:
        with self._bulk_lock:
            h, self._shm = self._shm, 0
            lib = self._shmlib
            old_dead = 0
            if h:
                # the retired ring stays CLAIMABLE (marked dead, not
                # closed): descriptors already flushed — or batched and
                # about to flush — reference bytes that are PUBLISHED
                # and parked in it, and the serial control channel may
                # deliver them to us after the shm down-notify that
                # caused this call.  Closing here would strand those claims
                # (rc -2) and kill their streams even though every byte
                # is sitting in the mapping.  Bounded at one retired
                # ring: a second death closes the first.
                old_dead, self._shm_dead = self._shm_dead, h
                # the retired ring keeps ITS stripe geometry for claims
                self._shm_dead_stripes = self._shm_stripes
                self._shm_stripes = 1
        if not h:
            return                      # already degraded / never bound
        if lib is not None:
            lib.brpc_tpu_shm_mark_dead(h)
            if old_dead:
                lib.brpc_tpu_shm_close(old_dead)
        log.warning("fabric %s: shm ring down (%s) — socket bulk tier "
                    "engaged", self.remote_side, reason)
        self._plane_shm.mark_down(reason)
        if notify:
            self._plane_notify_down("shm")
        # client side only (the end that created the original segment;
        # the engine's gate enforces it): ensure one revival loop
        self._plane_shm.kick()

    def _shm_revive_attempt(self) -> bool:
        """ONE re-create + handshake attempt, run by the engine's
        backoff loop: create a fresh segment, park it pending, and ask
        the server to attach it; our own attach happens on the read
        loop (_on_shm_reply) so descriptor ordering holds."""
        h, name, lib = self.node.create_shm_segment()
        if not h:
            return False
        self._shm_reestab_evt.clear()
        self._shm_reestab_ok = False
        with self._bulk_lock:
            self._shm_reestab_pending = (lib, h, name)
        try:
            self._ctrl_send(_F_SHM_REESTABLISH,
                            json.dumps({"shm_seg": name}).encode())
            ok = self._shm_reestab_evt.wait(5.0) and self._shm_reestab_ok
        except OSError:
            ok = False
        if ok:
            log.info("fabric %s: shm ring re-established (epoch %d)",
                     self.remote_side, self.shm_epoch())
            return True
        with self._bulk_lock:
            pending, self._shm_reestab_pending = \
                self._shm_reestab_pending, None
        if pending is not None:
            self.node.drop_shm_segment(pending[1], pending[2])
        return False

    def _on_shm_reestablish(self, req: dict) -> None:
        """Server side: attach the fresh segment the client created;
        runs on the control read loop so the attach is ordered BEFORE
        any descriptor that will use it."""
        name = req.get("shm_seg")
        node = self.node
        ok = False
        plan = _fi.fabric_active()
        if plan is not None and plan.on_shm_handshake(self):
            pass                                 # refuse deterministically
        elif name and node._shm_ok and node._shm_lib is not None \
                and _flags.get_flag("ici_fabric_shm"):
            h = node._shm_lib.brpc_tpu_shm_attach(name.encode())
            if h:
                node._shm_lib.brpc_tpu_shm_unlink(name.encode())
                self._attach_shm(node._shm_lib, h)
                ok = True
        try:
            self._ctrl_send(_F_SHM_OK if ok else _F_SHM_ERR, b"")
        except OSError:
            pass

    def _on_shm_reply(self, ok: bool) -> None:
        """Client side: _F_SHM_OK/_F_SHM_ERR.  The attach happens HERE
        on the read loop (descriptor-ordering, same as _on_bulk_reply)."""
        with self._bulk_lock:
            pending, self._shm_reestab_pending = \
                self._shm_reestab_pending, None
        if ok and pending is not None:
            self._attach_shm(pending[0], pending[1])
            self.node.unlink_shm_segment(pending[2])
        elif pending is not None:
            self.node.drop_shm_segment(pending[1], pending[2])
            ok = False
        self._shm_reestab_ok = ok and pending is not None
        self._shm_reestab_evt.set()

    def _on_plane_frame(self, which: str, op: int, body: bytes) -> None:
        """One read-loop dispatch row for both self-healing planes
        (_PLANE_FRAMES).  op 0: the peer observed the plane's death
        first — degrade without echoing (no notify ping-pong); the
        client side starts revival.  op 1: the client parked/created a
        fresh plane — the server attaches it HERE on the read loop, so
        the attach is ordered BEFORE any descriptor that will use it.
        op 2/3: the server's ok/err reply to our pending attempt."""
        if op == 0:
            if which == "bulk":
                self._bulk_plane_down(f"peer reported {which} death",
                                      notify=False)
            else:
                self._shm_plane_down(f"peer reported {which} death",
                                     notify=False)
        elif op == 1:
            req = json.loads(body)
            if which == "bulk":
                self._on_bulk_reestablish(req)
            else:
                self._on_shm_reestablish(req)
        else:
            if which == "bulk":
                self._on_bulk_reply(op == 2)
            else:
                self._on_shm_reply(op == 2)

    def _close_shm(self) -> None:
        """Socket-level teardown of the shm tier (no revival).  Claimed
        zero-copy views stay readable — the native side defers the unmap
        until the last release."""
        with self._bulk_lock:
            h, self._shm = self._shm, 0
            dead_h, self._shm_dead = self._shm_dead, 0
            pending, self._shm_reestab_pending = \
                self._shm_reestab_pending, None
            lib = self._shmlib
        if lib is not None:
            if h:
                lib.brpc_tpu_shm_close(h)
            if dead_h:
                lib.brpc_tpu_shm_close(dead_h)
        if pending is not None:
            self.node.drop_shm_segment(pending[1], pending[2])
        self._shm_reestab_evt.set()    # unblock a parked revival thread

    def describe_shm(self) -> Optional[dict]:
        """Ring-tier snapshot for the /ici builtin: byte totals, epoch,
        occupancy and doorbell waits from the native side."""
        with self._bulk_lock:
            h, lib = self._shm, self._shmlib
            stripes = self._shm_stripes
            out = {"epoch": self._shm_epoch,
                   "bytes_sent": self.shm_bytes_sent,
                   "bytes_claimed": self.shm_bytes_claimed,
                   "ring_bytes": self._shm_ring_bytes,
                   "stripes": stripes}
        if not h and not out["epoch"]:
            return None
        if h and lib is not None:
            st = (ctypes.c_uint64 * 6)()
            if lib.brpc_tpu_shm_stats(h, st, 6) == 6:
                out.update({"tx_occupancy": int(st[2]),
                            "rx_occupancy": int(st[3]),
                            "doorbell_waits": int(st[4])})
            if stripes > 1 and hasattr(lib, "brpc_tpu_shm_stripe_stats"):
                per = []
                for i in range(stripes):
                    if lib.brpc_tpu_shm_stripe_stats(h, i, st, 6) == 6:
                        per.append({"bytes_out": int(st[0]),
                                    "bytes_in": int(st[1]),
                                    "tx_occupancy": int(st[2]),
                                    "rx_occupancy": int(st[3]),
                                    "doorbell_waits": int(st[4])})
                out["stripe_stats"] = per
        return out

    # ---- device plane (kind-4 sequenced transfers) ---------------------
    def _dplane_usable(self, nbytes: int) -> bool:
        """Route this device payload through the sequenced cross-process
        device plane?  Needs the master+xproc flags, a peer that
        advertised the (v2, sequenced) capability, an eligible
        size/platform, a byte mover (the bulk plane, when this backend
        has no compiled multi-controller collectives), and a plane that
        is not down-latched (a lapsed latch re-probes)."""
        if not _flags.get_flag("ici_device_plane_xproc"):
            return False
        if not self._dplane_peer or not _dp.eligible(nbytes):
            return False
        if not _dp.xproc_compiled_ok() and not self._bulk_alive():
            return False       # bulk-carried leg needs a live bulk plane
        # the down-latch + lapsed-latch re-probe is the engine's
        # timer policy (the record shares _dplane_lock)
        return self._plane_device.usable(nbytes)

    def _dplane_sequencer(self) -> Optional["CollectiveSequencer"]:
        """The socket's (lazily created) collective sequencer; None after
        teardown.  Master role = server side, so exactly one end of the
        pair assigns."""
        with self._dplane_lock:
            if self._dplane_closed:
                return None
            seqr = self._dplane_seq
            if seqr is None:
                epoch = 0
                try:
                    from .pod import Pod
                    pod = Pod.current()
                    if pod is not None:
                        epoch = pod.epoch()
                except Exception:
                    pass
                seqr = self._dplane_seq = CollectiveSequencer(
                    self, master=self.is_server_side, epoch=epoch)
            return seqr

    def _device_plane_down(self, reason: str) -> None:
        """Degrade: device payloads ride the PR-2 bulk/inline machinery
        from the next frame until the re-probe deadline lapses (the
        engine's timer policy re-arms the deadline on repeat failures
        while counting/logging only the actual transition)."""
        first = self._plane_device.mark_down(reason)
        self.dplane_fallbacks += 1
        if first:
            log.warning("fabric %s: device plane down (%s) — bulk/inline "
                        "fallback engaged, re-probe in %.1fs",
                        self.remote_side, reason,
                        _flags.get_flag("ici_device_plane_retry_s"))

    def _dplane_execute_bulk(self, t) -> None:
        """The bulk-carried xproc leg: this backend has no compiled
        multi-controller collectives (the CPU jaxlib raises on them), so
        the payload's bytes cross on the native bulk plane under the
        SEQUENCED uuid — identical descriptors, total order, source
        pins, and CQ completions as the compiled leg; only the byte
        mover differs.  Runs on the sequencer's executor at this
        transfer's slot in the total order.  Failure fails the transfer
        (completion fires, pin releases) and re-raises so the plane
        latches down."""
        import numpy as np
        arr = t.source_array()
        try:
            if arr is not None:                    # sender half
                np_arr = np.asarray(arr)
                if not np_arr.flags["C_CONTIGUOUS"]:
                    np_arr = np.ascontiguousarray(np_arr)
                self._bulk_send(t.uuid, np_arr)
                _dp.plane().finish_remote(t, None)
            else:                                  # receiver half
                ca = self._claim_zero_copy(t.uuid, t.nbytes)
                with self._bulk_lock:
                    self.bulk_bytes_claimed += t.nbytes
                host = np.frombuffer(ca, dtype=np.uint8)
                if _flags.get_flag("ici_fabric_host_delivery"):
                    out = host                # zero-copy host delivery
                else:
                    import jax
                    owned = host.copy()
                    del host, ca              # owner releases the buffer
                    out = jax.device_put(
                        owned, _dp.plane().mesh().device(t.dst_dev))
                _dp.plane().finish_remote(t, out)
        except Exception as e:
            _dp.plane().fail_transfer(
                t, f"bulk-carried transfer failed: {e}")
            raise

    def _close_dplane(self) -> None:
        with self._dplane_lock:
            self._dplane_closed = True
            seqr = self._dplane_seq
        if seqr is not None:
            seqr.close()

    def describe_dplane_sequencer(self) -> Optional[dict]:
        """Locked snapshot of the sequencer state for the /ici builtin
        page (honors the _dplane_seq guarded-state contract)."""
        with self._dplane_lock:
            seqr = self._dplane_seq
        return None if seqr is None else seqr.describe()

    # ---- the route table's plane-health gate ---------------------------
    def plane_usable(self, plane: str, nbytes: int = 0) -> bool:
        """ONE health/capability gate for route.candidates(): engine
        state first (a down plane is skipped without probing; a lapsed
        timer latch re-probes), then the plane's own capability probe
        (ring fit, native alive check, xfer contact)."""
        rec = self._planes.get(plane)
        return rec is not None and rec.usable(nbytes)

    def _xfer_plane_down(self, reason: str) -> None:
        """Degrade the transfer-server route (today only chaos plans
        refusing a stage drive this): the xfer record rides the same
        timer-latch revival as the device plane, so a refused stage
        falls through in-frame and the route returns after the
        re-probe window."""
        if self._plane_xfer.mark_down(reason):
            log.warning("fabric %s: xfer plane down (%s) — inline "
                        "fallback engaged", self.remote_side, reason)

    def describe_planes(self) -> dict:
        """Per-plane health snapshots for the /ici builtin ``planes``
        block (state/reason/down_epoch/reprobe_in per plane)."""
        return {name: rec.snapshot()
                for name, rec in self._planes.items()}

    def start_io(self) -> None:
        self._reader = threading.Thread(target=self._read_loop,
                                        name="fabric_read", daemon=True)
        self._reader.start()

    def quiesce_reader(self, timeout: float = 2.0) -> None:
        """Deterministic teardown ordering: sever the control conn and
        JOIN the reader thread, so no fabric thread can race interpreter
        or C++ static teardown (the exit-abort class of flake).  Called
        from Server.stop after the socket failed, and from the process
        atexit quiesce."""
        try:
            self._conn.shutdown(_pysocket.SHUT_RDWR)
        except OSError:
            pass
        r = self._reader
        if r is not None and r.is_alive() \
                and r is not threading.current_thread():
            r.join(timeout)

    # ---- lame-duck (GOODBYE) -------------------------------------------
    def send_goodbye(self) -> None:
        """Server drain: tell the peer this endpoint is going lame-duck
        so it pulls it from LBs proactively instead of discovering the
        drain at the next health-check probe."""
        if self._peer_gone():
            return
        try:
            self._ctrl_send(_F_GOODBYE, b"")
        except OSError:
            pass

    def _on_goodbye(self) -> None:
        # runs on the control read loop of the RECEIVING side: stop
        # handing this socket out for new calls (SocketMap replaces
        # logoff sockets on next use) while in-flight responses and
        # stream frames keep flowing, and register the peer's drain
        self.logoff = True
        try:
            from ..rpc import lameduck
            lameduck.notify_peer_draining(self.remote_side)
        except Exception:
            pass

    def inflight_send_blocks(self) -> int:
        with self._staged_lock:
            return len(self._staged)

    def _peer_gone(self) -> bool:
        return self._peer_closed or self._conn_dead

    # ---- write path ----------------------------------------------------
    def _ctrl_send(self, ftype: int, body: bytes) -> None:
        """Every outbound control frame funnels through here: the one
        place the chaos harness can drop a frame (lossy link) or sever
        the control TCP (peer reset) deterministically."""
        plan = _fi.fabric_active()
        if plan is not None:
            action = plan.on_control_send(self)
            if action == _fi.DROP:
                return                   # bytes vanish
            if action == _fi.ERROR:
                # sever both directions: our read loop observes the
                # reset and runs the connection-over path, exactly as a
                # mid-conversation RST would
                try:
                    self._conn.shutdown(_pysocket.SHUT_RDWR)
                except OSError:
                    pass
                raise ConnectionError("fabric control channel: "
                                      "injected sever")
        if ftype in _PLANE_FRAMES and _rdump.dump_enabled():
            # A/B parity seam: the plane-healing handshake, as sent
            _rdump.maybe_dump_fabric_frame(self, "out", ftype, body)
        with self._conn_wlock:
            _send_frame(self._conn, ftype, body)

    def _do_write(self, data: IOBuf) -> int:
        # DEVICE bytes go a whole piece at a time, as on an IciSocket; the
        # header is charged (no borrowed window on this transport)
        device = self._device_lead(data) is not None
        n = self._consume_window(len(data), 0 if device else None)
        if n < 0:
            return -1
        frame = data.cut(n)
        body = self._encode_data(frame)
        try:
            self._ctrl_send(_F_DATA, body)
        except OSError as e:
            raise ConnectionError(f"fabric control channel: {e}")
        return n

    def _encode_data(self, frame: IOBuf) -> bytes:
        """Serialize a frame: host refs inline, DEVICE refs out-of-band.
        Byte-mover selection goes through the route table (ici/route.py
        — payload class × size × peer capability × plane health):
        same-host pairs prefer the shm ring (kind 5 device / kind 6
        host; one copy into shared memory, zero-copy claim), then the
        socket bulk conn (kind 2/3; synchronous-send custody), then
        transfer-server staging for device payloads (kind 1; pinned
        until the PULLED ack), then inline (kind 0).

        Degradation: every fast-plane use is health-gated and a failed
        send falls through to the NEXT route WITHIN the same frame —
        nothing is committed to the control stream until its bytes are
        already with a transport, so a dying plane can never strand a
        descriptor."""
        out = [b""]
        nchunks = 0
        pending_host: List[bytes] = []

        def flush_host():
            nonlocal nchunks
            if not pending_host:
                return
            blob = b"".join(pending_host)
            pending_host.clear()
            nchunks += 1
            for rt in _route.candidates(self, _route.HOST, len(blob)):
                if rt == _route.SHM:
                    uuid = self.shm_tag_uuid(self.node.next_uuid())
                    try:
                        self._shm_send(uuid, blob)
                    except _ShmOversize:
                        continue
                    except ConnectionError:
                        self._shm_plane_down("shm send failed mid-encode")
                        continue
                    out.append(struct.pack("<BQQ", 6, uuid, len(blob)))
                    _route.record(self, rt, len(blob))
                    return
                if rt == _route.BULK:
                    uuid = self.node.next_uuid()
                    try:
                        self._bulk_send(uuid, blob)
                    except ConnectionError:
                        self._bulk_plane_down(
                            "bulk send failed mid-encode")
                        continue
                    out.append(struct.pack("<BQQ", 3, uuid, len(blob)))
                    _route.record(self, rt, len(blob))
                    return
                break                              # INLINE
            out.append(struct.pack("<BI", 0, len(blob)))
            out.append(blob)
            _route.record(self, _route.INLINE, len(blob))

        for i in range(frame.backing_block_num()):
            r = frame.backing_block(i)
            if r.block.kind != DEVICE:
                pending_host.append(
                    bytes(r.block.host_view(r.offset, r.length)))
                continue
            arr = r.block.data
            if r.offset or r.length != len(arr):
                arr = arr[r.offset:r.offset + r.length]
            kind = 0
            # device plane first (kind 4): the payload crosses through
            # the sequenced xproc plane — a compiled XLA program both
            # processes enter in the agreed total order (or its
            # bulk-carried leg on backends without multi-controller
            # collectives).  A refused post degrades to the bulk/inline
            # machinery below WITHIN this same frame (the
            # descriptor-consistency rule: nothing is committed to the
            # control stream until its transport is decided).
            dplane_src = -1
            dplane_seq = -1
            dplane_trace = (0, 0)
            if (hasattr(arr, "devices")
                    and self._dplane_usable(r.length)):
                # the route's true source is wherever the array LIVES —
                # a process owns several devices and the receiver must
                # compile the identical (src, dst) submesh program, so
                # src rides the descriptor
                src_idx = _dp.mesh_index_of(arr)
                if src_idx >= 0 and src_idx != self.remote_dev:
                    try:
                        t = _dp.plane().post_send(
                            arr, src_idx, self.remote_dev,
                            socket=self, uuid=self.node.next_uuid(),
                            remote=True)
                        t.add_source_release(
                            getattr(r.block, "on_send_complete", None))
                        seqr = self._dplane_sequencer()
                        assigned = (seqr.submit_local(t)
                                    if seqr is not None else None)
                        if assigned is None:
                            # torn down between usable-check and submit:
                            # fail the posted WR (pin releases) and fall
                            # back in this same frame
                            _dp.plane().fail_transfer(
                                t, "sequencer closed before submit")
                            raise _dp.DevicePlaneError(
                                "device-plane sequencer closed")
                        dplane_seq = assigned
                        uuid = t.uuid
                        dplane_src = src_idx
                        dplane_trace = (t.trace_id, t.parent_span_id)
                        kind = 4
                        self.dplane_bytes_sent += r.length
                    except _dp.DevicePlaneError as e:
                        self._device_plane_down(str(e))
            if kind == 0:
                for rt in _route.candidates(self, _route.DEVICE,
                                            r.length):
                    if rt in (_route.SHM, _route.BULK):
                        # device -> host staging (on CPU backends a
                        # zero-copy view; on TPU the D2H leg of a
                        # host-staged fabric)
                        import numpy as np
                        np_arr = np.asarray(arr)
                        if not np_arr.flags["C_CONTIGUOUS"]:
                            np_arr = np.ascontiguousarray(np_arr)
                        uuid = self.node.next_uuid()
                        try:
                            if rt == _route.SHM:
                                uuid = self.shm_tag_uuid(uuid)
                                self._shm_send(uuid, np_arr)
                                kind = 5
                            else:
                                self._bulk_send(uuid, np_arr)
                                kind = 2
                        except _ShmOversize:
                            continue
                        except ConnectionError:
                            if rt == _route.SHM:
                                self._shm_plane_down(
                                    "shm send failed mid-encode")
                            else:
                                self._bulk_plane_down(
                                    "bulk send failed mid-encode")
                            continue
                        _route.record(self, rt, r.length)
                        # synchronous-send custody: the kernel/ring owns
                        # a copy, the source block is reusable now
                        cb = getattr(r.block, "on_send_complete", None)
                        if cb is not None:
                            try:
                                cb()
                            except Exception:
                                pass
                        break
                    if rt == _route.XFER:
                        plan = _fi.fabric_active()
                        if plan is not None and plan.on_xfer_stage(self):
                            # injected refusal: degrade the xfer record
                            # and fall through IN-FRAME (nothing is
                            # committed yet), like the planes above
                            self._xfer_plane_down("injected stage refusal")
                            continue
                        if not hasattr(arr, "devices"):
                            # forwarding a host-delivered numpy over an
                            # xfer-mode socket: the transfer server
                            # stages jax arrays only — detach into an
                            # owned copy (aliasing a ctypes-backed view
                            # is unsafe)
                            import jax
                            import numpy as np
                            arr = jax.device_put(
                                np.array(arr, copy=True),
                                jax.devices()[self.local_dev])
                        uuid = self.node.next_uuid()
                        self.node.stage(uuid, [arr])
                        with self._staged_lock:
                            self._staged[uuid] = (r.block, arr)
                        kind = 1
                        _route.record(self, rt, r.length)
                        break
                    break                          # INLINE
            if kind == 0:
                # neither fast plane: the device payload crosses as plain
                # host bytes on the control channel (d2h here, h2d on
                # first use at the peer — the same residency contract as
                # host delivery)
                pending_host.append(
                    bytes(r.block.host_view(r.offset, r.length)))
                continue
            flush_host()
            dt = str(arr.dtype).encode()
            shape = arr.shape
            out.append(struct.pack("<BQH", kind, uuid, len(dt)))
            out.append(dt)
            out.append(struct.pack("<B", len(shape)))
            out.append(struct.pack("<%dQ" % len(shape), *shape)
                       if shape else b"")
            out.append(struct.pack("<Q", r.length))
            if kind == 4:
                # src device + the sequencer's total-order slot (-1 when
                # this side is the client: the master assigns on receipt
                # and answers with _F_DPLANE_SEQ) + the trace context the
                # transfer belongs to (0,0 when the RPC wasn't sampled):
                # the RECEIVER parents its transfer span under the same
                # RPC span, so both halves land in one stitched trace
                out.append(struct.pack("<IqQQ", dplane_src, dplane_seq,
                                       dplane_trace[0], dplane_trace[1]))
            nchunks += 1
        flush_host()
        out[0] = struct.pack("<I", nchunks)
        return b"".join(out)

    def _bulk_send(self, uuid: int, data) -> None:
        """Blocking bulk-plane send (the GIL is dropped for the native
        write).  ``data``: bytes or a C-contiguous numpy array."""
        if isinstance(data, (bytes, bytearray)):
            ptr = (ctypes.c_uint8 * len(data)).from_buffer_copy(data) \
                if isinstance(data, bytearray) else \
                ctypes.cast(data, _u8p)
            n = len(data)
        else:
            ptr = data.ctypes.data_as(_u8p)
            n = data.nbytes
        with self._bulk_lock:
            h, lib = self._bulk, self._blib
        rc = lib.brpc_tpu_fab_send(h, uuid, ptr, n) if h else -1
        if rc != 0:
            raise ConnectionError("fabric bulk channel closed")
        with self._bulk_lock:
            # concurrent writers (streams share the socket) race this
            # cumulative counter; unguarded += lost updates (fablint)
            self.bulk_bytes_sent += n

    def shm_tag_uuid(self, uuid: int,
                     affinity: Optional[int] = None) -> int:
        """Stamp the chosen stripe into the uuid's top byte — the
        descriptor carries it to the claimer, so no wire format
        changes.  ``affinity`` pins a stripe (streams pass their stream
        id: per-stream ordering is decided by ONE ring); unary bulk
        frames round-robin.  A 1-stripe segment leaves the uuid
        untouched — the PR-10 shape, byte-identical."""
        with self._bulk_lock:
            n = self._shm_stripes
        if n <= 1:
            return uuid
        stripe = (affinity if affinity is not None
                  else self._shm_rr()) % n
        return (uuid & ~(0xff << _SHM_STRIPE_SHIFT)) | \
            (stripe << _SHM_STRIPE_SHIFT)

    @staticmethod
    def _shm_stripe_of(uuid: int, nstripes: int) -> int:
        """Decode the stripe a tagged uuid names; clamped so a
        malformed tag can never index out of range."""
        if nstripes <= 1:
            return 0
        return min(uuid >> _SHM_STRIPE_SHIFT, nstripes - 1)

    def _shm_send(self, uuid: int, data) -> None:
        """Blocking shm ring send (the GIL is dropped for the native
        copy; a full ring parks on the futex doorbell).  ``data``:
        bytes or a C-contiguous numpy array.  Raises _ShmOversize when
        the frame can never fit the ring (route elsewhere; the ring is
        healthy) and ConnectionError on death/timeout (degrade).  The
        uuid's top byte names the stripe (shm_tag_uuid)."""
        plan = _fi.fabric_active()
        if plan is not None:
            plan.on_plane_op(self, "shm")      # SLOW chaos injector
        if isinstance(data, (bytes, bytearray)):
            ptr = (ctypes.c_uint8 * len(data)).from_buffer_copy(data) \
                if isinstance(data, bytearray) else \
                ctypes.cast(data, _u8p)
            n = len(data)
        else:
            ptr = data.ctypes.data_as(_u8p)
            n = data.nbytes
        with self._bulk_lock:
            h, lib, stripes = self._shm, self._shmlib, self._shm_stripes
        timeout_us = int(
            _flags.get_flag("ici_shm_send_timeout_s") * 1e6)
        if not h:
            rc = -1
        elif stripes > 1:
            stripe = self._shm_stripe_of(uuid, stripes)
            rc = lib.brpc_tpu_shm_send2(h, stripe, uuid, ptr, n,
                                        timeout_us)
            if rc == 0:
                _route.record_shm_stripe(stripe, n)
        else:
            rc = lib.brpc_tpu_shm_send(h, uuid, ptr, n, timeout_us)
        if rc == -3:
            raise _ShmOversize()
        if rc != 0:
            raise ConnectionError("fabric shm ring closed")
        with self._bulk_lock:
            self.shm_bytes_sent += n

    # ---- stream fast plane ---------------------------------------------
    # Stream DATA frames above ici_stream_bulk_threshold post their
    # payload here (rpc/stream.py): bytes ride the dedicated bulk
    # connection under a reserved uuid, only a 16-byte descriptor rides
    # the control channel.  Custody is synchronous-send (the kernel owns
    # a copy when sendv returns) and delivery is zero-copy host-resident
    # (the claimed IOBuf wraps the native receive buffer) — the same
    # contract as the kind-2/3 attachment path above.

    def stream_fast_begin(self, nbytes: int,
                          affinity: Optional[int] = None
                          ) -> Tuple[int, Optional[str]]:
        """Route one stream DATA frame of ``nbytes``: (uuid, route) with
        route "shm"/"bulk", or (0, None) to keep the inline path.  The
        liveness check here is what lets a stream survive plane death: a
        dead plane is detected BEFORE the descriptor goes out, so the
        frame — and every later one until revival — rides the next tier
        instead.  ``affinity`` (the stream id) pins shm frames to one
        stripe so per-stream ordering is decided by a single ring."""
        for rt in _route.candidates(self, _route.STREAM, nbytes):
            if rt == _route.SHM:
                return self.shm_tag_uuid(self.node.next_uuid(),
                                         affinity), rt
            if rt == _route.BULK:
                return self.node.next_uuid(), rt
            break
        return 0, None

    def stream_bulk_begin(self) -> int:
        """Legacy single-plane reservation (bulk only); kept for callers
        that pin the socket bulk tier explicitly."""
        if not self._bulk_alive():
            return 0
        return self.node.next_uuid()

    def _gather_blocks(self, frame: IOBuf):
        """(ptrs, lens, n, total, keep) for a gather send — keep pins
        the block buffers until the native call returns."""
        import numpy as np
        nblocks = frame.backing_block_num()
        ptrs = (ctypes.c_void_p * nblocks)()
        lens = (ctypes.c_uint64 * nblocks)()
        keep = []                      # buffers must outlive the write
        n = 0
        total = 0
        for i in range(nblocks):
            r = frame.backing_block(i)
            if not r.length:
                continue
            a = np.frombuffer(r.block.host_view(r.offset, r.length),
                              dtype=np.uint8)
            keep.append(a)
            ptrs[n] = a.ctypes.data
            lens[n] = r.length
            total += r.length
            n += 1
        return ptrs, lens, n, total, keep

    def stream_fast_send(self, route: str, uuid: int,
                         frame: IOBuf) -> None:
        """Gather-send the frame's blocks as ONE uuid-tagged frame on
        the chosen plane, zero-copy hand-off (the native call drops the
        GIL; synchronous-send custody either way)."""
        if route == _route.SHM:
            ptrs, lens, n, total, keep = self._gather_blocks(frame)
            with self._bulk_lock:
                h, lib, stripes = self._shm, self._shmlib, \
                    self._shm_stripes
            timeout_us = int(
                _flags.get_flag("ici_shm_send_timeout_s") * 1e6)
            if not h:
                rc = -1
            elif stripes > 1:
                stripe = self._shm_stripe_of(uuid, stripes)
                rc = lib.brpc_tpu_shm_sendv2(h, stripe, uuid, ptrs,
                                             lens, n, timeout_us)
                if rc == 0:
                    _route.record_shm_stripe(stripe, total)
            else:
                rc = lib.brpc_tpu_shm_sendv(h, uuid, ptrs, lens, n,
                                            timeout_us)
            del keep
            if rc != 0:
                # descriptor already on the control channel: the peer's
                # claim fails and closes THAT stream; the socket only
                # degrades (rc -3 cannot happen: stream_fast_begin
                # screened the frame against the ring capacity)
                self._shm_plane_down("shm sendv failed")
                raise ConnectionError("fabric shm ring closed")
            with self._bulk_lock:
                self.shm_bytes_sent += total
            _route.record(self, _route.SHM, total)
            return
        self.stream_bulk_send(uuid, frame)
        _route.record(self, _route.BULK, len(frame))

    def stream_bulk_send(self, uuid: int, frame: IOBuf) -> None:
        """Gather-send the frame's blocks as ONE uuid-tagged bulk frame,
        zero-copy: block buffers are handed to writev as-is (fab_sendv
        drops the GIL; synchronous-send custody)."""
        ptrs, lens, n, total, keep = self._gather_blocks(frame)
        with self._bulk_lock:
            h, lib = self._bulk, self._blib
        rc = lib.brpc_tpu_fab_sendv(h, uuid, ptrs, lens, n) if h else -1
        del keep
        if rc != 0:
            # the descriptor for this frame is already on the control
            # channel: the peer's claim will fail and close THAT stream
            # (descriptor-consistency rule); this socket only degrades
            self._bulk_plane_down("bulk sendv failed")
            raise ConnectionError("fabric bulk channel closed")
        with self._bulk_lock:
            self.bulk_bytes_sent += total

    def stream_fast_abort(self, route: Optional[str]) -> None:
        """Sever the plane a descriptor went out on whose payload never
        will (sender-side Python failure): the peer's pending claim must
        fail promptly, not sit out the full claim timeout.  The failed
        claim closes the affected STREAM on the peer; the socket
        survives and the plane re-establishes in the background."""
        if route == _route.SHM:
            self._shm_plane_down("stream shm abort")
        else:
            self._bulk_plane_down("stream bulk abort")

    def stream_bulk_abort(self) -> None:
        self.stream_fast_abort(_route.BULK)

    def stream_bulk_claim(self, uuid: int, length: int) -> IOBuf:
        """Claim a stream DATA frame's bulk bytes as a zero-copy IOBuf:
        the USER block wraps the native receive buffer, released back to
        the conn's pool when the last ref dies (_NativeBufOwner)."""
        buf = IOBuf()
        buf.append_user_data(memoryview(self._claim_zero_copy(uuid, length)))
        with self._bulk_lock:
            self.bulk_bytes_claimed += length
        return buf

    def stream_shm_claim(self, uuid: int, length: int) -> IOBuf:
        """Claim a stream DATA frame's shm bytes as a zero-copy IOBuf:
        the USER block wraps the ring slot itself — released (ring
        credit returned) when the last ref dies (_ShmBufOwner)."""
        buf = IOBuf()
        buf.append_user_data(
            memoryview(self._shm_claim_zero_copy(uuid, length)))
        return buf

    def _claim_zero_copy(self, uuid: int, expect_len: int):
        """Claim a bulk frame of exactly ``expect_len`` bytes as a ctypes
        array WRAPPING the native receive buffer, with the exactly-once
        release chained through ``._owner`` — the one custody-critical
        sequence shared by stream claims and kind-2 host delivery."""
        ptr, n, h, lib = self._bulk_claim(uuid)
        if n != expect_len:
            lib.brpc_tpu_fab_buf_release(h, ptr, n)
            raise ConnectionError(
                f"bulk frame {uuid:#x}: {n} bytes, descriptor "
                f"said {expect_len}")
        ca = (ctypes.c_uint8 * n).from_address(
            ctypes.addressof(ptr.contents))
        # the owner pins the HANDLE the claim was served from: after a
        # degrade/re-attach, releasing against a closed handle falls
        # back to free() in the native layer — never a leak
        ca._owner = _NativeBufOwner(lib.brpc_tpu_fab_buf_release, h, ptr, n)
        return ca

    # ---- read path -----------------------------------------------------
    def _read_loop(self) -> None:
        try:
            while not self.failed:
                fr = _recv_frame(self._conn)
                if fr is None:
                    break
                plan = _fi.fabric_active()
                if plan is not None:
                    plan.on_control_recv(self)    # peer-crash chaos hook
                ftype, body = fr
                if ftype == _F_DATA:
                    self._on_data(body)
                elif ftype == _F_CREDIT:
                    self._on_credits(struct.unpack("<Q", body)[0])
                elif ftype == _F_PULLED:
                    self._on_pulled(struct.unpack("<Q", body)[0])
                elif ftype in _PLANE_FRAMES:
                    if _rdump.dump_enabled():
                        _rdump.maybe_dump_fabric_frame(
                            self, "in", ftype, body)
                    which, op = _PLANE_FRAMES[ftype]
                    self._on_plane_frame(which, op, body)
                elif ftype == _F_GOODBYE:
                    self._on_goodbye()
                elif ftype == _F_DPLANE_SEQ:
                    u, s = struct.unpack("<Qq", body)
                    seqr = self._dplane_sequencer()
                    if seqr is not None:
                        seqr.on_assignment(u, s)
                elif ftype == _F_COLL_CALL:
                    from ..channels import collective_fanout as _cf
                    _cf.on_remote_announce(self, json.loads(body))
                elif ftype == _F_COLL_OK:
                    from ..channels import collective_fanout as _cf
                    _cf.on_remote_reply(self, json.loads(body), ok=True)
                elif ftype == _F_COLL_ERR:
                    from ..channels import collective_fanout as _cf
                    _cf.on_remote_reply(self, json.loads(body), ok=False)
                elif ftype == _F_COLL_GO:
                    from ..channels import collective_fanout as _cf
                    _cf.on_remote_go(self, json.loads(body))
                elif ftype == _F_FIN:
                    if len(body) >= 4:
                        # the peer closed with an explicit code (lame-duck
                        # ELOGOFF): fail in-flight calls with IT, not the
                        # generic socket-death code
                        self._fin_code = struct.unpack("<I", body[:4])[0]
                    break
        except OSError:
            pass
        except Exception as e:
            # a malformed frame or failed pull must not strand the socket
            # with a silently-dead reader — surface it as a failure
            log.error("fabric read loop died on %s: %s", self.remote_side, e)
        self._on_connection_over()

    def _on_connection_over(self) -> None:
        """Connection teardown.  EOF must ride the ORDERED delivery
        queue: a graceful FIN can arrive while an earlier device-bearing
        frame is still awaiting its transfer-server pull — committing
        EOF first would make the reader see end-of-stream and drop the
        tail (ADVICE r2 finding; the reference's teardown completes in
        CQ order, rdma_endpoint.cpp:926).  Writers and pinned send
        blocks are released immediately — their acks can never arrive."""
        self._conn_dead = True
        self._wake_window()
        self._flush_staged()
        self._close_bulk()
        self._close_shm()
        self._close_dplane()

        def commit_eof():
            with self._inbox_lock:
                self._peer_closed = True
            if self._fin_code:
                # ordered behind every delivered frame: fail in-flight
                # calls with the peer's explicit close code (lame-duck
                # ELOGOFF) instead of the generic EOF
                self.set_failed(self._fin_code,
                                "peer server logged off (lame duck)")
                return
            self.start_input_event()

        self._enqueue_delivery([], commit_eof)

    def _flush_staged(self) -> None:
        with self._staged_lock:
            staged, self._staged = self._staged, {}
        for blk, _arr in staged.values():
            cb = getattr(blk, "on_send_complete", None)
            if cb is not None:
                try:
                    cb()
                except Exception:
                    pass

    def _on_data(self, body: bytes) -> None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import SingleDeviceSharding
        (nchunks,) = struct.unpack_from("<I", body, 0)
        off = 4
        # parts assemble into the delivered IOBuf at commit time: kind-4
        # outputs (device-plane transfers) do not exist until their
        # compiled program has run on the executor, so the buffer cannot
        # be built inline the way pure claim/pull kinds could
        parts: List = []
        pulled_uuids: List[int] = []
        waits: List = []
        local_device = jax.devices()[self.local_dev]
        for _ in range(nchunks):
            kind, = struct.unpack_from("<B", body, off)
            off += 1
            if kind == 0:
                (blen,) = struct.unpack_from("<I", body, off)
                off += 4
                parts.append(body[off:off + blen])
                off += blen
            elif kind == 3:
                uuid, blen = struct.unpack_from("<QQ", body, off)
                off += 16
                parts.append(self._bulk_claim_bytes(uuid, blen))
            elif kind == 6:
                uuid, blen = struct.unpack_from("<QQ", body, off)
                off += 16
                parts.append(self._shm_claim_bytes(uuid, blen))
            else:
                uuid, dtlen = struct.unpack_from("<QH", body, off)
                off += 10
                dt = body[off:off + dtlen].decode()
                off += dtlen
                (ndim,) = struct.unpack_from("<B", body, off)
                off += 1
                shape = struct.unpack_from("<%dQ" % ndim, body, off) \
                    if ndim else ()
                off += 8 * ndim
                (length,) = struct.unpack_from("<Q", body, off)
                off += 8
                if kind == 4:
                    src_dev, dseq, d_tid, d_psid = struct.unpack_from(
                        "<IqQQ", body, off)
                    off += 28
                    # device-plane descriptor: enqueue the matching recv
                    # at its slot in the total order (the rendezvous);
                    # when we are the master and the peer sent -1, the
                    # sequencer assigns here — on the control read loop,
                    # so assignment order is deterministic — and answers
                    # with _F_DPLANE_SEQ
                    t = _dp.plane().post_recv_remote(
                        uuid, length, src_dev=src_dev,
                        dst_dev=self.local_dev, socket=self,
                        trace_id=d_tid, parent_span_id=d_psid)
                    seqr = self._dplane_sequencer()
                    if seqr is None:
                        _dp.plane().fail_transfer(
                            t, "socket torn down before execution")
                    else:
                        seqr.submit_remote(t, dseq)
                    parts.append(t)
                    waits.append(t)
                    continue
                if kind in (2, 5):
                    claim = self._bulk_claim_array if kind == 2 \
                        else self._shm_claim_array
                    arr = claim(uuid, dt, shape, length, local_device)
                    # host-delivered numpy is resident by construction —
                    # only genuine device arrays gate ordered delivery
                    # on the device waiter
                    if hasattr(arr, "is_ready"):
                        waits.append(arr)
                else:
                    sds = jax.ShapeDtypeStruct(
                        shape, jnp.dtype(dt),
                        sharding=SingleDeviceSharding(local_device))
                    arr = self.node.xfer_connection(self.peer_pid).pull(
                        uuid, [sds])[0]
                    pulled_uuids.append(uuid)
                    waits.append(arr)
                parts.append(("dev", arr))

        def commit():
            from . import device_plane as _dpl
            buf = IOBuf()
            for p in parts:
                if isinstance(p, _dpl.DeviceTransfer):
                    if p.out is None or p.state == _dpl.FAILED:
                        # the payload can never be delivered and the
                        # control byte stream cannot be repaired — same
                        # terminal rule as a failed kind-2 claim
                        self.set_failed(
                            errors.EFAILEDSOCKET,
                            f"device-plane transfer {p.uuid:#x} failed: "
                            f"{p.error}")
                        return
                    self.dplane_bytes_recv += p.nbytes
                    buf.append_device_array(p.out)
                elif isinstance(p, tuple):
                    buf.append_device_array(p[1])
                else:
                    buf.append(p)
            # the PULLED ack (CQ completion): data is resident locally,
            # sender may reuse its source blocks
            for u in pulled_uuids:
                try:
                    self._ctrl_send(_F_PULLED, struct.pack("<Q", u))
                except OSError:
                    pass
            with self._inbox_lock:
                self._inbox.append(buf)
            self.start_input_event(inline=True)

        # ordered per-socket commit — a host-only frame must not jump
        # ahead of an earlier device-bearing frame still in flight
        self._enqueue_delivery(waits, commit)

    def _bulk_claim(self, uuid: int):
        # Bulk frames can trail their control descriptor (separate TCP
        # connections have no cross-ordering); the claim tolerates
        # ici_bulk_claim_timeout_s of skew before declaring the bytes
        # lost.  A frame parked BEFORE the conn died is still claimable
        # after it; a missing frame on a dead conn fails fast (-2).
        # Returns (ptr, len, handle, lib): callers MUST release against
        # the returned handle — their own snapshot could postdate a
        # degrade/re-attach and name a different conn than the one the
        # claim was served from (the buffer would then be free()d
        # instead of recycled into the owning conn's pool).
        with self._bulk_lock:
            h, lib = self._bulk, self._blib
        out, olen = _u8p(), ctypes.c_uint64()
        timeout_us = int(
            _flags.get_flag("ici_bulk_claim_timeout_s") * 1e6)
        rc = lib.brpc_tpu_fab_recv(
            h, uuid, timeout_us,
            ctypes.byref(out), ctypes.byref(olen)) if h else -2
        if rc != 0:
            # attachment frames surface this in _read_loop's catch-all ->
            # socket failure (the control byte stream cannot be repaired);
            # stream frames catch it in on_stream_frame and fail only the
            # stream (descriptor-consistency rule)
            raise ConnectionError(
                f"fabric bulk frame {uuid:#x} unclaimable (rc {rc})")
        return out, olen.value, h, lib

    def _bulk_claim_bytes(self, uuid: int, expect_len: int) -> bytes:
        ptr, n, h, lib = self._bulk_claim(uuid)
        try:
            if n != expect_len:
                raise ConnectionError(
                    f"bulk frame {uuid:#x}: {n} bytes, descriptor "
                    f"said {expect_len}")
            with self._bulk_lock:
                self.bulk_bytes_claimed += n
            return ctypes.string_at(ptr, n)
        finally:
            lib.brpc_tpu_fab_buf_release(h, ptr, n)

    def _bulk_claim_array(self, uuid: int, dt: str, shape, length: int,
                          local_device):
        """Claim a kind-2 frame and deliver it as an array.

        Host-delivery mode (default): ZERO-COPY — the numpy array wraps
        the native receive buffer directly, with an owner chained through
        numpy's base so the buffer is freed exactly when the last view
        dies.  This is the reference's RDMA delivery contract (bytes in
        registered host memory); first device use pays the H2D move.

        Eager mode: one owned numpy copy off the native buffer, then
        device_put.  The copy is NOT optional — device_put zero-copy
        ALIASES ctypes-backed donor views WITHOUT retaining them (proved
        by corrupted bounced payloads in the 2-process stress test, and
        by /tmp-scale repro: jax re-reads the donor after
        block_until_ready), so the native buffer may only be freed
        manually when device_put consumed an array it cannot alias
        unsafely (an owned copy)."""
        import numpy as np
        ca = self._claim_zero_copy(uuid, length)
        host = np.frombuffer(ca, dtype=np.uint8).view(
            np.dtype(dt)).reshape(shape)
        if _flags.get_flag("ici_fabric_host_delivery"):
            return host
        import jax
        np_arr = host.copy()          # the owned copy device_put may alias
        del host, ca                  # last refs: owner releases the buffer
        return jax.device_put(np_arr, local_device)

    # ---- shm ring claims (kinds 5/6 + FRAME_DATA_SHM) -------------------
    def _shm_claim(self, uuid: int):
        """(ptr, len, handle, lib) for one shm frame — the zero-copy
        twin of _bulk_claim, same skew-tolerant timeout, same release-
        against-the-served-handle custody rule.

        The RETIRED ring (if a degrade left one behind) is consulted
        FIRST: descriptors flushed around a plane death reference bytes
        published there, and asking a dead ring is instantaneous either
        way — parked frames return at once, missing ones fail -2
        without a wait.  Only then does the live ring get the full
        skew-tolerant timeout."""
        with self._bulk_lock:
            h, dead_h, lib = self._shm, self._shm_dead, self._shmlib
            stripes, dead_stripes = self._shm_stripes, \
                self._shm_dead_stripes
        out, olen = _u8p(), ctypes.c_uint64()
        if dead_h:
            if dead_stripes > 1:
                rc = lib.brpc_tpu_shm_recv2(
                    dead_h, self._shm_stripe_of(uuid, dead_stripes),
                    uuid, 0, ctypes.byref(out), ctypes.byref(olen))
            else:
                rc = lib.brpc_tpu_shm_recv(
                    dead_h, uuid, 0, ctypes.byref(out),
                    ctypes.byref(olen))
            if rc == 0:
                return out, olen.value, dead_h, lib
        timeout_us = int(
            _flags.get_flag("ici_bulk_claim_timeout_s") * 1e6)
        if not h:
            rc = -2
        elif stripes > 1:
            rc = lib.brpc_tpu_shm_recv2(
                h, self._shm_stripe_of(uuid, stripes), uuid, timeout_us,
                ctypes.byref(out), ctypes.byref(olen))
        else:
            rc = lib.brpc_tpu_shm_recv(
                h, uuid, timeout_us,
                ctypes.byref(out), ctypes.byref(olen))
        if rc != 0:
            raise ConnectionError(
                f"fabric shm frame {uuid:#x} unclaimable (rc {rc})")
        return out, olen.value, h, lib

    def _shm_claim_zero_copy(self, uuid: int, expect_len: int):
        """Claim an shm frame as a ctypes array WRAPPING the ring slot
        — zero receiver copies; the slot is retired (ring credit
        returned) when the last view dies (_ShmBufOwner)."""
        ptr, n, h, lib = self._shm_claim(uuid)
        if n != expect_len:
            lib.brpc_tpu_shm_release(h, ptr, n)
            raise ConnectionError(
                f"shm frame {uuid:#x}: {n} bytes, descriptor "
                f"said {expect_len}")
        ca = (ctypes.c_uint8 * n).from_address(
            ctypes.addressof(ptr.contents))
        ca._owner = _ShmBufOwner(lib, h, ptr, n)
        with self._bulk_lock:
            self.shm_bytes_claimed += n
        return ca

    def _shm_claim_bytes(self, uuid: int, expect_len: int) -> bytes:
        """Kind-6 host blobs: one owned copy off the ring (the blob is
        protocol bytes the parser consumes), slot retired immediately."""
        ptr, n, h, lib = self._shm_claim(uuid)
        try:
            if n != expect_len:
                raise ConnectionError(
                    f"shm frame {uuid:#x}: {n} bytes, descriptor "
                    f"said {expect_len}")
            with self._bulk_lock:
                self.shm_bytes_claimed += n
            return ctypes.string_at(ptr, n)
        finally:
            lib.brpc_tpu_shm_release(h, ptr, n)

    def _shm_claim_array(self, uuid: int, dt: str, shape, length: int,
                         local_device):
        """Kind-5 device payload: same delivery semantics as the kind-2
        bulk claim (_bulk_claim_array), zero-copy host-resident by
        default with the release chained through numpy's base."""
        import numpy as np
        ca = self._shm_claim_zero_copy(uuid, length)
        host = np.frombuffer(ca, dtype=np.uint8).view(
            np.dtype(dt)).reshape(shape)
        if _flags.get_flag("ici_fabric_host_delivery"):
            return host
        import jax
        np_arr = host.copy()          # the owned copy device_put may alias
        del host, ca                  # last refs: owner releases the slot
        return jax.device_put(np_arr, local_device)

    def _on_pulled(self, uuid: int) -> None:
        with self._staged_lock:
            entry = self._staged.pop(uuid, None)
        if entry is not None:
            blk = entry[0]
            cb = getattr(blk, "on_send_complete", None)
            if cb is not None:
                try:
                    cb()
                except Exception:
                    pass

    def _do_read(self, portal: IOPortal, max_count: int) -> int:
        with self._inbox_lock:
            avail = len(self._inbox)
            if avail == 0:
                return 0 if self._peer_closed else -1
            n = min(avail, max_count)
            self._inbox.cutn(portal, n)
        # batched credit return (the reference piggybacks acks on
        # completions rather than acking every read): parsers consume the
        # inbox in many small cuts, and a CREDIT frame per cut measured
        # ~66 tiny control sends per bulk chunk.  Deferring the return
        # until window/8 (``credit_batch``) keeps the sender pumping (7/8
        # of the window is still credited) at 1/66th the control traffic.
        flush = 0
        with self._inbox_lock:
            self._consumed_unacked += n
            if (self._consumed_unacked >= self.credit_batch
                    or self._peer_closed):
                flush = self._consumed_unacked
                self._consumed_unacked = 0
        if flush:
            try:
                self._ctrl_send(_F_CREDIT, struct.pack("<Q", flush))
            except OSError:
                pass
        return n

    def set_failed(self, error_code: int = errors.EFAILEDSOCKET,
                   reason: str = "") -> bool:
        """Socket death is no longer the end of the endpoint: the first
        transport-level failure hands the remote endpoint to the health
        checker, which probes with exponential backoff + jitter until a
        reconnect (fresh HELLO/bulk handshake, NEW versioned socket id —
        this id was already revoked by the base set_failed, so stale
        writes fail cleanly) can succeed; Channel retry / backup-request
        then recovers RPCs issued during the outage, and the endpoint's
        circuit breaker is reset on revival (ramp-up gating)."""
        first = super().set_failed(error_code, reason)
        if (first and not self.is_server_side
                and error_code != errors.ECLOSE
                and _flags.get_flag("ici_fabric_health_check")):
            try:
                from ..rpc.health_check import start_health_check
                start_health_check(self.remote_side)
            except Exception:
                pass
        return first

    def _transport_close(self) -> None:
        try:
            # FIN carries the closer's error code (empty body = old
            # peers / clean close): a lame-duck hard stop propagates
            # ELOGOFF so the peer's in-flight calls fail over without
            # burning their connection-failure backoff budget
            body = struct.pack("<I", self.failed_error) \
                if self.failed_error == errors.ELOGOFF else b""
            self._ctrl_send(_F_FIN, body)
        except OSError:
            pass
        try:
            self._conn.close()
        except OSError:
            pass
        self._wake_window()
        self._flush_staged()
        self._close_bulk()
        self._close_shm()
        self._close_dplane()

    def _close_bulk(self) -> None:
        """Tear down the bulk conn WITHOUT starting revival (socket-level
        teardown).  Safe while writers race: fab_send on a closed handle
        fails cleanly (shared-ptr registry), and the serial read loop has
        already claimed every pending frame by the time teardown runs."""
        with self._bulk_lock:
            h, self._bulk = self._bulk, 0
            pending, self._reestab_pending = self._reestab_pending, None
            lib = self._blib
        if h and lib is not None:
            lib.brpc_tpu_fab_conn_close(h)
        if pending is not None:
            pending[0].brpc_tpu_fab_conn_close(pending[1])
        self._reestab_evt.set()        # unblock a parked revival thread


def pair_plane_stats() -> Dict[int, dict]:
    """Live native bulk planes grouped by peer pid (the per-pair plane
    registry, native/fabric.cpp): {peer_pid: {conns, bytes_in,
    bytes_out}}.  Empty when the native core is absent."""
    try:
        from ..butil import native as _native
        lib = _native.load()
    except Exception:
        lib = None
    if lib is None or not hasattr(lib, "brpc_tpu_fab_peer_list"):
        return {}
    # a FULL buffer means the native list may have been truncated (the
    # C call returns min(count, cap) with no overflow signal): grow and
    # retry so a >64-process pod's /ici page never silently drops pairs
    cap = 64
    while True:
        peers = (ctypes.c_int32 * cap)()
        n = lib.brpc_tpu_fab_peer_list(peers, cap)
        if n < cap or cap >= (1 << 16):
            if n >= cap:
                log.warning("pair_plane_stats: peer list truncated "
                            "at %d entries", cap)
            break
        cap *= 2
    out: Dict[int, dict] = {}
    for i in range(n):
        conns = ctypes.c_uint64()
        bi = ctypes.c_uint64()
        bo = ctypes.c_uint64()
        lib.brpc_tpu_fab_pair_stats(peers[i], ctypes.byref(conns),
                                    ctypes.byref(bi), ctypes.byref(bo))
        out[int(peers[i])] = {"conns": int(conns.value),
                              "bytes_in": int(bi.value),
                              "bytes_out": int(bo.value)}
    return out


def connect_any(ep, local_dev: Optional[int] = None):
    """Route an ici:// connect: in-process targets use the zero-copy
    IciSocket path; remote ones the fabric.  This is what makes
    Channel("ici://k") work identically single- and multi-controller."""
    from .transport import ici_connect
    node = FabricNode.instance()
    target = ep.device_id
    if node is None:
        return ici_connect(ep, local_dev)
    if local_dev is None:
        # default client residence must be a device THIS process owns —
        # ici_connect's neighbor default can land on another controller's
        # device, which this process cannot address
        import jax
        me = node.process_id
        owned = [i for i, d in enumerate(jax.devices())
                 if d.process_index == me]
        local_dev = next((i for i in owned if i != target), owned[0])
    if FabricNode.device_owner(target) == node.process_id:
        return ici_connect(ep, local_dev)
    return node.connect(target, local_dev)
