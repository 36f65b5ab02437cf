"""ici:// transport: RPC frames between device endpoints, payloads in HBM.

This is the analogue of the reference's RDMA transport (SURVEY.md §3.5,
src/brpc/rdma/rdma_endpoint.cpp): where RdmaEndpoint posts zero-copy SGEs
from registered IOBuf blocks and completions arrive via CQ events, the ici
transport moves IOBuf *device blocks* between chips with XLA transfers and
completions arrive via device-stream readiness (bthread.device_waiter — the
CQ/EventDispatcher analogue).

Wire model (single-controller JAX):
  * An IciSocket connects two endpoints ``ici://a`` ↔ ``ici://b``.
  * ``write(iobuf)`` splits the buffer into the host-byte stream (protocol
    frames/meta — small) and its DEVICE block refs (bulk payload).  Host
    bytes are handed to the peer directly; device blocks are relocated to
    the peer's device with ``jax.device_put`` — on TPU hardware this is a
    direct HBM→HBM ICI transfer that never touches the host.  The delivered
    IOBuf has the same layout with device refs now resident on the target
    chip.
  * Delivery order per socket is preserved by a per-socket ExecutionQueue;
    a payload that MOVED (a device-plane transfer, a ``device_put``) is
    awaited through DeviceEventDispatcher before the peer's input path runs
    — "the read event fires after what moved has landed in local HBM", the
    RDMA completion contract.  A ref that is in the target chip's HBM
    already moves nothing and is delivered as written, by the thread that
    wrote it: the receiver is handed a ``jax.Array`` whose producer may not
    have finished, as every jax program is, and what it dispatches on it
    the runtime orders behind that producer (docs/ICI_DATAPATH.md).
  * The thread that commits a frame runs the peer's reader too, on the
    server's side as on the client's: the frame is cut, and a stream's
    frame consumed, with no hop to a reader tasklet.  A handler runs there
    only where the server asked for it (``usercode_inline``); otherwise a
    request gets a tasklet of its own (``queue_last_message``).

In a future multi-controller deployment the relocation step becomes paired
XLA Send/Recv (the handshake already exchanges device ids, mirroring the
reference's GID/QPN TCP handshake rdma_endpoint.h:37); everything above
Socket is unaffected.  Collectives (combo-channel lowering) do NOT go
through point-to-point sockets — see collective.py.
"""
from __future__ import annotations

import collections
import threading
import time as _time
from typing import Any, Callable, Dict, List, Optional, Tuple

from .. import bvar
from ..butil.endpoint import EndPoint
from ..butil import flags as _flags
from ..butil import layer_span as _span
from ..butil import debug_sync as _dbg
from ..butil import logging as log
from ..butil.iobuf import IOBuf, IOPortal, DEVICE
from ..bthread.butex import Butex
from ..bthread.device_waiter import DeviceEventDispatcher
from ..rpc import errors
from ..rpc.socket import Socket
from . import device_plane as _dp
from .mesh import IciMesh

_ici_stats_lock = _dbg.make_lock("ici.transport._ici_stats_lock")
_ici_bytes_moved = 0
_ici_device_bytes_moved = 0
# window pieces whose header rode on borrowed window (CreditWindow), pieces
# cut while earlier bytes of their socket were still un-consumed at the peer
# (the window held more than one), and DEVICE refs that crossed under the
# plane's threshold by slice + device_put
_g_borrowed_headers = bvar.Adder("ici_transport_borrowed_header_pieces")
_g_pipelined_pieces = bvar.Adder("ici_transport_pipelined_pieces")
_g_small_relocations = bvar.Adder("ici_transport_small_relocations")
# host-side cuts of a DEVICE ref out of a device array (``_cut``): by the
# compiled slicer, and by ``arr[a:b]`` where the slicer does not apply (an
# array spread over devices, a block an int32 start cannot index)
_g_compiled_cuts = bvar.Adder("ici_transport_compiled_cuts")
_g_eager_cuts = bvar.Adder("ici_transport_eager_cuts")
# cut runs (``IciSocket._resident_cut``): dispatches of the run slicer, the
# pieces handed out of a run (its first included), and the pieces a run cut
# that were never sent (a miss, a closed socket); ``compiled_cuts`` counts
# a run's pieces too, so compiled_cuts / cut_runs is pieces per dispatch
_g_cut_runs = bvar.Adder("ici_transport_cut_runs")
_g_run_pieces = bvar.Adder("ici_transport_run_pieces")
_g_run_pieces_dropped = bvar.Adder("ici_transport_run_pieces_dropped")
# what a delivery waited for: DEVICE refs that were in the target chip's HBM
# already and were committed with no gate, and arrays that a delivery handed
# to the device poller (what moved by ``device_put``, a fabric socket's waits)
_g_resident_ungated = bvar.Adder("ici_transport_resident_refs_ungated")
_g_gated_arrays = bvar.Adder("ici_transport_gated_arrays")

# the compiled cut's caches (``piece_slicer``, ``run_slicer``,
# ``_start_operand``)
_cut_lock = _dbg.make_lock("ici.transport._cut_lock")
_cut_program = None
_run_program = None
_cut_starts: Dict[tuple, Any] = {}
MAX_CUT_STARTS = 512

# fablint guarded-state contract for the module-level registries
_GUARDED_BY_GLOBALS = {
    "_ici_bytes_moved": "_ici_stats_lock",
    "_ici_device_bytes_moved": "_ici_stats_lock",
    "_listeners": "_listeners_lock",
    "_cut_program": "_cut_lock",
    "_run_program": "_cut_lock",
    "_cut_starts": "_cut_lock",
}

# Transport-level sliding window (reference: the RDMA explicit-ACK window,
# rdma_endpoint.cpp:771 CutFromIOBufList checks _window_size before posting;
# credits return piggybacked on completions).  A writer may have at most
# this many un-CONSUMED bytes at the peer, plus the header an IciSocket
# piece carried on borrowed window: less than one
# ``ici_device_plane_threshold`` (upstream counts its window in work
# requests whose SGEs are whole blocks, and the header travels in the same
# request).  Beyond it _do_write reports not-writable and the KeepWrite
# tasklet blocks until the reader drains.  This bounds the peer inbox (a
# slow reader exerts backpressure instead of growing memory) — the
# flow-control VERDICT.md item #3.
#
# The window and the piece are two numbers.  DEVICE bytes cross in pieces
# of PIECE_BYTES (a narrower window's piece is the window), so the slice
# shapes and the compiled transfer programs are one set whatever the
# window; the window is several pieces wide, so that the writer cuts and
# posts piece k+1 while piece k is on the wire, at the poller or on its
# credit's way back (upstream's ``_window_size`` is 128 work requests, not
# one).  PERF.md §6 PR 34 has the stall at 2, 4 and 8 pieces.
PIECE_BYTES = 4 * 1024 * 1024
_flags.define_flag("ici_socket_window_bytes", 4 * PIECE_BYTES,
                   "per-ici-socket send window (unconsumed bytes at peer)",
                   _flags.positive_integer)


def ici_transport_stats() -> Tuple[int, int]:
    with _ici_stats_lock:
        return _ici_bytes_moved, _ici_device_bytes_moved


def ici_piece_stats() -> Dict[str, int]:
    """How the window pieces were cut and relocated, process-wide: pieces
    that carried their header on borrowed window, pieces cut while earlier
    bytes of the same socket were still un-consumed at the peer, DEVICE
    refs that crossed chips under the device plane's threshold (slice +
    device_put), the host-side cuts out of a device array: by the
    compiled slicers (pieces, alone or in a run), and by the eager
    ``arr[a:b]`` left for an array the slicers do not take, the cut runs:
    dispatches of the run slicer, pieces handed out of a run and pieces a
    run cut that were never sent, and what the deliveries waited for:
    DEVICE refs resident on the target that were committed with no gate,
    and arrays handed to the device poller."""
    return {"borrowed_header_pieces": _g_borrowed_headers.get_value(),
            "pipelined_pieces": _g_pipelined_pieces.get_value(),
            "small_relocations": _g_small_relocations.get_value(),
            "compiled_cuts": _g_compiled_cuts.get_value(),
            "eager_cuts": _g_eager_cuts.get_value(),
            "cut_runs": _g_cut_runs.get_value(),
            "run_pieces": _g_run_pieces.get_value(),
            "run_pieces_dropped": _g_run_pieces_dropped.get_value(),
            "resident_refs_ungated": _g_resident_ungated.get_value(),
            "gated_arrays": _g_gated_arrays.get_value()}


class CreditWindow:
    """Mixin: explicit-ACK sliding window shared by the in-process
    IciSocket and the multi-controller FabricSocket (reference
    rdma_endpoint.cpp:771 window check; credits return on consume).

    Contract for the host class (a Socket subclass): call
    ``_init_window(window_bytes)`` in __init__, gate each ``_do_write``
    through ``_consume_window(len, lead)``, call ``_on_credits(n)`` when
    the peer reports n consumed bytes, and hold back at most
    ``credit_batch`` consumed bytes before reporting them.  A writer
    stalled past the ``_wait_writable`` timeout FAILS the socket — pending
    writes complete with an error instead of silently wedging forever.

    The bound: un-consumed bytes at the peer never pass ``window_bytes``
    plus the one header a piece may borrow (``_consume_window``'s
    ``lead``), which the caller keeps under
    ``min(ici_device_plane_threshold, window_bytes)``.  A host class that
    borrows no header (FabricSocket) stays at ``window_bytes``.

    The piece: DEVICE bytes are cut ``piece_bytes`` at a time —
    ``PIECE_BYTES``, or the window where that is narrower — so a window of
    several pieces keeps several on their way at once, each of the one
    shape."""

    _GUARDED_BY = {"_send_window": "_window_lock",
                   "_window_need": "_window_lock"}

    # fablint: init
    def _init_window(self, window_bytes: Optional[int]) -> None:
        self.window_bytes = (window_bytes if window_bytes is not None
                             else _flags.get_flag("ici_socket_window_bytes"))
        self.piece_bytes = min(PIECE_BYTES, self.window_bytes)
        # what a reader may sit on before it returns credits: an eighth of
        # the window, and never so much that a writer parked for a whole
        # piece could be waiting for bytes the peer holds back
        self.credit_batch = self.window_bytes // 8
        if self.piece_bytes < self.window_bytes:
            self.credit_batch = min(self.credit_batch,
                                    self.window_bytes - self.piece_bytes)
        self._send_window = self.window_bytes
        self._window_need = 1             # what the parked writer waits for
        self._cut_backlog = 0             # un-consumed bytes at the last cut
        self._window_lock = _dbg.make_lock("CreditWindow._window_lock")
        self._window_gen = Butex(0)       # bumped whenever credits return

    def send_window_left(self) -> int:
        with self._window_lock:
            return self._send_window

    def unacked_send_bytes(self) -> int:
        """Bytes written but not yet consumed by the peer (≤ window, plus
        a borrowed header)."""
        with self._window_lock:
            return self.window_bytes - self._send_window

    def _device_lead(self, data: IOBuf) -> Optional[int]:
        """``_consume_window``'s ``lead`` for ``data``: the short header in
        front of its DEVICE bytes, None where it is cut as host bytes."""
        return _header_run(data, min(
            _flags.get_flag("ici_device_plane_threshold"), self.window_bytes))

    def _consume_window(self, want: int, lead: Optional[int] = None) -> int:
        """Take up to ``want`` bytes of window; -1 when the transport is
        not writable: the window is closed, or has less left than the cut
        needs (``_wait_writable`` then waits for that much).

        ``lead`` is None for host bytes, which are cut byte for byte.
        Else DEVICE bytes follow a header of ``lead`` bytes (0: none), and
        the cut is a whole piece of what follows the header, or all of it
        where that is less — never what happens to be left of a partly
        open window, so every cut lies on the blocks' piece boundaries.
        Where the window covers the piece the header is not charged
        against the cut: the window stands below zero by what it borrowed
        until the peer's credits for the piece repay it.  Where it does
        not, the writer waits for the credit that makes it so; a window of
        one piece or less instead cuts what is left, header charged (its
        reader may be holding back credits for a fuller batch, and the
        whole window might never come)."""
        with self._window_lock:
            left = self._send_window
            body = 0 if lead is None else min(want - lead, self.piece_bytes)
            need = max(1, body) if self.piece_bytes < self.window_bytes \
                else 1
            if left < need:
                self._window_need = need
                return -1
            if lead is not None and left >= body:
                n = lead + body
            else:
                n = min(want, left)
            self._send_window = left - n
            self._cut_backlog = backlog = self.window_bytes - left
        if n > left:
            _g_borrowed_headers << 1
        if backlog > 0:
            _g_pipelined_pieces << 1
        return n

    def _on_credits(self, n: int) -> None:
        """Peer consumed n bytes: replenish the window, wake blocked
        writers (the piggybacked-ACK path of rdma_endpoint.cpp)."""
        with self._window_lock:
            self._send_window = min(self.window_bytes, self._send_window + n)
        self._wake_window()

    def _wake_window(self) -> None:
        self._window_gen.fetch_add(1)
        self._window_gen.wake_all()

    def _peer_gone(self) -> bool:
        """Transport-specific: the far side can no longer return credits."""
        return False

    def _wait_writable(self, timeout: float = 30.0) -> bool:
        deadline = _time.monotonic() + timeout
        stall = None    # layer span brpc.ici.stall, once it really waits
        try:
            while not self.failed:
                gen = self._window_gen.value
                with self._window_lock:
                    # what the head of the queue needs, not any byte at
                    # all: a writer woken short of a piece would find
                    # _do_write not writable again, and spin
                    if self._send_window >= self._window_need:
                        return True
                if self._peer_gone():
                    self.set_failed(errors.EFAILEDSOCKET,
                                    "ici peer closed while window full")
                    return False
                left = deadline - _time.monotonic()
                if left <= 0:
                    # a stalled window must not black-hole the socket: fail
                    # it so queued writes complete with an error and callers
                    # see EFAILEDSOCKET rather than waiting forever
                    log.error("ici socket to %s: send window stalled >%.0fs "
                              "(peer not consuming); %d bytes unacked — "
                              "socket set failed", self.remote_side, timeout,
                              self.unacked_send_bytes())
                    self.set_failed(
                        errors.EFAILEDSOCKET,
                        f"ici send window stalled >{timeout:.0f}s "
                        f"(peer not consuming)")
                    return False
                if stall is None and _span.layer_on():
                    stall = _span.layer_begin(
                        "brpc.ici.stall", n=self.unacked_send_bytes())
                self._window_gen.wait(gen, min(left, 0.5))
            return False
        finally:
            if stall is not None:
                stall.end()


class OrderedDelivery:
    """Mixin: per-socket in-order commit of received frames whose device
    payloads land asynchronously.  The contract: the read event fires
    after what MOVED has landed, and never out of arrival order — a
    host-only frame arriving after a gated one must not jump the queue
    (byte-stream ordering is the transport contract the parsers rely on).

    Waits are what moved: plain device arrays (gated through the
    per-device completion poller) or device-plane transfers / any object
    exposing ``add_done_callback`` (gated on its completion — the CQ
    entry).  What did not move is not a wait: a ref pass on one chip is
    delivered as written, and an entry with no waits commits on the thread
    that enqueued it, behind the entries ahead of it."""

    _GUARDED_BY = {"_dq": "_dq_lock", "_dq_draining": "_dq_lock"}

    # fablint: init
    def _init_delivery(self) -> None:
        self._dq = collections.deque()    # entries: [ready, commit_fn, mark]
        self._dq_lock = _dbg.make_lock("OrderedDelivery._dq_lock")
        self._dq_draining = False

    def _enqueue_delivery(self, waits: List,
                          commit_fn: Callable[[], None]) -> None:
        # layer span brpc.ici.gate: from here until commit_fn runs, behind
        # its own gates and the entries ahead of it
        entry = [False, commit_fn,
                 _span.layer_mark(len(waits)) if _span.layer_on() else None]
        with self._dq_lock:
            self._dq.append(entry)

        arrays = [w for w in waits if not hasattr(w, "add_done_callback")]
        handles = [w for w in waits if hasattr(w, "add_done_callback")]
        # readiness is sampled ONCE: an array that turns ready between a
        # count and a later re-check would be counted as a gate and never
        # handed to the poller — the entry then never opens, the peer
        # never consumes, and the writer's window stalls (seen on the
        # chip, where readiness really is asynchronous)
        poll_arrays = bool(arrays) and not _all_ready(arrays)
        gates = len(handles) + (1 if poll_arrays else 0)
        if gates == 0:
            entry[0] = True
            self._drain_deliveries()
            return

        left = [gates]
        left_lock = threading.Lock()

        def one_gate(_err=None):
            with left_lock:
                left[0] -= 1
                if left[0] > 0:
                    return
            entry[0] = True
            self._drain_deliveries()

        if poll_arrays:
            _g_gated_arrays << len(arrays)
            DeviceEventDispatcher.instance().on_ready(arrays, one_gate)
        for h in handles:
            h.add_done_callback(one_gate)

    def _drain_deliveries(self) -> None:
        while True:
            with self._dq_lock:
                if (self._dq_draining or not self._dq
                        or not self._dq[0][0]):
                    return
                self._dq_draining = True
                _, fn, mark = self._dq.popleft()
            try:
                if mark is not None:
                    _span.layer_waited("brpc.ici.gate", mark)
                fn()
            finally:
                with self._dq_lock:
                    self._dq_draining = False


class IciSocket(CreditWindow, OrderedDelivery, Socket):
    # fablint guarded-state contract: the inbox and the pinned-send
    # table are touched from the writer, the reader, and the device
    # completion poller
    _GUARDED_BY = {
        "_inbox": "_inbox_lock",
        "_inflight_sends": "_inflight_lock",
        "_inflight_seq": "_inflight_lock",
    }

    def __init__(self, local_dev: int, remote_dev: int,
                 mesh: Optional[IciMesh] = None,
                 window_bytes: Optional[int] = None):
        self.mesh = mesh or IciMesh.default()
        super().__init__(remote_side=self.mesh.endpoint(remote_dev))
        self.local_dev = local_dev
        self.remote_dev = remote_dev
        self.local_side = self.mesh.endpoint(local_dev)
        self.peer: Optional["IciSocket"] = None
        self._inbox = IOBuf()
        self._inbox_lock = _dbg.make_lock("IciSocket._inbox_lock")
        self.read_chunk_hint = 1 << 26    # _do_read cuts, never allocates
        self._peer_closed = False
        self._init_window(window_bytes)
        self._init_delivery()
        # source device blocks pinned until their ICI transfer completed
        # (reference frees _sbuf refs only on CQ completion,
        # rdma_endpoint.cpp:926 HandleCompletion) — load-bearing once
        # buffer donation reuses send blocks
        self._inflight_sends: Dict[int, Tuple] = {}
        self._inflight_seq = 0
        self._inflight_lock = _dbg.make_lock("IciSocket._inflight_lock")
        # the pieces of a resident ref that one dispatch cut ahead of the
        # window (``_resident_cut``): touched by the socket's one writer,
        # and dropped by ``_transport_close``
        self._cut_run: Optional[_CutRun] = None

    @property
    def queue_last_message(self) -> bool:
        """The reader runs on the delivering thread (``commit``): frames
        are cut, and a stream's consumed in order, with no hop; a handler
        must not run there unless the server asked for it
        (``usercode_inline``), so a server side's last message goes to a
        tasklet of its own like the ones before it."""
        return self.is_server_side and not getattr(
            self, "usercode_inline", False)

    def inflight_send_blocks(self) -> int:
        """Device source blocks pinned awaiting transfer completion."""
        with self._inflight_lock:
            return len(self._inflight_sends)

    # -- transport hooks -------------------------------------------------
    def _do_write(self, data: IOBuf) -> int:
        peer = self.peer
        if peer is None or peer.failed:
            raise ConnectionError("ici peer closed")
        # a short header in front of device bytes rides with its first
        # window piece, so the cuts land on the device blocks' boundaries
        n = self._consume_window(len(data), self._device_lead(data))
        if n < 0:
            return -1                     # window full: not writable now
        # layer spans: one window piece (m: the bytes un-consumed at the
        # peer when it was cut), and as its child (stamped, in the store
        # only) the cut and the slice / device_put / plane post dispatches
        piece = _span.layer_begin("brpc.ici.piece", n=n,
                                  m=self._cut_backlog, cpu=True) \
            if _span.layer_on() else None
        try:
            frame = data.cut(n)
            chunks = self._relocate(frame, data)
            if piece is not None:
                _span.layer_record("brpc.ici.relocate", piece.start_ns,
                                   _time.perf_counter_ns())
            self._deliver(peer, chunks)
        finally:
            if piece is not None:
                piece.end()
        global _ici_bytes_moved
        with _ici_stats_lock:
            _ici_bytes_moved += n
        return n

    def _relocate(self, frame: IOBuf, rest: IOBuf) -> List:
        """Move DEVICE refs to the peer's chip (HBM→HBM over ICI); host
        refs pass through as bytes.  Three routes, chosen on the ref's
        BLOCK before anything is cut: resident on the target, the ref is
        passed (sliced where it is not the whole block, by a cut run where
        the frame's last ref goes on in ``rest``, what is still queued:
        ``_resident_cut``) and nothing moved
        — the chunk ``(array, length, moved)`` says so, and ``_deliver``
        waits only for what did; not resident and
        at/above ``ici_device_plane_threshold``, a send WR is posted on the
        device plane with the whole block and ``(offset, length)`` — the
        payload then crosses through a COMPILED transfer program
        (shard_map + ppermute) that cuts the piece on
        the chip, with only a descriptor riding the delivery path; the
        matching recv is enqueued by ``_deliver`` (the QP rendezvous);
        else the slice and ``device_put``.  A refused post
        degrades to device_put in the same frame: a chaos/plane-health
        refusal is counted in the plane's ``fallbacks``, a program the
        compiler refused is logged at error and counted in
        ``build_failures`` by the plane (DevicePlaneBuildError)."""
        import jax
        target = self.mesh.device(self.remote_dev)
        chunks: List = []
        pending_host: List[bytes] = []
        global _ici_device_bytes_moved
        last = frame.backing_block_num() - 1
        for i in range(last + 1):
            r = frame.backing_block(i)
            if r.block.kind == DEVICE:
                if pending_host:
                    chunks.append(b"".join(pending_host))
                    pending_host = []
                # the route is decided on the BLOCK, not on a slice of it
                arr = r.block.data
                on_device = hasattr(arr, "devices")
                resident = False
                if on_device:
                    try:
                        resident = target in arr.devices()
                    except Exception:
                        pass
                # already in the target chip's HBM: pure ref pass — the
                # zero-copy case the block_pool discipline exists for
                if resident:
                    chunks.append((self._resident_cut(
                        arr, r, rest if i == last else None),
                        r.length, False))
                    with _ici_stats_lock:
                        _ici_device_bytes_moved += r.length
                    continue
                if on_device and _dp.eligible(r.length):
                    src_idx = _dp.mesh_index_of(arr, self.mesh)
                    if src_idx >= 0 and src_idx != self.remote_dev:
                        try:
                            # the WHOLE block and where the piece lies in
                            # it: the transfer program cuts on the chip,
                            # no arr[a:b] dispatch on the host
                            t = _dp.plane().post_send(
                                arr, src_idx, self.remote_dev, socket=self,
                                start=r.offset, nbytes=r.length)
                            t.add_source_release(
                                getattr(r.block, "on_send_complete", None))
                            chunks.append(_PlaneDesc(t, r.length))
                            with _ici_stats_lock:
                                _ici_device_bytes_moved += r.length
                            continue
                        except _dp.DevicePlaneError:
                            pass     # counted (and, for a build error,
                            #          logged) by the plane; device_put
                arr = _cut(arr, r)
                if not on_device:
                    # host-resident numpy delivered by the fabric bulk
                    # plane, now being forwarded in-process: detach into
                    # an owned copy before device_put — jax zero-copy
                    # ALIASES ctypes-backed views without retaining them
                    import numpy as _np
                    arr = _np.array(arr, copy=True)
                moved = jax.device_put(arr, target)
                if r.length < _flags.get_flag("ici_device_plane_threshold"):
                    _g_small_relocations << 1
                self._pin_until_sent(r.block, moved)
                chunks.append((moved, r.length, True))
                with _ici_stats_lock:
                    _ici_device_bytes_moved += r.length
            else:
                pending_host.append(bytes(r.block.host_view(r.offset, r.length)))
        if pending_host:
            chunks.append(b"".join(pending_host))
        return chunks

    def _resident_cut(self, arr, r, rest: Optional[IOBuf]):
        """The array a resident ref ``r`` of ``arr`` is passed as.  A piece
        the pending cut run holds is taken from it; any other piece drops
        the run (a miss).  Where the ref goes on in ``rest`` right where
        ``r`` ends, ONE dispatch of the run slicer cuts ``r`` and the next
        pieces of it on the window's piece grid — each ``piece_bytes``,
        the last clipped to the ref's end, at most a window of them — and
        keeps the rest for the frames that follow; else ``_cut`` as ever
        (a window of one piece, a ref whose rest fits in this piece).
        The socket's writer is single (the KeepWrite rule), so only
        ``_transport_close`` races it (``_CutRun``)."""
        run = self._cut_run
        if run is not None:
            got = run.take(r)
            if got is not None:
                if not run.pieces:
                    self._cut_run = None
                return got
            self._drop_run()
        plan = self._run_plan(arr, r, rest)
        if plan is None:
            return _cut(arr, r)
        device, lengths = plan
        run = _CutRun(r.block, r.offset, lengths,
                      _cut_pieces(arr, device, r.offset, lengths))
        got = run.take(r)
        self._cut_run = run
        if self.failed:
            self._drop_run()      # closed while it was cut
        return got

    def _run_plan(self, arr, r, rest: Optional[IOBuf]):
        """``(device, lengths)`` of a cut run that starts with ``r``, or
        None where there is no run of two: the window holds one piece,
        ``rest`` does not begin with the same block right where ``r``
        ends, or the run slicer does not take the array."""
        ahead = self.window_bytes // self.piece_bytes
        if ahead < 2 or rest is None or not rest.backing_block_num():
            return None
        nxt = rest.backing_block(0)
        at = r.offset + r.length
        if nxt.block is not r.block or nxt.offset != at or r.offset < 0:
            return None
        size, end = len(arr), nxt.offset + nxt.length
        if end > size or getattr(arr, "sharding", None) is None:
            return None
        device = _slicer_device(arr, size)
        if device is None:
            return None
        lengths = [r.length]
        while at < end and len(lengths) < ahead:
            lengths.append(min(self.piece_bytes, end - at))
            at += lengths[-1]
        return device, tuple(lengths)

    def _drop_run(self) -> None:
        run, self._cut_run = self._cut_run, None
        if run is not None:
            dropped = run.drop()
            if dropped:
                _g_run_pieces_dropped << dropped

    def _deliver(self, peer: "IciSocket", chunks: List) -> None:
        waits: List = []
        resident = 0
        for c in chunks:
            if isinstance(c, _PlaneDesc):
                # the matching recv: rendezvous with the posted send —
                # both sides join the same compiled transfer program
                c.transfer = _dp.plane().post_recv(c.transfer.uuid)
                waits.append(c.transfer)
            elif isinstance(c, tuple):
                # a ref that was on the target chip already is delivered as
                # written: no transfer to wait for, and what the receiver
                # dispatches on it the runtime orders behind its producer
                if c[2]:
                    waits.append(c[0])
                else:
                    resident += 1
        if resident:
            _g_resident_ungated << resident

        def commit() -> None:
            buf = IOBuf()
            for c in chunks:
                if isinstance(c, _PlaneDesc):
                    buf.append_device_array(c.transfer.out)
                elif isinstance(c, tuple):
                    buf.append_device_array(c[0])
                else:
                    buf.append(c)
            with peer._inbox_lock:
                peer._inbox.append(buf)
            peer.start_input_event(inline=True)

        # ordered per-socket commit: the read event fires only after what
        # moved has landed in peer HBM, and never out of arrival order; with
        # no waits it fires here, on the writer's thread
        peer._enqueue_delivery(waits, commit)

    def _pin_until_sent(self, src_block, moved) -> None:
        """Hold the SOURCE device block (and the moved array) until the
        ICI transfer completes; only then may the source block be reused /
        donated.  Mirrors the reference's completion-driven `_sbuf` free
        (rdma_endpoint.cpp:926): the completion source here is the device
        stream, observed through the per-device poller."""
        with self._inflight_lock:
            seq = self._inflight_seq
            self._inflight_seq += 1
            self._inflight_sends[seq] = (src_block, moved)

        def _done(seq=seq):
            with self._inflight_lock:
                entry = self._inflight_sends.pop(seq, None)
            if entry is not None:
                blk = entry[0]
                cb = getattr(blk, "on_send_complete", None)
                if cb is not None:
                    try:
                        cb()
                    except Exception:
                        pass

        DeviceEventDispatcher.instance().on_ready([moved], _done)

    def _do_read(self, portal: IOPortal, max_count: int) -> int:
        with self._inbox_lock:
            avail = len(self._inbox)
            if avail == 0:
                return 0 if self._peer_closed else -1
            n = min(avail, max_count)
            self._inbox.cutn(portal, n)
        # consumed-bytes feedback: replenish the writer's window (in
        # multi-controller mode this rides the control channel as an ACK
        # frame — see fabric.py)
        peer = self.peer
        if peer is not None and not peer.failed:
            peer._on_credits(n)
        return n

    def _peer_gone(self) -> bool:
        peer = self.peer
        return peer is None or peer.failed or self._peer_closed

    # ---- lame-duck (GOODBYE) -------------------------------------------
    def send_goodbye(self) -> None:
        """Server drain: the in-process flavor of the fabric GOODBYE
        control frame — notify the peer socket directly (same process,
        no wire needed)."""
        peer = self.peer
        if peer is not None and not peer.failed:
            peer.on_peer_goodbye()

    def on_peer_goodbye(self) -> None:
        # the peer endpoint is draining: no new calls ride this socket
        # (SocketMap replaces logoff sockets on next use) and every live
        # LB pulls the endpoint now — before any health-check probe
        self.logoff = True
        try:
            from ..rpc import lameduck
            lameduck.notify_peer_draining(self.remote_side)
        except Exception:
            pass

    def _transport_close(self) -> None:
        peer = self.peer
        if peer is not None and not peer.failed:
            if self.failed_error == errors.ELOGOFF:
                # lame-duck hard stop: the peer's in-flight calls fail
                # with the retryable server code, applied on the EOF
                # path AFTER queued responses drain (see mem_transport —
                # failing immediately would retry already-executed
                # calls)
                peer._eof_error_code = errors.ELOGOFF
            with peer._inbox_lock:
                peer._peer_closed = True
            peer.start_input_event()
            # wake the peer's blocked writers so they observe _peer_gone
            # instead of stalling out their full timeout
            peer._wake_window()
        # release our own writers blocked on the (now dead) window
        self._wake_window()
        self._drop_run()


def _header_run(data: IOBuf, bound: int) -> Optional[int]:
    """The host bytes in front of ``data``'s first DEVICE ref where they
    are fewer than ``bound`` (0 where it begins with one); None for a
    longer run and for data with no DEVICE bytes: both are cut byte for
    byte."""
    lead = 0
    for i in range(data.backing_block_num()):
        r = data.backing_block(i)
        if r.block.kind == DEVICE:
            return lead
        lead += r.length
        if lead >= bound:
            break
    return None


def piece_slicer():
    """The one jitted ``brpc_ici_cut``: ``dynamic_slice(block, (start,),
    (length,))`` with ``length`` static and ``start`` an operand, so one
    executable serves every offset of a (block bytes, length) shape.  Built
    on first use (this module imports no jax)."""
    global _cut_program
    with _cut_lock:
        fn = _cut_program
    if fn is None:
        import functools
        import jax

        @functools.partial(jax.jit, static_argnums=2)
        def brpc_ici_cut(block, start, length):
            return jax.lax.dynamic_slice(block, (start,), (length,))

        with _cut_lock:
            fn = _cut_program = _cut_program or brpc_ici_cut
    return fn


def run_slicer():
    """The jitted ``brpc_ici_cut_run``, ``piece_slicer``'s companion for a
    cut run: ``dynamic_slice(block, (start + sum(lengths[:i]),),
    (lengths[i],))`` for each of the static ``lengths``, out as separate
    arrays; ``start`` an operand, so one executable serves every offset of
    a (block bytes, lengths) shape."""
    global _run_program
    with _cut_lock:
        fn = _run_program
    if fn is None:
        import functools
        import jax

        @functools.partial(jax.jit, static_argnums=2)
        def brpc_ici_cut_run(block, start, lengths):
            out, at = [], 0
            for n in lengths:
                out.append(jax.lax.dynamic_slice(block, (start + at,), (n,)))
                at += n
            return tuple(out)

        with _cut_lock:
            fn = _run_program = _run_program or brpc_ici_cut_run
    return fn


def _start_operand(device, offset: int):
    """A cut's start as a device-resident int32 scalar, kept per (device,
    offset): a frame layout cuts at the same offsets again, and a host
    scalar handed to the program is a host-to-device copy a call
    (``DevicePlane._start_operand`` is the pattern).  A few bytes an entry,
    so a full table is simply dropped."""
    key = (device, offset)
    with _cut_lock:
        op = _cut_starts.get(key)
    if op is None:
        import jax
        import numpy as np
        op = jax.device_put(np.int32(offset), device)
        with _cut_lock:
            if len(_cut_starts) >= MAX_CUT_STARTS:
                _cut_starts.clear()
            _cut_starts[key] = op
    return op


def _cut(arr, r):
    """The host-side cut of a block ref, chosen on what ``arr`` is: the
    block itself where the ref covers all of it (no dispatch); out of a
    single-device array one dispatch of the compiled slicer
    (``piece_slicer``) with a cached start operand; else ``arr[a:b]`` — a
    host numpy block, an array spread over devices, a block whose offsets
    an int32 cannot hold.  A ref that is not inside its block raises:
    ``dynamic_slice`` would clamp the start and deliver other bytes."""
    size = len(arr)
    if not r.offset and r.length == size:
        return arr
    if r.offset < 0 or r.length < 0 or r.offset + r.length > size:
        raise ValueError(f"ref [{r.offset}, {r.offset + r.length}) is not "
                         f"inside its block of {size} bytes")
    if getattr(arr, "sharding", None) is None:
        return arr[r.offset:r.offset + r.length]
    device = _slicer_device(arr, size)
    compiled = device is not None
    (_g_compiled_cuts if compiled else _g_eager_cuts) << 1
    # layer span brpc.ici.cut: the dispatch alone, inside its piece's
    # brpc.ici.relocate (n: the cut's bytes, m: 1 for the eager fall-back)
    cut = _span.layer_begin("brpc.ici.cut", n=r.length,
                            m=0 if compiled else 1, cpu=True) \
        if _span.layer_on() else None
    try:
        if compiled:
            return piece_slicer()(arr, _start_operand(device, r.offset),
                                  r.length)
        return arr[r.offset:r.offset + r.length]
    finally:
        if cut is not None:
            cut.end()


def _slicer_device(arr, size: int):
    """The one device of a device array the compiled slicers take — on a
    single device, with offsets an int32 start can hold — else None."""
    devices = arr.sharding.device_set
    if len(devices) != 1 or size >= 1 << 31:
        return None
    (device,) = devices
    return device


def _cut_pieces(arr, device, offset: int, lengths: Tuple[int, ...]) -> Tuple:
    """ONE dispatch of the run slicer (``run_slicer``): the pieces of
    ``lengths`` from ``offset`` on, as separate arrays.  The caller checked
    that they lie inside the block."""
    _g_cut_runs << 1
    _g_compiled_cuts << len(lengths)
    # layer span brpc.ici.cut: one a dispatch, n the run's bytes
    cut = _span.layer_begin("brpc.ici.cut", n=sum(lengths), cpu=True) \
        if _span.layer_on() else None
    try:
        return run_slicer()(arr, _start_operand(device, offset), lengths)
    finally:
        if cut is not None:
            cut.end()


class _CutRun:
    """The pieces of one resident block that a cut run holds for the frames
    to come: a strong ref to the block (matched by ``is``) and
    ``(offset, length, array)`` in the order the window sends them.  A
    piece leaves by ``take`` or ``drop``, each a ``popleft``, so a writer
    that takes while ``_transport_close`` drops the run counts each piece
    once."""

    __slots__ = ("block", "pieces")

    def __init__(self, block, offset: int, lengths: Tuple[int, ...],
                 arrays: Tuple):
        self.block = block
        self.pieces = collections.deque()
        for n, a in zip(lengths, arrays):
            self.pieces.append((offset, n, a))
            offset += n

    def take(self, r):
        """The next piece's array where it is ``r``; None on a miss."""
        try:
            offset, length, arr = self.pieces[0]
            if (r.block is not self.block or r.offset != offset
                    or r.length != length):
                return None
            self.pieces.popleft()
        except IndexError:        # dropped under us
            return None
        _g_run_pieces << 1
        return arr

    def drop(self) -> int:
        """Free the pieces left; how many there were."""
        n = 0
        while True:
            try:
                self.pieces.popleft()
            except IndexError:
                return n
            n += 1


class _PlaneDesc:
    """A device-plane descriptor riding the in-process delivery path: the
    posted send's WR handle plus the payload length — the peer's
    ``post_recv`` fills in the dst-resident output at rendezvous."""

    __slots__ = ("transfer", "length")

    def __init__(self, transfer, length: int):
        self.transfer = transfer
        self.length = length


def _all_ready(arrays) -> bool:
    """True when every transfer already completed (skip the poller hop)."""
    try:
        return all(a.is_ready() for a in arrays)
    except AttributeError:
        return False


# ---- listener registry (ici "ports") ----------------------------------

_listeners: Dict[int, "IciListener"] = {}
_listeners_lock = _dbg.make_lock("ici.transport._listeners_lock")


class IciListener:
    def __init__(self, device_id: int, on_accept, mesh: IciMesh):
        self.device_id = device_id
        self.on_accept = on_accept
        self.mesh = mesh

    def connect(self, client_dev: int) -> IciSocket:
        client = IciSocket(client_dev, self.device_id, self.mesh)
        serv = IciSocket(self.device_id, client_dev, self.mesh)
        client.peer, serv.peer = serv, client
        serv.is_server_side = True
        self.on_accept(serv)
        return client


def ici_listen(device_id: int, on_accept,
               mesh: Optional[IciMesh] = None) -> IciListener:
    mesh = mesh or IciMesh.default()
    with _listeners_lock:
        if device_id in _listeners:
            raise OSError(errors.EINVAL, f"ici://{device_id} already listening")
        l = IciListener(device_id, on_accept, mesh)
        _listeners[device_id] = l
        return l


def ici_unlisten(device_id: int) -> None:
    with _listeners_lock:
        _listeners.pop(device_id, None)


def ici_connect(ep: EndPoint, local_dev: Optional[int] = None) -> IciSocket:
    with _listeners_lock:
        l = _listeners.get(ep.device_id)
    if l is None:
        raise ConnectionRefusedError(f"no server at {ep}")
    if local_dev is None:
        # default client residence: the neighbor that makes the hop one ICI
        # link (or the same chip when the mesh is size 1)
        local_dev = (ep.device_id + 1) % l.mesh.size
    return l.connect(local_dev)
