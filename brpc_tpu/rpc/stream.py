"""Streaming RPC: ordered byte/message streams attached to an RPC.

Reference: src/brpc/stream.{h,cpp} + policy/streaming_rpc_protocol.cpp
(SURVEY.md §3.4).  Semantics kept:

  * StreamCreate (client, stream.cpp:732) / StreamAccept (server, :756):
    the stream rides the host RPC's connection; ids are exchanged through
    RpcMeta.stream_settings (the reference's handshake).
  * Sliding window with consumed-bytes feedback: a writer may have at most
    ``max_buf_size`` unconsumed bytes in flight (AppendIfNotFull :274);
    the receiver reports consumption watermarks (SendFeedback :572) which
    wake blocked writers (SetRemoteConsumed :307).
  * Delivery through a per-stream ExecutionQueue so user handlers see
    ordered batches without blocking the socket reader (Consume :526).

Frames are tpu_std RpcMeta envelopes with ``stream_settings.frame_type``:
DATA / FEEDBACK / CLOSE; tpu_std routes them here from both server and
client parse paths.

Counters (``stream_stats()``, ``/vars`` ``rpc_stream_<key>``) are process-wide
totals that survive a stream's close.  Layer spans (``butil/layer_span.py``,
recorded only while a profiler session is on): ``brpc.stream.write``,
``.stall``, ``.queue`` and ``.handler``; docs/OBSERVABILITY.md.
"""
from __future__ import annotations

import struct
import time
from typing import Dict, List, Optional

from .. import bvar
from ..butil import flags as _flags
from ..butil import layer_span as _span
from ..butil.iobuf import IOBuf
from ..butil import debug_sync as _dbg
from ..butil.resource_pool import ResourcePool
from ..butil import custody_ledger as _ledger
from ..bthread.butex import Butex
from ..bthread.execution_queue import ExecutionQueue
from . import errors

FRAME_DATA = 0
FRAME_FEEDBACK = 1
FRAME_RST = 2
FRAME_CLOSE = 3
# DATA whose payload rode the fabric BULK plane: the control frame body
# is a 16-byte <u64 bulk uuid><u64 byte length> descriptor, the payload
# bytes move out-of-band on the dedicated bulk connection
# (native/fabric.cpp).  frame_type 4 is the tpu_std stream handshake.
FRAME_DATA_BULK = 5
# DATA whose payload rode the same-host SHM RING tier: identical
# 16-byte descriptor, bytes move through the mmap'd ring (one sender
# copy, zero-copy claim, no syscalls).  Which plane a frame rode is
# explicit in the frame type because the route can change mid-stream
# (plane death falls back tier by tier).
FRAME_DATA_SHM = 6
# N shm DATA frames announced by ONE control frame: the body is a
# CONCATENATION of 16-byte descriptors, in stream order.  On the ring
# tier the bytes are PUBLISHED before their descriptor is even queued
# (a memcpy, not a drained writev), so descriptors can coalesce without
# delaying any byte — and the per-frame control cost (RpcMeta pack +
# socket write on the sender, recv + protobuf parse + dispatch on the
# receiver) amortizes across the batch.  Measured: the 256KB-chunk
# cross-process stream tier is CONTROL-bound, not byte-bound, once the
# ring removes the copies.
FRAME_DATA_SHM_BATCH = 7

_BULK_DESC = struct.Struct("<QQ")

DEFAULT_MAX_BUF_SIZE = 2 * 1024 * 1024

# process-wide totals, kept past a stream's close.  ``writer_parks``: a
# ``write`` that really waited on its window; ``write_failures``: a DATA
# frame the socket refused, or a ``write`` that timed out;
# ``window_overruns``: a DATA frame that put a stream's unconsumed bytes
# past the window its writer said it keeps (the flow-control guarantee,
# counted on the receiving side so that a run can hold it at zero).
_STAT_KEYS = ("data_frames_sent", "data_bytes_sent", "data_frames_received",
              "data_bytes_received", "feedback_frames_sent",
              "feedback_frames_received", "writer_parks",
              "batches_delivered", "messages_delivered", "write_failures",
              "window_overruns")
_g = {k: bvar.Adder(f"rpc_stream_{k}") for k in _STAT_KEYS}


def stream_stats() -> Dict[str, int]:
    """What the streams of this process have done so far, live and closed."""
    return {k: v.get_value() for k, v in _g.items()}

# DATA frames at least this large ride the bulk fast plane when the
# socket binds one (ici:// cross-process FabricSocket); below it the
# descriptor + claim round trip costs more than the inline copy.  The
# stream's credit window and seq-ordered delivery are unchanged either
# way — only the byte transport differs.
_flags.define_flag("ici_stream_bulk_threshold", 64 * 1024,
                   "min stream DATA frame bytes routed over the fabric "
                   "bulk plane", _flags.positive_integer)
# Descriptor coalescing on the shm ring route: up to this many DATA
# frames share one control frame (1 = a descriptor per frame, the bulk
# tier's behavior).  Pending descriptors flush when the batch fills,
# when any OTHER frame must go out on the stream (ordering), before the
# writer parks on a full window (the receiver cannot return credits for
# frames it has not been told about), and after a short linger so a
# bursty-then-idle writer never strands a tail.  The effective batch is
# also bounded by the stream window (window-full forces a flush), so 32
# in practice means "amortize control across the in-flight window";
# latency-sensitive streams are bounded by the linger, not the batch.
_flags.define_flag("ici_stream_desc_batch", 32,
                   "max shm stream DATA descriptors coalesced into one "
                   "control frame", _flags.positive_integer)
_flags.define_flag("ici_stream_desc_flush_us", 1000,
                   "linger before a partial shm descriptor batch is "
                   "flushed", _flags.positive_integer)


class StreamOptions:
    def __init__(self, handler: Optional["StreamInputHandler"] = None,
                 max_buf_size: int = DEFAULT_MAX_BUF_SIZE,
                 messages_in_batch: int = 64):
        self.handler = handler
        self.max_buf_size = max_buf_size
        self.messages_in_batch = messages_in_batch


class StreamInputHandler:
    """User callback interface (reference StreamInputHandler)."""

    def on_received_messages(self, stream_id: int,
                             messages: List[IOBuf]) -> None:
        raise NotImplementedError

    def on_idle_timeout(self, stream_id: int) -> None:
        pass

    def on_closed(self, stream_id: int) -> None:
        pass


class Stream:
    # fablint guarded-state contract: flow-control counters under the
    # flow lock, lifecycle transitions + lazy queue under the state
    # lock, frame sequencing under the wire lock (see __init__ notes)
    # _flush_gen is deliberately NOT in this map: writes happen under
    # _wire_lock, but the linger timer's staleness probe reads it
    # lock-free on the shared TimerThread (a blocking acquire there
    # would stall every RPC deadline behind a writer parked in an shm
    # send) — GIL-atomic int read, false positives only spawn a no-op
    # flush tasklet.
    _GUARDED_BY = {
        "_produced": "_flow_lock",
        "_remote_consumed": "_flow_lock",
        "_exec": "_state_lock",
        "_sock_failed_cb": "_state_lock",
        "_release_conn": "_state_lock",
        "_seq": "_wire_lock",
        "_pending_desc": "_wire_lock",
    }

    def __init__(self, options: StreamOptions, is_client: bool):
        self.options = options
        self.is_client = is_client
        self.sid: int = 0               # local id (pool id)
        self.remote_sid: int = 0        # peer's id, set after handshake
        self.socket = None              # host connection
        self.connected = False
        self._conn_butex = Butex(0)
        # flow control (sender side)
        self._produced = 0
        self._remote_consumed = 0
        self._flow_lock = _dbg.make_lock("Stream._flow_lock")
        # a generation, bumped by every feedback and by close: a writer
        # reads it, looks at the window, and waits on the value it read
        self._writable_butex = Butex(0)
        # receiver side.  _local_received and _n_received are the reader
        # path's (frames arrive in cut order), _local_consumed and
        # _n_delivered the consumer's: one writer each
        self._local_received = 0
        self._local_consumed = 0
        self._last_feedback = 0
        self._n_received = 0
        self._n_delivered = 0
        self._peer_max_buf = 0          # the writer's window; 0 = not said
        self.closed = False
        self._seq = 0
        self._sock_failed_cb = None     # registered at mark_connected
        self._release_conn = None       # see hold_connection
        # guards the connected/closed transitions and the lazy _exec
        # creation: on_remote_close is runnable from ANY thread (socket
        # on_failed callbacks), and mark_connected has two concurrent
        # callers (the RPC response tasklet and a racing first stream
        # frame on the parse path) — unsynchronized check-then-act on
        # either flag double-registers callbacks or double-fires
        # on_closed (review findings)
        self._state_lock = _dbg.make_lock("Stream._state_lock")
        # serializes frame emission: seq assignment, the out-of-band bulk
        # post, and the control write must stay one atomic step so frame
        # k's bulk bytes can never trail frame k+1's descriptor
        self._wire_lock = _dbg.make_lock("Stream._wire_lock")
        # shm descriptor coalescing (FRAME_DATA_SHM_BATCH): published-
        # but-unannounced ring frames, flushed per the batch policy.
        # _flush_gen invalidates stale linger timers.
        self._pending_desc: List = []
        self._flush_gen = 0
        self._exec: Optional[ExecutionQueue] = None

    # -- sender ---------------------------------------------------------
    def writable_bytes(self) -> int:
        with self._flow_lock:
            return self.options.max_buf_size - (self._produced
                                                - self._remote_consumed)

    def append_if_not_full(self, data: IOBuf) -> int:
        """0 ok; EAGAIN window full; EINVAL closed (stream.cpp:274)."""
        n = len(data)
        with self._flow_lock:
            if self.closed:
                return errors.EINVAL
            if self._produced - self._remote_consumed + n \
                    > self.options.max_buf_size:
                return errors.EAGAIN
            self._produced += n
        try:
            self._send_frame(FRAME_DATA, data)
        except Exception:
            _g["write_failures"] << 1
            raise
        _g["data_frames_sent"] << 1
        _g["data_bytes_sent"] << n
        return 0

    def write(self, data: IOBuf, timeout: Optional[float] = None) -> int:
        """Blocking write: waits for window space (StreamWrite +
        StreamWait)."""
        if not _span.layer_on():
            return self._write(data, timeout)
        ls = _span.layer_begin("brpc.stream.write", n=len(data))
        try:
            return self._write(data, timeout)
        finally:
            if ls is not None:
                ls.end()

    def _write(self, data: IOBuf, timeout: Optional[float]) -> int:
        deadline = None if timeout is None else time.monotonic() + timeout
        stall = None    # layer span brpc.stream.stall, once it really parks
        try:
            while True:
                # the generation BEFORE the look at the window: a feedback
                # that lands after the look has bumped it, and the wait
                # below returns at once
                gen = self._writable_butex.value
                rc = self.append_if_not_full(data)
                if rc != errors.EAGAIN:
                    return rc
                # about to park on a full window: the receiver can only
                # return credits for frames it has been TOLD about — flush
                # any coalesced shm descriptors first or the wait deadlocks
                # until the linger timer fires
                self._flush_pending()
                remaining = None
                if deadline is not None:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        _g["write_failures"] << 1
                        return errors.ETIMEDOUT
                if stall is None:
                    _g["writer_parks"] << 1
                    if _span.layer_on():
                        stall = _span.layer_begin(
                            "brpc.stream.stall",
                            n=len(data) - self.writable_bytes())
                self._writable_butex.wait(
                    gen, remaining if remaining is not None else 1.0)
        finally:
            if stall is not None:
                stall.end()

    def set_remote_consumed(self, consumed: int) -> None:
        """Feedback arrival: wake blocked writers (stream.cpp:307)."""
        _g["feedback_frames_received"] << 1
        with self._flow_lock:
            if consumed > self._remote_consumed:
                self._remote_consumed = consumed
        self._wake_writers()

    def _wake_writers(self) -> None:
        self._writable_butex.fetch_add(1)
        self._writable_butex.wake_all()

    # -- receiver -------------------------------------------------------
    _CLOSE_MARKER = object()

    def on_data(self, data: IOBuf) -> None:
        with self._state_lock:
            if self.closed:
                return              # frame raced a cross-thread close:
                # on_closed already fired (or is firing), so delivering
                # now would violate the no-messages-after-closed contract
            if self._exec is None:
                # the linger keeps one consumer hot while frames stream
                # in serially (one per claim on the fabric path) —
                # without it every frame pays a tasklet spawn + park/wake
                self._exec = ExecutionQueue(self._consume_batch,
                                            linger_s=0.005)
            ex = self._exec
        n = len(data)
        self._local_received += n
        if self._peer_max_buf and \
                self._local_received - self._local_consumed \
                > self._peer_max_buf:
            _g["window_overruns"] << 1
        _g["data_frames_received"] << 1
        _g["data_bytes_received"] << n
        # layer span brpc.stream.queue: from here until the message's
        # batch enters the handler; n = messages ahead of it
        mark = _span.layer_mark(self._n_received - self._n_delivered) \
            if _span.layer_on() else None
        self._n_received += 1
        ex.execute((data, mark))

    def _consume_batch(self, it) -> None:
        msgs = []
        marks = []
        fire_closed = False
        for m in it:
            if m is Stream._CLOSE_MARKER:
                fire_closed = True
            else:
                msgs.append(m[0])
                if m[1] is not None:
                    marks.append(m[1])
        handler = self.options.handler
        if msgs:
            # what was delivered, taken before the handler sees it
            # (upstream's rule): a handler may cut the buffers it is handed
            consumed = sum(len(m) for m in msgs)
            _g["batches_delivered"] << 1
            _g["messages_delivered"] << len(msgs)
            for mark in marks:
                _span.layer_waited("brpc.stream.queue", mark)
            if handler is not None:
                ls = _span.layer_begin("brpc.stream.handler", n=len(msgs),
                                       cpu=True) \
                    if _span.layer_on() else None
                try:
                    handler.on_received_messages(self.sid, msgs)
                except Exception:
                    from ..butil import logging as log
                    log.error("stream handler raised", exc_info=True)
                finally:
                    if ls is not None:
                        ls.end()
            self._n_delivered += len(msgs)
            self._local_consumed += consumed
            # feedback when half a window was consumed since the last report
            if (self._local_consumed - self._last_feedback
                    >= self.options.max_buf_size // 2):
                self.send_feedback()
        if fire_closed and handler is not None:
            try:
                handler.on_closed(self.sid)
            except Exception:
                pass

    def send_feedback(self) -> None:
        self._last_feedback = self._local_consumed
        self._send_frame(FRAME_FEEDBACK, None,
                         consumed_bytes=self._local_consumed)
        _g["feedback_frames_sent"] << 1

    # -- lifecycle ------------------------------------------------------
    def wait_connected(self, timeout: float = 10.0) -> bool:
        if self.connected:
            return True
        self._conn_butex.wait(0, timeout)
        return self.connected

    def mark_connected(self, remote_sid: int, socket,
                       peer_max_buf: int = 0) -> None:
        """``peer_max_buf``: the window the far side's writer keeps, where
        the handshake said it (a racing first frame does not)."""
        if peer_max_buf:
            self._peer_max_buf = peer_max_buf
        with self._state_lock:
            if self.connected or self.closed:
                # connected: both the RPC-response path and a racing
                # first stream frame call this — a second registration
                # would append a duplicate on_failed callback that
                # close() can never remove.  closed: the user closed the
                # stream before the handshake response landed — a
                # registration now would never be removed (review
                # findings)
                return
            self.remote_sid = remote_sid
            self.socket = socket
            self.connected = True
            # a dying host connection must close every stream riding it —
            # without this, a socket failure (EOF, bulk-plane death,
            # parse error) would strand the stream's consumer waiting
            # forever for data or on_closed.  The callback is REMOVED
            # again when the stream closes; registration happens INSIDE
            # the state lock so a racing close cannot null the slot
            # between it and the append (review findings)
            self._sock_failed_cb = lambda _s: self.on_remote_close()
            socket.on_failed_callbacks.append(self._sock_failed_cb)
        if socket.failed:                # lost the race with set_failed
            self.on_remote_close()
        self._conn_butex.wake_all_and_set(1)

    def hold_connection(self, sock, release) -> bool:
        """The call that established this stream ends while the stream
        rides ``sock``, an exclusive (pooled or short) connection: it stays
        the stream's, and ``release`` (back to the pool, or closed) runs
        once the stream has closed.  False, and nothing kept, where the
        stream is not riding ``sock`` (never connected, closed already)."""
        with self._state_lock:
            if self.closed or not self.connected or self.socket is not sock:
                return False
            self._release_conn = release
            return True

    def close(self) -> None:
        with self._state_lock:
            if self.closed:
                return
            self.closed = True           # exactly-once transition: the
            # losing on_remote_close/close caller returns above instead
            # of double-firing _on_closed_local (review finding)
        if self.connected:
            try:
                self._send_frame(FRAME_CLOSE, None)
            except Exception:
                pass
        self._on_closed_local()

    def _on_closed_local(self) -> None:
        # published-but-unannounced ring frames must still be announced:
        # the receiver's stale-stream discard path claims and RELEASES
        # them, returning the ring space (otherwise those slots stay
        # parked until the whole socket dies)
        self._flush_pending()
        with self._state_lock:
            cb, self._sock_failed_cb = self._sock_failed_cb, None
            release, self._release_conn = self._release_conn, None
            sock = self.socket
        if cb is not None and sock is not None:
            try:
                sock.on_failed_callbacks.remove(cb)
            except ValueError:
                pass                     # set_failed already consumed it
        self._wake_writers()
        with self._state_lock:
            # self.closed is already True (set by every caller), so no
            # NEW queue can appear after this read — on_data drops
            # late frames instead
            ex = self._exec
        if ex is not None:
            # ordered after every queued data batch, then the queue stops
            ex.execute(Stream._CLOSE_MARKER)
            ex.stop()
        else:
            h = self.options.handler
            if h is not None:
                try:
                    h.on_closed(self.sid)
                except Exception:
                    pass
        _pool_remove(self.sid)
        if release is not None:
            release()

    def on_remote_close(self) -> None:
        with self._state_lock:
            if self.closed:
                return
            self.closed = True
        self._on_closed_local()

    # -- wire -----------------------------------------------------------
    def _send_frame(self, frame_type: int, data: Optional[IOBuf],
                    consumed_bytes: int = 0) -> None:
        from ..proto import rpc_meta_pb2 as meta_pb
        from ..policy.tpu_std import pack_frame
        sock = self.socket
        if sock is None:
            raise ConnectionError("stream not connected")
        payload = data if data is not None else IOBuf()
        # large DATA payloads ride a fast plane when the socket binds
        # one: the bytes go out-of-band under a reserved uuid and only a
        # 16-byte descriptor rides the control channel.  The ROUTE
        # (same-host shm ring vs the socket bulk conn) is the socket's
        # route-table decision (ici/route.py); sockets without a fast
        # plane (mem://, tcp://, in-process ici, or a fabric peer that
        # lacks the native core) return uuid 0 and the frame stays
        # inline — byte-identical to the pre-bulk wire.
        bulk_uuid = 0
        bulk_route = None
        if (frame_type == FRAME_DATA and len(payload)
                >= _flags.get_flag("ici_stream_bulk_threshold")):
            fast = getattr(sock, "stream_fast_begin", None)
            if fast is not None:
                # the stream id pins a striped shm plane's stripe —
                # per-stream ordering is decided by ONE ring
                bulk_uuid, bulk_route = fast(len(payload),
                                             affinity=self.sid)
            else:
                begin = getattr(sock, "stream_bulk_begin", None)
                if begin is not None:
                    bulk_uuid = begin()
                    if bulk_uuid:
                        bulk_route = "bulk"
        meta = meta_pb.RpcMeta()
        ss = meta.stream_settings
        ss.stream_id = self.remote_sid       # addressed to receiver's id
        ss.remote_stream_id = self.sid
        if consumed_bytes:
            ss.consumed_bytes = consumed_bytes
        bulk_exc = None
        rc = 0
        with self._wire_lock:
            if bulk_route == "shm":
                # RING route: bytes FIRST — publishing is a memcpy, not
                # a drained writev, so the descriptor can coalesce into
                # a batch (FRAME_DATA_SHM_BATCH) without delaying any
                # byte.  And because nothing references the frame until
                # its descriptor goes out, a failed publish falls back
                # to the next tier for THIS SAME FRAME — ring death
                # costs the sender zero stream casualties.
                try:
                    sock.stream_fast_send("shm", bulk_uuid, payload)
                except Exception:
                    rc = self._flush_desc_locked(sock)
                    bulk_uuid, bulk_route = 0, None
                    if rc == 0:
                        fast = getattr(sock, "stream_fast_begin", None)
                        if fast is not None:
                            bulk_uuid, bulk_route = fast(
                                len(payload), affinity=self.sid)
                    if bulk_route == "shm":
                        # the ring re-attached between degrade and
                        # re-screen: one more try, else next tier
                        try:
                            sock.stream_fast_send("shm", bulk_uuid,
                                                  payload)
                        except Exception:
                            bulk_uuid, bulk_route = 0, None
            if rc == 0 and bulk_route == "shm":
                self._pending_desc.append((bulk_uuid, len(payload)))
                if (len(self._pending_desc)
                        >= _flags.get_flag("ici_stream_desc_batch")):
                    rc = self._flush_desc_locked(sock)
                else:
                    self._arm_flush_timer(sock)
            elif rc == 0 and bulk_uuid:
                # socket bulk tier: descriptor FIRST, bulk bytes second
                # — the receiver parses the frame and parks in the claim
                # while the writev is still draining, overlapping its
                # per-frame Python work with the transfer.  A send that
                # fails after the descriptor went out degrades the
                # plane, which fails the peer's claim (-2) and with it
                # THIS stream (descriptor-consistency: no silent gap in
                # the stream's byte sequence) — the socket survives and
                # later frames ride the next tier until revival.
                # Pending shm descriptors flush first (stream order).
                rc = self._flush_desc_locked(sock)
                if rc == 0:
                    self._seq += 1
                    ss.frame_seq = self._seq
                    ss.frame_type = FRAME_DATA_BULK
                    desc = IOBuf(_BULK_DESC.pack(bulk_uuid, len(payload)))
                    rc = sock.write(pack_frame(meta, desc))
                if rc == 0:
                    try:
                        fast_send = getattr(sock, "stream_fast_send",
                                            None)
                        if fast_send is not None:
                            fast_send(bulk_route, bulk_uuid, payload)
                        else:
                            sock.stream_bulk_send(bulk_uuid, payload)
                    except Exception as e:
                        # descriptor went out but the payload never will:
                        # the peer's claim fails when the dead bulk conn
                        # cascades, but THIS end must not stay open with
                        # the frame's phantom bytes held against the
                        # window.  Handled OUTSIDE the wire lock —
                        # close() re-enters _send_frame for FRAME_CLOSE
                        # and the lock is not reentrant (review finding)
                        bulk_exc = e
            elif rc == 0:
                # inline frame (small DATA, FEEDBACK, CLOSE, RST):
                # pending shm descriptors flush first — the receiver
                # must learn of every preceding DATA frame before this
                # one (stream order; CLOSE after unflushed data would
                # drop the tail)
                rc = self._flush_desc_locked(sock)
                if rc == 0:
                    self._seq += 1
                    ss.frame_seq = self._seq
                    ss.frame_type = frame_type
                    rc = sock.write(pack_frame(meta, payload))
        if bulk_exc is not None:
            # the descriptor is on the wire but the payload never went.
            # A native write error already degraded the plane, but a
            # PYTHON-side failure (e.g. materializing a device block)
            # leaves it alive — sever it explicitly so the peer's pending
            # claim fails promptly (-2) and closes the peer's stream,
            # instead of stalling its control loop for the full claim
            # timeout (review finding)
            try:
                fast_abort = getattr(sock, "stream_fast_abort", None)
                if fast_abort is not None:
                    fast_abort(bulk_route)
                else:
                    abort = getattr(sock, "stream_bulk_abort", None)
                    if abort is not None:
                        abort()
            except Exception:
                pass
            self.close()
            raise bulk_exc
        if rc != 0:
            if frame_type == FRAME_DATA:
                # a refused DATA frame breaks the stream's byte sequence
                # (and on the bulk path would orphan a parked frame
                # through endless retries): fail the stream.  FEEDBACK is
                # cumulative — a transiently overcrowded socket just
                # re-reports with the next watermark, so it must NOT kill
                # a healthy stream (review finding).
                self.close()
            raise ConnectionError(f"stream write failed: {rc}")

    # -- shm descriptor batching -----------------------------------------
    # fablint: lock-held(_wire_lock)
    def _flush_desc_locked(self, sock) -> int:
        """Announce every published-but-unannounced ring frame in ONE
        control frame.  Caller holds _wire_lock.  Returns the socket
        write rc (0 when there was nothing to flush)."""
        if not self._pending_desc:
            return 0
        from ..proto import rpc_meta_pb2 as meta_pb
        from ..policy.tpu_std import pack_frame
        pending, self._pending_desc = self._pending_desc, []
        self._flush_gen += 1            # a parked linger timer is stale
        meta = meta_pb.RpcMeta()
        ss = meta.stream_settings
        ss.stream_id = self.remote_sid
        ss.remote_stream_id = self.sid
        self._seq += 1
        ss.frame_seq = self._seq
        # a lone descriptor goes out as plain FRAME_DATA_SHM (identical
        # 16-byte body) — the batch type is reserved for actual batches
        ss.frame_type = FRAME_DATA_SHM if len(pending) == 1 \
            else FRAME_DATA_SHM_BATCH
        body = IOBuf(b"".join(_BULK_DESC.pack(u, ln)
                              for u, ln in pending))
        return sock.write(pack_frame(meta, body))

    def _flush_pending(self) -> None:
        """Flush from outside the wire lock (linger timer, a writer
        about to park on a full window).  Write failures surface at the
        NEXT frame; the stream is usually dying already."""
        sock = self.socket
        if sock is None:
            return
        try:
            with self._wire_lock:
                self._flush_desc_locked(sock)
        except Exception:
            pass

    # fablint: lock-held(_wire_lock)
    def _arm_flush_timer(self, sock) -> None:
        """Caller holds _wire_lock: linger-flush a partial batch so a
        bursty-then-idle writer never strands announced-to-nobody
        frames (the window could never drain).  Armed once per batch;
        the generation check makes a timer whose batch already flushed
        a no-op.  The flush itself runs on a tasklet — a socket write
        must never run on the shared TimerThread."""
        if len(self._pending_desc) != 1:
            return
        gen = self._flush_gen
        from ..bthread.timer_thread import TimerThread

        def fire():
            # NO lock here: every RPC deadline rides the shared
            # TimerThread, and _wire_lock can be held for up to the shm
            # send timeout by a writer parked on a full ring.  The
            # staleness check is a lock-free int read (GIL-atomic;
            # _flush_gen only ever increments under the lock) — a stale
            # positive merely spawns a tasklet whose locked flush
            # no-ops on an empty pending list.
            if self._flush_gen != gen:
                return
            from ..bthread import scheduler
            scheduler.start_background(self._flush_pending,
                                       name="stream_desc_flush")

        TimerThread.instance().schedule_after(
            fire, _flags.get_flag("ici_stream_desc_flush_us") / 1e6)


# ---- stream registry (versioned ids like SocketId) ---------------------

# fablint custody contract (ISSUE 20): a registry slot handed out by
# get_resource comes back through return_resource exactly once (the
# versioned id rejects doubles); _pool_remove is the single drop point
# every close path funnels through.
_CUSTODY = {"get_resource": ("return_resource",)}

_streams: ResourcePool = ResourcePool()


def _pool_remove(sid: int) -> None:
    _streams.return_resource(sid)
    _ledger.release("stream", (sid,))


def stream_create(cntl, options: Optional[StreamOptions] = None) -> Stream:
    """Client side, before issuing the host RPC (StreamCreate
    stream.cpp:732)."""
    s = Stream(options or StreamOptions(), is_client=True)
    s.sid = _streams.get_resource(s)
    _ledger.acquire("stream", (s.sid,))
    cntl.stream_creator = s
    return s


def stream_accept(cntl, options: Optional[StreamOptions] = None) -> Stream:
    """Server side, inside the handler before done() (StreamAccept
    stream.cpp:756)."""
    s = Stream(options or StreamOptions(), is_client=False)
    s.sid = _streams.get_resource(s)
    _ledger.acquire("stream", (s.sid,))
    cntl.accepted_stream_id = s.sid
    return s


def find_stream(sid: int) -> Optional[Stream]:
    return _streams.address(sid)


def live_streams() -> List[Stream]:
    """Every registered (not yet closed-and-removed) stream — the server
    drain gate filters these down to the ones riding its connections."""
    return [s for s in _streams.live_payloads() if isinstance(s, Stream)]


def on_stream_frame(meta, body: IOBuf, socket) -> None:
    """Entry from tpu_std for frames carrying stream_settings.  Runs in
    the socket's reader-order consumption path (process_inline), so
    frames — including bulk claims — are resolved in cut order, which IS
    the stream's seq/byte order."""
    ss = meta.stream_settings
    s = find_stream(ss.stream_id)
    if s is None:
        if ss.frame_type in (FRAME_DATA_BULK, FRAME_DATA_SHM,
                             FRAME_DATA_SHM_BATCH):
            _discard_bulk_frame(ss.frame_type, body, socket)
        return                           # stale frame for a closed stream
    if not s.connected:
        s.mark_connected(ss.remote_stream_id, socket)
    if ss.frame_type == FRAME_DATA:
        s.on_data(body)
    elif ss.frame_type == FRAME_DATA_SHM_BATCH:
        # N coalesced ring descriptors: claim and deliver in order.  A
        # claim failure mid-batch keeps the delivered prefix (stream
        # order) and fails the stream exactly like a single-frame claim
        # failure below.
        raw = body.to_bytes()
        ok = True
        for off in range(0, len(raw), _BULK_DESC.size):
            uuid, blen = _BULK_DESC.unpack_from(raw, off)
            try:
                data = socket.stream_shm_claim(uuid, blen)
            except Exception as e:
                from ..butil import logging as log
                log.error("stream %d shm batch frame %#x unclaimable: %s",
                          s.sid, uuid, e)
                degrade = getattr(socket, "shm_plane_failed", None)
                try:
                    if degrade is not None:
                        degrade()
                        try:
                            s._send_frame(FRAME_RST, None)
                        except Exception:
                            pass
                    else:
                        socket.set_failed(
                            errors.EFAILEDSOCKET,
                            f"stream shm batch claim failed: {e}")
                finally:
                    s.on_remote_close()
                ok = False
                break
            s.on_data(data)
        if not ok:
            return
    elif ss.frame_type in (FRAME_DATA_BULK, FRAME_DATA_SHM):
        is_shm = ss.frame_type == FRAME_DATA_SHM
        uuid, blen = _BULK_DESC.unpack(body.to_bytes())
        try:
            if is_shm:
                data = socket.stream_shm_claim(uuid, blen)
            else:
                data = socket.stream_bulk_claim(uuid, blen)
        except Exception as e:
            # the fast plane died under the stream: this descriptor's
            # bytes will never arrive, and dropping the frame would
            # silently corrupt the byte stream — so THIS stream fails
            # (descriptor-consistency rule).  The socket survives: the
            # control channel is intact, later/other streams fall back
            # to the next tier, and the plane re-establishes in the
            # background (bulk_plane_failed / shm_plane_failed).
            # Sockets without a degradation hook keep the old
            # plane-death==socket-death contract.
            from ..butil import logging as log
            log.error("stream %d %s frame %#x unclaimable: %s",
                      s.sid, "shm" if is_shm else "bulk", uuid, e)
            degrade = getattr(
                socket,
                "shm_plane_failed" if is_shm else "bulk_plane_failed",
                None)
            try:
                if degrade is not None:
                    degrade()
                    # the socket survives, so the WRITER must be told its
                    # stream died (its bytes are gone) — otherwise it
                    # keeps writing into the void until its window wedges
                    try:
                        s._send_frame(FRAME_RST, None)
                    except Exception:
                        pass
                else:
                    socket.set_failed(errors.EFAILEDSOCKET,
                                      f"stream bulk claim failed: {e}")
            finally:
                s.on_remote_close()
            return
        s.on_data(data)
    elif ss.frame_type == FRAME_FEEDBACK:
        s.set_remote_consumed(ss.consumed_bytes)
    elif ss.frame_type in (FRAME_CLOSE, FRAME_RST):
        s.on_remote_close()


def _discard_bulk_frame(frame_type: int, body: IOBuf, socket) -> None:
    """A fast-plane descriptor addressed to a closed stream still has
    its payload parked (native frame map / shm ring slot) — claim and
    drop it, or it would pin a window's worth of receive buffers (or
    ring space) until the conn dies."""
    claim = getattr(socket, "stream_bulk_claim"
                    if frame_type == FRAME_DATA_BULK
                    else "stream_shm_claim", None)
    if claim is None:
        return
    raw = body.to_bytes()
    if frame_type != FRAME_DATA_SHM_BATCH and len(raw) != _BULK_DESC.size:
        return
    for off in range(0, len(raw) - _BULK_DESC.size + 1, _BULK_DESC.size):
        uuid, blen = _BULK_DESC.unpack_from(raw, off)
        try:
            claim(uuid, blen)
        except Exception:
            pass
