"""tpu_std: the canonical framed protocol (the baidu_std analogue).

Reference behavior: src/brpc/policy/baidu_rpc_protocol.cpp — 12-byte header
("PRPC", body_size, meta_size), protobuf RpcMeta, payload, then attachment;
server path ProcessRpcRequest (:312), response path SendRpcResponse (:139),
client path ProcessRpcResponse (:557).  This implementation keeps the frame
shape (magic "TRPC" + u32 meta_size + u32 body_size) with our own RpcMeta
schema (brpc_tpu/proto/rpc_meta.proto) and adds nothing CUDA/torch-ish: the
same frames travel over mem://, tcp://, and the ici:// device fabric.
"""
from __future__ import annotations

import time
from typing import Any

from .. import bvar
from ..butil.iobuf import IOBuf
from ..butil import flags as _flags
from ..butil import layer_span as _span
from ..butil import logging as log
from ..bthread import id as bthread_id
from ..proto import rpc_meta_pb2 as meta_pb
from ..rpc import errors
from ..rpc import rpc_dump
from ..rpc.controller import Controller, server_controller_pool
from ..rpc.span import start_server_span, end_server_span
from ..rpc.protocol import Protocol, ParseResult, register_protocol
from ..rpc import compress as compress_mod

MAGIC = b"TRPC"
HEADER_SIZE = 12

# ---- server-side latency decomposition (ROADMAP item 1's measurement
# substrate): where does a request's time go on the tpu_std/ici server
# path?  Five stages, each a LatencyRecorder (p50..p9999 exposed under
# tpu_std_server_<stage>_*) plus an rpcz annotation on the request's
# span:
#   queue   — frame cut on the read loop → process_request entry
#             (messenger dispatch + usercode-pool queue wait)
#   parse   — request payload decompress + ParseFromString
#   handler — md.invoke → done() (user code)
#   encode  — response meta/payload serialization + frame pack
#   write   — socket.write (transport enqueue + inline drain)
# Default "sampled" decomposes only rpcz-sampled requests, as SPAN
# ANNOTATIONS only — a LatencyRecorder `<<` measures ~4 µs and five
# stages would burn ~27 µs per request, blowing the ≤10% tracing
# budget on the 46 µs Python-handler path.  "on" additionally feeds
# the five tpu_std_server_<stage> recorders on EVERY request (the
# /vars-distribution mode for dedicated measurement runs); "off"
# disables everything.
#
# The same five boundaries are the layer spans brpc.server.<stage>
# (butil/layer_span.py): while a profiler session is on, every request
# is decomposed unless the flag is "off", and ONE clock read per
# boundary feeds the recorders, the rpcz annotation and the layer span.
_flags.define_flag("tpu_std_stage_metrics", "sampled",
                   "per-stage server latency decomposition: 'sampled' "
                   "(annotations on rpcz-sampled spans), 'on' (every "
                   "request + bvar recorders), 'off'")

_STAGES = ("queue", "parse", "handler", "encode", "write")
_stage_recorders = {s: bvar.LatencyRecorder(f"tpu_std_server_{s}")
                    for s in _STAGES}
# the Flag OBJECT, read as one attribute load per request instead of a
# registry-dict lookup per stage check (hot path)
_stage_flag = _flags.flag_object("tpu_std_stage_metrics")


_STAGE_SPANS = {s: f"brpc.server.{s}" for s in _STAGES}


def _stages_on() -> bool:
    """Every request is decomposed: the flag says so, or a profiler
    session wants the layer spans and the flag is not "off"."""
    mode = _stage_flag.value
    return mode == "on" or (mode != "off" and _span.layer_on())


def _stages_active(cntl: Controller) -> bool:
    if _stages_on():
        return True
    return _stage_flag.value != "off" and cntl.span is not None


def _record_stage(stage: str, start_ns: int, end_ns: int, span,
                  call_id: int = 0, opened=None) -> None:
    """One stage of one request, from its two stamps (CLOCK_MONOTONIC:
    ``time.monotonic_ns``, the native tier's ``recv_ns`` and
    ``perf_counter_ns`` read the same clock), to each consumer that is
    on: the ``tpu_std_server_<stage>`` recorder, the rpcz span's
    annotation, the layer span ``brpc.server.<stage>`` (``opened``: the
    one ``_enter_handler`` began)."""
    us = max(end_ns - start_ns, 0) // 1000
    if _stage_flag.value == "on":
        _stage_recorders[stage] << us
    if span is not None:
        span.annotate(f"{stage}_us={us}")
    if opened is not None:
        opened.finish(end_ns)
    elif _span.layer_on():
        _span.layer_record(_STAGE_SPANS[stage], start_ns, end_ns, call_id)


def _enter_handler(start_ns: int, call_id: int):
    """The handler stage as the thread's innermost layer span while user
    code runs on it, so that what the handler hands to other threads (a
    device completion, a nested call) names it as its cause.  The caller
    ``leave()``s it when the handler returns; ``_record_stage`` finishes
    it at ``done()``, on whichever thread that runs."""
    if not _span.layer_on():
        return None
    opened = _span.layer_begin(_STAGE_SPANS["handler"], call_id)
    if opened is not None:
        opened.start_ns = start_ns
    return opened


class StdMessage:
    """A cut but not yet parsed frame.  ``recv_ns`` stamps the cut on
    the read loop — the queue-wait stage's start."""
    __slots__ = ("meta", "body", "recv_ns")

    def __init__(self, meta: meta_pb.RpcMeta, body: IOBuf):
        self.meta = meta
        self.body = body
        self.recv_ns = 0


# ---- frame codec ------------------------------------------------------

def pack_frame(meta: meta_pb.RpcMeta, payload: IOBuf) -> IOBuf:
    meta_bytes = meta.SerializeToString()
    out = IOBuf()
    out.append(MAGIC + len(meta_bytes).to_bytes(4, "big")
               + len(payload).to_bytes(4, "big") + meta_bytes)
    out.append(payload)            # zero-copy ref share (device blocks ride)
    return out


def parse(source: IOBuf, socket, read_eof: bool, arg) -> ParseResult:
    header = source.fetch(HEADER_SIZE)
    if header is None:
        prefix = source.fetch(min(len(source), 4)) or b""
        if MAGIC.startswith(prefix):
            return ParseResult.not_enough_data()
        return ParseResult.try_others()
    if header[:4] != MAGIC:
        return ParseResult.try_others()
    meta_size = int.from_bytes(header[4:8], "big")
    body_size = int.from_bytes(header[8:12], "big")
    if meta_size > (1 << 26) or body_size > (1 << 31):
        return ParseResult.parse_error("absurd frame sizes")
    total = HEADER_SIZE + meta_size + body_size
    if len(source) < total:
        return ParseResult.not_enough_data()
    source.pop_front(HEADER_SIZE)
    meta_buf = source.cut(meta_size)
    body = source.cut(body_size)
    meta = meta_pb.RpcMeta()
    try:
        meta.ParseFromString(meta_buf.to_bytes())
    except Exception as e:
        return ParseResult.parse_error(f"bad meta: {e}")
    msg = StdMessage(meta, body)
    msg.recv_ns = time.monotonic_ns()
    return ParseResult.ok(msg)


# ---- client side ------------------------------------------------------

def serialize_request(request: Any, cntl: Controller) -> IOBuf:
    buf = IOBuf()
    if request is None:
        return buf
    if hasattr(request, "SerializeToString"):
        data = request.SerializeToString()
    elif isinstance(request, (bytes, bytearray)):
        data = bytes(request)
    else:
        raise TypeError(f"cannot serialize {type(request)}")
    if cntl.compress_type:
        data = compress_mod.compress(cntl.compress_type, data)
    buf.append(data)
    return buf


def pack_request(payload: IOBuf, cid: int, cntl: Controller,
                 method_full_name: str) -> IOBuf:
    meta = meta_pb.RpcMeta()
    service, _, method_name = method_full_name.rpartition(".")
    meta.request.service_name = service
    meta.request.method_name = method_name
    if cntl.stream_creator is not None:     # stream handshake rides the RPC
        meta.stream_settings.stream_id = cntl.stream_creator.sid
        meta.stream_settings.frame_type = 4
        meta.stream_settings.need_feedback = True
        # the window this writer keeps: the far side counts an overrun
        meta.stream_settings.write_window_bytes = \
            cntl.stream_creator.options.max_buf_size
    meta.request.log_id = cntl.log_id
    meta.correlation_id = cid
    meta.compress_type = cntl.compress_type
    if cntl.timeout_ms:
        meta.request.timeout_ms = cntl.timeout_ms
        # deadline budget REMAINING at send time (shrinks at each hop):
        # total budget minus what this caller already spent — a retry
        # issued late in the budget tells the server how little is left,
        # and the server sheds it before any work once it hits zero
        elapsed_ms = (time.monotonic_ns() // 1000
                      - cntl._start_us) / 1000.0 if cntl._start_us else 0.0
        meta.request.deadline_left_ms = max(
            int(cntl.timeout_ms - elapsed_ms), 1)
    if cntl.auth_token:
        meta.request.auth_token = cntl.auth_token
    if cntl.priority is not None:
        # offset-encoded: 0 on the wire = unset (server default band)
        meta.request.priority = cntl.priority + 1
    if cntl.tenant:
        meta.request.tenant = cntl.tenant
    if cntl.span is not None:
        meta.request.trace_id = cntl.span.trace_id
        meta.request.span_id = cntl.span.span_id
        meta.request.parent_span_id = cntl.span.parent_span_id
    body = IOBuf()
    body.append(payload)
    att_size = len(cntl.request_attachment)
    if att_size:
        meta.attachment_size = att_size
        body.append(cntl.request_attachment)
    return pack_frame(meta, body)


def process_inline(msg: StdMessage, socket) -> bool:
    """Reader-order consumption of stream frames (data/feedback/close):
    their relative order is the stream's byte order, so they must never go
    through the concurrent per-message dispatch."""
    meta = msg.meta
    if (meta.correlation_id == 0 and not meta.request.service_name
            and meta.HasField("stream_settings")):
        from ..rpc.stream import on_stream_frame
        on_stream_frame(meta, msg.body, socket)
        return True
    return False


def process_response(msg: StdMessage, socket) -> None:
    """ProcessRpcResponse: lock the correlation id; stale versions fail to
    lock and the response is dropped (the retry-race resolution)."""
    if msg.meta.correlation_id == 0 and msg.meta.HasField("stream_settings"):
        from ..rpc.stream import on_stream_frame
        on_stream_frame(msg.meta, msg.body, socket)
        return
    cid = msg.meta.correlation_id
    rc, cntl = bthread_id.lock(cid)
    if rc != 0 or cntl is None:
        return                      # stale/duplicate/cancelled — ignore
    cntl.remote_side = socket.remote_side
    if (msg.meta.HasField("stream_settings")
            and cntl.stream_creator is not None):
        # handshake completion: server accepted our stream
        cntl.stream_creator.mark_connected(
            msg.meta.stream_settings.remote_stream_id, socket,
            msg.meta.stream_settings.write_window_bytes)
    cntl.handle_response(cid, msg.meta, msg.body)


# ---- server side ------------------------------------------------------

def process_request(msg: StdMessage, socket, server) -> None:
    """ProcessRpcRequest (baidu_rpc_protocol.cpp:312): find method, check
    limits, run user code in this tasklet, respond via socket write.
    The per-request Controller comes from the server-side pool
    (controller.server_controller_pool) and is recycled once the
    response is written — the reference keeps this path allocation-free
    the same way."""
    meta = msg.meta
    if not meta.request.service_name and meta.HasField("stream_settings"):
        from ..rpc.stream import on_stream_frame
        on_stream_frame(meta, msg.body, socket)
        return
    req_meta = meta.request
    full_name = f"{req_meta.service_name}.{req_meta.method_name}"
    cid = meta.correlation_id
    start_us = time.monotonic_ns() // 1000
    if rpc_dump.dump_enabled():
        rpc_dump.maybe_dump_request(pack_frame(meta, msg.body))

    cntl = server_controller_pool.acquire()  # fablint: custody-moved(request-lifecycle) the shim rides the request; _maybe_recycle releases it back to the pool when the response (or failure path) completes
    cntl.server = server
    cntl.log_id = req_meta.log_id
    cntl.remote_side = socket.remote_side
    if req_meta.auth_token:
        cntl.auth_token = req_meta.auth_token
    if meta.compress_type:
        cntl.compress_type = meta.compress_type
    if req_meta.timeout_ms:
        cntl.method_deadline = time.monotonic() + req_meta.timeout_ms / 1000.0
    # admission-control propagation (offset-decoded; handlers may read)
    if req_meta.priority:
        cntl.priority = req_meta.priority - 1
    if req_meta.tenant:
        cntl.tenant = req_meta.tenant
    if req_meta.deadline_left_ms:
        cntl.deadline_left_ms = req_meta.deadline_left_ms

    start_server_span(cntl, full_name, req_meta.trace_id,
                      req_meta.span_id)
    stages = _stages_active(cntl)
    if stages and msg.recv_ns:
        _record_stage("queue", msg.recv_ns, time.monotonic_ns(),
                      cntl.span, cid)
    md = server.find_method(full_name)
    status = server.method_status(full_name) if md is not None else None
    server_counted = [False]
    handler_t0 = [0, None]      # start stamp, the open layer span

    def send_response(resp: Any = None) -> None:
        t_enc0 = time.monotonic_ns() if stages else 0
        if stages and handler_t0[0]:
            _record_stage("handler", handler_t0[0], t_enc0, cntl.span, cid,
                          handler_t0[1])
        rmeta = meta_pb.RpcMeta()
        rmeta.correlation_id = cid
        rmeta.response.error_code = cntl.error_code_
        rmeta.response.error_text = cntl.error_text_
        if cntl.retry_after_ms:
            # admission shed hint: how long the client should back off
            rmeta.response.retry_after_ms = cntl.retry_after_ms
        if cntl.accepted_stream_id:
            from ..rpc.stream import find_stream
            srv_stream = find_stream(cntl.accepted_stream_id)
            client_sid = meta.stream_settings.stream_id
            if srv_stream is None:
                pass
            elif cntl.failed():
                # the establishing call failed after stream_accept: the
                # client never learns the stream's id, so nothing else
                # would ever close it
                srv_stream.close()
            else:
                # complete the stream handshake: echo ids both ways, and
                # say the window this side's writer keeps
                rmeta.stream_settings.stream_id = client_sid
                rmeta.stream_settings.remote_stream_id = cntl.accepted_stream_id
                rmeta.stream_settings.write_window_bytes = \
                    srv_stream.options.max_buf_size
                srv_stream.mark_connected(
                    client_sid, socket,
                    meta.stream_settings.write_window_bytes)
        payload = IOBuf()
        if resp is not None and not cntl.failed():
            data = resp.SerializeToString() if hasattr(resp, "SerializeToString") \
                else bytes(resp)
            if meta.compress_type:
                data = compress_mod.compress(meta.compress_type, data)
                rmeta.compress_type = meta.compress_type
            payload.append(data)
        resp_att = cntl._peek_response_attachment()
        att_size = len(resp_att) if resp_att is not None else 0
        if att_size:
            rmeta.attachment_size = att_size
            payload.append(resp_att)
        frame = pack_frame(rmeta, payload)
        t_wr0 = time.monotonic_ns() if stages else 0
        if stages:
            _record_stage("encode", t_enc0, t_wr0, cntl.span, cid)
        socket.write(frame)
        if stages:
            _record_stage("write", t_wr0, time.monotonic_ns(), cntl.span,
                          cid)
        if cntl.span is not None:
            end_server_span(cntl)
        if status is not None:
            status.on_responded(cntl.error_code_,
                                time.monotonic_ns() // 1000 - start_us)
        if server_counted[0]:
            server.on_request_out()

    if server.is_draining():
        # lame-duck: a draining server rejects NEW requests with
        # retryable ELOGOFF so clients fail over to another replica
        # instantly; work admitted before the drain flipped keeps running
        # inside the grace window (stream frames never reach here — they
        # ride process_inline)
        cntl.set_failed(errors.ELOGOFF, "server is draining (lame duck)")
        status = None       # don't on_responded a rejected request
        send_response()
        cntl._maybe_recycle()
        return

    def _parse_and_invoke() -> None:
        # parse request payload (gates held; send_response accounts)
        t_parse0 = time.monotonic_ns() if stages else 0
        try:
            body = msg.body
            if meta.attachment_size:
                keep = len(body) - meta.attachment_size
                payload_part = body.cut(keep)
                body.cutn(cntl.request_attachment, meta.attachment_size)
                body = payload_part
            data = body.to_bytes()
            if meta.compress_type:
                data = compress_mod.decompress(meta.compress_type, data)
            request = md.request_cls()
            request.ParseFromString(data)
        except Exception as e:
            cntl.set_failed(errors.EREQUEST, f"fail to parse request: {e}")
            send_response()
            cntl._maybe_recycle()
            return
        if stages:
            _record_stage("parse", t_parse0, time.monotonic_ns(),
                          cntl.span, cid)

        response = md.response_cls()
        done_called = [False]
        if stages:
            handler_t0[0] = time.monotonic_ns()
            handler_t0[1] = _enter_handler(handler_t0[0], cid)

        def done() -> None:
            if done_called[0]:
                return
            done_called[0] = True
            send_response(response)

        cntl.set_server_done(done)
        try:
            try:
                md.invoke(cntl, request, response, done)
            finally:
                if handler_t0[1] is not None:
                    handler_t0[1].leave()
        except Exception as e:   # uncaught user exception → EINTERNAL
            log.error("method %s raised: %s", full_name, e, exc_info=True)
            if not done_called[0]:
                cntl.set_failed(errors.EINTERNAL, f"{type(e).__name__}: {e}")
                done()
                cntl._release_session_data()
                cntl._maybe_recycle()

    adm = server.admission
    if adm is None:
        # historical reject-at-gate path (no admission layer)
        if not server.on_request_in():
            cntl.set_failed(errors.ELIMIT, "server max_concurrency reached")
            status = None   # rejected before on_requested: accounting it
            #                 would skew concurrency and poison the
            #                 limiter floor (shed != method failure)
            send_response()
            cntl._maybe_recycle()
            return
        server_counted[0] = True
        if md is None:
            cntl.set_failed(errors.ENOMETHOD if req_meta.service_name in
                            server.services() else errors.ENOSERVICE,
                            f"no method {full_name}")
            send_response()
            cntl._maybe_recycle()
            return
        if status is not None and not status.on_requested():
            cntl.set_failed(errors.ELIMIT,
                            f"method {full_name} max_concurrency reached")
            status = None           # don't on_responded a rejected request
            send_response()
            cntl._maybe_recycle()
            return
        # auth (reference: protocol verify hook)
        if server.options.auth is not None:
            if not server.options.auth.verify(cntl.auth_token, socket):
                cntl.set_failed(errors.ERPCAUTH, "authentication failed")
                send_response()
                cntl._maybe_recycle()
                return
        _parse_and_invoke()
        return

    # ---- admission-control path (rpc/admission.py): the gate decision
    # moves into the shared controller — shed-before-queue, per-tenant
    # WFQ, deadline-expired shed — identical on all three call planes
    if md is None:
        cntl.set_failed(errors.ENOMETHOD if req_meta.service_name in
                        server.services() else errors.ENOSERVICE,
                        f"no method {full_name}")
        status = None               # never admitted: nothing to account
        send_response()
        cntl._maybe_recycle()
        return
    from ..rpc import admission as admission_mod

    def _admitted(queued_us: int) -> None:
        server_counted[0] = True
        if stages and queued_us:
            # admission-queue wait feeds the queue-stage decomposition
            now = time.monotonic_ns()
            _record_stage("queue", now - queued_us * 1000, now, cntl.span,
                          cid)
        if server.options.auth is not None:
            if not server.options.auth.verify(cntl.auth_token, socket):
                cntl.set_failed(errors.ERPCAUTH, "authentication failed")
                send_response()
                cntl._maybe_recycle()
                return
        _parse_and_invoke()

    def _shed(code: int, text: str, retry_after: int) -> None:
        nonlocal status
        status = None               # shed: no on_requested happened
        cntl.set_failed(code, text)
        if retry_after:
            cntl.retry_after_ms = retry_after
        send_response()
        cntl._maybe_recycle()

    adm.submit(priority=cntl.priority, tenant=cntl.tenant,
               deadline_left_ms=cntl.deadline_left_ms or None,
               recv_us=(msg.recv_ns // 1000) if msg.recv_ns else 0,
               try_enter=admission_mod.server_method_gate(server, status),
               run=_admitted, shed=_shed)


PROTOCOL = Protocol(
    name="tpu_std",
    parse=parse,
    process_request=process_request,
    process_response=process_response,
    serialize_request=serialize_request,
    pack_request=pack_request,
    process_inline=process_inline,
)


def _register() -> None:
    from ..rpc.protocol import find_protocol
    if find_protocol("tpu_std") is None:
        register_protocol(PROTOCOL)


_register()
