"""Channel: the client stub.

Reference: src/brpc/channel.{h,cpp} (Init :236-393, CallMethod :407-592) and
Controller::IssueRPC (controller.cpp:985-1144).  A channel targets a single
endpoint or a naming service + load balancer; per-call state lives in the
Controller; connection selection honors single/pooled/short types.
"""
from __future__ import annotations

import functools

import threading
from dataclasses import dataclass
from typing import Any, Callable, Optional

from ..butil.endpoint import EndPoint, parse_endpoint
from ..butil import layer_span as _span
from . import errors
from .controller import Controller
from .input_messenger import InputMessenger
from . import loopback as _loopback
from .protocol import find_protocol
from . import request_context as _reqctx
from .socket_map import SocketMap
from .span import end_client_span, maybe_start_client_span


@dataclass
class ChannelOptions:
    protocol: str = "tpu_std"
    # "" = adaptive: single when the protocol supports it, else pooled
    # (reference adaptive_connection_type.h); explicit values are enforced
    connection_type: str = ""           # "" | single | pooled | short
    timeout_ms: int = 1000
    max_retry: int = 3
    backup_request_ms: int = 0          # 0 = disabled
    # Opt-in: split timeout_ms evenly over max_retry+1 tries and hedge a
    # fresh try when a try's share elapses silently (recovers requests a
    # lossy fabric *dropped*).  Off by default because a hedged try can
    # duplicate a non-idempotent request — same caveat as backup_request_ms
    # (docs/cn/backup_request.md); the reference treats ERPCTIMEDOUT as
    # final.  Ignored when backup_request_ms is set (that is already the
    # user's explicit hedging schedule).
    retry_on_timeout: bool = False
    # Base delay before a retry after a connection-class failure
    # (EFAILEDSOCKET/ECONNREFUSED/...), doubling per retry with ±25%
    # seeded jitter.  0 (default) retries immediately — the historical
    # behavior.  Spaced retries are what let one generously-budgeted
    # call issued DURING an endpoint outage survive until health-check
    # revival brings the peer back (docs/PARITY.md failure semantics).
    retry_backoff_ms: int = 0
    connect_timeout_ms: int = 1000
    auth: object = None                 # Authenticator
    ssl_context: object = None          # ssl.SSLContext for TLS channels
    ns_filter: object = None            # NamingServiceFilter: fn(ServerEntry)->bool
    # The mesh device this channel's caller "lives on" for ici://
    # targets: response device refs relocate TOWARD it.  None keeps the
    # historical default (the target's neighbor, (remote+1) % mesh.size
    # — every response pays one relocation hop); a caller colocated with
    # the server passes the server's own device id for the pure ref-pass
    # round trip.
    ici_local_device: object = None     # Optional[int]
    # Admission-control defaults stamped on every call that didn't set
    # its own (Controller.priority/tenant): priority band 0=critical ..
    # 3=sheddable (None = let the server apply its default band) and the
    # fair-queueing tenant this channel's traffic belongs to.
    priority: Optional[int] = None
    tenant: str = ""


# loopback-screen module handles, resolved once at first call (lazy only
# to dodge the policy<->rpc import cycle at load time)
_loopback_screen = None


def _loopback_screen_modules():
    global _loopback_screen
    if _loopback_screen is None:
        from . import fault_injection as _fi
        from . import rpc_dump as _dump
        from ..policy.tpu_std import _stage_flag
        _loopback_screen = (_fi, _dump, _stage_flag)
    return _loopback_screen


class Channel:
    def __init__(self):
        self.options = ChannelOptions()
        self._endpoint: Optional[EndPoint] = None
        self._lb = None                 # LoadBalancer
        self._ns_thread = None          # NamingServiceThread
        self._protocol = None
        self.messenger = InputMessenger(server=None)
        self._native_ici = None
        self._native_ici_lock = threading.Lock()
        # exclusive connections that streams of this channel ride
        # (_on_call_end): out of the socket map, so close() fails them itself
        self._stream_conns = set()

    # ---- init ---------------------------------------------------------
    def init(self, target: Any, lb_name: str = "",
             options: Optional[ChannelOptions] = None) -> int:
        if options is not None:
            self.options = options
        self._protocol = find_protocol(self.options.protocol)
        if self._protocol is None:
            raise ValueError(f"unknown protocol {self.options.protocol!r}")
        from .protocol import (CONNECTION_TYPE_SINGLE, CONNECTION_TYPE_POOLED,
                               CONNECTION_TYPE_SHORT)
        _ctype_bits = {"single": CONNECTION_TYPE_SINGLE,
                       "pooled": CONNECTION_TYPE_POOLED,
                       "short": CONNECTION_TYPE_SHORT}
        if self.options.connection_type not in ("",) and \
                self.options.connection_type not in _ctype_bits:
            raise ValueError(
                f"unknown connection_type {self.options.connection_type!r}")
        want = _ctype_bits.get(self.options.connection_type)
        if want is not None and not (
                self._protocol.supported_connection_type & want):
            # the reference fails Channel::Init on an unsupported explicit
            # connection type rather than silently changing it
            raise ValueError(
                f"protocol {self._protocol.name!r} does not support "
                f"connection_type={self.options.connection_type!r}")
        if isinstance(target, EndPoint):
            self._endpoint = target
            return 0
        from ..policy.naming import is_naming_url
        if isinstance(target, str) and is_naming_url(target):
            # naming-service url (file://, list://, http://, mesh://, …)
            from ..policy.naming import get_naming_service_thread
            from ..policy.load_balancers import create_load_balancer
            self._lb = create_load_balancer(lb_name or "rr")
            self._ns_thread = get_naming_service_thread(target)
            watcher = self._lb
            if self.options.ns_filter is not None:
                watcher = _FilteredWatcher(self._lb, self.options.ns_filter)
            # remembered so close() can detach THIS object — removing
            # the raw LB would miss the filter wrapper (review finding)
            self._ns_watcher = watcher
            self._ns_thread.add_watcher(watcher)
            return 0
        self._endpoint = parse_endpoint(target) if isinstance(target, str) else target
        # loopback fast-plane eligibility (channel-level screens; the
        # per-call ones live in call_method): unary tpu_std against an
        # in-process mem:// server, no auth, no hedging
        from ..butil.endpoint import SCHEME_MEM as _MEM
        if (self._endpoint is not None
                and getattr(self._endpoint, "scheme", None) == _MEM
                and self.options.protocol == "tpu_std"
                and self.options.auth is None
                and self.options.backup_request_ms <= 0):
            self._loopback_name = self._endpoint.host
            # the breaker gate from _select_socket, honored on the fast
            # plane too: an isolated endpoint fails fast even in-process
            # (loopback traffic itself never trips or resets breakers —
            # there is no connection to be unhealthy)
            from .circuit_breaker import BreakerRegistry
            self._loopback_breaker = \
                BreakerRegistry.instance().breaker(self._endpoint)
        return 0

    # ---- calls ----------------------------------------------------------
    def call_method(self, method_full_name: str, cntl: Controller,
                    request: Any, response_cls: Any = None,
                    done: Optional[Callable[[Controller], None]] = None):
        """Sync when done is None (returns the response); async otherwise."""
        # layer span brpc.call: entry -> return, under the correlation id
        # where this plane has one (the native tier correlates in C++)
        ls = _span.layer_begin("brpc.call", cpu=True) \
            if _span.layer_on() else None
        try:
            # ici:// fast path: when the target device has a native listener in
            # this process, the whole unary hot path (frame/window/dispatch/
            # correlation) runs in native/rpc.cpp — no Python between
            # serialize and parse except device-ref relocation (VERDICT r3 #1).
            # Streaming, auth, non-tpu_std protocols, backup-request hedging,
            # and frames too large for the native send window ride the Python
            # plane (which drains big payloads chunkwise through its credit
            # window).
            nch = self._native_ici
            if nch is None:
                nch = self._native_ici_binding(cntl)
            elif cntl.stream_creator is not None:
                # the cached-binding fast path must re-screen the ONE
                # eligibility input that varies per call; the channel-level
                # ones (protocol, auth, endpoint) were screened at cache time
                nch = None
            if nch is not None and done is None:
                # every sync call — a channel's first included — runs ONE
                # flat code object (context inherit, screens, issue,
                # response, error tails all inside call_fused).  Anything it
                # can't serve — oversize frames, hedging, a dead conn's
                # one-shot re-route — returns the FALLTHROUGH sentinel and
                # rides the Python plane below.
                result = nch.call_fused(method_full_name, cntl, request,
                                        response_cls, self)
                if result is not nch.FUSED_FALLTHROUGH:
                    return result
                nch = None
            # cascading inbound context (rpc/request_context.py): a call made
            # inside a handler's scope inherits the inbound priority/tenant
            # unless THIS call overrides them, and its timeout is capped at
            # the inbound deadline budget minus the handler time already
            # spent.  Inherited values beat channel-wide defaults (a static
            # channel config must not demote a critical inbound request).
            _ctx = _reqctx.current()
            if _ctx is not None:
                if cntl.priority is None and _ctx.priority is not None:
                    cntl.priority = _ctx.priority
                if not cntl.tenant and _ctx.tenant:
                    cntl.tenant = _ctx.tenant
                residual = _ctx.residual_deadline_ms()
                if residual is not None:
                    if residual <= 0:
                        cntl.set_failed(
                            errors.ERPCTIMEDOUT,
                            "inherited deadline budget spent before call")
                        if cntl.span is not None:
                            end_client_span(cntl)
                        if done is not None:
                            done(cntl)
                            return None
                        return None
                    base = cntl.timeout_ms if cntl.timeout_ms is not None \
                        else self.options.timeout_ms
                    if base is None or base <= 0 or base > residual:
                        cntl.timeout_ms = max(int(residual), 1)
            # channel-level admission defaults (per-call Controller wins)
            if cntl.priority is None and self.options.priority is not None:
                cntl.priority = self.options.priority
            if not cntl.tenant and self.options.tenant:
                cntl.tenant = self.options.tenant
            # an async call on the native tier (a sync one went through
            # call_fused above, and nch is None if it fell through)
            if nch is not None \
                    and not self._fast_call_fits(nch, cntl, request):
                nch = None
            if nch is not None:
                if cntl.timeout_ms is None:
                    cntl.timeout_ms = self.options.timeout_ms
                from ..bthread import scheduler

                def _run():
                    try:
                        self._native_ici_call(nch, method_full_name, cntl,
                                              request, response_cls)
                    except Exception as e:   # done() must ALWAYS fire
                        if not cntl.failed():
                            cntl.set_failed(errors.EINTERNAL,
                                            f"{type(e).__name__}: {e}")
                        done(cntl)
                        return
                    if self._native_ici_fallback(cntl):
                        # dead native conn (server restarted) or oversize
                        # fast-fail: re-route through the Python plane
                        self.call_method(method_full_name, cntl, request,
                                         response_cls, done=done)
                    else:
                        if cntl.span is not None:
                            end_client_span(cntl)
                        done(cntl)

                scheduler.start_background(
                    _run, name=f"ici-call:{method_full_name}")
                return None
            # mem:// loopback fast plane (loopback.py): in-process direct
            # dispatch, no byte codec / socket machinery.  Per-call screens:
            # anything the wire plane implements that loopback doesn't
            # (streaming handshakes, compression, fault injection, rpc_dump
            # sampling) falls through.
            lb_name = getattr(self, "_loopback_name", None)
            if (lb_name is not None and cntl.stream_creator is None
                    and cntl.compress_type == 0 and not cntl.auth_token
                    and _loopback.enabled()):
                hot = _loopback_screen_modules()
                _fi, _dump, _stage_flag = hot
                if cntl.span is None:
                    maybe_start_client_span(cntl, method_full_name)
                srv = _loopback.server_for(lb_name)
                # rpcz-sampled requests and the stage-metrics measurement
                # mode ride the wire plane: they exist to observe it (server
                # span, five-stage decomposition); auth verification needs
                # the wire socket context
                if (srv is not None and cntl.span is None
                        and srv.options.auth is None
                        and not self._loopback_breaker.is_isolated()
                        and _stage_flag.value != "on"
                        and _fi.active() is None
                        and not _dump.dump_enabled()):
                    if cntl.timeout_ms is None:
                        cntl.timeout_ms = self.options.timeout_ms
                    # loopback completes the client span itself (the span
                    # ends with the response, also on async completions)
                    return _loopback.call(srv, method_full_name, cntl,
                                          request, response_cls, done)
            if self.options.auth is not None and not cntl.auth_token:
                cntl.auth_token = self.options.auth.generate_credential(cntl)
            payload = self._protocol.serialize_request(request, cntl)
            if cntl.span is None:
                maybe_start_client_span(cntl, method_full_name)
            if done is not None:
                cntl._start_call(self, method_full_name, payload,
                                 response_cls, done)
                return None
            # layer span brpc.call.wait: the frame handed to the socket (the
            # caller writes the first window piece itself) until this thread
            # resumes with the response
            wait = _span.layer_begin("brpc.call.wait") \
                if ls is not None else None
            try:
                cntl._start_call(self, method_full_name, payload,
                                 response_cls, done)
                cntl.join((cntl.timeout_ms or 0) / 1000.0 + 35.0)
            finally:
                if wait is not None:
                    wait.call_id = cntl._cid
                    wait.end()
            return cntl.response
        finally:
            if ls is not None:
                ls.call_id = cntl._cid
                ls.end()

    def _fast_call_fits(self, nch, cntl: Controller, request) -> bool:
        """Per-call screen for the native fast plane: the frame (payload
        + attachment + headroom) must fit the native send window, and
        backup-request hedging rides the Python plane."""
        try:                            # non-proto requests have no size
            req_sz = request.ByteSize()
        except Exception:
            req_sz = 0
        return (len(cntl.request_attachment) + req_sz + 65536
                <= nch.window_bytes
                and self.options.backup_request_ms <= 0)

    def inline_fast_call_ok(self, cntl: Controller, request,
                            method_full_name: str) -> bool:
        """True when THIS call would take the native in-process fast
        path AND the listener answers it inline on the caller's thread —
        i.e. issuing it synchronously from a fan-out loop costs nothing
        over a tasklet (the handler runs in the caller's stack either
        way).  Used by ParallelChannel's inline-issue optimization; must
        mirror call_method's routing screens exactly, or a fan-out
        commits to inline issue and then serializes on the Python plane
        (review finding r5)."""
        nch = self._native_ici
        if nch is None or cntl.stream_creator is not None:
            return False
        if not self._fast_call_fits(nch, cntl, request):
            return False
        from ..ici import native_plane
        return native_plane.listener_dispatch_inline(
            nch.remote_dev, method_full_name) is True

    def _native_ici_call(self, nch, method_full_name: str,
                         cntl: Controller, request, response_cls):
        """One fast-path RPC with the Python plane's client tracing
        (rpcz span).  No retry loop: the only retryable error an
        in-process transport can produce is EFAILEDSOCKET (our conn died
        with the server), which _native_ici_fallback re-routes; every
        other failure here is deterministic (ENOMETHOD, ELIMIT, parse,
        timeout) and would fail identically on a retry."""
        if cntl.span is None:
            maybe_start_client_span(cntl, method_full_name)
        return nch.call(method_full_name, cntl, request, response_cls)

    def _native_shed_retry(self, nch, method_full_name: str,
                           cntl: Controller, request, response_cls,
                           result):
        """Honor an admission shed's retry_after_ms on the native fast
        plane (sync calls): the server said how long its backlog needs —
        sleep the hint (plus jitter ABOVE it, never below: synchronized
        re-arrival is the storm the shed exists to prevent) and reissue,
        bounded by the retry budget and the overall deadline.  The wire
        plane gets the same behavior through the Controller retry
        machinery (handle_response)."""
        import time as _time

        from .admission import shed_backoff_s
        max_retry = cntl.max_retry if cntl.max_retry is not None \
            else self.options.max_retry
        attempt = 0
        orig_tms = cntl.timeout_ms
        # the budget started when the FIRST attempt was issued: count its
        # already-recorded duration against the deadline, so the whole
        # loop — attempts AND backoffs — is bounded by ONE timeout_ms
        # (the wire plane's single-deadline-timer semantics)
        t0 = _time.monotonic() - (cntl.latency_us / 1e6)
        try:
            while (cntl.error_code_ == errors.ELIMIT
                   and cntl.retry_after_ms > 0 and attempt < max_retry):
                attempt += 1
                delay_s = shed_backoff_s(cntl.retry_after_ms)
                if orig_tms and orig_tms > 0:
                    remaining = orig_tms / 1000.0 \
                        - (_time.monotonic() - t0)
                    if delay_s >= remaining:
                        # the backoff cannot fit the budget: the overall
                        # deadline wins, like the wire plane's timer
                        cntl.set_failed(
                            errors.ERPCTIMEDOUT,
                            f"reached timeout={orig_tms}ms backing "
                            "off from admission shed")
                        return None
                from ..bthread import scheduler as _sched
                _sched.note_worker_blocked()
                try:
                    _time.sleep(delay_s)
                finally:
                    _sched.note_worker_unblocked()
                cntl.error_code_ = 0
                cntl.error_text_ = ""
                cntl.retry_after_ms = 0
                cntl.retried_count += 1
                if orig_tms and orig_tms > 0:
                    # the reissue gets only what's LEFT of the budget
                    left_ms = int((orig_tms / 1000.0
                                   - (_time.monotonic() - t0)) * 1000)
                    if left_ms <= 0:
                        cntl.set_failed(errors.ERPCTIMEDOUT,
                                        f"reached timeout={orig_tms}ms")
                        return None
                    cntl.timeout_ms = left_ms
                result = self._native_ici_call(nch, method_full_name,
                                               cntl, request,
                                               response_cls)
        finally:
            cntl.timeout_ms = orig_tms
        return result

    def _native_ici_fallback(self, cntl: Controller) -> bool:
        """After a fast-path failure, decide whether to re-route the call
        through the Python plane (once per call).  Two cases:
        * EFAILEDSOCKET — OUR cached conn died (server restarted): drop
          the cache; the Python plane reconnects per call.
        * EOVERCROWDED oversize fast-fail — the frame can never fit the
          native window; the Python plane drains it chunkwise."""
        code = cntl.error_code_
        if code == errors.EFAILEDSOCKET:
            drop_cache = True
        elif code == errors.EOVERCROWDED and \
                cntl.error_text_.startswith("frame larger"):
            drop_cache = False
        else:
            return False
        if getattr(cntl, "_ici_rerouted", False):
            return False               # one re-route per call: no flapping
        cntl._ici_rerouted = True
        if drop_cache:
            with self._native_ici_lock:
                stale, self._native_ici = self._native_ici, None
            if stale is not None:
                stale.close()
        # reset the controller so the fallback attempt starts clean
        cntl.error_code_ = 0
        cntl.error_text_ = ""
        return True

    def _native_ici_binding(self, cntl: Controller):
        """The native in-process ici connection, or None (→ Python plane:
        other-controller targets, streaming calls, auth, non-tpu_std)."""
        ep = self._endpoint
        if (ep is None or getattr(ep, "scheme", None) != "ici"
                or self.options.protocol != "tpu_std"
                or self.options.auth is not None
                or getattr(cntl, "stream_creator", None) is not None):
            return None
        cached = getattr(self, "_native_ici", None)
        if cached is not None:
            return cached
        try:
            from ..ici import native_plane
            if not (native_plane.available()
                    and native_plane.has_listener(ep.device_id)):
                return None
            with self._native_ici_lock:
                if getattr(self, "_native_ici", None) is None:
                    self._native_ici = native_plane.ChannelBinding(
                        ep.device_id,
                        local_dev=self.options.ici_local_device)
                return self._native_ici
        except Exception:
            return None

    # IssueRPC: runs once per try -----------------------------------------
    def _issue_rpc(self, cntl: Controller) -> None:
        sock = self._select_socket(cntl)
        cntl.remote_side = sock.remote_side
        cntl._pack_socket = sock       # connection-stateful protocols (h2)
        cid = cntl.current_cid()
        if _span.layer_on():
            _span.layer_adopt_call(cid)
        packet = self._protocol.pack_request(
            cntl._request_buf, cid, cntl, cntl._method_full_name)
        if cntl.span is not None:
            cntl.span.annotate("issue try=%d to %s" % (cntl.current_try,
                                                       sock.remote_side))
        if self._protocol.pipelined:
            maker = getattr(self._protocol, "make_pipeline_ctx", None)
            ctx = maker(cid, cntl) if maker is not None else cid
            cntl._pipeline_ctx = ctx
            sock.push_pipelined_context(ctx)
        # publish the client span for the write path: relocation / bulk
        # / device-plane events raised while THIS thread encodes the
        # frame annotate the CLIENT span — previously only the
        # bthread-local server span was consulted, so caller-side
        # relocation annotations were silently lost.  SAVE/RESTORE, not
        # clear: a usercode_inline handler dispatched inside this very
        # write can issue its own call, and clearing would strip the
        # OUTER window for the rest of the outer frame's encode.
        from ..bthread import scheduler as _sched
        from .span import set_client_span_local
        # `published` is decided BEFORE the write: an inline-completed
        # call (usercode_inline handler + response inside this very
        # sock.write) runs _end_rpc, which clears cntl.span — re-reading
        # it in the finally would skip the restore and leak the finished
        # span into the thread-local forever
        published = cntl.span is not None
        prev_span = None
        if published:
            prev_span = _sched.local_get("rpcz_client_span")
            set_client_span_local(cntl.span)
        # BEFORE the write: an in-process reply can end the call while this
        # thread is still inside sock.write (a usercode_inline handler; a
        # bulk frame whose last window piece this thread cut itself), and
        # _on_call_end gives back only the pooled connection it finds here
        cntl._last_socket = sock
        try:
            rc = sock.write(packet, notify_cid=cid)
        finally:
            if published:
                set_client_span_local(prev_span)
        if rc != 0:
            raise ConnectionError(f"write failed: {rc}")

    def _select_socket(self, cntl: Controller):
        ctype = self.options.connection_type
        # adaptive connection type (reference adaptive_connection_type.h):
        # when unset, protocols without an on-wire correlation id can't
        # share a single connection across concurrent calls → pooled
        from .protocol import CONNECTION_TYPE_SINGLE
        if ctype == "" and not (self._protocol.supported_connection_type
                                & CONNECTION_TYPE_SINGLE):
            ctype = "pooled"
        smap = SocketMap.instance()
        # reference semantics: < 0 waits indefinitely; 0 takes the
        # default (1s); > 0 is the timeout
        cto_ms = self.options.connect_timeout_ms
        cto = None if cto_ms < 0 else (cto_ms or 1000) / 1000.0
        if self._lb is not None:
            ep = self._lb.select_server(cntl)
            if ep is None:
                raise ConnectionError("no available server")
        else:
            ep = self._endpoint
            # circuit breaker gating for single-endpoint channels: while
            # the endpoint is isolated (tripped by consecutive failures),
            # fail fast instead of stampeding reconnects at a recovering
            # peer — the health checker alone probes it, and its revival
            # (mark_recovered) lifts the isolation (cluster_recover
            # ramp-up discipline applied to one endpoint)
            from .circuit_breaker import BreakerRegistry
            if BreakerRegistry.instance().breaker(ep).is_isolated():
                raise ConnectionError(
                    f"{ep} isolated by circuit breaker")
        cntl._selected_endpoint = ep
        group = self._channel_signature()
        ssl_ctx = self.options.ssl_context
        # the caller's residence holds on BOTH ici planes: the native
        # binding takes it at bind, the Python plane here at connect
        local_dev = self.options.ici_local_device
        if ctype == "pooled":
            sock = smap.get_pooled_socket(ep, self.messenger, group=group,
                                          ssl_context=ssl_ctx,
                                          connect_timeout=cto,
                                          ici_local_device=local_dev)
            cntl._pooled_from = ep
        elif ctype == "short":
            sock = smap.get_short_socket(ep, self.messenger,
                                         ssl_context=ssl_ctx,
                                         connect_timeout=cto,
                                         ici_local_device=local_dev)
            cntl._short_socket = sock
        else:
            sock = smap.get_socket(ep, self.messenger,
                                   ssl_context=ssl_ctx, group=group,
                                   connect_timeout=cto,
                                   ici_local_device=local_dev)
        return sock

    def close(self) -> None:
        """Tear down this channel's connections: every socket the map
        holds for its endpoint is failed with ECLOSE (a deliberate
        local close — no health-check revival) and the native ici
        binding is released.  Idempotent; a later call on the channel
        simply reconnects.  Without this, a dropped client channel
        leaves its connection pair live in the socket pool until
        process exit (the resource-census leak class)."""
        if self._protocol is None:
            return          # init() never completed: nothing to close
        with self._native_ici_lock:
            nb, self._native_ici = getattr(self, "_native_ici", None), None
        if nb is not None:
            try:
                nb.close()
            except Exception:
                pass
        for conn in list(self._stream_conns):
            conn.set_failed(errors.ECLOSE, "channel closed")
        sig = self._channel_signature()
        smap = SocketMap.instance()
        if self._endpoint is not None:
            smap.close_endpoint(self._endpoint, sig)
        lb = self._lb
        if lb is not None:
            # load-balanced channel: detach from the (shared) naming
            # watcher and close every member's connections under this
            # channel's signature — a single-endpoint-only close would
            # silently leak the whole pool (review finding)
            ns = self._ns_thread
            if ns is not None:
                try:
                    ns.remove_watcher(getattr(self, "_ns_watcher", lb))
                except Exception:
                    pass
            dbd = getattr(lb, "_dbd", None)
            if dbd is not None:
                with dbd.read() as lst:
                    eps = [e.endpoint for e in lst]
                for ep in eps:
                    smap.close_endpoint(ep, sig)

    def _channel_signature(self) -> tuple:
        """Connection-compatibility key (reference channel.cpp
        ComputeChannelSignature): channels may share a connection only
        when the peer would parse it identically — protocol, TLS, and
        auth identity all partition the space.  The auth object itself is
        part of the key (the map then pins it, so identity can never be
        recycled while its connections live).  An ici:// connection also
        fixes where its responses land (``ici_local_device``), so callers
        resident on different chips never share one."""
        return (self._protocol.name,
                self.options.ssl_context is not None,
                self.options.auth,
                self.options.ici_local_device)

    def _on_call_end(self, cntl: Controller) -> None:
        # pooled sockets go back to the pool; short ones close
        sock = getattr(cntl, "_last_socket", None)
        ep = getattr(cntl, "_pooled_from", None)
        own_ctx = getattr(cntl, "_pipeline_ctx", None)
        exclusive = ep is not None or \
            getattr(cntl, "_short_socket", None) is not None
        if cntl.failed() and sock is not None and own_ctx is not None \
                and exclusive:
            # THIS call's context is still queued on an exclusive
            # (pooled/short) connection: the response never arrived, and
            # reusing the connection would mis-correlate the next call's
            # response (the reference closes cid-less connections on
            # error).  Shared single connections are left alone — their
            # other calls' contexts are legitimately outstanding and a
            # late response pops the stale context harmlessly.
            with sock._pipeline_lock:
                dangling = own_ctx in sock.pipelined_contexts
            if dangling:
                sock.set_failed(errors.ECLOSE,
                                "own pipelined context still outstanding")
        # a stream this call established rides its connection on: an
        # exclusive connection stays the stream's until the stream closes,
        # and is given back (or closed) then, not handed to the next call
        stream = cntl.stream_creator

        def release(conn, how) -> None:
            def when_closed():
                self._stream_conns.discard(conn)
                how()

            if stream is not None and stream.hold_connection(conn,
                                                             when_closed):
                self._stream_conns.add(conn)
                if stream.closed:       # it closed in between: not held
                    self._stream_conns.discard(conn)
            else:
                how()

        if ep is not None and sock is not None:
            release(sock, functools.partial(
                SocketMap.instance().return_pooled_socket, ep, sock,
                group=self._channel_signature()))
        short = getattr(cntl, "_short_socket", None)
        if short is not None:
            release(short, functools.partial(
                short.set_failed, errors.ECLOSE, "short connection done"))
        sel = getattr(cntl, "_selected_endpoint", None)
        # an admission shed (retryable ELIMIT + retry_after_ms) is an
        # OVERLOADED-BUT-HEALTHY endpoint saying "not now" — it must not
        # count as an endpoint failure for the circuit breaker, or a 10x
        # overload isolates the very server still serving critical-band
        # traffic (the client-side twin of the limiter-floor poisoning
        # fixed in MethodStatus).  LB feedback still sees the error:
        # steering weight away from an overloaded member is correct.
        breaker_code = 0 if (cntl.error_code_ == errors.ELIMIT
                             and cntl.retry_after_ms > 0) \
            else cntl.error_code_
        if self._lb is not None:
            if sel is not None:
                self._lb.feedback(sel, cntl.error_code_, cntl.latency_us)
                # circuit breaker + health-check revival (SURVEY.md §5.3)
                from .circuit_breaker import BreakerRegistry
                breaker = BreakerRegistry.instance().breaker(sel)
                if not breaker.on_call_end(breaker_code):
                    from .health_check import start_health_check
                    lb = self._lb
                    lb.exclude(sel, breaker.isolated_until())
                    # revive_key=the LB: repeated trips re-register the
                    # same (replaced) callback instead of accumulating
                    # one per trip, while distinct LBs watching the same
                    # endpoint each keep theirs
                    start_health_check(
                        sel, on_revived=lambda ep: lb.exclude(ep, 0.0),
                        revive_key=id(lb))
        elif sel is not None:
            # single-endpoint channels feed the same breaker: repeated
            # failures trip isolation (gating reconnect stampedes in
            # _select_socket) and hand the endpoint to the health
            # checker, whose successful probe resets the breaker
            from .circuit_breaker import BreakerRegistry
            if not BreakerRegistry.instance().breaker(sel).on_call_end(
                    breaker_code):
                from .health_check import start_health_check
                start_health_check(sel)


class _FilteredWatcher:
    """Per-channel membership filter (reference naming_service_filter.h)."""

    def __init__(self, lb, filter_fn):
        self._lb = lb
        self._filter = filter_fn

    def reset_servers(self, entries):
        self._lb.reset_servers([e for e in entries if self._filter(e)])
