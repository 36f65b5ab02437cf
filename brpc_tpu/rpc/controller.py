"""Controller: per-RPC state machine for both client and server sides.

Reference: src/brpc/controller.{h,cpp} + the client call flow of SURVEY.md
§3.3.  Client-side lifecycle:

  Channel.call_method
    → correlation id created ranged over max_retry+1 try-versions
      (channel.cpp:442): try k sends version k; a *retry* advances the
      current version so older tries' responses fail to lock (ignored); a
      *backup request* leaves older versions valid so the first response
      wins (backup_request.md semantics).
    → timeout / backup timers through TimerThread (channel.cpp:537-574)
    → issue_rpc: pick socket, pack, Socket.write (controller.cpp:985-1144)
    → completion funnels through the correlation id's on_error/lock — the
      single synchronization point (OnVersionedRPCReturned controller.cpp:568)

Server side carries request metadata (deadline, attachment, peer) and the
response sender closure.
"""
from __future__ import annotations

import random
import threading
import time
from typing import Any, Callable, Optional

from ..butil.iobuf import IOBuf
from ..butil.endpoint import EndPoint
from ..butil import custody_ledger as _ledger
from ..bthread import id as bthread_id
from ..bthread.timer_thread import TimerThread
from . import errors


class _LazyField:
    """Non-data descriptor: materializes a per-instance default on first
    READ (the instance dict shadows it afterwards, so steady-state access
    is a plain attribute load).  This is what makes Controller
    construction and pool reset nearly free: a request that never touches
    its attachments never pays for their IOBufs."""
    __slots__ = ("name", "factory")

    def __init__(self, name: str, factory):
        self.name = name
        self.factory = factory

    def __get__(self, obj, owner=None):
        if obj is None:
            return self
        val = obj.__dict__[self.name] = self.factory()
        return val


class _FanoutResult:
    """``fanout_result``, non-data as ``_LazyField``: what a merger stored,
    or — where it left only the recipe (a device fan-out's index-ordered
    refs ARE the result until the caller asks for ONE array,
    channels/collective_fanout.py) — that array, made on the first read."""
    __slots__ = ()

    def __get__(self, obj, owner=None):
        if obj is None:
            return None
        make = obj.__dict__.pop("_fanout_result_lazy", None)
        if make is None:
            return None
        val = obj.__dict__["fanout_result"] = make()
        return val


class Controller:
    # Every scalar default lives on the CLASS: __init__ sets nothing, so
    # construction is an empty-dict object and a pooled reset is one
    # ``__dict__.clear()`` — the "thin shim that inflates on first
    # access" design (reference Controller + ResetPods).  Writes shadow
    # the class default in the instance dict as usual; only the mutable
    # containers (attachments, excluded-server set) need the lazy
    # descriptor above.
    # common
    error_code_: int = 0
    error_text_: str = ""
    log_id: int = 0
    # admission-control propagation (rpc/admission.py): priority band
    # (0=critical .. 3=sheddable; None = the server's default band) and
    # fair-queueing tenant, carried in RequestMeta on every plane.  On
    # the server side these are the DECODED request values (handlers may
    # read them); retry_after_ms is the shed backoff hint — written by
    # the server before a shed response, filled from ResponseMeta on the
    # client so callers (and the retry machinery) can honor it.
    priority: Optional[int] = None
    tenant: str = ""
    retry_after_ms: int = 0
    deadline_left_ms: int = 0       # server side: budget at arrival
    # compiled fan-out call state (channels/collective_fanout.py): the
    # typed array operand the caller scatters across a Parallel/
    # Partition fan-out, the merged result, and which route actually
    # carried the call ("collective" = one compiled SPMD program,
    # "rpc" = the per-member loop, "" = not an operand fan-out) — the
    # route assertion surface for tools and tests.  A DEVICE operand's
    # gather on the per-member loop is ``fanout_attachment``: the
    # sub-replies' refs in sub-channel order, one IOBuf, nothing copied
    fanout_operand: Any = None
    fanout_result = _FanoutResult()
    fanout_attachment: Optional[IOBuf] = None
    fanout_route: str = ""
    request_attachment = _LazyField("request_attachment", IOBuf)
    # the response factory is swapped to ici/native_plane.py's
    # ResponseAttachment once that module loads (ISSUE 13): identical
    # to a plain IOBuf except that appending a whole, untouched
    # NativeAttachment view into it while empty ADOPTS the parked
    # native handle (the PR-8 echo idiom stops materializing)
    response_attachment = _LazyField("response_attachment", IOBuf)
    remote_side: Optional[EndPoint] = None
    local_side: Optional[EndPoint] = None
    auth_token: str = ""
    compress_type: int = 0
    # tracing
    trace_id: int = 0
    span_id: int = 0
    parent_span_id: int = 0
    span = None
    # client call state
    timeout_ms: Optional[int] = None
    max_retry: Optional[int] = None
    backup_request_ms: Optional[int] = None
    retry_on_timeout: Optional[bool] = None
    retry_backoff_ms: Optional[int] = None
    retried_count: int = 0
    current_try: int = 0
    latency_us: int = 0
    response: Any = None
    _response_cls: Any = None
    _done: Optional[Callable[["Controller"], None]] = None
    _cid: int = 0
    _timeout_timer = None
    _backup_timer = None
    _ending = False                 # _end_rpc has begun (timers are off)
    _channel = None                 # issuing channel (for re-issues)
    _method_full_name: str = ""
    _request_buf: Optional[IOBuf] = None
    _start_us: int = 0
    # lazy: ~3 µs of threading.Event construction per call that the
    # native ici fast path (sync, never joins) would pay for nothing
    _ended_ev: Optional[threading.Event] = None
    _excluded_servers = _LazyField("_excluded_servers", set)
    request_protocol: str = ""
    stream_creator = None           # set by stream.create on host RPC
    accepted_stream_id = 0
    # server side
    server = None
    _session_data: Any = None
    method_deadline: Optional[float] = None
    _server_done: Optional[Callable[[], None]] = None
    http_request = None
    http_response = None
    _recycle_pool = None            # ControllerPool that owns this shim

    # ---- attachment peeks (hot paths) ---------------------------------
    # Reading request_attachment/response_attachment MATERIALIZES the
    # IOBuf; presence checks on hot paths use these instead so an
    # attachment-less echo never allocates either buffer.
    def _peek_request_attachment(self) -> Optional[IOBuf]:
        return self.__dict__.get("request_attachment")

    def _peek_response_attachment(self) -> Optional[IOBuf]:
        return self.__dict__.get("response_attachment")

    # ---- per-RPC session data (reference Controller::session_local_data,
    # backed by ServerOptions.session_local_data_factory's pool) ---------
    def session_local_data(self) -> Any:
        if self._session_data is None and self.server is not None:
            self._session_data = self.server._get_session_data()
        return self._session_data

    def _release_session_data(self) -> None:
        # idempotent: called from MethodDescriptor.invoke's wrapped done
        if self._session_data is not None and self.server is not None:
            self.server._return_session_data(self._session_data)
            self._session_data = None

    _ended_create_lock = threading.Lock()

    @property
    def _ended(self) -> threading.Event:
        """Completion event, created on first touch (double-checked under
        a class lock: a completer's set() and a joiner's wait() may both
        be the first toucher, and each building its own Event would park
        the joiner forever).  The native ici fast path completes calls
        without ever touching this."""
        ev = self._ended_ev
        if ev is None:
            with Controller._ended_create_lock:
                ev = self._ended_ev
                if ev is None:
                    ev = self._ended_ev = threading.Event()
        return ev

    # ---- error surface (reference Controller::SetFailed/Failed) -------
    def set_failed(self, code: int, text: str = "") -> None:
        self.error_code_ = code
        self.error_text_ = text or errors.berror(code)

    def failed(self) -> bool:
        return self.error_code_ != 0

    @property
    def error_code(self) -> int:
        return self.error_code_

    @property
    def error_text(self) -> str:
        return self.error_text_

    def reset(self) -> None:
        # every field is a class default (see above): clearing the
        # instance dict restores pristine state in one C-level op
        self.__dict__.clear()

    def _maybe_recycle(self) -> None:
        """Return a pool-acquired server-side Controller to its pool once
        the response is fully sent (the protocol-agnostic recycle point —
        called by MethodDescriptor.invoke's wrapped done and by the
        pre-invoke error paths).  No-op for plain Controllers."""
        pool = self.__dict__.get("_recycle_pool")
        if pool is not None:
            pool.release(self)

    # ---- client call orchestration ------------------------------------
    def _start_call(self, channel, method_full_name: str, request_buf: IOBuf,
                    response_cls, done) -> None:
        self._channel = channel
        self._method_full_name = method_full_name
        self._request_buf = request_buf
        self._response_cls = response_cls
        self._done = done
        self._start_us = time.monotonic_ns() // 1000
        opts = channel.options
        if self.timeout_ms is None:
            self.timeout_ms = opts.timeout_ms
        if self.max_retry is None:
            self.max_retry = opts.max_retry
        if self.backup_request_ms is None:
            self.backup_request_ms = opts.backup_request_ms
        if self.retry_on_timeout is None:
            self.retry_on_timeout = opts.retry_on_timeout
        if self.retry_backoff_ms is None:
            self.retry_backoff_ms = getattr(opts, "retry_backoff_ms", 0)
        # +1: versions are try indices 0..max_retry
        self._cid = bthread_id.create_ranged(
            self, self._on_rpc_event, self.max_retry + 1)
        needs_backup = (self.backup_request_ms and self.backup_request_ms > 0
                        and self.backup_request_ms < (self.timeout_ms or 1 << 30))
        if needs_backup:
            # hedging must be armed before the first try leaves
            self._backup_timer = TimerThread.instance().schedule_after(
                self._handle_backup_request, self.backup_request_ms / 1000.0)
        self._issue_rpc()
        # deadline timer is only needed if the call is still in flight —
        # inline loopback/device completions skip the timer heap entirely
        if (self.timeout_ms and self.timeout_ms > 0
                and not self._ended.is_set()):
            self._schedule_try_timer()

    def _timeout_hedging(self) -> bool:
        """Per-try deadline hedging is active only when opted in via
        ChannelOptions.retry_on_timeout, and backup_request_ms is unset
        (that is already an explicit hedging schedule — running both would
        double-hedge and burn the retry budget)."""
        return bool(self.retry_on_timeout) and not self.backup_request_ms

    def _schedule_try_timer(self) -> None:
        """Arm the deadline timer for the current try.

        Default (reference semantics, controller.cpp HandleTimeout):
        timeout_ms is a single overall deadline and ERPCTIMEDOUT is final.
        With retry_on_timeout opted in, the deadline is instead split
        evenly over the tries that remain: a try that produces neither a
        response nor a connection error gets remaining/tries_left ms before
        the correlation id is poked with ERPCTIMEDOUT, where the funnel
        hedges a fresh try instead of failing (see _on_rpc_event).  The
        total deadline is always honored.
        """
        if self._timeout_timer is not None:
            TimerThread.instance().unschedule(self._timeout_timer)
            self._timeout_timer = None
        if not self.timeout_ms or self.timeout_ms <= 0 or self._ended.is_set():
            return
        elapsed_ms = (time.monotonic_ns() // 1000 - self._start_us) / 1000.0
        remaining = max(0.0, self.timeout_ms - elapsed_ms)
        if self._timeout_hedging():
            tries_left = max(1, (self.max_retry or 0) - self.current_try + 1)
            remaining = remaining / tries_left
        # Bind the try version NOW: unschedule() can't stop a timer that
        # already popped from the heap, and a stale tasklet reading
        # current_try at run time would poke the *live* try with
        # ERPCTIMEDOUT long before its deadline.  A version-bound stale
        # timer instead fails to lock (after reset_version) or is dropped
        # by the straggler guard.
        ver = self.current_try
        self._timeout_timer = TimerThread.instance().schedule_after(
            lambda: self._handle_timeout(ver), remaining / 1000.0)
        if self._ending:
            # the reply ended the call on another thread between
            # _start_call's look at _ended and here: _end_rpc has passed
            # its own unschedule, and a timer left armed would hold the
            # Controller and its attachments until the deadline
            TimerThread.instance().unschedule(self._timeout_timer)

    def current_cid(self) -> int:
        return bthread_id.with_version(self._cid, self.current_try)

    def _issue_rpc(self) -> None:
        try:
            self._channel._issue_rpc(self)
        except Exception as e:
            bthread_id.error(self.current_cid(),
                             errors.EFAILEDSOCKET)

    # timer callbacks ---------------------------------------------------
    def _handle_timeout(self, ver: int) -> None:
        # ver is bound at arm time by _schedule_try_timer — never read
        # current_try here (a stale pop would shoot the live try).
        bthread_id.error(bthread_id.with_version(self._cid, ver),
                         errors.ERPCTIMEDOUT)

    def _handle_backup_request(self) -> None:
        bthread_id.error(bthread_id.with_version(self._cid, self.current_try),
                         errors.EBACKUPREQUEST)

    # the correlation-id funnel (always entered with the id locked) ------
    def _on_rpc_event(self, data, cid: int, error_code: int) -> None:
        """on_error callback: timeout, backup trigger, send failure, or
        remote response error all land here — the retry decision point."""
        ver = bthread_id.get_version(cid)
        if ver < self.current_try and error_code not in (
                errors.EBACKUPREQUEST, errors.ECANCELED):
            # A straggler: an older hedge try died *after* a newer try was
            # issued (hedging keeps old versions lockable so their slow
            # responses can still win — but their failures must not decide
            # the call while the live try is in flight, nor blacklist the
            # live try's server).
            bthread_id.unlock(cid)
            return
        if error_code == errors.EBACKUPREQUEST:
            # hedge: issue one more try; older versions stay valid so the
            # first response to arrive wins.
            if self.current_try < self.max_retry:
                self.current_try += 1
                self.retried_count += 1
                # the deadline timer is version-bound; re-arm it at the
                # new current version or the straggler guard would swallow
                # the overall deadline after this hedge
                self._schedule_try_timer()
                self._issue_rpc()
            bthread_id.unlock(cid)
            return
        if error_code == errors.ERPCTIMEDOUT:
            elapsed_ms = (time.monotonic_ns() // 1000
                          - self._start_us) / 1000.0
            remaining = (self.timeout_ms or 0) - elapsed_ms
            if (self._timeout_hedging() and remaining > 1.0
                    and self.current_try < self.max_retry):
                # This try's share of the deadline elapsed with no reply:
                # hedge a fresh try.  Old versions stay valid (no
                # reset_version) so a merely-slow response still wins; the
                # silent server is excluded so an LB steers elsewhere.
                sel = getattr(self, "_selected_endpoint", None)
                if sel is not None:
                    self._excluded_servers.add(sel)
                self.current_try += 1
                self.retried_count += 1
                self._schedule_try_timer()
                self._issue_rpc()
                bthread_id.unlock(cid)
                return
            self.set_failed(errors.ERPCTIMEDOUT,
                            f"reached timeout={self.timeout_ms}ms")
            self._end_rpc(cid)
            return
        # send/socket failure or server-pushed error: retry if allowed
        if self._retryable(error_code) and self.current_try < self.max_retry:
            sel = getattr(self, "_selected_endpoint", None)
            if sel is not None:
                self._excluded_servers.add(sel)   # per-call blacklist
            self.current_try += 1
            self.retried_count += 1
            bthread_id.reset_version(self._cid, self.current_try)  # stale old tries
            self._schedule_try_timer()
            # a lame-duck rejection (ELOGOFF) is the peer explicitly
            # saying "go elsewhere" — an instant failover, not an outage:
            # it must not consume the connection-failure backoff budget
            delay_s = 0.0 if error_code == errors.ELOGOFF \
                else self._retry_backoff_s()
            if delay_s > 0:
                # spaced retry: the endpoint may be DOWN rather than
                # flaky — immediate re-connects would burn the whole
                # retry budget in microseconds, while spaced ones ride
                # out an outage until health-check revival brings the
                # peer back.  The deadline timer armed above still
                # bounds the call; a delay past it just loses to
                # ERPCTIMEDOUT, which is correct.
                from ..bthread import scheduler as _sched
                TimerThread.instance().schedule_after(
                    lambda: _sched.start_background(
                        self._issue_rpc, name="retry_backoff"),
                    delay_s)
            else:
                self._issue_rpc()
            bthread_id.unlock(cid)
            return
        self.set_failed(error_code)
        self._end_rpc(cid)

    def _retry_backoff_s(self) -> float:
        """Exponential backoff with deterministic per-call jitter for
        connection-failure retries; 0 when the channel didn't opt in."""
        base_ms = self.retry_backoff_ms or 0
        if base_ms <= 0:
            return 0.0
        delay_ms = min(base_ms * (2 ** (self.retried_count - 1)),
                       1000.0)
        rng = random.Random((self._cid << 8) ^ self.retried_count)
        return delay_ms * (1.0 + 0.25 * rng.random()) / 1000.0

    @staticmethod
    def _retryable(error_code: int) -> bool:
        return error_code in (errors.EFAILEDSOCKET, errors.EEOF,
                              errors.ELOGOFF, errors.ECONNREFUSED,
                              errors.ECONNRESET, errors.EAGAIN)

    def handle_response(self, cid: int, meta, payload: IOBuf) -> None:
        """Called by the protocol with the correlation id locked and
        validated (stale tries never get here)."""
        rmeta = meta.response
        if rmeta.error_code != 0:
            if bthread_id.get_version(cid) < self.current_try:
                # Under hedging old versions stay lockable so a slow
                # *success* can still win — but an abandoned try's error
                # response must not decide the call or stale the live
                # hedge (same rule as the straggler guard in
                # _on_rpc_event).
                bthread_id.unlock(cid)
                return
            err = rmeta.error_code
            self.set_failed(err, rmeta.error_text)
            hint_ms = getattr(rmeta, "retry_after_ms", 0)
            if hint_ms:
                self.retry_after_ms = hint_ms
            # an admission shed (ELIMIT + retry_after_ms) is retryable —
            # but only after the server's hint: the server said exactly
            # how long its backlog needs, and an immediate re-dispatch
            # (or a hedge) would be the retry storm the shed exists to
            # prevent
            shed_retry = err == errors.ELIMIT and hint_ms > 0
            if (self._retryable(err) or shed_retry) \
                    and self.current_try < self.max_retry:
                # the retry must land on a DIFFERENT replica: a server
                # that pushed a retryable error (lame-duck ELOGOFF most
                # of all) will push it again — the reference's per-call
                # blacklist applies to server-pushed errors too
                sel = getattr(self, "_selected_endpoint", None)
                if sel is not None:
                    self._excluded_servers.add(sel)
                self.error_code_ = 0
                self.error_text_ = ""
                self.current_try += 1
                self.retried_count += 1
                bthread_id.reset_version(self._cid, self.current_try)
                self._schedule_try_timer()
                if shed_retry:
                    # honor the hint via the shared shed-backoff policy
                    # (admission.shed_backoff_s: hint + above-only
                    # jitter).  A delay past the overall deadline just
                    # loses to ERPCTIMEDOUT, which is the correct bound.
                    from .admission import shed_backoff_s
                    delay_s = shed_backoff_s(
                        hint_ms, seed=(self._cid << 8)
                        ^ self.retried_count)
                    from ..bthread import scheduler as _sched
                    TimerThread.instance().schedule_after(
                        lambda: _sched.start_background(
                            self._issue_rpc, name="shed_retry"),
                        delay_s)
                else:
                    self._issue_rpc()
                bthread_id.unlock(cid)
                return
            self._end_rpc(cid)
            return
        try:
            att_size = meta.attachment_size
            body = payload
            if att_size:
                att = IOBuf()
                keep = len(body) - att_size
                tmp = body.cut(keep)
                body.cutn(att, att_size)
                body = tmp
                self.response_attachment = att
            data = body.to_bytes()
            if meta.compress_type:
                from .compress import decompress
                data = decompress(meta.compress_type, data)
            if self._response_cls is not None:
                resp = self._response_cls()
                resp.ParseFromString(data)
                self.response = resp
            else:
                self.response = data
        except Exception as e:
            self.set_failed(errors.ERESPONSE, f"fail to parse response: {e}")
        self._end_rpc(cid)

    def finish_parsed_response(self, cid: int) -> None:
        """Completion for protocols that parse the response themselves
        (http/redis/memcache): cntl.response is already set."""
        self._end_rpc(cid)

    def handle_parsed_http_response(self, cid: int, http_msg) -> None:
        """HTTP client completion: response object was already parsed by the
        protocol (json2pb); just record and finish."""
        self.http_response = http_msg
        self._end_rpc(cid)

    def _end_rpc(self, cid: int) -> None:
        self._ending = True     # before the look: _schedule_try_timer's rule
        if self._timeout_timer is not None:
            TimerThread.instance().unschedule(self._timeout_timer)
        if self._backup_timer is not None:
            TimerThread.instance().unschedule(self._backup_timer)
        self.latency_us = time.monotonic_ns() // 1000 - self._start_us
        chan = self._channel
        if chan is not None:
            try:
                chan._on_call_end(self)
            except Exception:
                pass
        if self.span is not None:
            from .span import end_client_span
            end_client_span(self)
        done = self._done
        bthread_id.unlock_and_destroy(cid)   # wakes sync joiner
        self._ended.set()
        if done is not None:
            from ..bthread import scheduler
            scheduler.start_background(done, self, name="rpc_done")

    def join(self, timeout: Optional[float] = None) -> None:
        """Wait for RPC completion (sync calls).  When the caller is a
        scheduler tasklet, compensate the blocked worker so server-side
        processing can't be starved by sync callers (the reference blocks
        on a butex, which yields the bthread worker for free)."""
        from ..bthread import scheduler
        state = self.__dict__.get("_loopback_state")
        if state is not None:
            ev = state.wait_begin()
            if ev is None:
                return                   # already completed
            scheduler.note_worker_blocked()
            try:
                if not ev.wait(timeout):
                    raise TimeoutError("RPC join timed out")
            finally:
                scheduler.note_worker_unblocked()
            return
        scheduler.note_worker_blocked()
        try:
            if not self._ended.wait(timeout):
                raise TimeoutError("RPC join timed out")
        finally:
            scheduler.note_worker_unblocked()

    def cancel(self) -> None:
        """Cancel the in-flight call (reference StartCancel/CancelRPC): the
        caller completes with ECANCELED; a late response is dropped by the
        correlation id (wire path) or the loopback claim."""
        if self.__dict__.get("_loopback_state") is not None:
            from . import loopback
            loopback.cancel(self)
            return
        if self._cid and not self._ended.is_set():
            bthread_id.error(
                bthread_id.with_version(self._cid, self.current_try),
                errors.ECANCELED)

    # ---- server side ---------------------------------------------------
    def set_server_done(self, fn: Callable[[], None]) -> None:
        self._server_done = fn

    def send_response(self) -> None:
        if self._server_done is not None:
            fn, self._server_done = self._server_done, None
            fn()


class ControllerPool:
    """Server-side Controller pool (reference: brpc keeps the whole
    server path allocation-free; src/butil/resource_pool.h).

    In-use shims are tracked through a versioned-id
    :class:`~brpc_tpu.butil.resource_pool.ResourcePool` — ``live()`` and
    ``live_controllers()`` are the census/debug enumeration, and a
    double release is rejected by the id version instead of corrupting
    the free list.  Reset is ``Controller.reset()`` (one dict clear), so
    a recycled shim can never leak request k's error code, attachment,
    or span into request k+1 — the classic pool bug, pinned by
    tests/test_controller_pool.py."""

    _GUARDED_BY = {"_free": "_lock"}

    # fablint custody contract (ISSUE 20): a pooled shim handed out by
    # acquire() comes back through release() exactly once; the id
    # version makes a double release a no-op, the ledger makes a NO
    # release attributable to its acquiring call site.
    _CUSTODY = {"acquire": ("release",)}

    def __init__(self, capacity: int = 1024):
        from ..butil import debug_sync as _dbg
        from ..butil.resource_pool import ResourcePool
        self.capacity = capacity
        self._ids: "ResourcePool[Controller]" = ResourcePool()
        self._free: list = []
        self._lock = _dbg.make_lock("ControllerPool._lock")

    def acquire(self) -> Controller:
        with self._lock:
            c = self._free.pop() if self._free else None
        if c is None:
            c = Controller()
        d = c.__dict__
        d["_pool_rid"] = self._ids.get_resource(c)
        d["_recycle_pool"] = self
        _ledger.acquire("cntl", (id(self), d["_pool_rid"]))
        return c

    def release(self, c: Controller) -> None:
        rid = c.__dict__.get("_pool_rid", 0)
        if not rid or not self._ids.return_resource(rid):
            return                   # not ours / already released: drop
        _ledger.release("cntl", (id(self), rid))
        # native att custody (ISSUE 12): pool-recycle is the blessed
        # drop point for an attachment view whose handle never exited
        # (handler ignored it / response failed before the pass-back) —
        # duck-typed so this module never imports the ici plane.  Both
        # hooks are idempotent; plain IOBufs don't carry them.
        d = c.__dict__
        att = d.get("request_attachment")
        if att is not None:
            fn = getattr(att, "_dispose_native", None)
            if fn is not None:
                fn()
        att = d.get("response_attachment")
        if att is not None:
            fn = getattr(att, "_dispose_native", None)
            if fn is not None:
                fn()
        c.reset()
        with self._lock:
            if len(self._free) < self.capacity:
                self._free.append(c)

    def live(self) -> int:
        """Controllers currently handed out (in-flight requests)."""
        return self._ids.size()

    def live_controllers(self) -> list:
        return self._ids.live_payloads()

    def free_count(self) -> int:
        with self._lock:
            return len(self._free)


# The process-wide server-side pool: every server protocol that
# constructs per-request Controllers (tpu_std, the native ici upcall
# tier, the loopback plane) draws from it.
server_controller_pool = ControllerPool()
