"""Admission control (ISSUE 9): priority/deadline-aware shed-before-queue
with per-tenant weighted fair queueing — rpc/admission.py plus its
integration on all three call planes (tpu_std wire, mem:// loopback,
native-ici), the client-side retry_after_ms honoring, and the
shed-exclusion bugfix in MethodStatus.

The deterministic mini-overload test (TestMiniOverload, `overload`
marker) drives the whole shed logic with a SIMULATED clock and an
injectable service rate, so tier-1 exercises it without a full
10x-capacity adversary (which has no cell yet: ROADMAP Queue 2, C6).
"""
from __future__ import annotations

import threading
import time

import pytest

import brpc_tpu.policy  # noqa: F401 — registers protocols
from brpc_tpu import rpc
from brpc_tpu.ici import IciMesh
from brpc_tpu.rpc import errors
from brpc_tpu.rpc.admission import (AdmissionController, AdmissionOptions,
                                    SHED_DEADLINE_TEXT,
                                    SHED_QUEUE_TIMEOUT_TEXT,
                                    server_method_gate)
from brpc_tpu.rpc.method_status import MethodStatus

from echo_pb2 import EchoRequest, EchoResponse


# ---------------------------------------------------------------------
# controller-level units (simulated clock, fake gate)
# ---------------------------------------------------------------------

class _Gate:
    """A fake concurrency gate with explicit capacity."""

    def __init__(self, slots: int):
        self.slots = slots
        self.lock = threading.Lock()

    def try_enter(self) -> bool:
        with self.lock:
            if self.slots > 0:
                self.slots -= 1
                return True
            return False

    def release(self) -> None:
        with self.lock:
            self.slots += 1


def _mk_controller(gate, clock, *, dispatch_log=None, **opt_kw):
    opts = AdmissionOptions(use_timers=False, **opt_kw)
    runs = dispatch_log if dispatch_log is not None else []
    return AdmissionController(
        None, opts, now_us=lambda: clock[0],
        dispatch=lambda run, waited_us: (runs.append(waited_us),
                                         run(waited_us)))


def _submit(adm, gate, order, tag, pri, tenant, clock, deadline_ms=5000):
    adm.submit(priority=pri, tenant=tenant, deadline_left_ms=deadline_ms,
               recv_us=clock[0], try_enter=gate.try_enter,
               run=lambda w, t=tag: order.append(t),
               shed=lambda c, txt, ra, t=tag: order.append(
                   ("SHED", t, c, ra, txt)))


class TestAdmissionQueueUnits:
    def test_strict_priority_and_drr_fairness(self):
        clock = [1_000_000]
        gate = _Gate(0)
        adm = _mk_controller(gate, clock, service_rate_override=100.0,
                             queue_capacity=64,
                             tenant_weights={"a": 3, "b": 1},
                             queueable_priority_max=1)
        order = []
        for i in range(6):
            for t in ("a", "b"):
                _submit(adm, gate, order, f"{t}{i}", 0, t, clock)
        for i in range(2):
            _submit(adm, gate, order, f"p1-{i}", 1, "a", clock)
        assert adm.queued() == 14
        gate.slots = 100
        n = adm.pump()
        assert n == 14
        # strict priority: every band-0 entry before any band-1 entry
        assert order.index("p1-0") > max(order.index(f"a{i}")
                                         for i in range(6))
        # DRR 3:1 — among the first 4 served, tenant a gets 3
        a_first4 = sum(1 for x in order[:4]
                       if isinstance(x, str) and x.startswith("a"))
        assert a_first4 == 3, order[:4]

    def test_shed_before_queue_for_sheddable_band(self):
        clock = [1_000_000]
        gate = _Gate(0)
        adm = _mk_controller(gate, clock, service_rate_override=100.0)
        order = []
        _submit(adm, gate, order, "low", 3, "t", clock)
        assert order and order[0][0] == "SHED"
        _, _, code, retry_after, _ = order[0]
        assert code == errors.ELIMIT and retry_after > 0
        assert adm.queued() == 0          # never queued: shed BEFORE queue

    def test_fair_share_shed(self):
        clock = [1_000_000]
        gate = _Gate(0)
        adm = _mk_controller(gate, clock, service_rate_override=100.0,
                             queue_capacity=8,
                             tenant_weights={"a": 3, "b": 1})
        order = []
        # alone, a tenant may use the whole queue; once a competes,
        # b's share is capacity * 1/(3+1) = 2
        _submit(adm, gate, order, "a0", 0, "a", clock)
        for i in range(3):
            _submit(adm, gate, order, f"b{i}", 0, "b", clock)
        sheds = [x for x in order if isinstance(x, tuple)]
        assert len(sheds) == 1 and sheds[0][1] == "b2"
        assert "fair share" in sheds[0][4]
        assert adm.queued() == 3

    def test_deadline_expired_shed_before_any_work(self):
        clock = [10_000_000]
        gate = _Gate(10)                  # capacity available — deadline
        adm = _mk_controller(gate, clock)  # check still rejects first
        order = []
        adm.submit(priority=0, tenant="t", deadline_left_ms=100,
                   recv_us=clock[0] - 200_000,   # 200ms ago
                   try_enter=gate.try_enter,
                   run=lambda w: order.append("RAN"),
                   shed=lambda c, txt, ra: order.append((c, txt, ra)))
        assert order == [(errors.ERPCTIMEDOUT, SHED_DEADLINE_TEXT, 0)]
        assert gate.slots == 10           # no gate entered, no work done

    def test_queue_timeout_shed_with_retry_after(self):
        clock = [1_000_000]
        gate = _Gate(0)
        adm = _mk_controller(gate, clock, service_rate_override=50.0,
                             max_queue_ms=30.0)
        order = []
        _submit(adm, gate, order, "q", 0, "t", clock)
        assert adm.queued() == 1
        clock[0] += 31_000                # past the 30ms bound
        assert adm.expire_queued() == 1
        assert order and order[0][0] == "SHED"
        _, _, code, ra, txt = order[0]
        assert code == errors.ELIMIT and ra > 0
        assert txt == SHED_QUEUE_TIMEOUT_TEXT

    def test_retry_after_tracks_backlog_and_rate(self):
        clock = [1_000_000]
        gate = _Gate(0)
        adm = _mk_controller(gate, clock, service_rate_override=100.0,
                             queue_capacity=64)
        # empty queue: backlog 1 @ 100 rps -> 10ms
        assert adm.retry_after_ms() == 10
        order = []
        for i in range(9):
            _submit(adm, gate, order, f"q{i}", 0, "t", clock)
        # backlog 10 @ 100 rps -> 100ms
        assert adm.retry_after_ms() == 100
        adm.fail_all(errors.ELOGOFF, "cleanup")

    def test_service_rate_ema_from_release_events(self):
        clock = [1_000_000]
        gate = _Gate(0)
        adm = _mk_controller(gate, clock)
        # releases every 10ms -> ~100 rps observed
        for _ in range(20):
            clock[0] += 10_000
            adm.on_release()
        assert 80.0 <= adm.service_rate() <= 120.0

    def test_fail_all_bounces_queued_and_refuses_later(self):
        clock = [1_000_000]
        gate = _Gate(0)
        adm = _mk_controller(gate, clock, service_rate_override=100.0)
        order = []
        _submit(adm, gate, order, "q0", 0, "t", clock)
        n = adm.fail_all(errors.ELOGOFF, "server stopping")
        assert n == 1
        assert order[0][0] == "SHED" and order[0][2] == errors.ELOGOFF
        # later enqueues bounce with the stop reason
        _submit(adm, gate, order, "q1", 0, "t", clock)
        assert order[1][0] == "SHED" and order[1][2] == errors.ELOGOFF
        # reset lifts the refusal
        adm.reset()
        gate.slots = 1
        _submit(adm, gate, order, "q2", 0, "t", clock)
        assert order[2] == "q2"

    def test_queue_bound_capped_by_residual_deadline(self):
        """Review fix: the queue stay is bounded by what's LEFT of the
        propagated deadline (deadline_left_ms minus time already burned
        since receive), not the raw deadline_left_ms — a request that
        spent 45 of its 50ms in the dispatch backlog may queue at most
        ~5ms more."""
        clock = [10_000_000]
        gate = _Gate(0)
        adm = _mk_controller(gate, clock, service_rate_override=100.0,
                             max_queue_ms=50.0)
        order = []
        adm.submit(priority=0, tenant="t", deadline_left_ms=50,
                   recv_us=clock[0] - 45_000,     # 45ms already burned
                   try_enter=gate.try_enter,
                   run=lambda w: order.append("RAN"),
                   shed=lambda c, txt, ra: order.append((c, txt)))
        assert adm.queued() == 1
        clock[0] += 6_000                          # 6ms later: residual
        assert adm.expire_queued() == 1            # (5ms) elapsed
        assert order == [(errors.ELIMIT, SHED_QUEUE_TIMEOUT_TEXT)]

    def test_method_gate_rollback_does_not_pump_or_poison_rate(self):
        """Review fix: a method-gate refusal after the server gate
        passed must roll back via on_request_rollback — NOT
        on_request_out, whose admission release-pump would recurse
        (pump → gate → rollback → pump) and whose phantom 'releases'
        would inflate the service-rate EMA."""
        calls = {"out": 0, "rollback": 0}

        class _SpyServer:
            def on_request_in(self):
                return True

            def on_request_out(self):
                calls["out"] += 1

            def on_request_rollback(self):
                calls["rollback"] += 1

        class _RefusingStatus:
            def on_requested(self):
                return False

        gate = server_method_gate(_SpyServer(), _RefusingStatus())
        assert gate() is False
        assert calls == {"out": 0, "rollback": 1}

    def test_method_limited_server_release_does_not_recurse(self):
        """End-to-end shape of the rollback recursion: a method-level
        limiter keeps refusing while the admission queue holds many
        entries; a completing request's release pump must terminate
        (restore-at-head) instead of recursing once per queued entry."""
        gate_evt = threading.Event()
        entered = []

        class Echo(rpc.Service):
            @rpc.method(EchoRequest, EchoResponse)
            def Echo(self, cntl, request, response, done):
                if request.message == "block":
                    entered.append(1)
                    gate_evt.wait(10)
                response.message = "ok"
                done()

        opts = rpc.ServerOptions()
        opts.method_max_concurrency = {"Echo.Echo": 1}
        opts.admission = AdmissionOptions(max_queue_ms=3000.0,
                                          service_rate_override=50.0)
        server = rpc.Server(opts)
        server.add_service(Echo())
        assert server.start("mem://adm-mlimit") == 0
        ch = rpc.Channel()
        ch.init("mem://adm-mlimit",
                options=rpc.ChannelOptions(timeout_ms=4000, max_retry=0))
        threads = []
        try:
            threads = _saturate(ch, entered, n=1)
            results = []
            lock = threading.Lock()

            def hp(i):
                c = rpc.Controller()
                c.priority = 0
                r = ch.call_method("Echo.Echo", c,
                                   EchoRequest(message=f"q{i}"),
                                   EchoResponse)
                with lock:
                    results.append(c.error_code_)
            qthreads = [threading.Thread(target=hp, args=(i,))
                        for i in range(8)]
            for t in qthreads:
                t.start()
            deadline = time.monotonic() + 3
            while server.admission.queued() < 8 \
                    and time.monotonic() < deadline:
                time.sleep(0.01)
            assert server.admission.queued() == 8
            gate_evt.set()
            for t in qthreads:
                t.join(10)
            # every queued request completed, one at a time, without a
            # RecursionError blowing up the release path
            assert results == [0] * 8, results
            # the rate EMA reflects real completions, not the phantom
            # rollback releases (which would read in the tens of
            # thousands of rps)
            assert server.admission.service_rate() == 50.0
        finally:
            gate_evt.set()
            for t in threads:
                t.join(5)
            ch.close()
            server.stop()

    def test_tenant_counter_cardinality_is_capped(self):
        """Review fix: the per-tenant counters are fed by untrusted wire
        input — distinct non-configured tenants beyond the cap fold
        into '~other' instead of registering unbounded bvar Adders."""
        clock = [1_000_000]
        gate = _Gate(1_000_000)
        adm = _mk_controller(gate, clock)
        for i in range(AdmissionController.MAX_TRACKED_TENANTS + 40):
            adm.submit(priority=0, tenant=f"uuid-{i}",
                       deadline_left_ms=None, recv_us=clock[0],
                       try_enter=gate.try_enter,
                       run=lambda w: None,
                       shed=lambda c, t, r: None)
        assert len(adm._tenant_labels) == \
            AdmissionController.MAX_TRACKED_TENANTS
        per = adm.describe()["by_tenant_band"]
        assert per.get("admitted[~other][b0]") == 40

    def test_gate_refusal_restores_entry_at_queue_head(self):
        clock = [1_000_000]
        gate = _Gate(0)
        adm = _mk_controller(gate, clock, service_rate_override=100.0)
        order = []
        _submit(adm, gate, order, "first", 0, "t", clock)
        _submit(adm, gate, order, "second", 0, "t", clock)
        assert adm.pump() == 0            # gate still closed: nothing ran
        assert adm.queued() == 2          # both restored, none lost
        gate.slots = 2
        adm.pump()
        assert order == ["first", "second"]   # FIFO preserved


# ---------------------------------------------------------------------
# satellite bugfix: shed responses must not poison the limiter
# ---------------------------------------------------------------------

class _SpyLimiter:
    def __init__(self):
        self.samples = []

    def on_requested(self, conc):
        return True

    def on_responded(self, code, latency_us):
        self.samples.append((code, latency_us))

    def max_concurrency(self):
        return 1 << 30


class TestShedExclusionFromLimiter:
    def test_shed_codes_skip_limiter_and_error_count(self):
        lim = _SpyLimiter()
        ms = MethodStatus("Svc.M", limiter=lim)
        assert ms.on_requested()
        ms.on_responded(errors.ELIMIT, 5000)
        assert ms.on_requested()
        ms.on_responded(errors.ELOGOFF, 5000)
        # shed traffic: no limiter samples, no error_count — only shed
        assert lim.samples == []
        assert ms.error_count.get_value() == 0
        assert ms.shed_count.get_value() == 2
        # real outcomes still feed both
        assert ms.on_requested()
        ms.on_responded(0, 1000)
        assert ms.on_requested()
        ms.on_responded(errors.EINTERNAL, 1000)
        assert lim.samples == [(0, 1000), (errors.EINTERNAL, 1000)]
        assert ms.error_count.get_value() == 1
        assert ms.concurrency == 0

    def test_wire_gate_reject_does_not_skew_method_status(self):
        """Regression pin: a server-max_concurrency ELIMIT used to call
        status.on_responded WITHOUT a matching on_requested — method
        concurrency went negative and the limiter ate a failure sample
        (the learned-floor poisoning of ISSUE 9's bugfix satellite)."""
        gate = threading.Event()
        entered = threading.Event()

        class Echo(rpc.Service):
            @rpc.method(EchoRequest, EchoResponse)
            def Echo(self, cntl, request, response, done):
                entered.set()
                gate.wait(5)
                response.message = "ok"
                done()

        opts = rpc.ServerOptions()
        opts.max_concurrency = 1          # NO admission layer: gate path
        server = rpc.Server(opts)
        server.add_service(Echo())
        assert server.start(0) == 0       # tcp: the wire plane
        status = server.method_status("Echo.Echo")
        spy = _SpyLimiter()
        status.limiter = spy
        ch = rpc.Channel()
        ch.init(f"127.0.0.1:{server.listen_port}",
                options=rpc.ChannelOptions(timeout_ms=3000, max_retry=0))
        try:
            blocked = []
            t = threading.Thread(
                target=lambda: blocked.append(ch.call_method(
                    "Echo.Echo", rpc.Controller(),
                    EchoRequest(message="b"), EchoResponse)))
            t.start()
            assert entered.wait(3)
            cntl = rpc.Controller()
            ch.call_method("Echo.Echo", cntl, EchoRequest(message="x"),
                           EchoResponse)
            assert cntl.error_code_ == errors.ELIMIT
            gate.set()
            t.join(5)
            deadline = time.monotonic() + 3
            while status.concurrency != 0 and time.monotonic() < deadline:
                time.sleep(0.01)
            # the reject left NO trace: concurrency balanced (not -1),
            # no error counted, no limiter sample for the shed
            assert status.concurrency == 0
            assert status.error_count.get_value() == 0
            assert all(code == 0 for code, _ in spy.samples), spy.samples
        finally:
            ch.close()
            server.stop()


# ---------------------------------------------------------------------
# plane-level shed semantics (wire / loopback / native-ici)
# ---------------------------------------------------------------------

def _overloadable_server(addr, *, rate=50.0, queue_ms=2000.0):
    gate = threading.Event()
    entered = []

    class Echo(rpc.Service):
        @rpc.method(EchoRequest, EchoResponse)
        def Echo(self, cntl, request, response, done):
            if request.message == "block":
                entered.append(1)
                gate.wait(10)
            response.message = f"{cntl.priority}/{cntl.tenant}"
            done()

    opts = rpc.ServerOptions()
    opts.max_concurrency = 2
    opts.admission = AdmissionOptions(max_queue_ms=queue_ms,
                                      service_rate_override=rate)
    server = rpc.Server(opts)
    server.add_service(Echo())
    assert server.start(addr) == 0
    return server, gate, entered


def _saturate(ch, entered, n=2):
    """Fill the server's 2 slots with blocking calls on real threads."""
    threads = []
    for _ in range(n):
        def blocker():
            c = rpc.Controller()
            c.priority = 0
            ch.call_method("Echo.Echo", c, EchoRequest(message="block"),
                           EchoResponse)
        t = threading.Thread(target=blocker)
        t.start()
        threads.append(t)
    deadline = time.monotonic() + 5
    while len(entered) < n and time.monotonic() < deadline:
        time.sleep(0.01)
    assert len(entered) == n, "server slots did not fill"
    return threads


@pytest.fixture
def mesh():
    import jax
    m = IciMesh(jax.devices())
    IciMesh.set_default(m)
    return m


class TestPlaneShedSemantics:
    """The same three assertions on every call plane: a sheddable-band
    request sheds immediately with retryable ELIMIT + nonzero
    retry_after_ms; a high-priority request queues and completes when a
    slot frees; priority/tenant propagate to the handler's controller."""

    def _drive(self, server, gate, entered, target, copts=None):
        ch = rpc.Channel()
        ch.init(target, options=copts or rpc.ChannelOptions(
            timeout_ms=4000, max_retry=0))
        threads = []
        try:
            threads = _saturate(ch, entered)
            # sheddable band: immediate ELIMIT + retry hint
            c = rpc.Controller()
            c.priority = 3
            c.tenant = "bulk"
            r = ch.call_method("Echo.Echo", c,
                               EchoRequest(message="x"), EchoResponse)
            assert r is None and c.error_code_ == errors.ELIMIT
            assert c.retry_after_ms > 0
            assert "shed" in c.error_text_
            # high priority queues, admitted on release, sees metadata
            res = {}

            def hp():
                c2 = rpc.Controller()
                c2.priority = 0
                c2.tenant = "svc"
                r2 = ch.call_method("Echo.Echo", c2,
                                    EchoRequest(message="hi"),
                                    EchoResponse)
                res["code"] = c2.error_code_
                res["msg"] = r2.message if r2 else c2.error_text_
            t = threading.Thread(target=hp)
            t.start()
            deadline = time.monotonic() + 3
            while server.admission.queued() != 1 \
                    and time.monotonic() < deadline:
                time.sleep(0.01)
            assert server.admission.queued() == 1
            gate.set()
            t.join(5)
            assert res == {"code": 0, "msg": "0/svc"}
            d = server.admission.describe()
            assert d["by_tenant_band"].get("shed_band[bulk][b3]") == 1
            assert d["by_tenant_band"].get("admitted[svc][b0]") == 1
        finally:
            gate.set()
            for t in threads:
                t.join(5)
            ch.close()

    def test_wire_plane(self):
        server, gate, entered = _overloadable_server(0)
        try:
            self._drive(server, gate, entered,
                        f"127.0.0.1:{server.listen_port}")
        finally:
            server.stop()

    def test_loopback_plane(self):
        server, gate, entered = _overloadable_server("mem://adm-loopback")
        try:
            self._drive(server, gate, entered, "mem://adm-loopback")
            # loopback really engaged: no wire connections were opened
            assert server.connections() == []
        finally:
            server.stop()

    def test_native_ici_plane(self, mesh):
        from brpc_tpu.ici import native_plane
        if not native_plane.available():
            pytest.skip("native plane unavailable")
        server, gate, entered = _overloadable_server("ici://71")
        try:
            assert native_plane.has_listener(71)
            self._drive(server, gate, entered, "ici://71")
        finally:
            server.stop()

    def test_draining_bounces_queued_entries_with_elogoff(self):
        server, gate, entered = _overloadable_server("mem://adm-drain")
        ch = rpc.Channel()
        ch.init("mem://adm-drain",
                options=rpc.ChannelOptions(timeout_ms=4000, max_retry=0))
        threads = []
        try:
            threads = _saturate(ch, entered)
            res = {}

            def hp():
                c2 = rpc.Controller()
                c2.priority = 0
                ch.call_method("Echo.Echo", c2,
                               EchoRequest(message="hi"), EchoResponse)
                res["code"] = c2.error_code_
            t = threading.Thread(target=hp)
            t.start()
            deadline = time.monotonic() + 3
            while server.admission.queued() != 1 \
                    and time.monotonic() < deadline:
                time.sleep(0.01)
            assert server.admission.queued() == 1
            # graceful stop: the queued-not-started entry bounces with
            # retryable ELOGOFF at drain start; the executing blockers
            # complete inside the grace window
            stopper = threading.Thread(target=lambda: server.stop(3.0))
            stopper.start()
            t.join(5)
            assert res["code"] == errors.ELOGOFF
            gate.set()
            stopper.join(10)
        finally:
            gate.set()
            for t in threads:
                t.join(5)
            ch.close()
            server.stop()


class TestDeadlineExpiredShedOnWire:
    def test_stale_request_shed_before_parse(self):
        """A wire request whose deadline budget was spent while it sat
        in the dispatch queue (stale recv stamp) is rejected before any
        work, with the distinct deadline-shed error text."""
        from brpc_tpu.policy import tpu_std
        from brpc_tpu.proto import rpc_meta_pb2 as meta_pb

        class Echo(rpc.Service):
            @rpc.method(EchoRequest, EchoResponse)
            def Echo(self, cntl, request, response, done):
                response.message = "ran"
                done()

        opts = rpc.ServerOptions()
        opts.admission = AdmissionOptions()
        server = rpc.Server(opts)
        server.add_service(Echo())
        assert server.start("mem://adm-deadline") == 0
        try:
            meta = meta_pb.RpcMeta()
            meta.correlation_id = 7
            meta.request.service_name = "Echo"
            meta.request.method_name = "Echo"
            meta.request.deadline_left_ms = 50
            from brpc_tpu.butil.iobuf import IOBuf
            body = IOBuf()
            body.append(EchoRequest(message="x").SerializeToString())
            msg = tpu_std.StdMessage(meta, body)
            # the frame was cut 200ms ago — budget (50ms) long spent
            msg.recv_ns = time.monotonic_ns() - 200_000_000

            writes = []

            class _Sock:
                remote_side = None

                def write(self, frame, notify_cid=None):
                    writes.append(bytes(frame.to_bytes()))
                    return 0

            tpu_std.process_request(msg, _Sock(), server)
            deadline = time.monotonic() + 2
            while not writes and time.monotonic() < deadline:
                time.sleep(0.01)
            assert writes, "no response written"
            raw = writes[0]
            meta_size = int.from_bytes(raw[4:8], "big")
            rmeta = meta_pb.RpcMeta()
            rmeta.ParseFromString(raw[12:12 + meta_size])
            assert rmeta.response.error_code == errors.ERPCTIMEDOUT
            assert rmeta.response.error_text == SHED_DEADLINE_TEXT
        finally:
            server.stop()


# ---------------------------------------------------------------------
# client leg (satellite): honoring retry_after_ms
# ---------------------------------------------------------------------

class TestClientRetryAfter:
    def test_retry_waits_for_hint_then_succeeds(self):
        """A shed call must not re-dispatch before the server's hint
        (jitter only ABOVE it): the retry lands >= retry_after_ms after
        the shed, and succeeds once capacity freed."""
        # service_rate_override=10 -> retry_after = 1000*(0+1)/10 = 100ms
        server, gate, entered = _overloadable_server(0, rate=10.0)
        ch = rpc.Channel()
        ch.init(f"127.0.0.1:{server.listen_port}",
                options=rpc.ChannelOptions(timeout_ms=4000, max_retry=3))
        threads = []
        try:
            # warm the channel (connect + first-dispatch costs) BEFORE
            # saturating: the probe below must reach the still-full
            # server ahead of the free timer, and a cold first dispatch
            # under full-suite load can eat tens of ms (observed flake:
            # the probe arrived after the slots freed, was never shed,
            # and retried_count stayed 0)
            warm = rpc.Controller()
            ch.call_method("Echo.Echo", warm, EchoRequest(message="w"),
                           EchoResponse)
            assert not warm.failed(), warm.error_text
            threads = _saturate(ch, entered)
            # free the slots well BEFORE the 100ms hint elapses: any
            # early re-dispatch would succeed too soon
            t_free = threading.Timer(0.05, gate.set)
            t_free.start()
            c = rpc.Controller()
            c.priority = 3
            t0 = time.monotonic()
            r = ch.call_method("Echo.Echo", c, EchoRequest(message="x"),
                               EchoResponse)
            dt = time.monotonic() - t0
            assert c.error_code_ == 0 and r is not None
            assert c.retried_count >= 1
            # the hint was 100ms; jitter adds up to +25% — the success
            # can only have landed after the full hint
            assert dt >= 0.1, dt
            t_free.cancel()
        finally:
            gate.set()
            for t in threads:
                t.join(5)
            ch.close()
            server.stop()

    def test_retry_bounded_by_overall_deadline(self):
        """A hint longer than the remaining budget loses to
        ERPCTIMEDOUT — the deadline, not the hint, bounds the call."""
        # rate 0.5 rps -> hint = 2000ms (the cap), way past the deadline
        server, gate, entered = _overloadable_server(0, rate=0.5)
        ch = rpc.Channel()
        ch.init(f"127.0.0.1:{server.listen_port}",
                options=rpc.ChannelOptions(timeout_ms=300, max_retry=3))
        threads = []
        try:
            threads = _saturate(ch, entered)
            c = rpc.Controller()
            c.priority = 3
            t0 = time.monotonic()
            ch.call_method("Echo.Echo", c, EchoRequest(message="x"),
                           EchoResponse)
            dt = time.monotonic() - t0
            assert c.error_code_ == errors.ERPCTIMEDOUT
            assert dt < 1.5, dt          # not the 2s hint: the deadline
        finally:
            gate.set()
            for t in threads:
                t.join(5)
            ch.close()
            server.stop()

    def test_sheds_do_not_trip_the_client_circuit_breaker(self):
        """Review fix: an admission shed is an overloaded-but-HEALTHY
        endpoint — a burst of sheds must not isolate it via the client
        breaker (which would block the critical-band traffic the server
        is still serving)."""
        from brpc_tpu.rpc.circuit_breaker import BreakerRegistry
        server, gate, entered = _overloadable_server(0, rate=50.0)
        ch = rpc.Channel()
        ch.init(f"127.0.0.1:{server.listen_port}",
                options=rpc.ChannelOptions(timeout_ms=2000, max_retry=0))
        threads = []
        try:
            threads = _saturate(ch, entered)
            for _ in range(60):           # a shed burst well past any
                c = rpc.Controller()      # breaker error-rate window
                c.priority = 3
                ch.call_method("Echo.Echo", c,
                               EchoRequest(message="x"), EchoResponse)
                assert c.error_code_ == errors.ELIMIT
            breaker = BreakerRegistry.instance().breaker(
                ch._endpoint)
            assert not breaker.is_isolated()
            # the endpoint still serves: a high-priority call completes
            gate.set()
            for t in threads:
                t.join(5)
            threads = []
            c = rpc.Controller()
            c.priority = 0
            r = ch.call_method("Echo.Echo", c,
                               EchoRequest(message="after"), EchoResponse)
            assert c.error_code_ == 0 and r is not None
        finally:
            gate.set()
            for t in threads:
                t.join(5)
            ch.close()
            server.stop()

    def test_hedging_does_not_amplify_into_retry_storm(self):
        """backup-request hedging against a shedding server: the shed
        hint still gates every re-dispatch, so one logical call lands at
        most max_retry+1 tries on the server — never a storm."""
        server, gate, entered = _overloadable_server(0, rate=10.0)
        ch = rpc.Channel()
        ch.init(f"127.0.0.1:{server.listen_port}",
                options=rpc.ChannelOptions(timeout_ms=600, max_retry=2,
                                           backup_request_ms=20))
        threads = []
        try:
            threads = _saturate(ch, entered)
            shed_before = server.admission.shed_total.get_value()
            c = rpc.Controller()
            c.priority = 3
            ch.call_method("Echo.Echo", c, EchoRequest(message="x"),
                           EchoResponse)
            assert c.failed()
            # settle: any straggler re-issues land within the deadline
            time.sleep(0.3)
            shed_delta = server.admission.shed_total.get_value() \
                - shed_before
            # max_retry+1 tries (+1 tolerance for a stale straggler
            # issue) — a storm would be dozens within the 600ms window
            assert 1 <= shed_delta <= 4, shed_delta
        finally:
            gate.set()
            for t in threads:
                t.join(5)
            ch.close()
            server.stop()


# ---------------------------------------------------------------------
# the deterministic mini-overload (tier-1; simulated clock + rate)
# ---------------------------------------------------------------------

@pytest.mark.overload
class TestMiniOverload:
    """The shed logic under a simulated 10x overload, fully
    deterministic: a fake gate of capacity 2, a simulated clock, an
    injected 100 rps service rate, 4 tenants offering 3:1 low:high."""

    def test_shed_absorbs_excess_high_priority_survives(self):
        clock = [1_000_000]
        gate = _Gate(2)
        adm = _mk_controller(gate, clock, service_rate_override=100.0,
                             queue_capacity=16, max_queue_ms=20.0)
        tenants = [f"t{i}" for i in range(4)]
        outcomes = {"hi_ok": {t: 0 for t in tenants}, "lo_ok": 0,
                    "shed": 0, "hints": []}
        inflight = []

        def submit(pri, tenant):
            def shed(code, txt, ra):
                outcomes["shed"] += 1
                if code == errors.ELIMIT:
                    outcomes["hints"].append(ra)
                assert code in (errors.ELIMIT, errors.ERPCTIMEDOUT)
            adm.submit(priority=pri, tenant=tenant, deadline_left_ms=500,
                       recv_us=clock[0], try_enter=gate.try_enter,
                       run=(lambda w, p=pri, t=tenant:
                            inflight.append((p, t))),
                       shed=shed)

        def complete_one():
            if inflight:
                pri, t = inflight.pop(0)
                if pri == 0:
                    outcomes["hi_ok"][t] += 1
                else:
                    outcomes["lo_ok"] += 1
                gate.release()
                adm.on_release()

        # 40 ticks of 10ms: each tick offers 1 request per tenant
        # alternating 3 low : 1 high (10x the 2-slot capacity), and the
        # "server" completes at the injected service rate (1 per tick)
        for tick in range(40):
            clock[0] += 10_000
            for ti, t in enumerate(tenants):
                pri = 0 if (tick + ti) % 4 == 0 else 3
                submit(pri, t)
            complete_one()
            adm.expire_queued()
        for _ in range(30):               # drain the queue
            clock[0] += 10_000
            complete_one()
            adm.expire_queued()
        # the excess was absorbed by SHED, not by queueing: the queue
        # never exceeded its bound and ended empty
        assert adm.queued() == 0
        assert outcomes["shed"] > 80          # ~10x excess was shed
        # every ELIMIT shed carried a nonzero, rate-derived hint
        assert outcomes["hints"] and all(h > 0 for h in outcomes["hints"])
        # zero tenant starvation: every tenant's high-priority stream
        # got service
        assert all(n > 0 for n in outcomes["hi_ok"].values()), \
            outcomes["hi_ok"]
        # high-priority goodput dominates low (strict bands)
        assert sum(outcomes["hi_ok"].values()) > outcomes["lo_ok"]


# ---------------------------------------------------------------------
# observability: admission wait feeds the queue-stage decomposition
# ---------------------------------------------------------------------

class TestQueueStageDecomposition:
    def test_admission_wait_recorded_in_queue_stage(self):
        from brpc_tpu.butil import flags as _flags
        from brpc_tpu.policy import tpu_std
        server, gate, entered = _overloadable_server(0, rate=50.0)
        ch = rpc.Channel()
        ch.init(f"127.0.0.1:{server.listen_port}",
                options=rpc.ChannelOptions(timeout_ms=4000, max_retry=0))
        threads = []
        _flags.set_flag("tpu_std_stage_metrics", "on")
        try:
            before = tpu_std._stage_recorders["queue"].count()
            threads = _saturate(ch, entered)
            res = {}

            def hp():
                c2 = rpc.Controller()
                c2.priority = 0
                ch.call_method("Echo.Echo", c2,
                               EchoRequest(message="hi"), EchoResponse)
                res["code"] = c2.error_code_
            t = threading.Thread(target=hp)
            t.start()
            deadline = time.monotonic() + 3
            while server.admission.queued() != 1 \
                    and time.monotonic() < deadline:
                time.sleep(0.01)
            time.sleep(0.05)              # accrue measurable queue wait
            gate.set()
            t.join(5)
            assert res["code"] == 0
            # the admitted-from-queue request contributed queue-stage
            # samples (arrival dispatch + admission wait)
            assert tpu_std._stage_recorders["queue"].count() > before
        finally:
            _flags.set_flag("tpu_std_stage_metrics", "sampled")
            gate.set()
            for t in threads:
                t.join(5)
            ch.close()
            server.stop()
