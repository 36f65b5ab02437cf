"""ns_filter, EOVERCROWDED, restful mappings, pooled/short connections."""
import json
import socket as pysocket
import time

import pytest

import brpc_tpu.policy
from brpc_tpu import rpc
from brpc_tpu.butil import flags as _flags
from brpc_tpu.butil.iobuf import IOBuf
from brpc_tpu.rpc import errors
from tests.echo_pb2 import EchoRequest, EchoResponse

_seq = [9000]


def unique(p):
    _seq[0] += 1
    return f"{p}-{_seq[0]}"


class EchoService(rpc.Service):
    @rpc.method(EchoRequest, EchoResponse)
    def Echo(self, cntl, request, response, done):
        response.message = request.message
        done()


class TestNsFilter:
    def test_filter_excludes_tagged_servers(self, tmp_path):
        names = [unique("nsf") for _ in range(2)]
        servers = []
        for i, name in enumerate(names):
            s = rpc.Server()
            s.add_service(EchoService())
            assert s.start(f"mem://{name}") == 0
            servers.append(s)
        listing = tmp_path / "servers"
        listing.write_text(f"mem://{names[0]} 100 keep\n"
                           f"mem://{names[1]} 100 drop\n")
        opts = rpc.ChannelOptions(timeout_ms=1000)
        opts.ns_filter = lambda e: e.tag != "drop"
        ch = rpc.Channel()
        assert ch.init(f"file://{listing}", "rr", opts) == 0
        assert ch._lb.server_count() == 1
        for _ in range(5):
            cntl = rpc.Controller()
            ch.call_method("EchoService.Echo", cntl,
                           EchoRequest(message="f"), EchoResponse)
            assert not cntl.failed()
        for s in servers:
            s.stop()


class TestOvercrowded:
    def test_write_backlog_rejected(self):
        from brpc_tpu.rpc.mem_transport import new_mem_pair
        a, b = new_mem_pair()
        _flags.set_flag("socket_max_unwritten_bytes", 1024)
        try:
            # block the drain by failing the peer reference AFTER hooking:
            # simulate stuck transport by monkeypatching _do_write to EAGAIN
            a._do_write = lambda data: -1
            rc1 = a.write(IOBuf(b"x" * 800))
            rc2 = a.write(IOBuf(b"y" * 800))
            rc3 = a.write(IOBuf(b"z" * 800))
            assert rc1 == 0
            assert errors.EOVERCROWDED in (rc2, rc3)
        finally:
            _flags.set_flag("socket_max_unwritten_bytes", 64 * 1024 * 1024)
            a.set_failed()
            b.set_failed()


class TestRestful:
    def test_restful_mapping(self):
        opts = rpc.ServerOptions()
        opts.restful_mappings = {"/v1/echo": "EchoService.Echo"}
        server = rpc.Server(opts)
        server.add_service(EchoService())
        assert server.start("127.0.0.1:0") == 0
        try:
            import urllib.request
            req = urllib.request.Request(
                f"http://127.0.0.1:{server.listen_port}/v1/echo",
                data=json.dumps({"message": "restful"}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=5) as r:
                body = json.loads(r.read())
            assert body["message"] == "restful"
        finally:
            server.stop()


class TestConnectionTypes:
    @pytest.mark.parametrize("ctype", ["pooled", "short"])
    def test_connection_type_works(self, ctype):
        name = unique("conn")
        server = rpc.Server()
        server.add_service(EchoService())
        assert server.start(f"mem://{name}") == 0
        try:
            ch = rpc.Channel()
            ch.init(f"mem://{name}",
                    options=rpc.ChannelOptions(connection_type=ctype,
                                               timeout_ms=2000))
            for i in range(5):
                cntl = rpc.Controller()
                resp = ch.call_method("EchoService.Echo", cntl,
                                      EchoRequest(message=f"c{i}"),
                                      EchoResponse)
                assert not cntl.failed(), cntl.error_text
                assert resp.message == f"c{i}"
        finally:
            server.stop()

    def test_pooled_reuses_connections(self):
        name = unique("pool")
        server = rpc.Server()
        server.add_service(EchoService())
        assert server.start(f"mem://{name}") == 0
        try:
            ch = rpc.Channel()
            ch.init(f"mem://{name}",
                    options=rpc.ChannelOptions(connection_type="pooled",
                                               timeout_ms=2000))
            for _ in range(10):
                cntl = rpc.Controller()
                ch.call_method("EchoService.Echo", cntl,
                               EchoRequest(message="p"), EchoResponse)
            # sequential pooled calls reuse one connection
            from brpc_tpu.butil.endpoint import parse_endpoint
            from brpc_tpu.rpc.socket_map import SocketMap
            stats = SocketMap.instance().stats()
            ep = parse_endpoint(f"mem://{name}")
            assert stats.get(ep, 0) <= 2
        finally:
            server.stop()


class TestReplyBeatsTheWritesReturn:
    """An in-process reply can end a call while the caller's thread is
    still inside ``sock.write`` or just out of it (a ``usercode_inline``
    handler on the Python ici plane; on the chip a bulk frame whose last
    window piece the caller cut itself, PERF.md §6 PR 34).  What the caller
    does after the write must not assume the call is still in flight."""

    @pytest.fixture
    def python_plane(self, monkeypatch):
        """call(attachment=None) -> controller, from chip 5 over ici://6
        with the native tier's binding off; gives the server too.  The
        caller sits on the next chip so that a DEVICE attachment MOVES and
        its delivery is gated: a ref pass on one chip commits on the
        writer's own thread, inside ``sock.write``."""
        opts = rpc.ServerOptions()
        opts.usercode_inline = True
        server = rpc.Server(opts)
        server.add_service(EchoService())
        assert server.start("ici://6") == 0
        ch = rpc.Channel()
        ch.init("ici://6", options=rpc.ChannelOptions(
            connection_type="pooled", timeout_ms=60000, ici_local_device=5))
        monkeypatch.setattr(ch, "_native_ici_binding", lambda cntl: None)

        def call(message, attachment=None, cntl=None):
            cntl = cntl or rpc.Controller()
            if attachment is not None:
                cntl.request_attachment.append_device_array(attachment)
            resp = ch.call_method("EchoService.Echo", cntl,
                                  EchoRequest(message=message), EchoResponse)
            assert not cntl.failed(), cntl.error_text
            assert resp.message == message
            return cntl
        try:
            yield call, server
        finally:
            server.stop()

    def test_the_pooled_connection_goes_back(self, python_plane,
                                             monkeypatch):
        from brpc_tpu.rpc.socket_map import SocketMap
        call, server = python_plane
        connects, smap = [], SocketMap.instance()
        real = smap._checked_connect
        monkeypatch.setattr(
            smap, "_checked_connect",
            lambda *a, **kw: (connects.append(1), real(*a, **kw))[1])
        for i in range(10):
            call(f"p{i}")
        # the next call takes the connection from the pool
        assert len(connects) == 1
        assert len([s for s in server._connections if not s.failed]) == 1

    def test_no_deadline_timer_is_left_armed(self, python_plane,
                                             monkeypatch):
        """The reply ends the call on the device poller's thread while the
        caller is between ``_issue_rpc`` and arming the deadline:
        ``_end_rpc`` has passed its own unschedule, so the timer armed
        after it is taken back — left live it would hold the Controller
        and its attachments for the whole timeout."""
        import threading
        import jax
        import jax.numpy as jnp
        from brpc_tpu.bthread.timer_thread import TimerThread
        from brpc_tpu.ici import transport as tr
        call, _server = python_plane
        # every delivery of what moved through the poller: the reply ends
        # the call there
        monkeypatch.setattr(tr, "_all_ready", lambda arrays: False)
        ending, armed = threading.Event(), threading.Event()
        cntl = rpc.Controller()
        real_arm = cntl._schedule_try_timer

        def arm_once_the_end_has_begun():
            assert ending.wait(10)
            real_arm()
            armed.set()
        monkeypatch.setattr(cntl, "_schedule_try_timer",
                            arm_once_the_end_has_begun)
        real_end = rpc.Channel._on_call_end

        def end_slowly(chan, c):
            if c is cntl:               # inside _end_rpc, past its unschedule
                ending.set()
                assert armed.wait(10)
            return real_end(chan, c)
        monkeypatch.setattr(rpc.Channel, "_on_call_end", end_slowly)
        block = jax.device_put(jnp.arange(4096, dtype=jnp.uint8),
                               jax.devices()[5])
        call("late", attachment=block, cntl=cntl)
        assert armed.is_set() and cntl._timeout_timer is not None
        assert not TimerThread.instance()._entries.get(cntl._timeout_timer)


class TestServerOptionsLifecycle:
    """idle_timeout_s / internal_port / server_info_name (server.h parity:
    these options must DO something, not just exist)."""

    def test_idle_timeout_reaps_stale_connections(self):
        import time
        from tests.echo_pb2 import EchoRequest, EchoResponse

        class Echo(rpc.Service):
            SERVICE_NAME = "EchoService"

            @rpc.method(EchoRequest, EchoResponse)
            def Echo(self, cntl, request, response, done):
                response.message = request.message
                done()

        opts = rpc.ServerOptions()
        opts.idle_timeout_s = 1
        server = rpc.Server(opts)
        server.add_service(Echo())
        assert server.start("127.0.0.1:0") == 0
        try:
            ch = rpc.Channel()
            ch.init(f"127.0.0.1:{server.listen_port}",
                    options=rpc.ChannelOptions(timeout_ms=5000))
            cntl = rpc.Controller()
            resp = ch.call_method("EchoService.Echo", cntl,
                                  EchoRequest(message="a"), EchoResponse)
            assert not cntl.failed() and resp.message == "a"
            assert len(server.connections()) == 1
            deadline = time.monotonic() + 6
            while server.connections() and time.monotonic() < deadline:
                time.sleep(0.2)
            assert not server.connections(), "idle connection not reaped"
            # a fresh call reconnects and succeeds
            cntl = rpc.Controller()
            resp = ch.call_method("EchoService.Echo", cntl,
                                  EchoRequest(message="b"), EchoResponse)
            assert not cntl.failed(), cntl.error_text
            assert resp.message == "b"
        finally:
            server.stop()

    def test_internal_port_separates_admin_pages(self):
        import json
        import urllib.request
        from tests.echo_pb2 import EchoRequest, EchoResponse

        class Echo(rpc.Service):
            SERVICE_NAME = "EchoService"

            @rpc.method(EchoRequest, EchoResponse)
            def Echo(self, cntl, request, response, done):
                response.message = "ok"
                done()

        opts = rpc.ServerOptions()
        opts.internal_port = 0          # ephemeral
        opts.server_info_name = "unit-fixture"
        server = rpc.Server(opts)
        server.add_service(Echo())
        assert server.start("127.0.0.1:0") == 0
        try:
            pub, adm = server.listen_port, server.internal_port
            assert adm > 0 and adm != pub
            # admin page on the internal port, with the display name
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{adm}/status", timeout=10).read()
            assert json.loads(body)["name"] == "unit-fixture"
            # admin page REFUSED on the public port
            try:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{pub}/status", timeout=10)
                assert False, "public port served an admin page"
            except urllib.error.HTTPError as e:
                assert e.code == 403
            # user method REFUSED on the internal port
            req = urllib.request.Request(
                f"http://127.0.0.1:{adm}/EchoService/Echo",
                data=b'{"message":"x"}',
                headers={"Content-Type": "application/json"})
            try:
                urllib.request.urlopen(req, timeout=10)
                assert False, "internal port served a user method"
            except urllib.error.HTTPError as e:
                assert e.code == 403
            # user method SERVED on the public port
            req = urllib.request.Request(
                f"http://127.0.0.1:{pub}/EchoService/Echo",
                data=b'{"message":"x"}',
                headers={"Content-Type": "application/json"})
            body = urllib.request.urlopen(req, timeout=10).read()
            assert json.loads(body)["message"] == "ok"
        finally:
            server.stop()

    def test_internal_port_refuses_non_http_protocols(self):
        """The admin/service separation must hold for EVERY protocol: a
        tpu_std client speaking to the internal port is refused at the
        dispatch point, not served."""
        from tests.echo_pb2 import EchoRequest, EchoResponse

        class Echo(rpc.Service):
            SERVICE_NAME = "EchoService"

            @rpc.method(EchoRequest, EchoResponse)
            def Echo(self, cntl, request, response, done):
                response.message = "leak!"
                done()

        opts = rpc.ServerOptions()
        opts.internal_port = 0
        server = rpc.Server(opts)
        server.add_service(Echo())
        assert server.start("127.0.0.1:0") == 0
        try:
            ch = rpc.Channel()
            ch.init(f"127.0.0.1:{server.internal_port}",
                    options=rpc.ChannelOptions(timeout_ms=3000,
                                               max_retry=0))
            cntl = rpc.Controller()
            ch.call_method("EchoService.Echo", cntl,
                           EchoRequest(message="x"), EchoResponse)
            assert cntl.failed(), "tpu_std served on the internal port"
        finally:
            server.stop()

    def test_connect_timeout_ms_reaches_tcp_connect(self, monkeypatch):
        """ChannelOptions.connect_timeout_ms must flow into the TCP
        connect (it was declared but hardcoded to 5s)."""
        from brpc_tpu.rpc import socket_map as smod
        from brpc_tpu.rpc import tcp_transport as tmod
        from tests.echo_pb2 import EchoRequest, EchoResponse

        class Echo(rpc.Service):
            SERVICE_NAME = "EchoService"

            @rpc.method(EchoRequest, EchoResponse)
            def Echo(self, cntl, request, response, done):
                response.message = "ok"
                done()

        server = rpc.Server()
        server.add_service(Echo())
        assert server.start("127.0.0.1:0") == 0
        seen = {}
        real = tmod.tcp_connect

        def spy(ep, timeout=5.0, ssl_context=None):
            seen["timeout"] = timeout
            return real(ep, timeout=timeout, ssl_context=ssl_context)

        monkeypatch.setattr(tmod, "tcp_connect", spy)
        try:
            ch = rpc.Channel()
            ch.init(f"127.0.0.1:{server.listen_port}",
                    options=rpc.ChannelOptions(timeout_ms=5000,
                                               connect_timeout_ms=1234))
            cntl = rpc.Controller()
            resp = ch.call_method("EchoService.Echo", cntl,
                                  EchoRequest(message="x"), EchoResponse)
            assert not cntl.failed(), cntl.error_text
            assert resp.message == "ok"
            assert abs(seen["timeout"] - 1.234) < 1e-9
        finally:
            server.stop()

    def test_internal_port_with_mem_listener_stays_loopback(self):
        """internal_port on a non-TCP main listener must neither crash
        (mem:// host is not a network name) nor bind 0.0.0.0."""
        opts = rpc.ServerOptions()
        opts.internal_port = 0
        server = rpc.Server(opts)
        assert server.start("mem://internal-port-probe") == 0
        try:
            import json
            import urllib.request
            adm = server.internal_port
            assert adm > 0
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{adm}/health", timeout=10).read()
            assert body
        finally:
            server.stop()

    def test_server_restart_keeps_idle_reaper_alive(self):
        import time
        from tests.echo_pb2 import EchoRequest, EchoResponse

        class Echo(rpc.Service):
            SERVICE_NAME = "EchoService"

            @rpc.method(EchoRequest, EchoResponse)
            def Echo(self, cntl, request, response, done):
                response.message = "ok"
                done()

        opts = rpc.ServerOptions()
        opts.idle_timeout_s = 1
        server = rpc.Server(opts)
        server.add_service(Echo())
        assert server.start("127.0.0.1:0") == 0
        server.stop()
        # second run: the stopped-event must have been cleared, or the
        # reaper exits instantly and idle conns are never collected
        assert server.start("127.0.0.1:0") == 0
        try:
            assert server.is_running()
            ch = rpc.Channel()
            ch.init(f"127.0.0.1:{server.listen_port}",
                    options=rpc.ChannelOptions(timeout_ms=5000))
            cntl = rpc.Controller()
            ch.call_method("EchoService.Echo", cntl,
                           EchoRequest(message="x"), EchoResponse)
            assert not cntl.failed(), cntl.error_text
            deadline = time.monotonic() + 6
            while server.connections() and time.monotonic() < deadline:
                time.sleep(0.2)
            assert not server.connections(), \
                "reaper dead after server restart"
        finally:
            server.stop()
