"""Native ici:// datapath (native/rpc.cpp ici plane + ici/native_plane.py).

The fusion VERDICT r3 #1 demanded: framing, window accounting, dispatch and
correlation in C++, with Python upcalled only for device-ref relocation.
These tests pin down the custody discipline (no registry leaks on ANY
path), the credit window, cross-device relocation on the 8-device CPU
mesh, and interop with the rpc.Server/Channel front doors.
"""
import threading
import time

import numpy as np
import pytest

import brpc_tpu.policy  # noqa: F401  (registers protocols)
from brpc_tpu import rpc, ici
from brpc_tpu.ici import native_plane
from tests.echo_pb2 import EchoRequest, EchoResponse

pytestmark = pytest.mark.skipif(not native_plane.available(),
                                reason="native core unavailable")


@pytest.fixture(scope="module")
def mesh():
    import jax
    m = ici.IciMesh(jax.devices())
    ici.IciMesh.set_default(m)
    return m


def _device_payload(mesh, dev=0, n=4096):
    import jax
    import jax.numpy as jnp
    arr = jax.device_put(jnp.arange(n, dtype=jnp.uint8), mesh.device(dev))
    jax.block_until_ready(arr)
    return arr


class EchoService(rpc.Service):
    SERVICE_NAME = "EchoService"

    @rpc.method(EchoRequest, EchoResponse)
    def Echo(self, cntl, request, response, done):
        response.message = request.message
        if len(cntl.request_attachment):
            cntl.response_attachment.append(cntl.request_attachment)
        done()


class TestNativeDatapath:
    def test_channel_rides_native_plane(self, mesh):
        """rpc.Channel → ici:// routes through the C++ plane: the native
        request counter moves, and the registry never leaks."""
        server = rpc.Server()
        server.add_service(EchoService())
        assert server.start("ici://2") == 0
        try:
            binding = getattr(server, "_native_ici", None)
            assert binding is not None, "native ici plane not attached"
            ch = rpc.Channel()
            ch.init("ici://2")
            payload = _device_payload(mesh)
            before = binding.requests()
            cntl = rpc.Controller()
            cntl.request_attachment.append_device_array(payload)
            resp = ch.call_method("EchoService.Echo", cntl,
                                  EchoRequest(message="native"),
                                  EchoResponse)
            assert not cntl.failed(), cntl.error_text
            assert resp.message == "native"
            assert cntl.response_attachment.to_bytes() == bytes(
                np.arange(4096, dtype=np.uint8))
            assert binding.requests() == before + 1
        finally:
            server.stop()
        assert native_plane.registry().live() == 0

    def test_native_echo_tier_and_relocation(self, mesh):
        """Compiled echo tier: zero Python dispatch; a payload resident on
        another mesh device is relocated toward the CLIENT device on the
        way back (the rdma zero-copy SGE pass-through)."""
        if mesh.size < 2:
            pytest.skip("needs >=2 devices")
        server = rpc.Server()
        server.add_service(EchoService())
        assert server.start("ici://3") == 0
        try:
            server._native_ici.register_native_echo("EchoService.Echo")
            ch = rpc.Channel()
            ch.init("ici://3")
            payload = _device_payload(mesh, dev=1)
            cntl = rpc.Controller()
            cntl.request_attachment.append_device_array(payload)
            ch.call_method("EchoService.Echo", cntl,
                           EchoRequest(message="m"), EchoResponse)
            assert not cntl.failed(), cntl.error_text
            refs = cntl.response_attachment.device_refs()
            assert len(refs) == 1
            # echoed ref was relocated to the channel's local device
            # (ici_connect default: the neighbor of ici://3 → device 4)
            local_dev = ch._native_ici.local_dev
            assert {str(d) for d in refs[0].block.data.devices()} == \
                {str(mesh.device(local_dev))}
            assert cntl.response_attachment.to_bytes() == bytes(
                np.arange(4096, dtype=np.uint8))
        finally:
            server.stop()
        assert native_plane.registry().live() == 0

    def test_handler_sees_resident_attachment(self, mesh):
        """Python-tier handler observes its device refs already resident
        on the SERVER device (relocation happened before the upcall)."""
        if mesh.size < 3:
            pytest.skip("needs >=3 devices")
        seen = {}

        class Probe(rpc.Service):
            @rpc.method(EchoRequest, EchoResponse)
            def P(self, cntl, request, response, done):
                refs = cntl.request_attachment.device_refs()
                seen["devs"] = {str(d) for r in refs
                                for d in r.block.data.devices()}
                response.message = "ok"
                done()

        server = rpc.Server()
        server.add_service(Probe())
        assert server.start("ici://4") == 0
        try:
            ch = rpc.Channel()
            ch.init("ici://4")
            payload = _device_payload(mesh, dev=2)
            cntl = rpc.Controller()
            cntl.request_attachment.append_device_array(payload)
            ch.call_method("Probe.P", cntl, EchoRequest(message="x"),
                           EchoResponse)
            assert not cntl.failed(), cntl.error_text
            assert seen["devs"] == {str(mesh.device(4))}
        finally:
            server.stop()
        assert native_plane.registry().live() == 0

    def test_mixed_host_device_attachment_order(self, mesh):
        """Interleaved host/device attachment segments keep their order
        across the plane (the segment-descriptor sidecar)."""
        got = {}

        class Mix(rpc.Service):
            @rpc.method(EchoRequest, EchoResponse)
            def M(self, cntl, request, response, done):
                got["bytes"] = cntl.request_attachment.to_bytes()
                got["blocks"] = [
                    cntl.request_attachment.backing_block(i).block.kind
                    for i in range(
                        cntl.request_attachment.backing_block_num())]
                response.message = "ok"
                done()

        server = rpc.Server()
        server.add_service(Mix())
        assert server.start("ici://5") == 0
        try:
            from brpc_tpu.butil.iobuf import DEVICE, HOST
            ch = rpc.Channel()
            ch.init("ici://5")
            payload = _device_payload(mesh, n=16)
            cntl = rpc.Controller()
            cntl.request_attachment.append(b"head-")
            cntl.request_attachment.append_device_array(payload)
            cntl.request_attachment.append(b"-tail")
            ch.call_method("Mix.M", cntl, EchoRequest(message="x"),
                           EchoResponse)
            assert not cntl.failed(), cntl.error_text
            assert got["bytes"] == b"head-" + bytes(range(16)) + b"-tail"
            assert got["blocks"][0] == HOST
            assert DEVICE in got["blocks"]
        finally:
            server.stop()
        assert native_plane.registry().live() == 0

    def test_error_paths_release_custody(self, mesh):
        """ENOMETHOD with a device attachment must release the refs (the
        drop-path release upcall), not leak them pinned forever."""
        server = rpc.Server()
        server.add_service(EchoService())
        assert server.start("ici://6") == 0
        try:
            ch = rpc.Channel()
            ch.init("ici://6")
            payload = _device_payload(mesh)
            cntl = rpc.Controller()
            cntl.request_attachment.append_device_array(payload)
            ch.call_method("NoSuch.Method", cntl,
                           EchoRequest(message="x"), EchoResponse)
            assert cntl.failed()
            assert cntl.error_code_ == rpc.errors.ENOMETHOD
        finally:
            server.stop()
        assert native_plane.registry().live() == 0

    def test_error_response_with_segs_releases_on_client(self, mesh,
                                                         monkeypatch):
        """An ABI server may respond err != 0 AND device segs (the Python
        server never does, but brpc_tpu_ici_respond allows it); native
        copies segs_out regardless of rc, so the CLIENT must release the
        keys on its rc != 0 path or they strand in the registry forever
        (exactly-one-exit custody)."""
        from brpc_tpu.butil.iobuf import IOBuf
        from brpc_tpu.ici.native_plane import split_attachment

        class Failing(rpc.Service):
            @rpc.method(EchoRequest, EchoResponse)
            def F(self, cntl, request, response, done):
                cntl.set_failed(rpc.errors.EINTERNAL, "deliberate")
                done()

        server = rpc.Server()
        server.add_service(Failing())
        assert server.start("ici://5") == 0
        try:
            binding = server._native_ici
            arr = _device_payload(mesh)

            def err_with_segs(token, err, text, collector=None, post=None,
                              retry_after=0):
                att = IOBuf()
                att.append_device_array(arr)
                att_host, segs = split_attachment(att)
                binding._respond_flush([(token, err, text.encode(), b"",
                                         att_host, segs, post,
                                         retry_after, 0)])

            monkeypatch.setattr(binding, "_respond_one", err_with_segs)
            ch = rpc.Channel()
            ch.init("ici://5")
            cntl = rpc.Controller()
            ch.call_method("Failing.F", cntl, EchoRequest(message="x"),
                           EchoResponse)
            assert cntl.failed()
            assert cntl.error_code_ == rpc.errors.EINTERNAL
        finally:
            server.stop()
        assert native_plane.registry().live() == 0

    def test_timeout_drops_late_response_and_releases(self, mesh):
        """A handler answering after the client deadline: the client gets
        ERPCTIMEDOUT, the late response is dropped, custody released."""
        release = threading.Event()
        responded = threading.Event()

        class Slow(rpc.Service):
            @rpc.method(EchoRequest, EchoResponse)
            def S(self, cntl, request, response, done):
                def later():
                    release.wait(5)
                    if len(cntl.request_attachment):
                        cntl.response_attachment.append(
                            cntl.request_attachment)
                    response.message = "late"
                    done()
                    responded.set()
                threading.Thread(target=later, daemon=True).start()

        server = rpc.Server()
        server.add_service(Slow())
        assert server.start("ici://7") == 0
        try:
            ch = rpc.Channel()
            ch.init("ici://7",
                    options=rpc.ChannelOptions(timeout_ms=150, max_retry=0))
            payload = _device_payload(mesh)
            cntl = rpc.Controller()
            cntl.request_attachment.append_device_array(payload)
            ch.call_method("Slow.S", cntl, EchoRequest(message="x"),
                           EchoResponse)
            assert cntl.failed()
            assert cntl.error_code_ == rpc.errors.ERPCTIMEDOUT
            release.set()
            assert responded.wait(5)
            deadline = time.monotonic() + 5
            while native_plane.registry().live() and \
                    time.monotonic() < deadline:
                time.sleep(0.01)
            assert native_plane.registry().live() == 0
        finally:
            release.set()
            server.stop()

    def test_oversize_frame_fails_fast(self, mesh):
        """A frame that can never fit the send window fails EOVERCROWDED
        immediately instead of burning the whole deadline."""
        server = rpc.Server()
        server.add_service(EchoService())
        assert server.start("ici://8") == 0
        try:
            binding = native_plane.ChannelBinding(8, window_bytes=1024)
            try:
                cntl = rpc.Controller()
                cntl.timeout_ms = 10000
                cntl.request_attachment.append(b"x" * 8192)
                t0 = time.monotonic()
                binding.call("EchoService.Echo", cntl,
                             EchoRequest(message="x"), EchoResponse)
                assert cntl.failed()
                assert cntl.error_code_ == rpc.errors.EOVERCROWDED
                assert time.monotonic() - t0 < 2.0   # did NOT wait 10 s
            finally:
                binding.close()
        finally:
            server.stop()
        assert native_plane.registry().live() == 0

    def test_concurrent_callers(self, mesh):
        """Many threads over one channel: correlation never crosses wires
        and nothing leaks."""
        server = rpc.Server()
        server.add_service(EchoService())
        assert server.start("ici://9") == 0
        errs = []
        try:
            ch = rpc.Channel()
            ch.init("ici://9")

            def worker(wid):
                try:
                    for i in range(25):
                        cntl = rpc.Controller()
                        msg = f"w{wid}-{i}"
                        resp = ch.call_method("EchoService.Echo", cntl,
                                              EchoRequest(message=msg),
                                              EchoResponse)
                        assert not cntl.failed(), cntl.error_text
                        assert resp.message == msg
                except Exception as e:   # pragma: no cover
                    errs.append(e)

            threads = [threading.Thread(target=worker, args=(w,))
                       for w in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert not errs, errs
        finally:
            server.stop()
        assert native_plane.registry().live() == 0

    def test_server_stop_fails_inflight_cleanly(self, mesh):
        """Channel outliving its server gets EFAILEDSOCKET, and a fresh
        server on the same device id serves a fresh channel."""
        server = rpc.Server()
        server.add_service(EchoService())
        assert server.start("ici://10") == 0
        ch = rpc.Channel()
        ch.init("ici://10")
        cntl = rpc.Controller()
        ch.call_method("EchoService.Echo", cntl,
                       EchoRequest(message="a"), EchoResponse)
        assert not cntl.failed()
        server.stop()
        cntl = rpc.Controller()
        ch.call_method("EchoService.Echo", cntl,
                       EchoRequest(message="b"), EchoResponse)
        assert cntl.failed()
        # fresh server, fresh channel: the device id is reusable
        server2 = rpc.Server()
        server2.add_service(EchoService())
        assert server2.start("ici://10") == 0
        try:
            ch2 = rpc.Channel()
            ch2.init("ici://10")
            cntl = rpc.Controller()
            resp = ch2.call_method("EchoService.Echo", cntl,
                                   EchoRequest(message="c"), EchoResponse)
            assert not cntl.failed(), cntl.error_text
            assert resp.message == "c"
        finally:
            server2.stop()
        assert native_plane.registry().live() == 0

    def test_async_done_callback(self, mesh):
        """done= callbacks run off the caller thread and see the filled
        controller (the ParallelChannel composition contract)."""
        server = rpc.Server()
        server.add_service(EchoService())
        assert server.start("ici://11") == 0
        try:
            ch = rpc.Channel()
            ch.init("ici://11")
            ev = threading.Event()
            out = {}

            def done(cntl):
                out["failed"] = cntl.failed()
                out["resp"] = cntl.response
                ev.set()

            cntl = rpc.Controller()
            ch.call_method("EchoService.Echo", cntl,
                           EchoRequest(message="async"), EchoResponse,
                           done=done)
            assert ev.wait(10)
            assert out["failed"] is False
            assert out["resp"].message == "async"
        finally:
            server.stop()


class TestReviewFindings:
    """Regression pins for the r4 code-review findings."""

    def test_channel_survives_server_restart(self, mesh):
        """A long-lived Channel must keep working across a server restart
        (the cached native conn is invalidated and the call re-routes)."""
        server = rpc.Server()
        server.add_service(EchoService())
        assert server.start("ici://12") == 0
        ch = rpc.Channel()
        ch.init("ici://12")
        cntl = rpc.Controller()
        resp = ch.call_method("EchoService.Echo", cntl,
                              EchoRequest(message="one"), EchoResponse)
        assert not cntl.failed() and resp.message == "one"
        server.stop()
        server2 = rpc.Server()
        server2.add_service(EchoService())
        assert server2.start("ici://12") == 0
        try:
            cntl = rpc.Controller()
            resp = ch.call_method("EchoService.Echo", cntl,
                                  EchoRequest(message="two"), EchoResponse)
            assert not cntl.failed(), cntl.error_text
            assert resp.message == "two"
        finally:
            server2.stop()
        assert native_plane.registry().live() == 0

    def test_oversize_attachment_falls_back_to_python_plane(self, mesh):
        """An attachment bigger than the native send window rides the
        Python plane (which drains it chunkwise) instead of failing."""
        server = rpc.Server()
        server.add_service(EchoService())
        assert server.start("ici://13") == 0
        try:
            ch = rpc.Channel()
            ch.init("ici://13",
                    options=rpc.ChannelOptions(timeout_ms=60000,
                                               max_retry=0))
            big = b"z" * (6 * 1024 * 1024)      # > the 4MB native window
            cntl = rpc.Controller()
            cntl.request_attachment.append(big)
            resp = ch.call_method("EchoService.Echo", cntl,
                                  EchoRequest(message="big"), EchoResponse)
            assert not cntl.failed(), cntl.error_text
            assert resp.message == "big"
            assert cntl.response_attachment.to_bytes() == big
        finally:
            server.stop()

    def test_no_deadline_means_no_deadline(self, mesh):
        """timeout_ms=0 over the native plane waits, matching the Python
        plane's no-deadline semantics (not a silent 5s default)."""
        gate = threading.Event()

        class Slowish(rpc.Service):
            @rpc.method(EchoRequest, EchoResponse)
            def S(self, cntl, request, response, done):
                def later():
                    gate.wait(10)
                    response.message = "eventually"
                    done()
                threading.Thread(target=later, daemon=True).start()

        server = rpc.Server()
        server.add_service(Slowish())
        assert server.start("ici://14") == 0
        try:
            ch = rpc.Channel()
            ch.init("ici://14", options=rpc.ChannelOptions(timeout_ms=0))
            out = {}
            def call():
                cntl = rpc.Controller()
                cntl.timeout_ms = 0
                out["resp"] = ch.call_method(
                    "Slowish.S", cntl, EchoRequest(message="x"),
                    EchoResponse)
                out["failed"] = cntl.failed()
            t = threading.Thread(target=call, daemon=True)
            t.start()
            time.sleep(0.3)
            assert t.is_alive()          # still waiting, not timed out
            gate.set()
            t.join(10)
            assert not t.is_alive()
            assert out["failed"] is False
            assert out["resp"].message == "eventually"
        finally:
            gate.set()
            server.stop()

    def test_out_of_mesh_array_still_relocates(self, mesh):
        """An attachment on a device OUTSIDE the mesh gets dev=-1 and is
        relocated via the upcall (never silently passed through)."""
        import jax
        if len(jax.devices()) == mesh.size:
            # build a smaller mesh so an out-of-mesh device exists
            if mesh.size < 2:
                pytest.skip("needs >=2 devices")
            small = ici.IciMesh(jax.devices()[:1])
            old = mesh
            ici.IciMesh.set_default(small)
            try:
                seen = {}

                class Probe(rpc.Service):
                    @rpc.method(EchoRequest, EchoResponse)
                    def P(self, cntl, request, response, done):
                        refs = cntl.request_attachment.device_refs()
                        seen["devs"] = {str(d) for r in refs
                                        for d in r.block.data.devices()}
                        response.message = "ok"
                        done()

                server = rpc.Server()
                server.add_service(Probe())
                assert server.start("ici://0") == 0
                try:
                    import jax.numpy as jnp
                    outside = jax.device_put(
                        jnp.arange(64, dtype=jnp.uint8), jax.devices()[1])
                    jax.block_until_ready(outside)
                    ch = rpc.Channel()
                    ch.init("ici://0")
                    cntl = rpc.Controller()
                    cntl.request_attachment.append_device_array(outside)
                    ch.call_method("Probe.P", cntl,
                                   EchoRequest(message="x"), EchoResponse)
                    assert not cntl.failed(), cntl.error_text
                    # resident on the SERVER's mesh device, not the
                    # out-of-mesh source
                    assert seen["devs"] == {str(small.device(0))}
                finally:
                    server.stop()
            finally:
                ici.IciMesh.set_default(old)
        assert native_plane.registry().live() == 0


class TestAsyncPoolSafety:
    def test_async_calls_beyond_pool_size_complete(self, mesh):
        """More concurrent async (done=) calls than bthread workers, each
        parking in the native condvar while its Python-tier handler needs
        a tasklet: blocked-worker compensation must keep the pool live
        (review finding r4: without note_worker_blocked this deadlocks
        until timeout)."""
        class Nap(rpc.Service):
            @rpc.method(EchoRequest, EchoResponse)
            def N(self, cntl, request, response, done):
                time.sleep(0.05)
                response.message = request.message
                done()

        server = rpc.Server()
        server.add_service(Nap())
        assert server.start("ici://15") == 0
        try:
            ch = rpc.Channel()
            ch.init("ici://15",
                    options=rpc.ChannelOptions(timeout_ms=10000))
            n = 8                       # > bthread_concurrency default (4)
            evs = [threading.Event() for _ in range(n)]
            outs = [None] * n

            def make_done(i):
                def done(cntl):
                    outs[i] = (cntl.failed(), cntl.response)
                    evs[i].set()
                return done

            t0 = time.monotonic()
            for i in range(n):
                cntl = rpc.Controller()
                ch.call_method("Nap.N", cntl,
                               EchoRequest(message=f"m{i}"), EchoResponse,
                               done=make_done(i))
            for i, ev in enumerate(evs):
                assert ev.wait(8), f"call {i} never completed (deadlock?)"
            assert time.monotonic() - t0 < 8
            for i, (failed, resp) in enumerate(outs):
                assert failed is False
                assert resp.message == f"m{i}"
        finally:
            server.stop()
        assert native_plane.registry().live() == 0


class TestNativeLoopBench:
    def test_cpp_loop_echo_runs(self, mesh):
        p50 = native_plane.native_ici_echo_p50_us(200, 64)
        assert p50 > 0
        arr = _device_payload(mesh, n=1024)
        p50d = native_plane.native_ici_echo_p50_us(200, 64,
                                                   device_array=arr)
        assert p50d > 0
        assert native_plane.registry().live() == 0


class TestFaultInjectionOnFastPlane:
    def test_injected_fault_reaches_native_ici_calls(self, mesh):
        """Fault injection covers the native plane (the Python plane
        injects at Socket.write; the binding is the equivalent edge)."""
        from brpc_tpu.rpc import fault_injection as fi
        server = rpc.Server()
        server.add_service(EchoService())
        assert server.start("ici://16") == 0
        try:
            ch = rpc.Channel()
            ch.init("ici://16",
                    options=rpc.ChannelOptions(timeout_ms=2000,
                                               max_retry=0))
            with fi.inject(fi.FaultInjector(error_ratio=1.0)):
                cntl = rpc.Controller()
                ch.call_method("EchoService.Echo", cntl,
                               EchoRequest(message="x"), EchoResponse)
                assert cntl.failed()
                assert cntl.error_code_ == rpc.errors.EFAILEDSOCKET
            # injector uninstalled: the plane works again
            cntl = rpc.Controller()
            resp = ch.call_method("EchoService.Echo", cntl,
                                  EchoRequest(message="y"), EchoResponse)
            assert not cntl.failed(), cntl.error_text
            assert resp.message == "y"
        finally:
            server.stop()
        assert native_plane.registry().live() == 0


class TestRelocateCustody:
    def test_relocate_detaches_ctypes_backed_views(self, mesh):
        """ADVICE r5: _relocate used to jax.device_put ctypes-backed
        numpy views (host-delivered fabric bulk payloads forwarded into
        an in-process native-plane call) directly — device_put zero-copy
        ALIASES such buffers without retaining them, so recycling the
        native receive buffer corrupted the relocated payload.  The fix
        detaches into an owned copy first (transport.py discipline)."""
        import ctypes

        import jax

        n = 4096
        # 64-byte-aligned backing memory, like the native plane's malloc'd
        # receive buffers: XLA only zero-copy-aliases sufficiently aligned
        # hosts, so an unaligned buffer would mask the bug
        raw = (ctypes.c_uint8 * (n + 64))()
        addr = ctypes.addressof(raw)
        buf = (ctypes.c_uint8 * n).from_address(addr + (-addr) % 64)
        np.ctypeslib.as_array(buf)[:] = np.arange(n, dtype=np.uint8) % 251
        view = np.frombuffer(buf, dtype=np.uint8)   # what _bulk_claim_array
        expect = view.copy()                        # hands to host delivery
        reg = native_plane.registry()
        key = reg.put(view)
        new_key = 0
        try:
            new_key = native_plane._relocate(key, 0)
            assert new_key != 0, "relocate failed"
            assert new_key != key, "numpy view cannot be 'resident'"
            moved = reg.peek(new_key)
            jax.block_until_ready(moved)
            # the native pool recycles the receive buffer under the view
            ctypes.memset(buf, 0, n)
            np.testing.assert_array_equal(np.asarray(moved), expect)
        finally:
            reg.release(key)
            if new_key and new_key != key:
                reg.release(new_key)


class TestNativeAttCustody:
    """ISSUE 12: native-side attachment custody.  Every path a parked
    handle can take — pass-through, materialize, pool-recycle dispose,
    reject, per-request batch failure, late response after timeout —
    must end with the exactly-one-exit invariant: the device-ref
    registry AND the native att table drain to zero (also enforced
    fleet-wide by the conftest census)."""

    def _echo_server(self, dev, body):
        class Svc(rpc.Service):
            SERVICE_NAME = "EchoService"

            @rpc.method(EchoRequest, EchoResponse)
            def Echo(self, cntl, request, response, done):
                body(cntl, request, response)
                done()

        server = rpc.Server()
        server.add_service(Svc())
        assert server.start(f"ici://{dev}") == 0
        ch = rpc.Channel()
        ch.init(f"ici://{dev}",
                options=rpc.ChannelOptions(timeout_ms=10000, max_retry=0,
                                           ici_local_device=dev))
        return server, ch

    @staticmethod
    def _drained():
        deadline = time.monotonic() + 3
        import gc
        while time.monotonic() < deadline:
            if (native_plane.registry().live() == 0
                    and native_plane.att_table_live() == 0):
                return True
            gc.collect()
            time.sleep(0.02)
        return False

    def test_passthrough_view_is_lazy_and_byte_exact(self, mesh):
        """The echo shape: the handler sees a lazily-materialized
        NativeAttachment (len answers WITHOUT inflating), assigns it as
        the response, and the handle rides back natively — the client's
        view materializes to the exact bytes."""
        seen = {}

        def body(cntl, request, response):
            att = cntl.request_attachment
            seen["type"] = type(att).__name__
            seen["len"] = len(att)
            seen["mat_before_len"] = att._mat
            response.message = request.message
            cntl.response_attachment = att

        server, ch = self._echo_server(20, body)
        try:
            payload = _device_payload(mesh, dev=20)
            cntl = rpc.Controller()
            cntl.request_attachment.append_device_array(payload)
            resp = ch.call_method("EchoService.Echo", cntl,
                                  EchoRequest(message="pt"), EchoResponse)
            assert not cntl.failed(), cntl.error_text
            assert resp.message == "pt"
            assert seen["type"] == "NativeAttachment"
            assert seen["len"] == 4096
            assert seen["mat_before_len"] is False, \
                "len() must not materialize the view"
            out = cntl.response_attachment
            assert type(out).__name__ == "NativeAttachment"
            assert len(out) == 4096 and not out._mat
            assert out.to_bytes() == bytes(np.arange(4096, dtype=np.uint8))
            assert out._mat                     # touch materialized it
            del cntl, out
        finally:
            server.stop()
        assert self._drained()

    def test_append_pattern_materializes_and_stays_correct(self, mesh):
        """The PR-8 idiom (response_attachment.append(request_attachment))
        keeps working: appending an unmaterialized view into another
        IOBuf inflates it (keys taken, entry dropped) and the bytes are
        exact — slower than the pass-through, never wrong."""
        def body(cntl, request, response):
            response.message = request.message
            if len(cntl.request_attachment):
                cntl.response_attachment.append(cntl.request_attachment)

        server, ch = self._echo_server(21, body)
        try:
            payload = _device_payload(mesh, dev=21)
            for _ in range(3):
                cntl = rpc.Controller()
                cntl.request_attachment.append_device_array(payload)
                ch.call_method("EchoService.Echo", cntl,
                               EchoRequest(message="ap"), EchoResponse)
                assert not cntl.failed(), cntl.error_text
                assert cntl.response_attachment.to_bytes() == bytes(
                    np.arange(4096, dtype=np.uint8))
            del cntl
        finally:
            server.stop()
        assert self._drained()

    def test_ignored_attachment_disposed_at_pool_recycle(self, mesh):
        """A handler that never touches its attachment: the parked
        handle's ONLY exit is Controller pool-recycle — the registry
        and att table must still drain."""
        def body(cntl, request, response):
            response.message = "ok"        # attachment deliberately unread

        server, ch = self._echo_server(22, body)
        try:
            payload = _device_payload(mesh, dev=22)
            cntl = rpc.Controller()
            cntl.request_attachment.append_device_array(payload)
            ch.call_method("EchoService.Echo", cntl,
                           EchoRequest(message="x"), EchoResponse)
            assert not cntl.failed(), cntl.error_text
            del cntl
        finally:
            server.stop()
        assert self._drained()

    def test_reject_path_disposes_view(self, mesh):
        """ENOMETHOD with a device attachment: the reject runs before
        any handler — _release_attachment_custody must dispose the
        parked handle."""
        server = rpc.Server()
        server.add_service(EchoService())
        assert server.start("ici://23") == 0
        try:
            ch = rpc.Channel()
            ch.init("ici://23",
                    options=rpc.ChannelOptions(timeout_ms=5000,
                                               max_retry=0,
                                               ici_local_device=23))
            payload = _device_payload(mesh, dev=23)
            cntl = rpc.Controller()
            cntl.request_attachment.append_device_array(payload)
            ch.call_method("NoSuch.Method", cntl,
                           EchoRequest(message="x"), EchoResponse)
            assert cntl.failed()
            assert cntl.error_code_ == rpc.errors.ENOMETHOD
            del cntl
        finally:
            server.stop()
        assert self._drained()

    def test_per_request_failure_isolation_disposes_handle(self, mesh):
        """A handler raising mid-request: the EINTERNAL answer must not
        strand the parked handle (the batch loop's isolation path or
        the invoke error path dispose it)."""
        def body(cntl, request, response):
            if request.message == "boom":
                raise RuntimeError("deliberate")
            response.message = request.message

        server, ch = self._echo_server(24, body)
        try:
            payload = _device_payload(mesh, dev=24)
            cntl = rpc.Controller()
            cntl.request_attachment.append_device_array(payload)
            ch.call_method("EchoService.Echo", cntl,
                           EchoRequest(message="boom"), EchoResponse)
            assert cntl.failed()
            assert cntl.error_code_ == rpc.errors.EINTERNAL
            # a healthy request right after: the route stays up
            cntl2 = rpc.Controller()
            cntl2.request_attachment.append_device_array(payload)
            resp = ch.call_method("EchoService.Echo", cntl2,
                                  EchoRequest(message="fine"),
                                  EchoResponse)
            assert not cntl2.failed() and resp.message == "fine"
            del cntl, cntl2
        finally:
            server.stop()
        assert self._drained()

    def test_late_passthrough_after_timeout_releases(self, mesh):
        """Chaos kill mid-batch shape: the client times out, the handler
        passes the handle back LATE — native delivers to an abandoned
        slot and must release the parked keys (no strand)."""
        release = threading.Event()
        responded = threading.Event()

        class Slow(rpc.Service):
            @rpc.method(EchoRequest, EchoResponse)
            def S(self, cntl, request, response, done):
                def later():
                    release.wait(5)
                    cntl.response_attachment = cntl.request_attachment
                    response.message = "late"
                    done()
                    responded.set()
                threading.Thread(target=later, daemon=True).start()

        server = rpc.Server()
        server.add_service(Slow())
        assert server.start("ici://25") == 0
        try:
            ch = rpc.Channel()
            ch.init("ici://25",
                    options=rpc.ChannelOptions(timeout_ms=150,
                                               max_retry=0,
                                               ici_local_device=25))
            payload = _device_payload(mesh, dev=25)
            cntl = rpc.Controller()
            cntl.request_attachment.append_device_array(payload)
            ch.call_method("Slow.S", cntl, EchoRequest(message="x"),
                           EchoResponse)
            assert cntl.failed()
            assert cntl.error_code_ == rpc.errors.ERPCTIMEDOUT
            release.set()
            assert responded.wait(5)
            del cntl
        finally:
            release.set()
            server.stop()
        assert self._drained()

    def test_client_view_del_is_the_release(self, mesh):
        """A client that never reads its response attachment: dropping
        the view (refcount/GC) must dispose the handle — the steady
        bench shape, where cleanup rides __del__ between calls."""
        def body(cntl, request, response):
            response.message = "ok"
            cntl.response_attachment = cntl.request_attachment

        server, ch = self._echo_server(26, body)
        try:
            payload = _device_payload(mesh, dev=26)
            for _ in range(4):
                cntl = rpc.Controller()
                cntl.request_attachment.append_device_array(payload)
                ch.call_method("EchoService.Echo", cntl,
                               EchoRequest(message="x"), EchoResponse)
                assert not cntl.failed(), cntl.error_text
                # response view intentionally untouched; the rebind of
                # `cntl` next iteration drops it
            del cntl
        finally:
            server.stop()
        assert self._drained()

    def test_proxy_forwarding_view_as_request(self, mesh):
        """Proxy shape: handler A forwards its (unmaterialized) view as
        the REQUEST attachment of a nested call to server B —
        materialization + re-registration keep bytes and custody
        exact end to end."""
        inner_server = rpc.Server()
        inner_server.add_service(EchoService())
        assert inner_server.start("ici://28") == 0
        inner_ch = rpc.Channel()
        inner_ch.init("ici://28",
                      options=rpc.ChannelOptions(timeout_ms=10000,
                                                 max_retry=0,
                                                 ici_local_device=28))

        def body(cntl, request, response):
            inner = rpc.Controller()
            inner.request_attachment.append(cntl.request_attachment)
            r = inner_ch.call_method("EchoService.Echo", inner,
                                     EchoRequest(message="inner"),
                                     EchoResponse)
            assert not inner.failed(), inner.error_text
            response.message = r.message
            cntl.response_attachment = inner.response_attachment

        server, ch = self._echo_server(29, body)
        try:
            payload = _device_payload(mesh, dev=29)
            cntl = rpc.Controller()
            cntl.request_attachment.append_device_array(payload)
            resp = ch.call_method("EchoService.Echo", cntl,
                                  EchoRequest(message="outer"),
                                  EchoResponse)
            assert not cntl.failed(), cntl.error_text
            assert resp.message == "inner"
            assert cntl.response_attachment.to_bytes() == bytes(
                np.arange(4096, dtype=np.uint8))
            del cntl
        finally:
            server.stop()
            inner_server.stop()
        assert self._drained()


class TestBuildAttachmentExceptionSafety:
    """ISSUE 12 satellite: build_attachment_from_c used to strand every
    not-yet-walked device key when IOBuf construction raised mid-walk
    (native clears its seg list when the upcall returns — the remaining
    keys had no owner left).  Pinned with a fault-injected mid-walk
    failure at the unit level."""

    def test_midwalk_failure_releases_unwalked_keys(self, mesh,
                                                    monkeypatch):
        from brpc_tpu.butil.iobuf import IOBuf
        from brpc_tpu.ici.native_plane import (build_attachment_from_c,
                                               fill_seg_array)
        reg = native_plane.registry()
        base = reg.live()
        arrs = [_device_payload(mesh, dev=0, n=256) for _ in range(3)]
        segs = [(reg.put(a), 256, 0, 1) for a in arrs]
        seg_arr = fill_seg_array(segs)
        calls = {"n": 0}
        real = IOBuf.append_device_array_unchecked

        def flaky(self, arr, nbytes):
            calls["n"] += 1
            if calls["n"] == 2:
                raise MemoryError("injected mid-walk failure")
            return real(self, arr, nbytes)

        monkeypatch.setattr(IOBuf, "append_device_array_unchecked", flaky)
        with pytest.raises(MemoryError):
            build_attachment_from_c(b"", seg_arr, 3)
        # seg 0: taken into the dropped buf (custody exited into Python);
        # seg 1: taken then the append failed (the local ref released it);
        # seg 2: NEVER walked — the fix releases it before re-raising
        assert reg.live() == base, (
            f"{reg.live() - base} keys stranded after mid-walk failure")

    def test_clean_walk_unchanged(self, mesh):
        from brpc_tpu.ici.native_plane import (build_attachment_from_c,
                                               fill_seg_array)
        reg = native_plane.registry()
        arrs = [_device_payload(mesh, dev=0, n=128) for _ in range(2)]
        segs = [(reg.put(arrs[0]), 128, 0, 1), (0, 3, 0, 0),
                (reg.put(arrs[1]), 128, 0, 1)]
        buf = build_attachment_from_c(b"abc", fill_seg_array(segs), 3)
        assert len(buf) == 128 + 3 + 128
        assert buf.to_bytes() == bytes(np.arange(128, dtype=np.uint8)) \
            + b"abc" + bytes(np.arange(128, dtype=np.uint8))
        assert reg.live() == 0


class TestFusedDispatch:
    """ISSUE 13 tentpole: the fused per-RPC code objects (server
    _process_fused/_FusedDone, client call_fused) must be semantically
    byte-identical to the legacy PR-12 chain — same responses, same
    error codes, same gate ordering, same custody exits — while the
    frame count per RPC stays inside the pinned budget."""

    def _server_channel(self, dev, fused, service=None, opts=None):
        from brpc_tpu.butil import flags as fl
        prev = fl.get_flag("ici_fused_dispatch")
        fl.set_flag("ici_fused_dispatch", fused)
        try:
            server = rpc.Server(opts or rpc.ServerOptions(
                usercode_inline=True))
            server.add_service(service or EchoService())
            assert server.start(f"ici://{dev}") == 0
            ch = rpc.Channel()
            ch.init(f"ici://{dev}",
                    options=rpc.ChannelOptions(timeout_ms=10000,
                                               max_retry=0,
                                               ici_local_device=dev))
        finally:
            fl.set_flag("ici_fused_dispatch", prev)
        return server, ch

    def _echo(self, ch, mesh, msg="m", n=512):
        payload = _device_payload(mesh, dev=0, n=n)
        cntl = rpc.Controller()
        cntl.request_attachment.append_device_array(payload)
        resp = ch.call_method("EchoService.Echo", cntl,
                              EchoRequest(message=msg), EchoResponse)
        return cntl, resp

    def test_fused_vs_legacy_byte_parity(self, mesh):
        """The same echo (attachment + payload) through both dispatch
        generations produces identical bytes; the route counters prove
        which chain actually ran."""
        results = {}
        for fused in (True, False):
            server, ch = self._server_channel(3, fused)
            try:
                cntl, resp = self._echo(ch, mesh, msg="parity")
                assert not cntl.failed(), cntl.error_text
                results[fused] = (resp.message,
                                  cntl.response_attachment.to_bytes())
                binding = server._native_ici
                if fused:
                    assert binding.fused_dispatched >= 1
                    assert binding.legacy_dispatched == 0
                else:
                    assert binding.legacy_dispatched >= 1
                    assert binding.fused_dispatched == 0
            finally:
                server.stop()
        assert results[True] == results[False]

    def test_fused_error_paths_match_legacy(self, mesh):
        """ENOMETHOD, handler exception, and parse failure return the
        same codes through both chains."""
        class Boom(rpc.Service):
            SERVICE_NAME = "EchoService"

            @rpc.method(EchoRequest, EchoResponse)
            def Echo(self, cntl, request, response, done):
                raise ValueError("kaboom")

        for fused in (True, False):
            server, ch = self._server_channel(3, fused, service=Boom())
            try:
                cntl = rpc.Controller()
                ch.call_method("EchoService.Nope", cntl,
                               EchoRequest(message="x"), EchoResponse)
                assert cntl.error_code == rpc.errors.ENOMETHOD
                cntl = rpc.Controller()
                ch.call_method("EchoService.Echo", cntl,
                               EchoRequest(message="x"), EchoResponse)
                assert cntl.error_code == rpc.errors.EINTERNAL
                assert "kaboom" in cntl.error_text
                # parse failure: raw garbage bytes as the request
                cntl = rpc.Controller()
                ch.call_method("EchoService.Echo", cntl,
                               b"\xff\xff\xff\xff\xff", None)
                assert cntl.error_code == rpc.errors.EREQUEST, \
                    cntl.error_text
            finally:
                server.stop()

    def test_fused_async_handler_and_send_response(self, mesh):
        """A handler that parks done() for a later thread, and one that
        answers via cntl.send_response(), both complete under fusion."""
        import threading as _th

        class Async(rpc.Service):
            SERVICE_NAME = "EchoService"

            @rpc.method(EchoRequest, EchoResponse)
            def Echo(self, cntl, request, response, done):
                if request.message == "sendresp":
                    response.message = "via-send-response"
                    cntl.send_response()
                    return
                response.message = "later"
                _th.Timer(0.03, done).start()

        server, ch = self._server_channel(3, True, service=Async())
        try:
            cntl = rpc.Controller()
            resp = ch.call_method("EchoService.Echo", cntl,
                                  EchoRequest(message="park"),
                                  EchoResponse)
            assert not cntl.failed() and resp.message == "later"
            cntl = rpc.Controller()
            resp = ch.call_method("EchoService.Echo", cntl,
                                  EchoRequest(message="sendresp"),
                                  EchoResponse)
            assert not cntl.failed() \
                and resp.message == "via-send-response"
        finally:
            server.stop()

    def test_fused_admission_delegates_to_legacy_chain(self, mesh):
        """An admission-controlled server keeps the full shed/WFQ
        decision tree: the fused entry resolves the method but the
        request rides the legacy chain (counter proves it)."""
        opts = rpc.ServerOptions(usercode_inline=True, admission=True)
        server, ch = self._server_channel(3, True, opts=opts)
        try:
            cntl, resp = self._echo(ch, mesh, msg="adm")
            assert not cntl.failed(), cntl.error_text
            assert resp.message == "adm"
            binding = server._native_ici
            assert binding.fused_dispatched == 0
            assert binding.legacy_dispatched >= 1
        finally:
            server.stop()

    def test_fused_draining_bounces_elogoff(self, mesh):
        server, ch = self._server_channel(3, True)
        try:
            cntl, resp = self._echo(ch, mesh)
            assert not cntl.failed()
            server._draining = True
            cntl = rpc.Controller()
            ch.call_method("EchoService.Echo", cntl,
                           EchoRequest(message="x"), EchoResponse)
            assert cntl.error_code == rpc.errors.ELOGOFF
        finally:
            server._draining = False
            server.stop()

    def test_fused_context_masking_for_nested_dispatch(self, mesh):
        """A handler WITHOUT admission meta must not leak an outer
        inline context into its own outbound calls: the fused path
        masks exactly like _reqctx.scope."""
        from brpc_tpu.rpc import request_context as reqctx
        seen = {}

        class Svc(rpc.Service):
            SERVICE_NAME = "EchoService"

            @rpc.method(EchoRequest, EchoResponse)
            def Echo(self, cntl, request, response, done):
                seen["ctx"] = reqctx.current()
                seen["ddl"] = cntl.deadline_left_ms
                response.message = "ok"
                done()

        server, ch = self._server_channel(3, True, service=Svc())
        try:
            cntl = rpc.Controller()
            ch.call_method("EchoService.Echo", cntl,
                           EchoRequest(message="x"), EchoResponse)
            assert not cntl.failed()
            # the channel stamps deadline_left from timeout_ms, so the
            # handler sees a real inbound context with that budget (the
            # legacy scope() behavior)
            assert seen["ctx"] is not None
            assert seen["ctx"].deadline_left_ms == seen["ddl"] > 0
        finally:
            server.stop()

    def test_frame_budget(self, mesh):
        """ISSUE 13 satellite: interpreter frames per RPC on the
        native-ici echo path, measured with sys.setprofile around ONE
        call_method.  The budget pins this PR's measured number (+
        slack) so frame creep fails a named test instead of surfacing
        as a bench surprise.  PR-12's equivalent-methodology count was
        93 (the cProfile figure in ROADMAP, ~170, also counted C
        calls); this PR measured ~40 fused."""
        import sys as _sys
        server, ch = self._server_channel(3, True)
        try:
            # resident payload (the bench shape): a cross-device payload
            # would add jax's whole device_put stack to every call and
            # measure relocation, not dispatch
            payload = _device_payload(mesh, dev=3, n=512)
            req = EchoRequest(message="f")

            def one():
                cntl = rpc.Controller()
                cntl.request_attachment.append_device_array(payload)
                return cntl

            for _ in range(30):
                cntl = one()
                ch.call_method("EchoService.Echo", cntl, req,
                               EchoResponse)
                assert not cntl.failed(), cntl.error_text
            counts = []
            for _ in range(20):
                cntl = one()
                n = [0]

                def prof(frame, event, arg, _n=n):
                    if event == "call":
                        _n[0] += 1

                _sys.setprofile(prof)
                ch.call_method("EchoService.Echo", cntl, req,
                               EchoResponse)
                _sys.setprofile(None)
                assert not cntl.failed(), cntl.error_text
                counts.append(n[0])
            counts.sort()
            frames = counts[len(counts) // 2]
            BUDGET = 60          # measured ~40 + slack
            assert frames <= BUDGET, (
                f"frame creep: {frames} frames/RPC on the fused "
                f"native-ici echo path (budget {BUDGET}; PR-12 "
                f"same-methodology baseline was 93)")
        finally:
            server.stop()


class TestAppendPassThrough:
    """ISSUE 13 satellite: the PR-8 append idiom on a WHOLE, untouched
    NativeAttachment view adopts the parked handle (ResponseAttachment)
    instead of materializing — byte-exact, with exactly-one-exit
    holding (census-enforced per test, asserted explicitly here)."""

    @staticmethod
    def _drained():
        deadline = time.monotonic() + 3
        import gc
        while time.monotonic() < deadline:
            if (native_plane.registry().live() == 0
                    and native_plane.att_table_live() == 0):
                return True
            gc.collect()
            time.sleep(0.02)
        return False

    def _run(self, mesh, body, n=1024):
        class Svc(rpc.Service):
            SERVICE_NAME = "EchoService"

            @rpc.method(EchoRequest, EchoResponse)
            def Echo(self, cntl, request, response, done):
                body(cntl, response)
                done()

        server = rpc.Server(rpc.ServerOptions(usercode_inline=True))
        server.add_service(Svc())
        assert server.start("ici://3") == 0
        try:
            ch = rpc.Channel()
            ch.init("ici://3",
                    options=rpc.ChannelOptions(timeout_ms=10000,
                                               max_retry=0,
                                               ici_local_device=3))
            payload = _device_payload(mesh, dev=3, n=n)
            cntl = rpc.Controller()
            cntl.request_attachment.append_device_array(payload)
            resp = ch.call_method("EchoService.Echo", cntl,
                                  EchoRequest(message="a"), EchoResponse)
            assert not cntl.failed(), cntl.error_text
            out = cntl.response_attachment.to_bytes()
        finally:
            server.stop()
        return out

    def test_append_whole_view_adopts_handle(self, mesh):
        """The idiom's destination ADOPTS the parked handle (no
        materialization: the response attachment stays lazy inside the
        handler) and the bytes come back exact."""
        adopted = {}

        def body(cntl, response):
            response.message = "x"
            cntl.response_attachment.append(cntl.request_attachment)
            ra = cntl._peek_response_attachment()
            adopted["lazy"] = isinstance(ra, native_plane.NativeAttachment) \
                and not ra._mat and ra._h != 0
            adopted["donor_surrendered"] = \
                cntl.request_attachment._h == 0

        out = self._run(mesh, body)
        assert out == bytes(np.arange(1024, dtype=np.uint8))
        assert adopted["lazy"], "append materialized instead of adopting"
        assert adopted["donor_surrendered"]
        assert self._drained()

    def test_append_then_more_bytes_materializes(self, mesh):
        """Touching the adopted buffer again inflates it — correctness
        beats the fast path."""
        def body(cntl, response):
            response.message = "x"
            cntl.response_attachment.append(cntl.request_attachment)
            cntl.response_attachment.append(b"tail")

        out = self._run(mesh, body, n=256)
        assert out == bytes(np.arange(256, dtype=np.uint8)) + b"tail"
        assert self._drained()

    def test_append_into_nonempty_keeps_legacy_path(self, mesh):
        """A non-empty destination cannot adopt: the view materializes
        (the pre-fix behavior) and the bytes stay exact."""
        def body(cntl, response):
            response.message = "x"
            cntl.response_attachment.append(b"head")
            cntl.response_attachment.append(cntl.request_attachment)

        out = self._run(mesh, body, n=256)
        assert out == b"head" + bytes(np.arange(256, dtype=np.uint8))
        assert self._drained()
