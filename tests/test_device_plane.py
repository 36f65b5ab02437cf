"""Device data plane: payloads cross the mesh through compiled XLA
programs (ici/device_plane.py — the rdma_endpoint.cpp:771 analogue).

Covers the QP lifecycle (post_send → descriptor → post_recv rendezvous →
completion), program-cache reuse, both kernels (shard_map+ppermute and
the Pallas remote-DMA variant in interpret mode), the match-timeout
reaper, chaos-forced degradation + recovery, and the full RPC stack
crossing the 8-device virtual CPU mesh through the plane with no host
staging in the datapath (asserted on the transfer/byte counters).
"""
import sys
import time

import numpy as np
import pytest

import brpc_tpu.policy  # noqa: F401  (registers protocols)
from brpc_tpu import rpc
from brpc_tpu.butil import flags as fl
from brpc_tpu.butil.iobuf import IOBuf
from brpc_tpu.ici import device_plane as dp
from brpc_tpu.ici.mesh import IciMesh
from brpc_tpu.rpc import fault_injection as fi

sys.path.insert(0, "tests")
from echo_pb2 import EchoRequest, EchoResponse  # noqa: E402


@pytest.fixture()
def plane_on():
    """Engage the plane on this host-memory mesh with a low threshold,
    restoring every flag after."""
    saved = {n: fl.get_flag(n) for n in
             ("ici_device_plane", "ici_device_plane_host_mesh",
              "ici_device_plane_threshold",
              "ici_device_plane_match_timeout_s")}
    fl.set_flag("ici_device_plane", True)
    fl.set_flag("ici_device_plane_host_mesh", True)
    fl.set_flag("ici_device_plane_threshold", 1024)
    yield dp.plane()
    for n, v in saved.items():
        fl.set_flag(n, v)


def _payload(nbytes, dev, mod=251):
    import jax
    import jax.numpy as jnp
    arr = jax.device_put(jnp.arange(nbytes, dtype=jnp.uint8) % mod,
                         IciMesh.default().device(dev))
    jax.block_until_ready(arr)
    return arr


class TestQPLifecycle:
    def test_post_recv_rendezvous_moves_payload(self, plane_on):
        plane = plane_on
        arr = _payload(8192, 2)
        t = plane.post_send(arr, 2, 5)
        assert t.state == dp.POSTED
        assert plane.pending_sends() >= 1
        got = plane.post_recv(t.uuid)
        assert got is t                      # both sides share the WR
        assert t.wait(30) == 0
        assert t.state == dp.COMPLETE
        np.testing.assert_array_equal(np.asarray(t.out), np.asarray(arr))
        # delivered RESIDENT on the destination chip
        assert dp.mesh_index_of(t.out) == 5
        # the lifecycle timeline was recorded (rpcz annotation source)
        d = t.describe()
        assert d["posted_to_matched_us"] >= 0
        assert d["matched_to_complete_us"] >= 0

    def test_source_pin_releases_exactly_once_at_completion(self, plane_on):
        plane = plane_on
        arr = _payload(4096, 1)
        released = []
        t = plane.post_send(arr, 1, 3)
        t.add_source_release(lambda: released.append(1))
        assert released == []               # pinned while POSTED
        plane.post_recv(t.uuid)
        assert t.wait(30) == 0
        assert released == [1]
        # registering after completion fires immediately, still once each
        t.add_source_release(lambda: released.append(2))
        assert released == [1, 2]

    def test_counters_track_bytes_and_transfers(self, plane_on):
        plane = plane_on
        before = plane.stats()
        arr = _payload(2048, 0)
        t = plane.post_send(arr, 0, 4)
        plane.post_recv(t.uuid)
        assert t.wait(30) == 0
        after = plane.stats()
        assert after["transfers"] == before["transfers"] + 1
        assert after["bytes_sent"] == before["bytes_sent"] + 2048
        assert after["bytes_recv"] == before["bytes_recv"] + 2048

    def test_same_device_post_is_refused(self, plane_on):
        arr = _payload(2048, 3)
        with pytest.raises(dp.DevicePlaneError):
            plane_on.post_send(arr, 3, 3)


class TestProgramCache:
    def test_repeated_shapes_reuse_the_compiled_program(self, plane_on):
        plane = plane_on
        misses0 = plane.stats()["program_cache_misses"]
        for _ in range(4):
            arr = _payload(3072, 1)
            t = plane.post_send(arr, 1, 2)
            plane.post_recv(t.uuid)
            assert t.wait(30) == 0
        # one compile for four transfers of the same (shape, route)
        assert plane.stats()["program_cache_misses"] == misses0 + 1
        # a new size on the same route compiles exactly one more
        arr = _payload(5120, 1)
        t = plane.post_send(arr, 1, 2)
        plane.post_recv(t.uuid)
        assert t.wait(30) == 0
        assert plane.stats()["program_cache_misses"] == misses0 + 2


class TestPieceOfABlock:
    """The transfer program cuts the piece on the chip: the post carries
    the whole block and a start (no arr[a:b] and no (1, n) row on the
    host), and the destination's output shard is the flat piece."""

    BLOCK, PIECE = 64 * 1024, 8 * 1024

    def _cross(self, plane, block, start, nbytes, src=1, dst=6):
        t = plane.post_send(block, src, dst, start=start, nbytes=nbytes)
        assert plane.post_recv(t.uuid) is t
        assert t.wait(30) == 0, t.error
        return t

    @pytest.mark.parametrize("start", [0, 25, 64 * 1024 - 8 * 1024],
                             ids=["head", "odd", "tail"])
    def test_piece_arrives_byte_exact_and_flat(self, plane_on, start):
        block = _payload(self.BLOCK, 1)
        t = self._cross(plane_on, block, start, self.PIECE)
        assert (t.start, t.nbytes, t.block_bytes) == (start, self.PIECE,
                                                      self.BLOCK)
        assert t.out.shape == (self.PIECE,) and t.out.dtype == np.uint8
        assert dp.mesh_index_of(t.out) == 6
        np.testing.assert_array_equal(
            np.asarray(t.out), np.asarray(block)[start:start + self.PIECE])

    def test_every_start_of_one_block_and_piece_is_one_program(
            self, plane_on):
        block = _payload(self.BLOCK + 1024, 1)      # sizes no test shares
        self._cross(plane_on, block, 0, self.PIECE)
        misses = plane_on.stats()["program_cache_misses"]
        for start in (25, 4096, self.BLOCK + 1024 - self.PIECE):
            t = self._cross(plane_on, block, start, self.PIECE)
            np.testing.assert_array_equal(
                np.asarray(t.out),
                np.asarray(block)[start:start + self.PIECE])
        assert plane_on.stats()["program_cache_misses"] == misses

    def test_sliced_in_program_counts_pieces_not_whole_arrays(
            self, plane_on):
        block = _payload(self.BLOCK, 1)
        before = plane_on.stats()
        t = self._cross(plane_on, block, 0, self.BLOCK)     # the whole array
        np.testing.assert_array_equal(np.asarray(t.out), np.asarray(block))
        mid = plane_on.stats()
        assert mid["transfers"] == before["transfers"] + 1
        assert mid["sliced_in_program"] == before["sliced_in_program"]
        self._cross(plane_on, block, 0, self.PIECE)         # a head is a cut
        self._cross(plane_on, block, 25, self.PIECE)
        after = plane_on.stats()
        assert after["transfers"] == before["transfers"] + 3
        assert after["sliced_in_program"] == before["sliced_in_program"] + 2
        assert after["bytes_sent"] == (before["bytes_sent"] + self.BLOCK
                                       + 2 * self.PIECE)

    def test_source_block_is_released_once_per_transfer(self, plane_on):
        block = _payload(self.BLOCK, 1)
        released = []
        posted = [plane_on.post_send(block, 1, 6, start=k * self.PIECE,
                                     nbytes=self.PIECE) for k in range(3)]
        for k, t in enumerate(posted):
            t.add_source_release(lambda k=k: released.append(k))
        assert released == [] and plane_on.active_transfers() >= 3
        for t in posted:
            assert t.source_array() is block    # the block, not a slice
            plane_on.post_recv(t.uuid)
            assert t.wait(30) == 0
        assert sorted(released) == [0, 1, 2]
        assert all(t.source_array() is None for t in posted)

    def test_device_put_fallback_delivers_the_piece_not_the_block(
            self, plane_on, monkeypatch):
        block = _payload(self.BLOCK, 1)
        f0 = plane_on.stats()["fallbacks"]
        s0 = plane_on.stats()["sliced_in_program"]

        def broken(t, local_only=False):
            raise RuntimeError("program execution failed")
        monkeypatch.setattr(plane_on, "_run", broken)
        t = self._cross(plane_on, block, 25, self.PIECE)
        assert t.out.shape == (self.PIECE,)
        assert dp.mesh_index_of(t.out) == 6
        np.testing.assert_array_equal(
            np.asarray(t.out), np.asarray(block)[25:25 + self.PIECE])
        assert plane_on.stats()["fallbacks"] == f0 + 1
        assert plane_on.stats()["sliced_in_program"] == s0

    @pytest.mark.parametrize("start,nbytes", [(-1, 1024), (1, 64 * 1024),
                                              (64 * 1024, 1024)])
    def test_piece_outside_its_block_is_refused(self, plane_on, start,
                                                nbytes):
        block = _payload(self.BLOCK, 1)
        active = plane_on.active_transfers()
        with pytest.raises(ValueError, match="not inside"):
            plane_on.post_send(block, 1, 6, start=start, nbytes=nbytes)
        assert plane_on.active_transfers() == active

    def test_cross_process_post_is_a_whole_array(self, plane_on):
        """The kind-4 descriptor names the piece alone, so the peer process
        enters the whole-array program of that size: a sub-range posted
        for it is cut on the host."""
        block = _payload(self.BLOCK, 1)
        t = plane_on.post_send(block, 1, 6, remote=True, start=25,
                               nbytes=self.PIECE)
        assert (t.start, t.nbytes, t.block_bytes) == (0, self.PIECE,
                                                      self.PIECE)
        np.testing.assert_array_equal(
            np.asarray(t.source_array()),
            np.asarray(block)[25:25 + self.PIECE])
        plane_on.fail_transfer(t, "test teardown")


class TestFailureModes:
    def test_match_timeout_fails_only_that_transfer(self, plane_on):
        """A posted send whose recv never arrives (peer died between
        descriptor and rendezvous) reaps after the match timeout: THAT
        transfer fails and its pin releases; the plane keeps serving."""
        plane = plane_on
        fl.set_flag("ici_device_plane_match_timeout_s", 0.05)
        released = []
        orphan = plane.post_send(_payload(2048, 1), 1, 7)
        orphan.add_source_release(lambda: released.append(1))
        time.sleep(0.1)
        timeouts0 = plane.stats()["match_timeouts"]
        plane._sweep_stale()
        assert orphan.state == dp.FAILED
        assert "match timeout" in orphan.error
        assert orphan.wait(1) != 0
        assert released == [1]
        assert plane.stats()["match_timeouts"] == timeouts0 + 1
        with pytest.raises(KeyError):
            plane.post_recv(orphan.uuid)    # reaped: rendezvous refused
        # an unrelated transfer is untouched
        fl.set_flag("ici_device_plane_match_timeout_s", 30.0)
        t = plane.post_send(_payload(2048, 1), 1, 7)
        plane.post_recv(t.uuid)
        assert t.wait(30) == 0

    def test_chaos_forced_post_failure_degrades_then_recovers(
            self, plane_on):
        plane = plane_on
        f0 = plane.stats()["fallbacks"]
        arr = _payload(2048, 3)
        with fi.inject_fabric(
                fi.FabricFaultPlan(device_plane_fail_posts=2)) as plan:
            for _ in range(2):
                with pytest.raises(dp.DevicePlaneError):
                    plane.post_send(arr, 3, 4)
            # budget exhausted: the plane serves again even mid-plan
            t = plane.post_send(arr, 3, 4)
            plane.post_recv(t.uuid)
            assert t.wait(30) == 0
        assert plan.injected["device_plane"] == 2
        assert plane.stats()["fallbacks"] == f0 + 2

    def test_ineligible_payloads_never_touch_the_plane(self, plane_on):
        assert not dp.eligible(512)          # below threshold
        fl.set_flag("ici_device_plane", False)
        assert not dp.eligible(1 << 20)      # master switch off
        fl.set_flag("ici_device_plane", True)
        fl.set_flag("ici_device_plane_host_mesh", False)
        assert not dp.eligible(1 << 20)      # host mesh not opted in


class TestSocketIntegration:
    """A device-resident payload written to a Socket crosses the mesh
    through the compiled program — the acceptance criterion."""

    def _echo_server(self, addr):
        class EchoService(rpc.Service):
            @rpc.method(EchoRequest, EchoResponse)
            def Echo(self, cntl, request, response, done):
                response.message = request.message
                if len(cntl.request_attachment):
                    cntl.response_attachment.append(cntl.request_attachment)
                done()

        opts = rpc.ServerOptions()
        opts.usercode_inline = True
        server = rpc.Server(opts)
        server.add_service(EchoService())
        assert server.start(addr) == 0
        return server

    def test_rpc_attachment_crosses_via_compiled_program(self, plane_on):
        """Full RPC stack (native fast plane): a non-resident 64KB
        attachment relocates through the device plane both directions,
        asserted on the transfer/byte counters — no device_put staging."""
        plane = plane_on
        server = self._echo_server("ici://0")
        try:
            ch = rpc.Channel()
            ch.init("ici://0", options=rpc.ChannelOptions(
                timeout_ms=30000, max_retry=0))
            n = 64 * 1024
            payload = _payload(n, 1)
            before = plane.stats()
            cntl = rpc.Controller()
            cntl.request_attachment.append_device_array(payload)
            assert cntl.request_attachment.device_bytes() == n
            resp = ch.call_method("EchoService.Echo", cntl,
                                  EchoRequest(message="dp"), EchoResponse)
            assert not cntl.failed(), cntl.error_text
            assert resp.message == "dp"
            got = np.frombuffer(cntl.response_attachment.to_bytes(),
                                dtype=np.uint8)
            np.testing.assert_array_equal(got, np.asarray(payload))
            after = plane.stats()
            # request leg (1 -> 0) and response bounce (0 -> 1)
            assert after["transfers"] >= before["transfers"] + 2
            assert after["bytes_sent"] >= before["bytes_sent"] + 2 * n
        finally:
            server.stop()

    def test_small_payload_keeps_the_device_put_path(self, plane_on):
        """Below-threshold payloads keep the lower-fixed-cost path; the
        plane's counters must not move."""
        plane = plane_on
        server = self._echo_server("ici://1")
        try:
            ch = rpc.Channel()
            ch.init("ici://1", options=rpc.ChannelOptions(
                timeout_ms=30000, max_retry=0))
            before = plane.stats()["transfers"]
            payload = _payload(512, 2)       # < 1024 threshold
            cntl = rpc.Controller()
            cntl.request_attachment.append_device_array(payload)
            ch.call_method("EchoService.Echo", cntl,
                           EchoRequest(message="s"), EchoResponse)
            assert not cntl.failed(), cntl.error_text
            assert plane.stats()["transfers"] == before
        finally:
            server.stop()

    def test_chaos_refusal_falls_back_to_device_put_rpc_succeeds(
            self, plane_on):
        """Chaos-forced plane death: the RPC still completes (device_put
        fallback in the same frame), counted as a fallback; with the
        plan gone the next RPC rides the plane again — degrade AND
        recover, no socket death."""
        plane = plane_on
        server = self._echo_server("ici://2")
        try:
            ch = rpc.Channel()
            ch.init("ici://2", options=rpc.ChannelOptions(
                timeout_ms=30000, max_retry=0))
            payload = _payload(8192, 3)
            f0 = plane.stats()["fallbacks"]
            t0 = plane.stats()["transfers"]
            with fi.inject_fabric(
                    fi.FabricFaultPlan(device_plane_fail_posts=64)):
                cntl = rpc.Controller()
                cntl.request_attachment.append_device_array(payload)
                ch.call_method("EchoService.Echo", cntl,
                               EchoRequest(message="c"), EchoResponse)
                assert not cntl.failed(), cntl.error_text
                got = np.frombuffer(cntl.response_attachment.to_bytes(),
                                    dtype=np.uint8)
                np.testing.assert_array_equal(got, np.asarray(payload))
            assert plane.stats()["fallbacks"] > f0
            assert plane.stats()["transfers"] == t0      # plane bypassed
            # plan uninstalled: the same route uses the plane again
            cntl = rpc.Controller()
            cntl.request_attachment.append_device_array(payload)
            ch.call_method("EchoService.Echo", cntl,
                           EchoRequest(message="r"), EchoResponse)
            assert not cntl.failed(), cntl.error_text
            assert plane.stats()["transfers"] > t0
        finally:
            server.stop()

    @pytest.mark.parametrize("nbytes", [64 * 1024, 9 * 1024 * 1024],
                             ids=["native-tier", "python-plane"])
    def test_response_lands_on_the_callers_device(self, plane_on, nbytes):
        """ChannelOptions.ici_local_device holds on BOTH ici planes: a
        call under the native send window (native tier) and one above it
        (Python plane) both relocate the response toward the caller's
        chip — the Python plane used to default to the server's
        neighbour, whatever the option said."""
        mesh = IciMesh.default()
        server = self._echo_server("ici://1")
        try:
            ch = rpc.Channel()
            ch.init("ici://1", options=rpc.ChannelOptions(
                timeout_ms=60000, max_retry=0, ici_local_device=0))
            payload = _payload(nbytes, 0)
            cntl = rpc.Controller()
            cntl.request_attachment.append_device_array(payload)
            ch.call_method("EchoService.Echo", cntl,
                           EchoRequest(message="home"), EchoResponse)
            assert not cntl.failed(), cntl.error_text
            refs = cntl.response_attachment.device_refs()
            assert refs
            for r in refs:
                assert set(r.block.data.devices()) == {mesh.device(0)}
            got = np.frombuffer(cntl.response_attachment.to_bytes(),
                                dtype=np.uint8)
            np.testing.assert_array_equal(got, np.asarray(payload))
            ch.close()
        finally:
            server.stop()

    def test_python_ici_socket_routes_through_plane(self, plane_on):
        """The Python-plane IciSocket (streaming / non-tpu_std wire):
        a DEVICE block in a written IOBuf crosses via the plane and is
        delivered as a resident DEVICE block, in order."""
        from brpc_tpu.ici.transport import ici_connect, ici_listen, \
            ici_unlisten
        plane = plane_on
        mesh = IciMesh.default()
        accepted = []
        ici_listen(7, accepted.append, mesh)
        try:
            client = ici_connect(mesh.endpoint(7), local_dev=4)
            serv = accepted[0]
            n = 16 * 1024
            payload = _payload(n, 4)
            before = plane.stats()["transfers"]
            buf = IOBuf(b"hdr:")
            buf.append_device_array(payload)
            assert client.write(buf) == 0
            deadline = time.monotonic() + 10
            while len(serv._inbox) < 4 + n and time.monotonic() < deadline:
                time.sleep(0.01)
            assert len(serv._inbox) == 4 + n
            assert plane.stats()["transfers"] == before + 1
            # the delivered device ref is resident on the server's chip
            dev_refs = serv._inbox.device_refs()
            assert len(dev_refs) == 1
            assert dp.mesh_index_of(dev_refs[0].block.data) == 7
            got = serv._inbox.to_bytes()
            assert got[:4] == b"hdr:"
            np.testing.assert_array_equal(
                np.frombuffer(got[4:], dtype=np.uint8), np.asarray(payload))
        finally:
            from brpc_tpu.rpc import errors
            for s in accepted + ([client] if "client" in locals() else []):
                s.set_failed(errors.ECLOSE, "test teardown")
            ici_unlisten(7)


class TestBuiltinPage:
    def test_ici_page_reports_plane_stats(self, plane_on):
        server = rpc.Server()
        from brpc_tpu.rpc.builtin.services import BuiltinDispatcher
        disp = BuiltinDispatcher(server)
        ctype, body = disp.dispatch("ici")
        assert ctype == "application/json"
        import json
        page = json.loads(body)
        assert "device_plane" in page
        assert "transfers" in page["device_plane"]
        assert "transport" in page
