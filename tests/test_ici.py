"""ici:// transport + collectives tests on the 8-device virtual CPU mesh."""
import threading
import time

import numpy as np
import pytest

import brpc_tpu.policy  # registers protocols
from brpc_tpu import rpc, ici
from tests.echo_pb2 import EchoRequest, EchoResponse


@pytest.fixture(scope="module")
def mesh():
    import jax
    m = ici.IciMesh(jax.devices())
    ici.IciMesh.set_default(m)
    return m


class DeviceEchoService(rpc.Service):
    SERVICE_NAME = "EchoService"

    @rpc.method(EchoRequest, EchoResponse)
    def Echo(self, cntl, request, response, done):
        response.message = request.message
        if len(cntl.request_attachment):
            cntl.response_attachment.append(cntl.request_attachment)
        done()


class TestIciTransport:
    def test_echo_over_ici(self, mesh):
        server = rpc.Server()
        server.add_service(DeviceEchoService())
        assert server.start("ici://0") == 0
        try:
            ch = rpc.Channel()
            ch.init("ici://0")
            cntl = rpc.Controller()
            resp = ch.call_method("EchoService.Echo", cntl,
                                  EchoRequest(message="chip-to-chip"),
                                  EchoResponse)
            assert not cntl.failed(), cntl.error_text
            assert resp.message == "chip-to-chip"
        finally:
            server.stop()

    def test_device_payload_stays_in_hbm(self, mesh):
        """Attachment carried as a DEVICE block must arrive as a DEVICE
        block resident on the server's chip."""
        import jax
        import jax.numpy as jnp
        seen = {}

        class AttachmentService(rpc.Service):
            @rpc.method(EchoRequest, EchoResponse)
            def Probe(self, cntl, request, response, done):
                refs = cntl.request_attachment.device_refs()
                seen["n_device_refs"] = len(refs)
                if refs:
                    seen["devices"] = {str(d) for d in refs[0].block.data.devices()}
                seen["bytes"] = cntl.request_attachment.to_bytes()
                response.message = "ok"
                done()

        server = rpc.Server()
        server.add_service(AttachmentService())
        assert server.start("ici://1") == 0
        try:
            payload = jnp.arange(4096, dtype=jnp.uint8)
            payload = jax.device_put(payload, mesh.device(2))
            ch = rpc.Channel()
            ch.init("ici://1")
            cntl = rpc.Controller()
            cntl.request_attachment.append_device_array(payload)
            ch.call_method("AttachmentService.Probe", cntl,
                           EchoRequest(message="m"), EchoResponse)
            assert not cntl.failed(), cntl.error_text
            assert seen["n_device_refs"] == 1
            assert seen["devices"] == {str(mesh.device(1))}   # relocated
            assert seen["bytes"] == bytes(np.arange(4096, dtype=np.uint8) & 0xFF)
        finally:
            server.stop()

    def test_transport_stats_count_device_bytes(self, mesh):
        before_total, before_dev = ici.ici_transport_stats()
        # covered by previous tests having moved traffic
        assert before_total > 0
        assert before_dev >= 4096


class TestIciWindow:
    """Transport-level sliding window (VERDICT #3; reference
    rdma_endpoint.cpp:771 window check, :926 completion-driven free)."""

    def _pair(self, mesh, window):
        from brpc_tpu.ici.transport import IciSocket
        a = IciSocket(0, 0, mesh, window_bytes=window)
        b = IciSocket(0, 0, mesh, window_bytes=window)
        a.peer, b.peer = b, a
        return a, b

    def test_slow_reader_bounds_memory_and_stalls_writer(self, mesh):
        from brpc_tpu.butil.iobuf import IOBuf, IOPortal
        win = 8 * 1024
        a, b = self._pair(mesh, win)
        chunk = 4 * 1024
        total = 10 * chunk
        done_codes = []
        for _ in range(total // chunk):
            rc = a.write(IOBuf(b"x" * chunk),
                         on_done=lambda ec: done_codes.append(ec))
            assert rc == 0
        # nobody reads: the peer inbox must stay bounded by the window
        deadline = time.monotonic() + 2
        while time.monotonic() < deadline and len(b._inbox) < win:
            time.sleep(0.01)
        assert len(b._inbox) <= win
        assert a.send_window_left() == 0
        stalled_unacked = a.unacked_send_bytes()
        assert stalled_unacked == win
        # reader drains: writer must resume and deliver everything
        portal = IOPortal()
        got = 0
        deadline = time.monotonic() + 10
        while got < total and time.monotonic() < deadline:
            n = b._do_read(portal, 1 << 20)
            if n <= 0:
                time.sleep(0.005)
                continue
            got += n
        assert got == total, f"delivered {got}/{total}"
        # all writes completed OK once the window reopened
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and len(done_codes) < total // chunk:
            time.sleep(0.01)
        assert done_codes == [0] * (total // chunk)
        a.set_failed()
        b.set_failed()

    def test_window_replenishes_exactly_consumed_bytes(self, mesh):
        from brpc_tpu.butil.iobuf import IOBuf, IOPortal
        win = 4096
        a, b = self._pair(mesh, win)
        assert a.write(IOBuf(b"y" * 3000)) == 0
        assert a.send_window_left() == win - 3000
        portal = IOPortal()
        n = b._do_read(portal, 1000)
        assert n == 1000
        assert a.send_window_left() == win - 2000
        assert b._do_read(portal, 1 << 20) == 2000
        assert a.send_window_left() == win
        a.set_failed()
        b.set_failed()

    def test_device_blocks_pinned_until_transfer_complete(self, mesh):
        """A cross-device write pins the SOURCE block until the moved
        array is ready (completion-driven reuse, rdma_endpoint.cpp:926)."""
        import jax
        import jax.numpy as jnp
        from brpc_tpu.butil.iobuf import IOBuf, IOPortal
        if mesh.size < 2:
            pytest.skip("needs 2 devices")
        from brpc_tpu.ici.transport import IciSocket
        a = IciSocket(0, 1, mesh, window_bytes=1 << 20)
        b = IciSocket(1, 0, mesh, window_bytes=1 << 20)
        a.peer, b.peer = b, a
        freed = []
        arr = jax.device_put(jnp.arange(1024, dtype=jnp.uint8),
                             mesh.device(0))
        jax.block_until_ready(arr)
        buf = IOBuf()
        buf.append_device_array(arr)
        ref_block = buf.backing_block(0).block
        ref_block.on_send_complete = lambda: freed.append(1)
        assert a.write(buf) == 0
        portal = IOPortal()
        deadline = time.monotonic() + 5
        got = 0
        while got < 1024 and time.monotonic() < deadline:
            n = b._do_read(portal, 1 << 20)
            got += max(0, n)
            if n <= 0:
                time.sleep(0.005)
        assert got == 1024
        deadline = time.monotonic() + 5
        while not freed and time.monotonic() < deadline:
            time.sleep(0.005)
        assert freed, "source block completion hook never fired"
        assert a.inflight_send_blocks() == 0
        a.set_failed()
        b.set_failed()


class TestOrderedDelivery:
    def test_host_frame_cannot_jump_pending_device_frame(self, monkeypatch):
        """Byte-stream ordering: a host-only frame arriving after a
        device-bearing frame whose transfer is still in flight must wait
        for it (the parsers rely on transport ordering)."""
        from brpc_tpu.ici import transport as T

        class Host(T.OrderedDelivery):
            def __init__(self):
                self._init_delivery()

        h = Host()
        order = []
        pending = []

        class FakeDisp:
            def on_ready(self, arrays, cb):
                pending.append(cb)

        monkeypatch.setattr(T, "_all_ready", lambda arrays: False)
        monkeypatch.setattr(T.DeviceEventDispatcher, "instance",
                            classmethod(lambda cls: FakeDisp()))
        h._enqueue_delivery([object()], lambda: order.append(1))
        h._enqueue_delivery([], lambda: order.append(2))
        assert order == []          # 2 must not jump ahead of pending 1
        pending[0]()                # device payload lands
        assert order == [1, 2]


class TestCollectives:
    def test_all_reduce(self, mesh):
        import jax.numpy as jnp
        coll = ici.Collectives(mesh)
        n = mesh.size
        x = coll.shard(jnp.arange(n * 4, dtype=jnp.float32).reshape(n, 4))
        out = coll.all_reduce(x)
        expect = np.arange(n * 4, dtype=np.float32).reshape(n, 4).sum(0)
        np.testing.assert_allclose(np.asarray(out), expect)

    def test_all_gather(self, mesh):
        import jax.numpy as jnp
        coll = ici.Collectives(mesh)
        n = mesh.size
        x = coll.shard(jnp.arange(n, dtype=jnp.float32).reshape(n, 1) * 10)
        out = coll.all_gather(x)
        np.testing.assert_allclose(
            np.asarray(out).ravel(), np.arange(n) * 10)

    def test_broadcast(self, mesh):
        import jax.numpy as jnp
        coll = ici.Collectives(mesh)
        n = mesh.size
        rows = jnp.stack([jnp.full((3,), i, jnp.float32) for i in range(n)])
        out = coll.broadcast(coll.shard(rows), root=2)
        np.testing.assert_allclose(np.asarray(out), np.full((3,), 2.0))

    def test_ppermute_ring(self, mesh):
        import jax.numpy as jnp
        coll = ici.Collectives(mesh)
        n = mesh.size
        x = coll.shard(jnp.arange(n, dtype=jnp.float32).reshape(n, 1))
        out = coll.ppermute(x, shift=1)
        np.testing.assert_allclose(
            np.asarray(out).ravel(),
            np.roll(np.arange(n, dtype=np.float32), 1))

    def test_all_to_all(self, mesh):
        import jax.numpy as jnp
        coll = ici.Collectives(mesh)
        n = mesh.size
        x = jnp.arange(n * n, dtype=jnp.float32).reshape(n, n, 1)
        out = coll.all_to_all(coll.shard(x))
        np.testing.assert_allclose(np.asarray(out)[:, :, 0],
                                   np.arange(n * n).reshape(n, n).T)

    def test_reduce_scatter(self, mesh):
        import jax.numpy as jnp
        coll = ici.Collectives(mesh)
        n = mesh.size
        x = jnp.ones((n, n, 2), jnp.float32)
        out = coll.reduce_scatter(coll.shard(x))
        np.testing.assert_allclose(np.asarray(out), np.full((n, 1, 2), n))


class TestRing:
    def test_ring_all_reduce_matches_psum(self, mesh):
        import jax.numpy as jnp
        coll = ici.Collectives(mesh)
        n = mesh.size
        x = coll.shard(jnp.arange(n * 8, dtype=jnp.float32).reshape(n, 8))
        ring_out = ici.ring_all_reduce(x, mesh)
        expect = np.arange(n * 8, dtype=np.float32).reshape(n, 8).sum(0)
        for row in np.asarray(ring_out):
            np.testing.assert_allclose(row, expect)

    def test_two_writers_never_overshoot_window(self, monkeypatch):
        """Concurrent writers racing the window check must not both pass
        before either reserves its credit (VERDICT r3 #2: the reference's
        AppendIfNotFull is check-and-reserve atomically, stream.cpp:274).
        The pre-fix code reserved AFTER dispatch, so two writers could
        dispatch with window=1."""
        import brpc_tpu.ici.ring as ring_mod

        lock = threading.Lock()
        state = {"active": 0, "peak": 0}
        pending = []

        class FakeColl:
            def ppermute(self, x, shift):
                with lock:
                    state["active"] += 1
                    state["peak"] = max(state["peak"], state["active"])
                time.sleep(0.03)         # widen the race window
                with lock:
                    state["active"] -= 1
                return x

        class FakeDisp:
            def on_ready(self, arrays, cb):
                # consume asynchronously, like the device poller
                t = threading.Timer(0.01, cb)
                t.daemon = True
                t.start()
                pending.append(t)

        monkeypatch.setattr(ring_mod.DeviceEventDispatcher, "instance",
                            classmethod(lambda cls: FakeDisp()))
        stream = ring_mod.RingStream.__new__(ring_mod.RingStream)
        stream.mesh = None
        stream.coll = FakeColl()
        stream.hops = 1
        stream.window = 1
        stream.on_chunk = None
        stream._cv = threading.Condition()
        stream._produced = 0
        stream._consumed = 0

        errs = []

        def writer():
            try:
                for _ in range(5):
                    assert stream.write(object(), timeout=10)
            except Exception as e:       # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=writer) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs, errs
        assert stream.flush(10)
        # with window=1, at most ONE chunk may ever be mid-dispatch
        assert state["peak"] == 1, \
            f"window overshoot: {state['peak']} concurrent dispatches"
        assert stream.in_flight == 0

    def test_failed_dispatch_returns_reserved_credit(self, monkeypatch):
        """A raising ppermute must roll back its reservation so later
        writes and flush() are not wedged by a phantom in-flight chunk."""
        import brpc_tpu.ici.ring as ring_mod

        class BoomColl:
            def __init__(self):
                self.calls = 0

            def ppermute(self, x, shift):
                self.calls += 1
                if self.calls == 1:
                    raise RuntimeError("transfer failed")
                return x

        class FakeDisp:
            def on_ready(self, arrays, cb):
                cb()

        monkeypatch.setattr(ring_mod.DeviceEventDispatcher, "instance",
                            classmethod(lambda cls: FakeDisp()))
        stream = ring_mod.RingStream.__new__(ring_mod.RingStream)
        stream.mesh = None
        stream.coll = BoomColl()
        stream.hops = 1
        stream.window = 1
        stream.on_chunk = None
        stream._cv = threading.Condition()
        stream._produced = 0
        stream._consumed = 0

        with pytest.raises(RuntimeError):
            stream.write(object(), timeout=1)
        assert stream.in_flight == 0     # credit rolled back
        assert stream.write(object(), timeout=1)   # window not wedged
        assert stream.flush(5)

    def test_ring_stream_window_and_order(self, mesh):
        import jax.numpy as jnp
        coll = ici.Collectives(mesh)
        n = mesh.size
        got = []
        stream = ici.RingStream(hops=1, window=2, mesh=mesh,
                                on_chunk=lambda c: got.append(np.asarray(c)))
        for i in range(6):
            ok = stream.write(coll.shard(
                jnp.full((n, 4), i, jnp.float32)))
            assert ok
        assert stream.flush(60)
        assert len(got) == 6
        for i, chunk in enumerate(got):
            np.testing.assert_allclose(chunk, np.full((n, 4), i))
        assert stream.in_flight == 0


def test_delivery_gate_samples_readiness_once(monkeypatch):
    """An array that turns ready between two looks must still open its
    delivery entry: readiness is sampled once, so what was counted as a
    gate is what is handed to the poller (the chip found the stall — the
    peer never consumed and the writer's window never reopened)."""
    import threading
    import jax.numpy as jnp
    from brpc_tpu.ici import transport as tr

    class Delivery(tr.OrderedDelivery):
        pass

    d = Delivery()
    d._init_delivery()
    looks = iter([False, True, True, True])
    monkeypatch.setattr(tr, "_all_ready", lambda arrays: next(looks))
    committed = threading.Event()
    d._enqueue_delivery([jnp.zeros(8)], committed.set)
    assert committed.wait(10), "the delivery entry never opened"


def test_a_windowed_echos_gates_never_leave_the_poller(mesh, monkeypatch):
    """Every piece of an echo cut by a small send window is gated through
    the device poller's inline entry: its commit runs on the poller thread
    and the hand-off of ``device_on_ready`` is not taken once."""
    import jax
    import jax.numpy as jnp
    from brpc_tpu.bthread.device_waiter import DeviceEventDispatcher
    from brpc_tpu.butil import flags as _flags
    from brpc_tpu.ici import transport as tr
    # above the native tier's 4 MB window, so the Python ici plane carries
    # it: three pieces each way
    window, nbytes = 2 << 20, 5_000_000
    monkeypatch.setattr(_flags.flag_object("ici_socket_window_bytes"),
                        "value", window)
    # on the CPU every slice is ready at once: send each through the poller
    monkeypatch.setattr(tr, "_all_ready", lambda arrays: False)
    disp = DeviceEventDispatcher.instance()
    options = rpc.ServerOptions()
    options.usercode_inline = True
    server = rpc.Server(options)
    server.add_service(DeviceEchoService())
    assert server.start("ici://5") == 0
    try:
        payload = jax.device_put(
            jnp.arange(nbytes, dtype=jnp.uint8), mesh.device(5))
        ch = rpc.Channel()
        assert ch.init("ici://5",
                       options=rpc.ChannelOptions(ici_local_device=5)) == 0
        handed, completed = disp.handoffs(), sum(disp.stats().values())
        cntl = rpc.Controller()
        cntl.request_attachment.append_device_array(payload)
        resp = ch.call_method("EchoService.Echo", cntl,
                              EchoRequest(message="windowed"), EchoResponse)
        assert not cntl.failed(), cntl.error_text
        assert resp.message == "windowed"
        assert cntl.response_attachment.to_bytes() == bytes(
            np.asarray(payload))
        assert sum(disp.stats().values()) >= completed + 6
        assert disp.handoffs() == handed
    finally:
        server.stop()


class TestRelocateCutsOnTheChip:
    """A DEVICE block that is not resident on the target and is large
    enough for the device plane is posted WHOLE with (offset, length): the
    transfer program cuts it, the host-side slice (``transport._cut``)
    is not called on that branch.  The resident branch and the branch
    under the plane's threshold slice as they did."""

    WINDOW, PIECES = 64 * 1024, 16

    @pytest.fixture()
    def host_mesh_plane(self):
        from brpc_tpu.butil import flags as fl
        from brpc_tpu.ici import device_plane as dp
        saved = {n: fl.get_flag(n) for n in
                 ("ici_device_plane", "ici_device_plane_host_mesh",
                  "ici_device_plane_threshold")}
        fl.set_flag("ici_device_plane", True)
        fl.set_flag("ici_device_plane_host_mesh", True)
        fl.set_flag("ici_device_plane_threshold", 1024)
        yield dp.plane()
        for n, v in saved.items():
            fl.set_flag(n, v)

    def _write_and_drain(self, mesh, monkeypatch, src_dev, dst_dev):
        """A frame of a 4-byte header and one PIECES x WINDOW device block
        through a socket pair whose window is WINDOW: PIECES whole window
        pieces and the header's remainder.  Returns (host cuts made, bytes
        delivered, the payload's bytes, source releases)."""
        import jax
        import jax.numpy as jnp
        from brpc_tpu.butil.iobuf import IOBuf, IOPortal
        from brpc_tpu.ici import transport as tr
        a = tr.IciSocket(src_dev, dst_dev, mesh, window_bytes=self.WINDOW)
        b = tr.IciSocket(dst_dev, src_dev, mesh, window_bytes=self.WINDOW)
        a.peer, b.peer = b, a
        cuts, released = [], []
        real_cut = tr._cut

        def counting_cut(arr, r):
            out = real_cut(arr, r)
            if out is not arr:
                cuts.append((r.offset, r.length))
            return out
        monkeypatch.setattr(tr, "_cut", counting_cut)
        nbytes = self.PIECES * self.WINDOW
        payload = jax.device_put(
            (jnp.arange(nbytes, dtype=jnp.uint32) * 7 % 251).astype(
                jnp.uint8), mesh.device(src_dev))
        jax.block_until_ready(payload)
        buf = IOBuf(b"hdr:")
        buf.append_device_array(payload)
        buf.backing_block(1).block.on_send_complete = \
            lambda: released.append(1)
        try:
            assert a.write(buf) == 0
            portal, got = IOPortal(), 0
            deadline = time.monotonic() + 30
            while got < 4 + nbytes and time.monotonic() < deadline:
                n = b._do_read(portal, 1 << 20)
                if n <= 0:
                    time.sleep(0.002)
                    continue
                got += n
            assert got == 4 + nbytes
            for r in portal.device_refs():
                assert set(r.block.data.devices()) == {mesh.device(dst_dev)}
                assert r.block.data.ndim == 1
            deadline = time.monotonic() + 10
            while a.inflight_send_blocks() and time.monotonic() < deadline:
                time.sleep(0.002)
            return cuts, portal.to_bytes(), bytes(np.asarray(payload)), \
                released
        finally:
            a.set_failed()
            b.set_failed()

    def test_plane_branch_posts_the_block_and_never_slices(
            self, mesh, monkeypatch, host_mesh_plane):
        before = host_mesh_plane.stats()
        cuts, got, want, released = self._write_and_drain(
            mesh, monkeypatch, 2, 3)
        assert got == b"hdr:" + want
        after = host_mesh_plane.stats()
        # sixteen window pieces through the plane, each cut by the program
        assert after["transfers"] - before["transfers"] == self.PIECES
        assert (after["sliced_in_program"] - before["sliced_in_program"]
                == self.PIECES)
        assert after["fallbacks"] == before["fallbacks"]
        assert (after["bytes_sent"] - before["bytes_sent"]
                == self.PIECES * self.WINDOW - 4)
        # the only host-side cut is the header's 4-byte remainder, which is
        # under the plane's threshold and goes by slice and device_put
        assert cuts == [(self.PIECES * self.WINDOW - 4, 4)]
        # the block's pin is released once per transfer and once for the
        # remainder's device_put
        deadline = time.monotonic() + 10
        while len(released) < self.PIECES + 1 \
                and time.monotonic() < deadline:
            time.sleep(0.005)
        assert len(released) == self.PIECES + 1

    def test_resident_branch_slices_as_before(self, mesh, monkeypatch,
                                              host_mesh_plane):
        before = host_mesh_plane.stats()["transfers"]
        cuts, got, want, _ = self._write_and_drain(mesh, monkeypatch, 4, 4)
        assert got == b"hdr:" + want
        assert host_mesh_plane.stats()["transfers"] == before
        assert len(cuts) == self.PIECES + 1
        assert cuts[0] == (0, self.WINDOW - 4)
        assert cuts[-1] == (self.PIECES * self.WINDOW - 4, 4)

    def test_without_the_plane_every_piece_is_sliced_and_device_put(
            self, mesh, monkeypatch, host_mesh_plane):
        from brpc_tpu.butil import flags as fl
        fl.set_flag("ici_device_plane_threshold", 1 << 30)
        before = host_mesh_plane.stats()["transfers"]
        cuts, got, want, _ = self._write_and_drain(mesh, monkeypatch, 2, 3)
        assert got == b"hdr:" + want
        assert host_mesh_plane.stats()["transfers"] == before
        assert len(cuts) == self.PIECES + 1
