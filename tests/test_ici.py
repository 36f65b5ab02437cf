"""ici:// transport + collectives tests on the 8-device virtual CPU mesh."""
import sys
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


def _windowed_echo(mesh, monkeypatch, client_dev, message):
    """An echo of 5,000,000 DEVICE bytes to ici://5 through a 2 MiB send
    window — above the native tier's 4 MB window, so the Python ici plane
    carries it: three pieces each way — with ``_all_ready`` saying no, as
    the chip does of a block that was just cut.  Returns what the device
    poller and the transport counted over the call."""
    import jax
    import jax.numpy as jnp
    from brpc_tpu.bthread.device_waiter import DeviceEventDispatcher
    from brpc_tpu.butil import flags as _flags
    from brpc_tpu.ici import transport as tr
    window, nbytes = 2 << 20, 5_000_000
    monkeypatch.setattr(_flags.flag_object("ici_socket_window_bytes"),
                        "value", window)
    monkeypatch.setattr(tr, "_all_ready", lambda arrays: False)
    disp = DeviceEventDispatcher.instance()
    options = rpc.ServerOptions()
    options.usercode_inline = True
    server = rpc.Server(options)
    server.add_service(DeviceEchoService())
    assert server.start("ici://5") == 0
    try:
        payload = jax.device_put(
            jnp.arange(nbytes, dtype=jnp.uint8), mesh.device(client_dev))
        ch = rpc.Channel()
        assert ch.init("ici://5", options=rpc.ChannelOptions(
            ici_local_device=client_dev)) == 0
        before = dict(tr.ici_piece_stats(), handoffs=disp.handoffs(),
                      completions=sum(disp.stats().values()))
        cntl = rpc.Controller()
        cntl.request_attachment.append_device_array(payload)
        resp = ch.call_method("EchoService.Echo", cntl,
                              EchoRequest(message=message), EchoResponse)
        assert not cntl.failed(), cntl.error_text
        assert resp.message == message
        assert cntl.response_attachment.to_bytes() == bytes(
            np.asarray(payload))
        after = dict(tr.ici_piece_stats(), handoffs=disp.handoffs(),
                     completions=sum(disp.stats().values()))
        return {k: after[k] - before[k] for k in after}
    finally:
        server.stop()


def test_a_windowed_echos_gates_never_leave_the_poller(mesh, monkeypatch):
    """The route that still gates: an echo between two devices of the mesh
    under the device plane (on the CPU mesh every relocation is slice +
    ``device_put``).  Every piece MOVED, so every piece is gated through
    the device poller's inline entry: its commit runs on the poller thread
    and the hand-off of ``device_on_ready`` is not taken once."""
    moved = _windowed_echo(mesh, monkeypatch, 4, "windowed")
    assert moved["gated_arrays"] == 6 and moved["resident_refs_ungated"] == 0
    assert moved["completions"] >= 6
    assert moved["handoffs"] == 0


def test_a_windowed_echo_on_one_chip_meets_no_gate(mesh, monkeypatch):
    """Both ends on chip 5: nothing moves, so no piece waits for the device
    poller although none of them says it is ready; the two counters add up
    to the echo's six DEVICE refs either way."""
    passed = _windowed_echo(mesh, monkeypatch, 5, "resident")
    assert passed["resident_refs_ungated"] == 6
    assert passed["gated_arrays"] == 0
    assert passed["completions"] == 0 and passed["handoffs"] == 0
    # the request's three were cut out of the caller's block, the reply's
    # three passed whole
    assert passed["compiled_cuts"] == 3 and passed["small_relocations"] == 0


def _socket_pair(mesh, local, remote, window=1 << 20):
    """Two connected in-process sockets, ``local`` -> ``remote`` and back."""
    from brpc_tpu.ici.transport import IciSocket
    a = IciSocket(local, remote, mesh, window_bytes=window)
    b = IciSocket(remote, local, mesh, window_bytes=window)
    a.peer, b.peer = b, a
    return a, b


class TestResidentRefsAreNotGated:
    """``IciSocket._deliver`` waits for what MOVED.  A DEVICE ref whose
    block is in the target chip's HBM already is delivered as written, by
    the thread that wrote it, whether or not its producer has finished."""

    @pytest.fixture
    def no_array_is_ready(self, monkeypatch):
        """``_all_ready`` says no (the chip's answer for a block just cut)
        and the device poller only collects what it is handed."""
        from brpc_tpu.ici import transport as T
        handed = []

        class FakeDisp:
            def on_ready(self, arrays, cb):
                handed.append((list(arrays), cb))

        monkeypatch.setattr(T, "_all_ready", lambda arrays: False)
        monkeypatch.setattr(T.DeviceEventDispatcher, "instance",
                            classmethod(lambda cls: FakeDisp()))
        return handed

    def _pair(self, mesh, local, remote):
        a, b = _socket_pair(mesh, local, remote)
        events = []
        b.start_input_event = lambda inline=False: events.append(
            threading.get_ident())
        return a, b, events

    @pytest.mark.parametrize("offset,length", [(0, 4096), (1024, 2048)],
                             ids=["whole", "cut"])
    def test_it_commits_on_the_writing_thread(self, mesh, no_array_is_ready,
                                              offset, length):
        import jax
        import jax.numpy as jnp
        from brpc_tpu.butil.iobuf import IOBuf
        from brpc_tpu.ici import transport as T
        a, b, events = self._pair(mesh, 0, 0)
        try:
            arr = jax.device_put(jnp.arange(4096, dtype=jnp.uint8),
                                 mesh.device(0))
            whole = IOBuf()
            whole.append_device_array(arr)
            whole.pop_front(offset)
            buf = whole.cut(length)
            before = T.ici_piece_stats()
            assert a.write(buf) == 0
            # committed before write() returned, by this thread, and the
            # poller was not asked
            assert events == [threading.get_ident()]
            assert no_array_is_ready == []
            refs = b._inbox.device_refs()
            assert len(refs) == 1 and len(b._inbox) == length
            if length == 4096:          # delivered as written: the same array
                assert refs[0].block.data is arr
            assert b._inbox.to_bytes() == bytes(
                np.arange(4096, dtype=np.uint8)[offset:offset + length])
            after = T.ici_piece_stats()
            assert after["resident_refs_ungated"] == \
                before["resident_refs_ungated"] + 1
            assert after["gated_arrays"] == before["gated_arrays"]
        finally:
            a.set_failed()
            b.set_failed()

    def test_a_host_frame_behind_a_moved_ref_still_waits(
            self, mesh, no_array_is_ready):
        """What moved keeps its gate, and the byte stream its order: the
        host frame written after it is committed behind it."""
        import jax
        import jax.numpy as jnp
        from brpc_tpu.butil.iobuf import IOBuf
        from brpc_tpu.ici import transport as T
        if mesh.size < 2:
            pytest.skip("needs 2 devices")
        a, b, events = self._pair(mesh, 0, 1)
        try:
            arr = jax.device_put(jnp.arange(1024, dtype=jnp.uint8),
                                 mesh.device(0))
            buf = IOBuf()
            buf.append_device_array(arr)
            before = T.ici_piece_stats()
            assert a.write(buf) == 0
            assert a.write(IOBuf(b"after")) == 0
            assert events == [] and len(b._inbox) == 0
            after = T.ici_piece_stats()
            assert after["gated_arrays"] == before["gated_arrays"] + 1
            assert after["resident_refs_ungated"] == \
                before["resident_refs_ungated"]
            # the source block's pin and the delivery's gate
            assert len(no_array_is_ready) == 2
            for _arrays, cb in no_array_is_ready:
                cb()
            assert len(events) == 2
            assert b._inbox.to_bytes() == bytes(
                np.arange(1024, dtype=np.uint8) & 0xFF) + b"after"
            moved = b._inbox.device_refs()[0].block.data
            assert mesh.device(1) in moved.devices()
        finally:
            a.set_failed()
            b.set_failed()


class TestTheDeliveringThreadReads:
    """``commit`` runs the peer's reader where it is — the writer's thread
    for a frame with no waits, the poller's for a gated one — on the server
    side as on the client's: frames are cut, and a stream's consumed in
    order, with no hop to a reader tasklet.  What must not run there is a
    handler the server did not ask to have inline."""

    class Messenger:
        def __init__(self):
            self.seen = []

        def on_new_messages(self, sock):
            from brpc_tpu.butil.iobuf import IOPortal
            assert sock._do_read(IOPortal(), 1 << 20) > 0
            self.seen.append(("read", threading.get_ident()))
            return ("proto", "last")

        def process_in_place(self, last, sock):
            self.seen.append(("in_place", threading.get_ident()))

        def _queue_message(self, proto, msg, sock):
            self.seen.append(("queued", threading.get_ident()))

    @pytest.mark.parametrize("server_side,usercode_inline,last", [
        (False, False, "in_place"),     # a client's socket: the response
        (True, True, "in_place"),       # the server asked for it
        (True, False, "queued"),        # a handler gets a tasklet of its own
    ])
    def test_where_the_last_message_runs(self, mesh, server_side,
                                         usercode_inline, last):
        from brpc_tpu.butil.iobuf import IOBuf
        a, b = _socket_pair(mesh, 0, 0)
        b.is_server_side = server_side
        b.usercode_inline = usercode_inline     # what Server._on_accept sets
        b.messenger = self.Messenger()
        try:
            assert b.queue_last_message is (last == "queued")
            assert a.write(IOBuf(b"frame")) == 0
            me = threading.get_ident()
            # read before write() returned, by this thread, whoever's side
            assert b.messenger.seen == [("read", me), (last, me)]
        finally:
            a.set_failed()
            b.set_failed()

    @pytest.mark.parametrize("usercode_inline", [False, True])
    def test_a_handler_runs_inline_only_where_the_server_asked(
            self, mesh, monkeypatch, usercode_inline):
        """End to end over the Python plane on one chip: the request is cut
        and parsed on the caller's thread either way, the handler runs there
        only under ``usercode_inline``."""
        import jax
        import jax.numpy as jnp
        ran = []

        class Where(rpc.Service):
            SERVICE_NAME = "EchoService"

            @rpc.method(EchoRequest, EchoResponse)
            def Echo(self, cntl, request, response, done):
                ran.append(threading.get_ident())
                response.message = request.message
                cntl.response_attachment.append(cntl.request_attachment)
                done()

        options = rpc.ServerOptions()
        options.usercode_inline = usercode_inline
        server = rpc.Server(options)
        server.add_service(Where())
        assert server.start("ici://7") == 0
        try:
            ch = rpc.Channel()
            assert ch.init("ici://7", options=rpc.ChannelOptions(
                ici_local_device=7)) == 0
            monkeypatch.setattr(ch, "_native_ici_binding", lambda cntl: None)
            payload = jax.device_put(jnp.arange(4096, dtype=jnp.uint8),
                                     mesh.device(7))
            cntl = rpc.Controller()
            cntl.request_attachment.append_device_array(payload)
            resp = ch.call_method("EchoService.Echo", cntl,
                                  EchoRequest(message="where"), EchoResponse)
            assert not cntl.failed(), cntl.error_text
            assert resp.message == "where"
            assert cntl.response_attachment.to_bytes() == bytes(
                np.asarray(payload))
            assert len(ran) == 1
            assert (ran[0] == threading.get_ident()) is usercode_inline
        finally:
            server.stop()


class TestBorrowedHeaderWindow:
    """``CreditWindow._consume_window(want, lead)``: a header of ``lead``
    bytes in front of device bytes is not charged against the cut where the
    window covers in full what follows it; the window then stands below
    zero by what it borrowed until the piece's credits repay it."""

    W = 4096

    def _window(self, left=None):
        from brpc_tpu.ici.transport import CreditWindow

        class Sink(CreditWindow):
            failed = False

        w = Sink()
        w._init_window(self.W)
        if left is not None:
            w._send_window = left
        return w

    @pytest.mark.parametrize("left, want, lead, n", [
        # a full window: the header and a whole window of what follows
        (4096, 40 + 16 * 4096, 40, 40 + 4096),
        # ... or all that follows, where that is less than a window
        (4096, 40 + 4090, 40, 40 + 4090),
        (4096, 40 + 100, 40, 140),
        # a partly credited window covers the rest of the data in full
        (1000, 40 + 990, 40, 40 + 990),
        # ... and where it cannot, the cut is today's, header charged
        (1000, 40 + 4096, 40, 1000),
        (4095, 40 + 4096, 40, 4095),
        (30, 40 + 4096, 40, 30),
        # device bytes with no header in front (a frame's later pieces)
        (4096, 16 * 4096, 0, 4096),
        (1000, 5000, 0, 1000),
        (4096, 100, 0, 100),
        # host bytes: byte for byte
        (4096, 16 * 4096, None, 4096),
        (1000, 5000, None, 1000),
    ])
    def test_piece_length(self, left, want, lead, n):
        from brpc_tpu.ici.transport import ici_piece_stats
        w = self._window(left)
        before = ici_piece_stats()["borrowed_header_pieces"]
        assert w._consume_window(want, lead) == n
        assert n > 0
        assert w.send_window_left() == left - n
        # never below zero by more than the header
        assert w.send_window_left() >= -(lead or 0)
        borrowed = ici_piece_stats()["borrowed_header_pieces"] - before
        assert borrowed == (1 if n > left else 0)

    # a window of four pieces (PIECE_BYTES at the tests' size)
    P = 4096

    def _wide(self, monkeypatch, left=None, pieces=4):
        from brpc_tpu.ici import transport as tr
        monkeypatch.setattr(tr, "PIECE_BYTES", self.P)
        monkeypatch.setattr(self, "W", pieces * self.P)
        w = self._window(left)
        assert (w.piece_bytes, w.window_bytes) == (self.P, pieces * self.P)
        return w

    @pytest.mark.parametrize("left, want, lead, n", [
        # the whole window: the header and ONE piece of what follows it
        (4 * P, 40 + 16 * P, 40, 40 + P),
        # ... then a piece at a time while a whole one is left
        (3 * P - 40, 15 * P, 0, P),
        (2 * P - 40, 14 * P, 0, P),
        # exactly a piece left: the header rides on borrowed window
        (P, 40 + 16 * P, 40, 40 + P),
        # the data's end is a piece of its own length
        (100, 90, 0, 90),
        (100, 40 + 60, 40, 100),
        (60, 40 + 60, 40, 100),
        (4 * P, 40 + 100, 40, 140),
        # host bytes: byte for byte, whatever the piece
        (P - 40, 5000, None, P - 40),
        (4 * P, 10 * P, None, 4 * P),
        (1, 5000, None, 1),
    ])
    def test_a_cut_in_device_bytes_is_a_whole_piece_or_the_end(
            self, monkeypatch, left, want, lead, n):
        from brpc_tpu.ici.transport import ici_piece_stats
        w = self._wide(monkeypatch, left)
        before = ici_piece_stats()
        assert w._consume_window(want, lead) == n
        assert w.send_window_left() == left - n >= -(lead or 0)
        after = ici_piece_stats()
        assert after["borrowed_header_pieces"] \
            - before["borrowed_header_pieces"] == (1 if n > left else 0)
        assert after["pipelined_pieces"] - before["pipelined_pieces"] \
            == (1 if left < 4 * self.P else 0)

    @pytest.mark.parametrize("left, want, lead, need", [
        # less left than the next whole piece needs: never what is left
        (P - 40, 13 * P, 0, P),
        (P - 1, 40 + 16 * P, 40, P),
        (1, 40 + 16 * P, 40, P),
        (50, 40 + 60, 40, 60),
        # closed
        (0, 13 * P, 0, P),
        (-40, 13 * P, 0, P),
        (0, 5000, None, 1),
        (-1, 5000, None, 1),
    ])
    def test_a_partly_open_window_is_not_writable_for_device_bytes(
            self, monkeypatch, left, want, lead, need):
        w = self._wide(monkeypatch, left)
        assert w._consume_window(want, lead) == -1
        assert w.send_window_left() == left
        # ... and the wait is for what the cut needs, not for any byte
        got = []
        t = threading.Thread(
            target=lambda: got.append(w._wait_writable(timeout=20)))
        t.start()
        if left + 1 < need:
            w._on_credits(need - left - 1)      # one byte short
            time.sleep(0.7)                     # past the 0.5 s re-check
            assert got == [] and t.is_alive()
            w._on_credits(1)
        else:
            w._on_credits(need - left)
        t.join(10)
        assert got == [True]
        assert w._consume_window(want, lead) > 0

    @pytest.mark.parametrize("window, batch", [
        (4 * P, 4 * P // 8),         # an eighth of the window, as before
        (P, P // 8), (1000, 125),    # ... and of one piece or less
        (P + 100, 100),              # never what a parked writer needs
        (2 * P, 2 * P // 8),
    ])
    def test_a_reader_never_holds_back_what_a_parked_writer_needs(
            self, monkeypatch, window, batch):
        """``credit_batch`` (FabricSocket returns credits in such steps):
        with all but one batch returned, a whole piece is writable."""
        from brpc_tpu.ici import transport as tr
        monkeypatch.setattr(tr, "PIECE_BYTES", self.P)
        monkeypatch.setattr(self, "W", window)
        w = self._window()
        assert w.credit_batch == batch
        w._send_window = window - (batch - 1)   # the peer sits on the rest
        assert w._consume_window(16 * self.P, 0) > 0

    @pytest.mark.parametrize("left", [0, -1, -40])
    def test_a_window_at_or_under_zero_is_closed(self, left):
        w = self._window(left)
        assert w._consume_window(100) == -1
        assert w._consume_window(100, 40) == -1
        assert w.send_window_left() == left

    def test_the_pieces_credits_repay_the_borrowed_header(self):
        w = self._window()
        n = w._consume_window(40 + 3 * self.W, 40)
        assert n == 40 + self.W
        assert w.send_window_left() == -40
        assert w.unacked_send_bytes() == self.W + 40
        assert w._consume_window(2 * self.W) == -1
        w._on_credits(39)                   # still closed
        assert w.send_window_left() == -1
        assert w._consume_window(2 * self.W) == -1
        w._on_credits(n - 39)
        assert w.send_window_left() == self.W    # exactly, and capped
        w._on_credits(10)
        assert w.send_window_left() == self.W

    def test_racing_pieces_and_credits_keep_the_bound(self):
        """Writers that borrow and readers that credit, more threads than
        cores and a short switch interval: the window never stands below
        zero by more than one header, never above ``window_bytes``, and
        ends where it began."""
        import sys
        w = self._window()
        lead, taken, lock = 40, [], threading.Lock()
        stop = time.monotonic() + 0.5
        low, high, written = [0], [0], threading.Event()

        def writer():
            while time.monotonic() < stop:
                n = w._consume_window(lead + 3 * self.W, lead)
                left = w.send_window_left()
                low[0], high[0] = min(low[0], left), max(high[0], left)
                if n > 0:
                    with lock:
                        taken.append(n)

        def reader():
            while not written.is_set() or taken:
                with lock:
                    n = taken.pop() if taken else 0
                if n:
                    w._on_credits(n // 2)
                    w._on_credits(n - n // 2)

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            writers = [threading.Thread(target=writer) for _ in range(8)]
            readers = [threading.Thread(target=reader) for _ in range(8)]
            for t in writers + readers:
                t.start()
            for t in writers:
                t.join(20)
            written.set()
            for t in readers:
                t.join(20)
            assert not any(t.is_alive() for t in writers + readers)
        finally:
            sys.setswitchinterval(old)
        assert low[0] >= -lead and high[0] <= self.W
        assert w.send_window_left() == self.W

    @pytest.mark.parametrize("refs, bound, lead", [
        ((b"h" * 25, "dev"), 1024, 25),
        ((b"h" * 25, b"m" * 42, "dev"), 1024, 67),
        ((b"h" * 1023, "dev"), 1024, 1023),
        # cut as host bytes (None): a run at the bound or over it
        ((b"h" * 1024, "dev"), 1024, None),
        ((b"h" * 600, b"m" * 600, "dev"), 1024, None),
        ((b"h" * 600, "dev"), 512, None),    # a window under the threshold
        ((b"h" * 25,), 1024, None),          # no device bytes
        (("dev",), 1024, 0),                 # nothing in front of them
        (("dev", b"t" * 25, "dev"), 1024, 0),
        ((), 1024, None),
    ])
    def test_header_run(self, mesh, refs, bound, lead):
        import jax.numpy as jnp
        from brpc_tpu.butil.iobuf import IOBuf
        from brpc_tpu.ici.transport import _header_run
        buf = IOBuf()
        for r in refs:
            if r == "dev":
                buf.append_device_array(jnp.zeros(2048, jnp.uint8))
            else:
                buf.append_user_data(r)      # a block of its own
        assert _header_run(buf, bound) == lead


class TestRelocateCutsOnTheChip:
    """A frame's header rides with its first window piece, so the window's
    cuts land on the device blocks' boundaries: a block of PIECES x WINDOW
    goes in exactly PIECES pieces and blocks of WINDOW go whole, whatever
    the header's length.  A DEVICE block that is not resident on the target
    and is large enough for the device plane is posted WHOLE with
    (offset, length): the transfer program cuts it, the host-side slice
    (``transport._cut``) is not called on that branch.  The resident branch
    and the branch under the plane's threshold slice as they did."""

    WINDOW, PIECES, THRESHOLD = 64 * 1024, 16, 1024
    HEADERS = [4, 25, 67, 1023]

    @pytest.fixture()
    def host_mesh_plane(self):
        from brpc_tpu.butil import flags as fl
        from brpc_tpu.ici import device_plane as dp
        saved = {n: fl.get_flag(n) for n in
                 ("ici_device_plane", "ici_device_plane_host_mesh",
                  "ici_device_plane_threshold")}
        fl.set_flag("ici_device_plane", True)
        fl.set_flag("ici_device_plane_host_mesh", True)
        fl.set_flag("ici_device_plane_threshold", self.THRESHOLD)
        yield dp.plane()
        for n, v in saved.items():
            fl.set_flag(n, v)

    def _payload(self, mesh, dev, nbytes, salt=7):
        import jax
        import jax.numpy as jnp
        arr = jax.device_put(
            (jnp.arange(nbytes, dtype=jnp.uint32) * salt % 251).astype(
                jnp.uint8), mesh.device(dev))
        jax.block_until_ready(arr)
        return arr

    def _write_and_drain(self, mesh, monkeypatch, src_dev, dst_dev,
                         header=b"hdr:", blocks=1, window=None,
                         unread_first=b"", nbytes=None, reads=None,
                         reader=None):
        """One frame — ``header`` and then ``blocks`` device blocks, ONE of
        PIECES x WINDOW bytes (a request) or PIECES of WINDOW (a reply, as
        a server echoes what it received) — through a socket pair whose
        window is ``window``.  ``unread_first`` is written before it and
        not read until the frame's first piece has gone (a partly credited
        window).  ``reads`` gives the most each read takes, one after
        another (1 MiB each by default); ``reader(b, portal)`` instead
        reads from inside each delivery and returns what it took.  Returns
        what happened, and holds the window's bound all the way."""
        from brpc_tpu.butil import flags as fl
        from brpc_tpu.butil.iobuf import IOBuf, IOPortal
        from brpc_tpu.ici import transport as tr
        window = window or self.WINDOW
        a = tr.IciSocket(src_dev, dst_dev, mesh, window_bytes=window)
        b = tr.IciSocket(dst_dev, src_dev, mesh, window_bytes=window)
        a.peer, b.peer = b, a
        nbytes = nbytes or self.PIECES * self.WINDOW
        arrays = [self._payload(mesh, src_dev, nbytes // blocks, 7 + k)
                  for k in range(blocks)]     # none: a host-only frame
        out = type("Wrote", (), {})()
        out.cuts, out.released, out.pieces, out.posts, out.puts = \
            [], [], [], [], []
        out.peak_unacked = out.refused = 0
        real_cut, real_pin = tr._cut, a._pin_until_sent
        real_write, real_post = a._do_write, tr._dp.plane().post_send

        def counting_cut(arr, r):
            got = real_cut(arr, r)
            if got is not arr:
                out.cuts.append((r.offset, r.length))
            return got

        def counting_pin(src_block, moved):
            # the transport's device_put route: one pin a moved array
            out.puts.append(len(moved))
            return real_pin(src_block, moved)

        def counting_post(arr, *args, **kw):
            out.posts.append(kw["nbytes"])
            return real_post(arr, *args, **kw)

        def counting_write(data):
            n = real_write(data)
            if n >= 0:
                out.pieces.append(n)
            else:
                out.refused += 1
            out.peak_unacked = max(out.peak_unacked, a.unacked_send_bytes())
            return n
        monkeypatch.setattr(tr, "_cut", counting_cut)
        monkeypatch.setattr(a, "_pin_until_sent", counting_pin)
        monkeypatch.setattr(tr._dp.plane(), "post_send", counting_post)
        monkeypatch.setattr(a, "_do_write", counting_write)
        buf = IOBuf(header)
        for arr in arrays:
            buf.append_device_array(arr)
        for k in range(blocks):
            buf.backing_block(buf.backing_block_num() - 1 - k).block \
                .on_send_complete = lambda: out.released.append(1)
        out.want = header + b"".join(bytes(np.asarray(x)) for x in arrays)
        out.sent = arrays
        stats = tr.ici_piece_stats()
        try:
            total = len(unread_first) + len(out.want)
            portal, got, got_lock = IOPortal(), [0], threading.Lock()
            if reader is not None:
                def input_event(inline=False):
                    with got_lock:
                        got[0] += reader(b, portal)
                monkeypatch.setattr(b, "start_input_event", input_event)
            if unread_first:
                assert a.write(IOBuf(unread_first)) == 0
            written = []
            assert a.write(buf, on_done=written.append) == 0
            reads = iter(reads(out) if callable(reads) else reads or ())
            deadline = time.monotonic() + 30
            while got[0] < total and time.monotonic() < deadline:
                with got_lock:
                    # beside a reader: only what it left behind at the end
                    n = b._do_read(portal, next(reads, 1 << 20)) \
                        if reader is None or written else 0
                    got[0] += max(n, 0)
                if n <= 0:
                    time.sleep(0.002)
            assert got[0] == total and written == [0]
            out.delivered = [r.block.data for r in portal.device_refs()]
            for arr in out.delivered:
                assert set(arr.devices()) == {mesh.device(dst_dev)}
                assert arr.ndim == 1
            deadline = time.monotonic() + 10
            while a.inflight_send_blocks() and time.monotonic() < deadline:
                time.sleep(0.002)
            out.got = portal.to_bytes()[len(unread_first):]
            # the window's contract: never more unconsumed bytes at the
            # peer than the window and one header under the threshold, no
            # empty piece, and every credit back at the end
            bound = min(fl.get_flag("ici_device_plane_threshold"), window)
            assert out.peak_unacked < window + bound
            assert all(n > 0 for n in out.pieces)
            assert sum(out.pieces) == total
            assert a.send_window_left() == window
            after = tr.ici_piece_stats()
            out.borrowed = (after["borrowed_header_pieces"]
                            - stats["borrowed_header_pieces"])
            out.small = (after["small_relocations"]
                         - stats["small_relocations"])
            out.pipelined = (after["pipelined_pieces"]
                             - stats["pipelined_pieces"])
            return out
        finally:
            a.set_failed()
            b.set_failed()

    def _released(self, out, n):
        deadline = time.monotonic() + 10
        while len(out.released) < n and time.monotonic() < deadline:
            time.sleep(0.005)
        return len(out.released)

    @pytest.mark.parametrize("hdr", HEADERS)
    def test_plane_branch_posts_the_block_and_never_slices(
            self, mesh, monkeypatch, host_mesh_plane, hdr):
        before = host_mesh_plane.stats()
        out = self._write_and_drain(mesh, monkeypatch, 2, 3,
                                    header=b"h" * hdr)
        assert out.got == out.want
        after = host_mesh_plane.stats()
        # sixteen window pieces, the header in the first, and each a whole
        # window of the block through the plane, cut by the program
        assert out.pieces == [hdr + self.WINDOW] \
            + [self.WINDOW] * (self.PIECES - 1)
        assert after["transfers"] - before["transfers"] == self.PIECES
        assert out.posts == [self.WINDOW] * self.PIECES
        assert (after["sliced_in_program"] - before["sliced_in_program"]
                == self.PIECES)
        assert after["fallbacks"] == before["fallbacks"]
        assert (after["bytes_sent"] - before["bytes_sent"]
                == self.PIECES * self.WINDOW)
        # no remainder: nothing is cut on the host, nothing goes by
        # device_put, and the block's pin is released once per transfer
        assert out.cuts == [] and out.puts == []
        assert (out.borrowed, out.small) == (1, 0)
        assert self._released(out, self.PIECES) == self.PIECES
        time.sleep(0.05)
        assert len(out.released) == self.PIECES

    @pytest.mark.parametrize("hdr", HEADERS)
    def test_resident_branch_slices_as_before(self, mesh, monkeypatch,
                                              host_mesh_plane, hdr):
        before = host_mesh_plane.stats()["transfers"]
        out = self._write_and_drain(mesh, monkeypatch, 4, 4,
                                    header=b"h" * hdr)
        assert out.got == out.want
        assert host_mesh_plane.stats()["transfers"] == before
        assert out.pieces == [hdr + self.WINDOW] \
            + [self.WINDOW] * (self.PIECES - 1)
        # one static shape, on the block's own window boundaries
        assert out.cuts == [(k * self.WINDOW, self.WINDOW)
                            for k in range(self.PIECES)]
        assert out.puts == [] and (out.borrowed, out.small) == (1, 0)

    @pytest.mark.parametrize("hdr", HEADERS)
    def test_without_the_plane_every_piece_is_sliced_and_device_put(
            self, mesh, monkeypatch, host_mesh_plane, hdr):
        from brpc_tpu.butil import flags as fl
        # the window is now under the threshold: the bound is the window
        fl.set_flag("ici_device_plane_threshold", 1 << 30)
        before = host_mesh_plane.stats()["transfers"]
        out = self._write_and_drain(mesh, monkeypatch, 2, 3,
                                    header=b"h" * hdr)
        assert out.got == out.want
        assert host_mesh_plane.stats()["transfers"] == before
        assert out.cuts == [(k * self.WINDOW, self.WINDOW)
                            for k in range(self.PIECES)]
        assert out.puts == [self.WINDOW] * self.PIECES
        # each of them a DEVICE ref that crossed under the threshold
        assert (out.borrowed, out.small) == (1, self.PIECES)
        assert self._released(out, self.PIECES) == self.PIECES

    @pytest.mark.parametrize("hdr", HEADERS)
    @pytest.mark.parametrize("src, dst", [(2, 3), (4, 4)],
                             ids=["plane", "resident"])
    def test_a_reply_passes_every_block_whole(
            self, mesh, monkeypatch, host_mesh_plane, src, dst, hdr):
        """What a server echoes: the PIECES blocks of WINDOW it received,
        behind a header of another length.  Every piece is one whole
        block: no slice on either branch, no device_put across chips, and
        on one chip the very arrays are passed."""
        before = host_mesh_plane.stats()
        out = self._write_and_drain(mesh, monkeypatch, src, dst,
                                    header=b"r" * hdr, blocks=self.PIECES)
        assert out.got == out.want
        after = host_mesh_plane.stats()
        assert out.pieces == [hdr + self.WINDOW] \
            + [self.WINDOW] * (self.PIECES - 1)
        assert out.cuts == [] and out.puts == []
        assert (out.borrowed, out.small) == (1, 0)
        if src == dst:
            assert after["transfers"] == before["transfers"]
            assert all(x is y for x, y in zip(out.delivered, out.sent))
        else:
            assert out.posts == [self.WINDOW] * self.PIECES
            # B == n: the program has nothing to cut from
            assert after["sliced_in_program"] == before["sliced_in_program"]
            assert self._released(out, self.PIECES) == self.PIECES

    @pytest.mark.parametrize(
        "case, hdr, window, unread, first_pieces, borrowed", [
            # a host run at or over the bound (the plane's threshold)
            ("long_header", 1024, None, 0, [64 * 1024, 64 * 1024], 0),
            ("longer_header", 3000, None, 0, [64 * 1024, 64 * 1024], 0),
            # a window under the threshold bounds the header by itself
            ("small_window", 1024, 512, 0, [512, 512, 512], 0),
            # ... and what such a cut leaves of the header is a short run
            # in front of device bytes like any other
            ("small_window_rest", 600, 512, 0, [512, 88 + 512, 512], 1),
            # a partly credited window that cannot cover a whole piece
            ("part_credited", 25, None, 1000, [1000, 64 * 1024 - 1000], 0),
        ])
    def test_cut_byte_for_byte_as_before(self, mesh, monkeypatch,
                                         host_mesh_plane, case, hdr, window,
                                         unread, first_pieces, borrowed):
        out = self._write_and_drain(
            mesh, monkeypatch, 4, 4, header=b"h" * hdr, window=window,
            unread_first=b"u" * unread,
            nbytes=self.PIECES * (window or self.WINDOW))
        assert out.got == out.want
        assert out.pieces[:len(first_pieces)] == first_pieces
        assert out.borrowed == borrowed

    # ---- the window as several pieces: the default geometry --------------
    HDR = 40

    def _default_geometry(self):
        from brpc_tpu.butil import flags as fl
        from brpc_tpu.ici import transport as tr
        window = fl.get_flag("ici_socket_window_bytes")
        assert window % tr.PIECE_BYTES == 0 and window > tr.PIECE_BYTES
        return window, tr.PIECE_BYTES

    @pytest.mark.parametrize("case", [
        "a_piece_at_a_time", "the_first_pieces_credit_before_the_fourth_cut",
        "the_header_first", "all_at_once", "odd_amounts"])
    def test_sixty_four_mib_go_in_sixteen_whole_pieces(
            self, mesh, monkeypatch, host_mesh_plane, case):
        """The reader is withheld until the writer has met a window with
        less than a piece left, and then returns credits in the case's
        steps: whatever part of the window they open, a cut is a whole
        piece, on the block's piece boundaries, the header in the first."""
        import itertools
        window, piece = self._default_geometry()
        hdr = self.HDR
        steps = {
            # the first read leaves the header's 40 bytes: 2 pieces less
            # 40 open, one cut, and the writer parks again
            "a_piece_at_a_time": itertools.repeat(piece),
            # header included: exactly two pieces open
            "the_first_pieces_credit_before_the_fourth_cut":
                itertools.chain([hdr + piece], itertools.repeat(piece)),
            "the_header_first":
                itertools.chain([hdr], itertools.repeat(piece)),
            "all_at_once": itertools.repeat(1 << 26),
            "odd_amounts": itertools.repeat((1 << 20) + 7),
        }[case]

        def reads(out):
            time.sleep(0.2)             # the writer has parked
            assert out.pieces == [hdr + piece, piece, piece]
            yield from steps

        out = self._write_and_drain(
            mesh, monkeypatch, 4, 4, header=b"h" * hdr, window=window,
            nbytes=16 * piece, reads=reads)
        assert out.got == out.want
        assert out.pieces == [hdr + piece] + [piece] * 15
        assert out.cuts == [(k * piece, piece) for k in range(16)]
        # more than one piece really was in flight, under the bound
        assert piece < out.peak_unacked <= window + hdr
        assert out.pipelined >= 2 and out.small == 0

    def test_a_writer_short_of_a_piece_parks_and_does_not_spin(
            self, mesh, monkeypatch, host_mesh_plane):
        """``_do_write`` is not called again until the credit that makes a
        whole piece writable: not over the wait's 0.5 s re-check, and not
        for a credit that leaves the window short of a piece."""
        import itertools
        window, piece = self._default_geometry()
        hdr = self.HDR

        def reads(out):
            time.sleep(0.3)
            tries = out.refused         # write()'s own, and keep_write's
            assert len(out.pieces) == 3 and 1 <= tries <= 2
            time.sleep(0.7)
            assert out.refused == tries
            yield hdr - 10              # 10 bytes short of a whole piece
            time.sleep(0.3)
            assert (len(out.pieces), out.refused) == (3, tries)
            yield 10
            time.sleep(0.3)             # one piece, and parked again
            assert (len(out.pieces), out.refused) == (4, tries + 1)
            yield from itertools.repeat(1 << 26)

        out = self._write_and_drain(
            mesh, monkeypatch, 4, 4, header=b"h" * hdr, window=window,
            nbytes=16 * piece, reads=reads)
        assert out.got == out.want
        assert out.pieces == [hdr + piece] + [piece] * 15

    @pytest.mark.parametrize("case, pipelined", [
        ("a_window_of_one_piece", 0),
        ("consumed_inside_the_delivering_write", 0),
        ("each_credit_one_piece_late", 15)])
    def test_pipelined_pieces(self, mesh, monkeypatch, host_mesh_plane,
                              case, pipelined):
        """``ici_piece_stats()["pipelined_pieces"]``: pieces cut while
        earlier bytes of their socket were un-consumed at the peer."""
        window, piece = self._default_geometry()
        hdr = self.HDR

        def read_all(b, portal):
            return max(0, b._do_read(portal, 1 << 26))

        def all_but_the_newest_piece(b, portal):
            with b._inbox_lock:
                avail = len(b._inbox)
            if avail <= hdr + piece:
                return 0
            return b._do_read(portal, avail - piece)

        kw = {
            # the parent's geometry, and its cuts exactly
            "a_window_of_one_piece": dict(window=piece,
                                          reads=[1 << 26] * 64),
            # a local reply: sixteen whole blocks, each read by the
            # client's input event inside the write that delivers it
            "consumed_inside_the_delivering_write": dict(
                window=window, blocks=16, reader=read_all),
            "each_credit_one_piece_late": dict(
                window=window, reader=all_but_the_newest_piece),
        }[case]
        out = self._write_and_drain(mesh, monkeypatch, 4, 4,
                                    header=b"h" * hdr, nbytes=16 * piece,
                                    **kw)
        assert out.got == out.want
        assert out.pieces == [hdr + piece] + [piece] * 15
        assert out.pipelined == pipelined
        if case == "a_window_of_one_piece":
            assert out.borrowed == 1 and out.peak_unacked == hdr + piece
        elif case == "consumed_inside_the_delivering_write":
            assert out.refused == 0 and out.cuts == []

    def test_the_wider_window_asks_for_no_new_transfer_program(
            self, mesh, monkeypatch, host_mesh_plane):
        """A 64 MiB echo through the device plane: under the default
        window the plane is asked for the (block, piece) pairs a window of
        one piece asks for, and none is built that was not built then."""
        window, piece = self._default_geometry()
        asked, real = [], host_mesh_plane._program

        def spy(block_bytes, nbytes, *args, **kw):
            asked.append((block_bytes, nbytes))
            return real(block_bytes, nbytes, *args, **kw)
        monkeypatch.setattr(host_mesh_plane, "_program", spy)
        seen = {}
        for w in (piece, window):       # the narrow one first: it builds
            del asked[:]
            misses = host_mesh_plane.stats()["program_cache_misses"]
            for src, dst, blocks in ((2, 3, 1), (3, 2, 16)):
                out = self._write_and_drain(
                    mesh, monkeypatch, src, dst, header=b"h" * self.HDR,
                    window=w, nbytes=16 * piece, blocks=blocks,
                    reads=[1 << 26] * 64)
                assert out.got == out.want
                assert out.posts == [piece] * 16 and out.cuts == []
            seen[w] = (set(asked), host_mesh_plane.stats()
                       ["program_cache_misses"] - misses)
        assert seen[window][0] == seen[piece][0] \
            == {(16 * piece, piece), (piece, piece)}
        assert seen[window][1] == 0

    def test_a_host_only_frame_is_cut_byte_for_byte(self, mesh,
                                                    monkeypatch,
                                                    host_mesh_plane):
        out = self._write_and_drain(
            mesh, monkeypatch, 4, 4, header=b"h" * (3 * self.WINDOW + 5),
            blocks=0)
        assert out.got == out.want
        assert out.pieces == [self.WINDOW] * 3 + [5]
        assert out.borrowed == 0 and out.cuts == [] and out.puts == []

    def test_a_small_block_across_chips_is_counted(self, mesh, monkeypatch,
                                                   host_mesh_plane):
        """A DEVICE ref under the plane's threshold goes by slice and
        device_put, and the transport counts it."""
        import jax.numpy as jnp
        from brpc_tpu.butil.iobuf import IOBuf, IOPortal
        from brpc_tpu.ici import transport as tr
        a = tr.IciSocket(2, 3, mesh)
        b = tr.IciSocket(3, 2, mesh)
        a.peer, b.peer = b, a
        try:
            before = tr.ici_piece_stats()
            buf = IOBuf(b"hdr:")
            buf.append_device_array(
                self._payload(mesh, 2, self.THRESHOLD - 1))
            assert a.write(buf) == 0
            portal, got = IOPortal(), 0
            deadline = time.monotonic() + 10
            while got < len(buf) and time.monotonic() < deadline:
                got += max(0, b._do_read(portal, 1 << 20))
            after = tr.ici_piece_stats()
            assert after["small_relocations"] \
                == before["small_relocations"] + 1
            # the frame fits the window: nothing was borrowed
            assert after["borrowed_header_pieces"] \
                == before["borrowed_header_pieces"]
        finally:
            a.set_failed()
            b.set_failed()

    def test_the_counters_are_bvars(self):
        from brpc_tpu import bvar
        from brpc_tpu.ici import transport as tr
        stats = tr.ici_piece_stats()
        for name, key in (("ici_transport_borrowed_header_pieces",
                           "borrowed_header_pieces"),
                          ("ici_transport_pipelined_pieces",
                           "pipelined_pieces"),
                          ("ici_transport_small_relocations",
                           "small_relocations"),
                          ("ici_transport_compiled_cuts", "compiled_cuts"),
                          ("ici_transport_eager_cuts", "eager_cuts")):
            assert bvar.find_exposed(name).get_value() == stats[key]
        assert len(tr.ici_transport_stats()) == 2


class TestCompiledCut:
    """``transport._cut``: the host-side cut of a block ref.  Three
    outcomes, chosen on what the block is: the block itself for a ref that
    covers it, one dispatch of the compiled slicer (``piece_slicer``) out
    of a single-device array, ``arr[a:b]`` for anything else."""

    BLOCK, PIECE = 64 * 1024, 4 * 1024

    @staticmethod
    def _ref(offset, length):
        return type("Ref", (), {"offset": offset, "length": length})()

    @staticmethod
    def _stats():
        from brpc_tpu.ici import transport as tr
        s = tr.ici_piece_stats()
        return s["compiled_cuts"], s["eager_cuts"]

    def _host(self, nbytes=None, salt=7):
        return (np.arange(nbytes or self.BLOCK, dtype=np.uint32) * salt
                % 251).astype(np.uint8)

    def _block(self, mesh, dev=1, nbytes=None, salt=7):
        import jax
        host = self._host(nbytes, salt)
        return host, jax.block_until_ready(
            jax.device_put(host, mesh.device(dev)))

    def test_a_whole_ref_is_the_block_itself(self, mesh):
        from brpc_tpu.ici import transport as tr
        host, arr = self._block(mesh)
        before = self._stats()
        assert tr._cut(arr, self._ref(0, self.BLOCK)) is arr
        assert tr._cut(host, self._ref(0, self.BLOCK)) is host
        assert self._stats() == before

    @pytest.mark.parametrize("case, offset, length", [
        ("aligned_piece", 5 * 4096, 4096),
        ("first_piece", 0, 4096),
        ("ragged_offset_and_length", 4097, 1234),
        ("one_byte", 65535, 1),
        ("short_last_piece", 15 * 4096 + 1000, 4096 - 1000),
        ("all_but_the_first_byte", 1, 64 * 1024 - 1),
    ])
    def test_a_device_piece_byte_for_byte(self, mesh, case, offset, length):
        from brpc_tpu.ici import transport as tr
        host, arr = self._block(mesh, dev=2)
        before = self._stats()
        got = tr._cut(arr, self._ref(offset, length))
        assert set(got.devices()) == {mesh.device(2)}
        assert got.dtype == np.uint8 and got.shape == (length,)
        assert bytes(np.asarray(got)) == bytes(host[offset:offset + length])
        assert self._stats() == (before[0] + 1, before[1])

    def test_a_host_block_is_sliced_by_numpy_and_not_counted(self, mesh):
        from brpc_tpu.ici import transport as tr
        host = self._host()
        before = self._stats()
        got = tr._cut(host, self._ref(4097, 1234))
        assert isinstance(got, np.ndarray) and got.base is host
        assert bytes(got) == bytes(host[4097:4097 + 1234])
        assert self._stats() == before

    def test_an_array_spread_over_devices_keeps_the_eager_slice(self, mesh):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from brpc_tpu.ici import transport as tr
        host = self._host()
        spread = jax.device_put(host, NamedSharding(
            Mesh(np.array(jax.devices()[:2]), ("x",)), P("x")))
        before = self._stats()
        got = tr._cut(spread, self._ref(3 * 4096, 4096))
        assert bytes(np.asarray(got)) == bytes(host[3 * 4096:4 * 4096])
        assert self._stats() == (before[0], before[1] + 1)

    @pytest.mark.parametrize("on", ["device", "host"])
    @pytest.mark.parametrize("case, offset, length", [
        ("runs_past_the_end", 15 * 4096 + 1, 4096),
        ("starts_past_the_end", 64 * 1024, 1),
        ("longer_than_the_block", 0, 64 * 1024 + 1),
        ("negative_offset", -4096, 4096),
        ("negative_length", 4096, -1),
    ])
    def test_a_ref_outside_its_block_raises(self, mesh, on, case, offset,
                                            length):
        """``dynamic_slice`` clamps such a start and would deliver other
        bytes; ``arr[a:b]`` would deliver fewer."""
        from brpc_tpu.ici import transport as tr
        host, arr = self._block(mesh)
        before = self._stats()
        with pytest.raises(ValueError, match="not inside its block"):
            tr._cut(arr if on == "device" else host,
                    self._ref(offset, length))
        assert self._stats() == before

    def test_sixteen_offsets_of_one_shape_build_one_program(self, mesh):
        from brpc_tpu.ici import transport as tr
        # a (block, piece) pair no other test cuts, on two devices
        nbytes, piece = 16 * 3 * 1024, 3 * 1024
        slicer = tr.piece_slicer()
        assert tr.piece_slicer() is slicer
        programs = slicer._cache_size()
        for dev in (1, 2):
            host, arr = self._block(mesh, dev=dev, nbytes=nbytes, salt=11)
            for k in range(16):
                got = tr._cut(arr, self._ref(k * piece, piece))
                assert bytes(np.asarray(got)) \
                    == bytes(host[k * piece:(k + 1) * piece])
        # one executable a (shape, device), whatever the offset ...
        assert slicer._cache_size() == programs + 2
        # ... and the sixteen starts of each device, uploaded once
        for dev in (1, 2):
            starts = [tr._start_operand(mesh.device(dev), k * piece)
                      for k in range(16)]
            assert all(s is tr._start_operand(mesh.device(dev), k * piece)
                       for k, s in enumerate(starts))
            assert all(set(s.devices()) == {mesh.device(dev)}
                       and s.dtype == np.int32 and int(s) == k * piece
                       for k, s in enumerate(starts))

    def test_a_full_table_of_starts_is_dropped_whole(self, mesh, monkeypatch):
        from brpc_tpu.ici import transport as tr
        monkeypatch.setattr(tr, "MAX_CUT_STARTS", 4)
        with tr._cut_lock:
            tr._cut_starts.clear()
        host, arr = self._block(mesh)
        for k in range(11):
            got = tr._cut(arr, self._ref(k * 100 + 1, 50))
            assert bytes(np.asarray(got)) \
                == bytes(host[k * 100 + 1:k * 100 + 51])
            with tr._cut_lock:
                assert 1 <= len(tr._cut_starts) <= 4
        with tr._cut_lock:
            assert len(tr._cut_starts) == 3      # 4 + 4 + 3

    @pytest.mark.parametrize("threads", [2, 8])
    def test_threads_cutting_at_once(self, mesh, threads):
        from brpc_tpu.ici import transport as tr
        host, arr = self._block(mesh, dev=3, salt=13)
        with tr._cut_lock:
            tr._cut_starts.clear()
        before = self._stats()
        wrong, go = [], threading.Barrier(threads)

        def cutter(i):
            go.wait()
            for n in range(4):
                for k in range(16):
                    at = ((k + i) % 16) * self.PIECE
                    got = tr._cut(arr, self._ref(at, self.PIECE))
                    if bytes(np.asarray(got)) \
                            != bytes(host[at:at + self.PIECE]):
                        wrong.append((i, n, at))
        ts = [threading.Thread(target=cutter, args=(i,))
              for i in range(threads)]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)     # the look-ups' races, if any, show
        try:
            for t in ts:
                t.start()
            for t in ts:
                t.join(60)
        finally:
            sys.setswitchinterval(interval)
        assert not wrong and not any(t.is_alive() for t in ts)
        assert self._stats() == (before[0] + threads * 64, before[1])
        with tr._cut_lock:
            assert len(tr._cut_starts) == 16

    def _sixteen_piece_echo(self, mesh, monkeypatch, dev):
        """One echo of a device attachment sixteen pieces long over an
        ``ici://`` connection on one chip (5 MiB: above the native tier's
        window, so the Python ici plane carries it); the reply is held to
        the request byte for byte and to sixteen whole blocks on the chip.
        Returns the two cut counters' growth."""
        from brpc_tpu.ici import transport as tr
        piece = 320 * 1024
        monkeypatch.setattr(tr, "PIECE_BYTES", piece)
        host, arr = self._block(mesh, dev=dev, nbytes=16 * piece, salt=17)
        options = rpc.ServerOptions()
        options.usercode_inline = True
        server = rpc.Server(options)
        server.add_service(DeviceEchoService())
        assert server.start(f"ici://{dev}") == 0
        try:
            ch = rpc.Channel()
            assert ch.init(f"ici://{dev}", options=rpc.ChannelOptions(
                ici_local_device=dev)) == 0
            before = self._stats()
            cntl = rpc.Controller()
            cntl.request_attachment.append_device_array(arr)
            resp = ch.call_method("EchoService.Echo", cntl,
                                  EchoRequest(message="cut"), EchoResponse)
            assert not cntl.failed(), cntl.error_text
            assert resp.message == "cut"
            assert cntl.response_attachment.to_bytes() == bytes(host)
            refs = cntl.response_attachment.device_refs()
            assert [r.length for r in refs] == [piece] * 16
            assert all(set(r.block.data.devices()) == {mesh.device(dev)}
                       for r in refs)
            after = self._stats()
            return after[0] - before[0], after[1] - before[1]
        finally:
            server.stop()

    def test_a_windowed_echo_cuts_every_piece_with_the_slicer(
            self, mesh, monkeypatch):
        """The request's sixteen pieces are cut by the compiled slicer, the
        reply's are whole blocks."""
        assert self._sixteen_piece_echo(mesh, monkeypatch, 5) == (16, 0)

    # -- layer span brpc.ici.cut: the dispatch alone (ISSUE 37) ----------

    @pytest.fixture
    def session(self, tmp_path):
        import jax
        from brpc_tpu.rpc import span
        span.layer_spans_reset()
        jax.profiler.start_trace(str(tmp_path))
        try:
            yield span
        finally:
            jax.profiler.stop_trace()
            span.layer_spans_reset()

    def test_in_a_session_each_compiled_cut_has_a_span_inside_its_piece(
            self, mesh, monkeypatch, session):
        """Sixteen ``brpc.ici.cut`` for the request's sixteen compiled cuts
        and none for the reply's whole blocks; each lies inside the
        ``brpc.ici.relocate`` of the ``brpc.ici.piece`` that caused it, on
        that piece's thread, and carries its thread's CPU time."""
        piece = 320 * 1024
        assert self._sixteen_piece_echo(mesh, monkeypatch, 6) == (16, 0)
        spans = session.layer_spans()
        cuts = [s for s in spans if s.name == "brpc.ici.cut"]
        pieces = {s.span_id: s for s in spans if s.name == "brpc.ici.piece"}
        relocates = {s.cause_id: s for s in spans
                     if s.name == "brpc.ici.relocate"}
        assert len(cuts) == 16 and len(pieces) >= 32
        assert len({c.cause_id for c in cuts}) == 16    # one a piece
        for c in cuts:
            p, r = pieces[c.cause_id], relocates[c.cause_id]
            assert (c.n, c.m, c.thread) == (piece, 0, p.thread)
            assert c.call_id == p.call_id
            assert p.start_ns == r.start_ns <= c.start_ns
            assert c.end_ns <= r.end_ns <= p.end_ns
            assert 0 <= c.cpu_ns <= c.end_ns - c.start_ns
            assert 0 <= p.cpu_ns <= p.end_ns - p.start_ns

    def test_in_a_session_the_eager_fall_back_says_m_1_and_a_whole_ref_nothing(
            self, mesh, session):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from brpc_tpu.ici import transport as tr
        host, arr = self._block(mesh)
        spread = jax.device_put(host, NamedSharding(
            Mesh(np.array(jax.devices()[:2]), ("x",)), P("x")))
        assert tr._cut(arr, self._ref(0, self.BLOCK)) is arr
        assert tr._cut(host, self._ref(4096, 4096)).base is host
        assert session.layer_spans() == []
        got = tr._cut(spread, self._ref(3 * 4096, 4096))
        assert bytes(np.asarray(got)) == bytes(host[3 * 4096:4 * 4096])
        tr._cut(arr, self._ref(4096, 100))
        eager, compiled = session.layer_spans()
        assert (eager.name, eager.n, eager.m) == ("brpc.ici.cut", 4096, 1)
        assert (compiled.name, compiled.n, compiled.m) \
            == ("brpc.ici.cut", 100, 0)
        assert eager.cause_id == compiled.cause_id == 0   # no piece open

    def test_a_cut_that_raises_still_ends_its_span(self, mesh, monkeypatch,
                                                   session):
        from brpc_tpu.ici import transport as tr
        host, arr = self._block(mesh)

        def refused(*a):
            raise RuntimeError("the runtime refused the dispatch")
        monkeypatch.setattr(tr, "piece_slicer", lambda: refused)
        with pytest.raises(RuntimeError, match="refused"):
            tr._cut(arr, self._ref(4096, 4096))
        cut, = session.layer_spans()
        assert cut.name == "brpc.ici.cut"
        after = session.layer_begin("brpc.after")   # the innermost again
        after.end()
        assert session.layer_spans()[-1].cause_id == 0
