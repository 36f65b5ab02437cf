"""Multi-controller ici://: 2-process echo over the fabric (VERDICT #4).

The reference tests distributed behavior with multiple in-process servers
on localhost TCP (SURVEY.md §4); the multi-CONTROLLER equivalent needs real
process isolation — each child owns its slice of the global device list,
jax.distributed is the out-of-band handshake channel, and device payloads
cross process boundaries through the transfer server (the RDMA-READ pull
model of src/brpc/rdma/rdma_endpoint.cpp translated to XLA).
"""
import os
import socket
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = r"""
import os, sys, time
sys.path.insert(0, %(repo)r)
sys.path.insert(0, os.path.join(%(repo)r, "tests"))
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np

pid = int(sys.argv[1])
coord = sys.argv[2]

from brpc_tpu.ici.fabric import FabricNode
node = FabricNode.initialize(coord, num_processes=2, process_id=pid)
kv = node._kv

import brpc_tpu.policy
from brpc_tpu import rpc, ici
from echo_pb2 import EchoRequest, EchoResponse

mesh = ici.IciMesh()          # global devices, identical in both processes
ici.IciMesh.set_default(mesh)
assert mesh.size == 4, mesh.size

if pid == 0:
    class EchoService(rpc.Service):
        @rpc.method(EchoRequest, EchoResponse)
        def Echo(self, cntl, request, response, done):
            response.message = "srv0:" + request.message
            if len(cntl.request_attachment):
                # bounce the device payload straight back
                cntl.response_attachment.append(cntl.request_attachment)
            done()

    server = rpc.Server()
    server.add_service(EchoService())
    assert server.start("ici://0") == 0
    kv.key_value_set("srv_up", "1")
    kv.wait_at_barrier("fabric_echo_done", 120000)
    server.stop()
    print("CHILD0_OK", flush=True)
else:
    kv.blocking_key_value_get("srv_up", 60000)
    ch = rpc.Channel()
    ch.init("ici://0", options=rpc.ChannelOptions(timeout_ms=60000,
                                                  max_retry=0))
    # plain echo
    cntl = rpc.Controller()
    resp = ch.call_method("EchoService.Echo", cntl,
                          EchoRequest(message="hello"), EchoResponse)
    assert not cntl.failed(), cntl.error_text
    assert resp.message == "srv0:hello", resp.message

    # echo with a device attachment living on THIS process's device —
    # crosses the process boundary via transfer-server pull both ways
    local_dev_idx = next(i for i, d in enumerate(jax.devices())
                         if d.process_index == pid)
    payload = jax.device_put(jnp.arange(4096, dtype=jnp.uint8),
                             jax.devices()[local_dev_idx])
    jax.block_until_ready(payload)
    cntl = rpc.Controller()
    cntl.request_attachment.append_device_array(payload)
    resp = ch.call_method("EchoService.Echo", cntl,
                          EchoRequest(message="att"), EchoResponse)
    assert not cntl.failed(), cntl.error_text
    assert resp.message == "srv0:att"
    got = cntl.response_attachment.to_bytes()
    np.testing.assert_array_equal(
        np.frombuffer(got, dtype=np.uint8),
        np.arange(4096, dtype=np.uint8))
    kv.wait_at_barrier("fabric_echo_done", 120000)
    print("CHILD1_OK", flush=True)
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


STRESS_CHILD = r"""
import os, sys, threading, time
sys.path.insert(0, %(repo)r)
sys.path.insert(0, os.path.join(%(repo)r, "tests"))
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np

pid = int(sys.argv[1])
coord = sys.argv[2]

from brpc_tpu.ici.fabric import FabricNode
node = FabricNode.initialize(coord, num_processes=2, process_id=pid)
kv = node._kv

import brpc_tpu.policy
from brpc_tpu import rpc, ici
from echo_pb2 import EchoRequest, EchoResponse

mesh = ici.IciMesh()
ici.IciMesh.set_default(mesh)

CHUNK = 6 * 1024 * 1024      # 6MB payloads (a 4MB piece and the rest) vs
THREADS, CALLS = 3, 3        # the 16MB window: 3 threads saturate it

if pid == 0:
    total = [0]
    lock = threading.Lock()

    class Sink(rpc.Service):
        @rpc.method(EchoRequest, EchoResponse)
        def Push(self, cntl, request, response, done):
            n = len(cntl.request_attachment)
            with lock:
                total[0] += n
            # bounce it back: the response direction saturates too
            cntl.response_attachment.append(cntl.request_attachment)
            response.message = str(total[0])
            done()

    server = rpc.Server()
    server.add_service(Sink())
    assert server.start("ici://0") == 0
    kv.key_value_set("stress_srv_up", "1")
    kv.wait_at_barrier("stress_done", 300000)
    expect = THREADS * CALLS * CHUNK
    assert total[0] == expect, (total[0], expect)
    server.stop()
    print("STRESS0_OK", flush=True)
else:
    kv.blocking_key_value_get("stress_srv_up", 60000)
    local_dev = next(i for i, d in enumerate(jax.devices())
                     if d.process_index == pid)
    payload = jax.device_put(jnp.arange(CHUNK, dtype=jnp.uint8),
                             jax.devices()[local_dev])
    jax.block_until_ready(payload)
    expect_bytes = bytes(np.asarray(payload))
    errs = []

    def worker():
        try:
            ch = rpc.Channel()
            ch.init("ici://0", options=rpc.ChannelOptions(
                timeout_ms=240000, max_retry=0))
            for _ in range(CALLS):
                cntl = rpc.Controller()
                cntl.request_attachment.append_device_array(payload)
                resp = ch.call_method("Sink.Push", cntl,
                                      EchoRequest(message="p"),
                                      EchoResponse)
                assert not cntl.failed(), cntl.error_text
                got = cntl.response_attachment.to_bytes()
                assert got == expect_bytes, "bounced payload corrupted"
        except Exception as e:
            errs.append(repr(e))

    threads = [threading.Thread(target=worker) for _ in range(THREADS)]
    for t in threads: t.start()
    for t in threads: t.join()
    assert not errs, errs
    # the window held several pieces at once: cuts were made while earlier
    # bytes of the socket were still un-consumed at the peer
    from brpc_tpu.ici.transport import ici_piece_stats
    assert ici_piece_stats()["pipelined_pieces"] > 0, ici_piece_stats()
    kv.wait_at_barrier("stress_done", 300000)
    print("STRESS1_OK", flush=True)
"""


def _run_pair(script: str, timeout: int = 240):
    coord = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env.pop("JAX_NUM_PROCESSES", None)
    procs = [subprocess.Popen(
        [sys.executable, "-c", script, str(i), coord],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env)
        for i in range(2)]
    outs = []
    rcs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
        outs.append(out)
        rcs.append(p.returncode)
    assert rcs == [0, 0], (
        f"--- child0 ---\n{outs[0]}\n--- child1 ---\n{outs[1]}")
    return outs


def test_two_process_echo_over_ici_fabric():
    outs = _run_pair(CHILD % {"repo": REPO})
    assert "CHILD0_OK" in outs[0]
    assert "CHILD1_OK" in outs[1]


def test_two_process_window_saturation_stress():
    """Concurrent bulk device transfers past the send window, both
    directions, with byte-exact verification (VERDICT r3 #6: the fabric
    must survive window saturation, and a graceful close must not drop
    the in-flight tail)."""
    outs = _run_pair(STRESS_CHILD % {"repo": REPO}, timeout=300)
    assert "STRESS0_OK" in outs[0]
    assert "STRESS1_OK" in outs[1]


_XFER_FLAG = '''
from brpc_tpu.butil import flags as _xfl
_xfl.set_flag("ici_fabric_bulk", False)
'''

# pin the same-host shm ring tier off for tests that assert the socket
# bulk plane's engagement byte-exactly (shm outranks it in the route
# table; its own coverage lives in tests/test_shm.py)
_SHM_OFF_FLAG = '''
from brpc_tpu.butil import flags as _sfl
_sfl.set_flag("ici_fabric_shm", False)
'''


def test_two_process_stress_over_transfer_server():
    """The flagged pod-DMA alternative (ici_fabric_bulk=False: device
    payloads ride jax transfer-server pulls with staged-until-PULLED
    custody) must keep passing the same byte-exact saturation stress —
    the bulk plane's default would otherwise silently orphan this
    path's coverage."""
    child = STRESS_CHILD % {"repo": REPO}
    marker = "from brpc_tpu.ici.fabric import FabricNode"
    assert marker in child    # a silent no-op here would re-test the
    # bulk plane and leave the pod-DMA path uncovered again
    # the flag is defined at fabric-module import: inject AFTER it
    child = child.replace(marker, marker + _XFER_FLAG)
    outs = _run_pair(child, timeout=300)
    assert "STRESS0_OK" in outs[0]
    assert "STRESS1_OK" in outs[1]


def test_uds_failure_falls_back_to_tcp_bulk():
    """A same-host peer whose advertised abstract-unix name cannot be
    dialed (stale info, netns boundary) must fall back to the TCP bulk
    plane transparently — bulk still engaged, bytes still exact."""
    child = CHILD % {"repo": REPO}
    inject = '''
    info = node.peer_info(0)
    # preconditions: the UDS branch must actually be reachable, or this
    # test passes vacuously on plain TCP (review finding)
    assert info.get("bulk_uds"), "peer advertised no UDS plane"
    assert info.get("host") == node.host_ip, (info, node.host_ip)
    info["bulk_uds"] = "brpc_tpu_fab.nonexistent.0"   # poison the cache
'''
    marker = '    kv.blocking_key_value_get("srv_up", 60000)\n'
    assert marker in child
    child = child.replace(marker, marker + inject)
    check = '''
    from brpc_tpu.ici.fabric import FabricSocket
    from brpc_tpu.rpc.socket import list_sockets
    fabs = [s for s in list_sockets() if isinstance(s, FabricSocket)]
    assert fabs and all(s._bulk for s in fabs), "tcp bulk fallback failed"
'''
    tail = '    kv.wait_at_barrier("fabric_echo_done", 120000)\n'
    assert child.count(tail) == 2     # server branch + client branch
    head, client_part = child.rsplit(tail, 1)
    child = head + check + tail + client_part   # client-side only: the
    # server's barrier runs before any client has connected
    outs = _run_pair(child)
    assert "CHILD0_OK" in outs[0]
    assert "CHILD1_OK" in outs[1]


class TestFabricUnits:
    def test_derive_host_ip(self):
        from brpc_tpu.ici.fabric import FabricNode
        # loopback coordinator → loopback self (route resolution)
        assert FabricNode._derive_host_ip("127.0.0.1:1234") == "127.0.0.1"
        # no coordinator → safe default, never an exception
        assert FabricNode._derive_host_ip(None) == "127.0.0.1"
        assert FabricNode._derive_host_ip("") == "127.0.0.1"
        # unroutable/garbage host falls back instead of raising
        assert isinstance(
            FabricNode._derive_host_ip("nonexistent.invalid:1"), str)
        # port-less address: rpartition used to yield host='' and
        # port=<hostname>, so int(port) raised ValueError straight
        # through initialize() — must fall back/resolve, never raise
        assert FabricNode._derive_host_ip("127.0.0.1") == "127.0.0.1"
        assert FabricNode._derive_host_ip("somehost.invalid") == "127.0.0.1"
        # IPv6 forms misparse under AF_INET → clean fallback
        assert FabricNode._derive_host_ip("[::1]:1234") == "127.0.0.1"
        assert FabricNode._derive_host_ip("[::]") == "127.0.0.1"

    def test_graceful_fin_waits_for_inflight_device_frame(self, monkeypatch):
        """EOF rides the ordered delivery queue: a FIN arriving while a
        device frame still awaits its pull must not surface EOF first
        (the stream tail would be dropped)."""
        from brpc_tpu.ici import transport as T
        from brpc_tpu.ici.fabric import FabricSocket

        sock = FabricSocket.__new__(FabricSocket)
        import threading as _threading
        from brpc_tpu.butil.iobuf import IOBuf
        sock._inbox = IOBuf()
        sock._inbox_lock = _threading.Lock()
        sock._peer_closed = False
        sock._conn_dead = False
        sock._fin_code = 0
        sock._staged = {}
        sock._staged_lock = _threading.Lock()
        sock._bulk = 0
        sock._blib = None
        sock._bulk_lock = _threading.Lock()
        sock._reestab_pending = None
        sock._reestab_evt = _threading.Event()
        sock._shm = 0
        sock._shm_dead = 0
        sock._shmlib = None
        sock._shm_reestab_pending = None
        sock._shm_reestab_evt = _threading.Event()
        sock._dplane_lock = _threading.Lock()
        sock._dplane_seq = None
        sock._dplane_closed = False
        sock._init_delivery()
        events = []
        sock.start_input_event = lambda *a, **k: events.append("input")
        sock._wake_window = lambda: None
        sock._flush_staged = lambda: None

        pending = []

        class FakeDisp:
            def on_ready(self, arrays, cb):
                pending.append(cb)

        monkeypatch.setattr(T, "_all_ready", lambda arrays: False)
        monkeypatch.setattr(T.DeviceEventDispatcher, "instance",
                            classmethod(lambda cls: FakeDisp()))
        # a device-bearing frame is in flight...
        committed = []
        sock._enqueue_delivery([object()], lambda: committed.append(1))
        # ...when the connection ends
        sock._on_connection_over()
        assert sock._conn_dead is True       # writers fail immediately
        assert sock._peer_closed is False    # but EOF has NOT jumped ahead
        pending[0]()                         # the pull completes
        assert committed == [1]
        assert sock._peer_closed is True     # now EOF commits, in order
        assert "input" in events


class TestNativeBulkPlane:
    """The native bulk data plane alone (native/fabric.cpp): uuid-tagged
    frames over a dedicated connection, exercised single-process over
    both transports.  The 2-process tests above exercise it end-to-end
    under the RPC stack; these pin the ABI contract."""

    @pytest.fixture()
    def lib(self):
        from brpc_tpu.butil import native
        lib = native.load()
        if lib is None:
            pytest.skip("native core unavailable")
        return lib

    def _pair(self, lib, key=b"t", uds=False):
        import ctypes
        port = ctypes.c_int()
        uds_out = ctypes.create_string_buffer(108)
        lh = lib.brpc_tpu_fab_listen(b"127.0.0.1", ctypes.byref(port),
                                     uds_out, 108)
        assert lh
        if uds:
            assert uds_out.value, "abstract unix listener did not bind"
            ch = lib.brpc_tpu_fab_connect_uds(uds_out.value, key)
        else:
            ch = lib.brpc_tpu_fab_connect(b"127.0.0.1", port.value, key)
        assert ch
        sh = lib.brpc_tpu_fab_accept(lh, key, 10_000_000)
        assert sh
        return lh, ch, sh

    @pytest.mark.parametrize("uds", [False, True])
    def test_out_of_order_claim_both_transports(self, lib, uds):
        """Frames are claimed BY UUID, not arrival order — the control
        descriptor and the bulk bytes ride different connections, so the
        receiver must tolerate either order."""
        import ctypes
        u8p = ctypes.POINTER(ctypes.c_uint8)
        lh, ch, sh = self._pair(lib, b"ooo", uds=uds)
        try:
            for uuid, fill in ((7, 0x11), (8, 0x22), (9, 0x33)):
                data = (ctypes.c_uint8 * 1000)(*([fill] * 1000))
                assert lib.brpc_tpu_fab_send(ch, uuid, data, 1000) == 0
            for uuid, fill in ((9, 0x33), (7, 0x11), (8, 0x22)):
                out, olen = u8p(), ctypes.c_uint64()
                rc = lib.brpc_tpu_fab_recv(sh, uuid, 10_000_000,
                                           ctypes.byref(out),
                                           ctypes.byref(olen))
                assert rc == 0 and olen.value == 1000
                assert out[0] == fill and out[999] == fill
                lib.brpc_tpu_fab_buf_release(sh, out, olen.value)
        finally:
            lib.brpc_tpu_fab_conn_close(ch)
            lib.brpc_tpu_fab_conn_close(sh)
            lib.brpc_tpu_fab_listener_close(lh)

    def test_claim_timeout_and_dead_conn(self, lib):
        import ctypes
        u8p = ctypes.POINTER(ctypes.c_uint8)
        lh, ch, sh = self._pair(lib, b"to")
        try:
            out, olen = u8p(), ctypes.c_uint64()
            # absent uuid: bounded timeout, rc -1
            rc = lib.brpc_tpu_fab_recv(sh, 404, 50_000, ctypes.byref(out),
                                       ctypes.byref(olen))
            assert rc == -1
            # a frame sent BEFORE the peer closes is claimable AFTER the
            # close (control descriptor may lag the bulk bytes)
            data = (ctypes.c_uint8 * 16)(*([5] * 16))
            assert lib.brpc_tpu_fab_send(ch, 42, data, 16) == 0
            import time
            time.sleep(0.2)              # let the reader park the frame
            lib.brpc_tpu_fab_conn_close(ch)
            rc = lib.brpc_tpu_fab_recv(sh, 42, 5_000_000,
                                       ctypes.byref(out),
                                       ctypes.byref(olen))
            assert rc == 0 and olen.value == 16 and out[3] == 5
            lib.brpc_tpu_fab_buf_release(sh, out, olen.value)
            # now the conn is dead and drained: missing uuids fail fast
            rc = lib.brpc_tpu_fab_recv(sh, 505, 10_000_000,
                                       ctypes.byref(out),
                                       ctypes.byref(olen))
            assert rc == -2
            # send on the closed side fails cleanly
            assert lib.brpc_tpu_fab_send(ch, 1, data, 16) == -1
        finally:
            lib.brpc_tpu_fab_conn_close(sh)
            lib.brpc_tpu_fab_listener_close(lh)

    def test_buffer_pool_reuses_exact_size(self, lib):
        """Released buffers recycle for same-size frames (the page-fault
        economy the pool exists for): the second claim of an equal-size
        frame returns the SAME address."""
        import ctypes
        u8p = ctypes.POINTER(ctypes.c_uint8)
        lh, ch, sh = self._pair(lib, b"pool")
        try:
            data = (ctypes.c_uint8 * 4096)(*([1] * 4096))
            assert lib.brpc_tpu_fab_send(ch, 1, data, 4096) == 0
            out, olen = u8p(), ctypes.c_uint64()
            assert lib.brpc_tpu_fab_recv(sh, 1, 5_000_000,
                                         ctypes.byref(out),
                                         ctypes.byref(olen)) == 0
            first_addr = ctypes.addressof(out.contents)
            lib.brpc_tpu_fab_buf_release(sh, out, olen.value)
            assert lib.brpc_tpu_fab_send(ch, 2, data, 4096) == 0
            out2, olen2 = u8p(), ctypes.c_uint64()
            assert lib.brpc_tpu_fab_recv(sh, 2, 5_000_000,
                                         ctypes.byref(out2),
                                         ctypes.byref(olen2)) == 0
            assert ctypes.addressof(out2.contents) == first_addr
            lib.brpc_tpu_fab_buf_release(sh, out2, olen2.value)
        finally:
            lib.brpc_tpu_fab_conn_close(ch)
            lib.brpc_tpu_fab_conn_close(sh)
            lib.brpc_tpu_fab_listener_close(lh)

    def test_accept_key_mismatch_times_out(self, lib):
        import ctypes
        port = ctypes.c_int()
        uds_out = ctypes.create_string_buffer(108)
        lh = lib.brpc_tpu_fab_listen(b"127.0.0.1", ctypes.byref(port),
                                     uds_out, 108)
        try:
            ch = lib.brpc_tpu_fab_connect(b"127.0.0.1", port.value, b"A")
            assert ch
            assert lib.brpc_tpu_fab_accept(lh, b"B", 100_000) == 0
            sh = lib.brpc_tpu_fab_accept(lh, b"A", 5_000_000)
            assert sh
            lib.brpc_tpu_fab_conn_close(ch)
            lib.brpc_tpu_fab_conn_close(sh)
        finally:
            lib.brpc_tpu_fab_listener_close(lh)

    def test_concurrent_send_recv_close_hammer(self, lib):
        """Teardown vs traffic: concurrent senders, claimers, and an
        asynchronous close must end in clean failures (rc -1/-2), never
        a hang, crash, or double free.  Pins the close_join/wmu
        exclusion (a closing fd must not be recycled under a writer)."""
        import ctypes
        import threading
        import time
        u8p = ctypes.POINTER(ctypes.c_uint8)
        for round_ in range(6):
            lh, ch, sh = self._pair(lib, b"hammer%d" % round_)
            stop = threading.Event()
            errs = []

            def sender():
                # stop is only a wedge-breaker: the sender may be
                # descheduled across the close+stop window and exit via
                # the flag without ever observing a failed send — that
                # is a scheduling outcome, not a product failure
                data = (ctypes.c_uint8 * 8192)(*([3] * 8192))
                uuid = round_ * 1_000_000
                while not stop.is_set():
                    uuid += 1
                    if lib.brpc_tpu_fab_send(ch, uuid, data, 8192) != 0:
                        return      # conn died under us: expected

            def claimer():
                out, olen = u8p(), ctypes.c_uint64()
                uuid = round_ * 1_000_000
                while True:
                    uuid += 1
                    rc = lib.brpc_tpu_fab_recv(sh, uuid, 2_000_000,
                                               ctypes.byref(out),
                                               ctypes.byref(olen))
                    if rc == 0:
                        lib.brpc_tpu_fab_buf_release(sh, out, olen.value)
                    else:
                        return      # timeout (-1) or dead (-2): expected

            ts = [threading.Thread(target=sender, daemon=True),
                  threading.Thread(target=claimer, daemon=True)]
            for t in ts:
                t.start()
            time.sleep(0.05)
            # close BOTH ends while traffic is in flight
            lib.brpc_tpu_fab_conn_close(ch)
            lib.brpc_tpu_fab_conn_close(sh)
            stop.set()
            for t in ts:
                t.join(timeout=10)
                assert not t.is_alive(), "hammer thread wedged"
            assert not errs, errs
            lib.brpc_tpu_fab_listener_close(lh)


STREAM_CHILD = r"""
import os, sys, threading, time
sys.path.insert(0, %(repo)r)
sys.path.insert(0, os.path.join(%(repo)r, "tests"))
import jax
jax.config.update("jax_platforms", "cpu")

pid = int(sys.argv[1]); coord = sys.argv[2]
from brpc_tpu.ici.fabric import FabricNode
node = FabricNode.initialize(coord, num_processes=2, process_id=pid)
kv = node._kv
import brpc_tpu.policy
from brpc_tpu import rpc, ici
from brpc_tpu.butil.iobuf import IOBuf
from echo_pb2 import EchoRequest, EchoResponse
mesh = ici.IciMesh(); ici.IciMesh.set_default(mesh)

CHUNK = 256 * 1024   # >= ici_stream_bulk_threshold: DATA rides the bulk plane
N = %(n)d            # chunks per pass
PASSES = %(passes)d  # peak-of-passes: the two processes share one core
                     # with the OS, a single pass can eat a scheduling
                     # artifact (same methodology as the bulk tier)

def body_for(seq):
    return b"%%08d" %% seq + bytes([seq %% 251]) * (CHUNK - 8)

# chunk bodies are precomputed OUTSIDE the timed region on both ends:
# constructing a 256KB pattern per chunk costs ~50us of the one shared
# core per frame — harness work that would be billed to the transport
EXPECT = [body_for(s) for s in range(PASSES * N)]

if pid == 0:
    got = {"n": 0, "bytes": 0, "bad": 0}
    done_evt = threading.Event()

    class Sink:
        def on_received_messages(self, sid, msgs):
            for m in msgs:
                b = m.to_bytes()
                # byte-exact AND order-exact: memcmp against the
                # precomputed body for the next expected seq
                if got["n"] >= len(EXPECT) or b != EXPECT[got["n"]]:
                    got["bad"] += 1
                # bytes BEFORE n: the main loop publishes the ack on
                # byte volume, and a preemption between the two writes
                # would ack short of the final chunk (review finding)
                got["bytes"] += len(b)
                got["n"] += 1

        def on_closed(self, sid):
            done_evt.set()

    class StreamSvc(rpc.Service):
        @rpc.method(EchoRequest, EchoResponse)
        def Start(self, cntl, request, response, done):
            rpc.stream_accept(cntl, rpc.StreamOptions(handler=Sink()))
            response.message = "ok"
            done()

    server = rpc.Server(); server.add_service(StreamSvc())
    assert server.start("ici://0") == 0
    kv.key_value_set("st_srv_up", "1")
    deadline = time.time() + 240
    for p in range(PASSES):
        want = (p + 1) * N * CHUNK
        while got["bytes"] < want and time.time() < deadline:
            time.sleep(0.001)
        # per-pass consumption ack BEFORE any assertion: the client's
        # clock stops on this, so it must reflect delivered-and-verified
        # volume (not bytes still in flight)
        kv.key_value_set("st_acked_%%d" %% p, str(got["bytes"]))
    assert done_evt.wait(120), "stream never closed"
    assert got["n"] == PASSES * N, got
    assert got["bytes"] == PASSES * N * CHUNK, got
    assert got["bad"] == 0, got
    kv.wait_at_barrier("st_done", 120000)
    server.stop()
    print("ST0_OK", flush=True)
else:
    kv.blocking_key_value_get("st_srv_up", 60000)
    ch = rpc.Channel()
    ch.init("ici://0", options=rpc.ChannelOptions(timeout_ms=60000,
                                                  max_retry=0))
    cntl = rpc.Controller()
    stream = rpc.stream_create(cntl, rpc.StreamOptions(max_buf_size=8 << 20))
    resp = ch.call_method("StreamSvc.Start", cntl,
                          EchoRequest(message="s"), EchoResponse)
    assert not cntl.failed(), cntl.error_text
    assert stream.wait_connected(10)
    best = 0.0
    seq = 0
    for p in range(PASSES):
        t0 = time.perf_counter()
        for _ in range(N):
            assert stream.write(IOBuf(EXPECT[seq]), timeout=30) == 0
            seq += 1
        # clock stops on the server's consumed-and-verified ack, not on
        # the last write returning — up to max_buf_size of the volume is
        # still in flight at that point and would inflate the number
        acked = int(kv.blocking_key_value_get("st_acked_%%d" %% p, 120000))
        dt = time.perf_counter() - t0
        assert acked >= (p + 1) * N * CHUNK, acked
        best = max(best, N * CHUNK / dt / 1e6)
    stream.close()
    print("FABRIC_STREAM_MBPS %%.1f best_of=%%d" %% (best, PASSES),
          flush=True)
    # which fast plane carried the DATA payloads (route assertion)
    from brpc_tpu.ici.fabric import FabricSocket
    from brpc_tpu.rpc.socket import list_sockets
    shm_b = sum(s.shm_bytes_sent for s in list_sockets()
                if isinstance(s, FabricSocket))
    bulk_b = sum(s.bulk_bytes_sent for s in list_sockets()
                 if isinstance(s, FabricSocket))
    print("ST_ROUTE shm=%%d bulk=%%d" %% (shm_b, bulk_b), flush=True)
    kv.wait_at_barrier("st_done", 120000)
    print("ST1_OK", flush=True)
"""


# Correctness child for streaming-over-bulk: frames alternate below and
# above ici_stream_bulk_threshold, the server asserts byte-exact payloads
# IN SEQ ORDER, both ends assert the credit/feedback loop moved, and the
# client asserts the large frames actually rode the bulk plane.
MIXED_STREAM_CHILD = r"""
import os, sys, threading, time
sys.path.insert(0, %(repo)r)
sys.path.insert(0, os.path.join(%(repo)r, "tests"))
import jax
jax.config.update("jax_platforms", "cpu")

pid = int(sys.argv[1]); coord = sys.argv[2]
from brpc_tpu.ici.fabric import FabricNode
node = FabricNode.initialize(coord, num_processes=2, process_id=pid)
kv = node._kv
import brpc_tpu.policy
from brpc_tpu import rpc, ici
from brpc_tpu.butil.iobuf import IOBuf
from echo_pb2 import EchoRequest, EchoResponse
mesh = ici.IciMesh(); ici.IciMesh.set_default(mesh)

BIG = 256 * 1024     # >= threshold: descriptor on control, bytes on bulk
SMALL = 1024         # < threshold: inline control frame (latency path)
N = %(n)d            # alternating big/small, starting big
WINDOW = 2 * 1024 * 1024

def body_for(seq):
    size = BIG if seq %% 2 == 0 else SMALL
    return b"%%08d" %% seq + bytes([(seq * 7 + 3) %% 251]) * (size - 8)

TOTAL = sum(len(body_for(s)) for s in range(N))

if pid == 0:
    state = {"next": 0, "bad": []}
    streams = []
    done_evt = threading.Event()

    class Sink:
        def on_received_messages(self, sid, msgs):
            for m in msgs:
                # byte-exact AND in seq order: a reordered or corrupted
                # frame fails here, whichever plane carried it
                if m.to_bytes() != body_for(state["next"]):
                    state["bad"].append(state["next"])
                state["next"] += 1

        def on_closed(self, sid):
            done_evt.set()

    class StreamSvc(rpc.Service):
        @rpc.method(EchoRequest, EchoResponse)
        def Start(self, cntl, request, response, done):
            streams.append(rpc.stream_accept(
                cntl, rpc.StreamOptions(handler=Sink())))
            response.message = "ok"
            done()

    server = rpc.Server(); server.add_service(StreamSvc())
    assert server.start("ici://0") == 0
    kv.key_value_set("mx_srv_up", "1")
    assert done_evt.wait(180), ("stream never closed", state["next"])
    assert state["next"] == N, state
    assert not state["bad"], state["bad"][:5]
    # credit accounting unchanged by the bulk route: every byte passed
    # through the consumption/feedback machinery
    assert streams[0]._local_consumed == TOTAL, (
        streams[0]._local_consumed, TOTAL)
    kv.wait_at_barrier("mx_done", 120000)
    server.stop()
    print("MX0_OK", flush=True)
else:
    kv.blocking_key_value_get("mx_srv_up", 60000)
    ch = rpc.Channel()
    ch.init("ici://0", options=rpc.ChannelOptions(timeout_ms=60000,
                                                  max_retry=0))
    cntl = rpc.Controller()
    stream = rpc.stream_create(
        cntl, rpc.StreamOptions(max_buf_size=WINDOW))
    resp = ch.call_method("StreamSvc.Start", cntl,
                          EchoRequest(message="s"), EchoResponse)
    assert not cntl.failed(), cntl.error_text
    assert stream.wait_connected(10)
    assert TOTAL > 2 * WINDOW   # the writer MUST block on the window at
    # least once, so the assertions below prove feedback actually flowed
    for seq in range(N):
        assert stream.write(IOBuf(body_for(seq)), timeout=60) == 0
    # sender-side credit accounting: produced == total, and feedback
    # advanced the remote-consumed watermark (the final write could not
    # have been admitted otherwise)
    assert stream._produced == TOTAL, (stream._produced, TOTAL)
    assert stream._remote_consumed >= TOTAL - WINDOW, (
        stream._remote_consumed, TOTAL, WINDOW)
    from brpc_tpu.ici.fabric import FabricSocket
    from brpc_tpu.rpc.socket import list_sockets
    fabs = [s for s in list_sockets() if isinstance(s, FabricSocket)]
    assert fabs, "no fabric socket"
    big_total = sum(len(body_for(s)) for s in range(N) if s %% 2 == 0)
    bulk_out = sum(s._blib.brpc_tpu_fab_bytes(s._bulk, 1)
                   for s in fabs if s._bulk)
    %(bulk_assert)s
    stream.close()
    kv.wait_at_barrier("mx_done", 120000)
    print("MX1_OK", flush=True)
"""

# with the bulk plane bound, every big frame's payload must have ridden
# it — and ONLY the big frames (small ones keep the inline latency path)
_BULK_ON_ASSERT = ("assert bulk_out == big_total, (bulk_out, big_total)")
# with the bulk plane disabled end-to-end, the stream must fall back to
# the inline path transparently: no bulk conn, no bulk bytes
_BULK_OFF_ASSERT = (
    "assert all(not s._bulk for s in fabs), 'bulk conn unexpectedly bound'\n"
    "    assert bulk_out == 0, bulk_out")


def test_streaming_over_cross_process_fabric():
    """Streaming RPC across a real process boundary rides the bulk fast
    plane: DATA frames >= ici_stream_bulk_threshold put only a 16-byte
    descriptor on the control channel while the payload gather-sends on
    the native bulk connection; smaller frames keep the inline path.
    Byte-exact seq-order verification server-side, credit accounting
    asserted on both ends, bulk engagement asserted byte-exactly.

    The same-host shm ring tier is pinned OFF here: it outranks the
    socket bulk conn in the route table, and this test exists to keep
    the UDS/TCP leg honest (tests/test_shm.py owns the shm leg)."""
    child = MIXED_STREAM_CHILD % {"repo": REPO, "n": 80,
                                  "bulk_assert": _BULK_ON_ASSERT}
    marker = "from brpc_tpu.ici.fabric import FabricNode"
    assert marker in child
    child = child.replace(marker, marker + _SHM_OFF_FLAG)
    outs = _run_pair(child, timeout=240)
    assert "MX0_OK" in outs[0]
    assert "MX1_OK" in outs[1]


def test_streaming_falls_back_inline_without_bulk_plane():
    """With the native bulk plane disabled (ici_fabric_bulk=False — the
    pod-DMA configuration), stream DATA frames of every size must fall
    back to the inline control-channel path transparently: same bytes,
    same order, same credit loop."""
    child = MIXED_STREAM_CHILD % {"repo": REPO, "n": 40,
                                  "bulk_assert": _BULK_OFF_ASSERT}
    marker = "from brpc_tpu.ici.fabric import FabricNode"
    assert marker in child
    child = child.replace(marker, marker + _XFER_FLAG)
    outs = _run_pair(child, timeout=240)
    assert "MX0_OK" in outs[0]
    assert "MX1_OK" in outs[1]


def test_streaming_perf_child_smoke():
    """The streaming child with per-pass acks (STREAM_CHILD) stays runnable:
    a short 2-pass run with per-pass consumed acks."""
    outs = _run_pair(STREAM_CHILD % {"repo": REPO, "n": 8, "passes": 2},
                     timeout=240)
    assert "ST0_OK" in outs[0]
    assert "ST1_OK" in outs[1]
    assert any(line.startswith("FABRIC_STREAM_MBPS")
               for line in outs[1].splitlines())
