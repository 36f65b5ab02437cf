"""Layer spans (brpc_tpu/butil/layer_span.py, by the names rpc/span.py
re-exports): the recorder that a jax profiler
session switches on, and the span sites at the layer boundaries — the client
call, the five server stages, the ici plane's window pieces, the device
poller.

One profiler session is made for the whole file (``traced``): a call whose
reply is parked on a device completion over the native ici tier and over tcp
tpu_std, and an echo whose frames cross a small ici send window in three
pieces each way.  Most cases below read what that session recorded; the
closed window's stall is driven on a socket pair, where it cannot be missed.
"""
import glob
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import brpc_tpu.policy  # noqa: F401  (registers protocols)
from brpc_tpu import rpc, ici
from brpc_tpu.bthread.device_waiter import device_on_ready
from brpc_tpu.butil import flags as _flags
from brpc_tpu.butil import layer_span
from brpc_tpu.ici import native_plane
from brpc_tpu.rpc import span
from tests.echo_pb2 import EchoRequest, EchoResponse

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STAGES = ["queue", "parse", "handler", "encode", "write"]
WINDOW = 2 << 20                # the small send window of the 3-piece echo
BULK = 5_000_000                # above the native tier's 4 MB window


class LayerService(rpc.Service):
    SERVICE_NAME = "LayerService"

    @rpc.method(EchoRequest, EchoResponse)
    def Echo(self, cntl, request, response, done):
        response.message = request.message
        cntl.response_attachment.append(cntl.request_attachment)
        done()

    @rpc.method(EchoRequest, EchoResponse)
    def Parked(self, cntl, request, response, done):
        """Compute on the device, answer on its completion."""
        import jax.numpy as jnp
        x = jnp.asarray(np.frombuffer(
            cntl.request_attachment.to_bytes(), dtype=np.uint8))
        y = x ^ jnp.uint8(0x5A)

        def reply():
            response.message = request.message
            done()

        cntl.response_attachment.append_device_array(y)
        device_on_ready([y], reply)


def _call(ch, method, payload, message="m"):
    cntl = rpc.Controller()
    cntl.request_attachment.append_device_array(payload)
    resp = ch.call_method(f"LayerService.{method}", cntl,
                          EchoRequest(message=message), EchoResponse)
    assert not cntl.failed(), cntl.error_text
    assert resp.message == message
    return cntl


class _Deployment:
    def __init__(self, address, options=None, server_options=None):
        self.server = rpc.Server(server_options or rpc.ServerOptions())
        self.server.add_service(LayerService())
        assert self.server.start(address) == 0
        if address.startswith("tcp"):
            address = f"tcp://127.0.0.1:{self.server.listen_port}"
        self.channel = rpc.Channel()
        assert self.channel.init(
            address, options=options or rpc.ChannelOptions()) == 0

    def close(self):
        self.channel.close()
        self.server.stop()


@pytest.fixture(scope="module")
def mesh():
    import jax
    m = ici.IciMesh(jax.devices())
    before = ici.IciMesh._default
    ici.IciMesh.set_default(m)
    yield m
    ici.IciMesh.set_default(before)


def _payload(mesh, dev, nbytes):
    import jax
    import jax.numpy as jnp
    arr = jax.device_put(jnp.arange(nbytes, dtype=jnp.uint8),
                         mesh.device(dev))
    jax.block_until_ready(arr)
    return arr


@pytest.fixture(scope="module")
def traced(mesh, tmp_path_factory):
    """{scenario: its spans}, the session's trace directory under
    ``"dir"``, what the store held before the session under ``"before"``."""
    import jax
    if not native_plane.available():
        pytest.skip("native core unavailable")
    trace_dir = str(tmp_path_factory.mktemp("layer_trace"))
    old_window = _flags.get_flag("ici_socket_window_bytes")
    native = _Deployment("ici://2", rpc.ChannelOptions(ici_local_device=2))
    tcp = _Deployment("tcp://127.0.0.1:0")
    _flags.set_flag("ici_socket_window_bytes", WINDOW)
    inline = rpc.ServerOptions()
    inline.usercode_inline = True
    bulk = _Deployment("ici://3", rpc.ChannelOptions(ici_local_device=3),
                       inline)
    small, big = _payload(mesh, 2, 4096), _payload(mesh, 3, BULK)
    out = {}
    try:
        span.layer_spans_reset()
        for _ in range(100):            # no session: nothing is recorded
            _call(native.channel, "Echo", small)
        _call(tcp.channel, "Parked", small)
        _call(bulk.channel, "Echo", big)
        out["before"] = (span.layer_spans(), span.layer_spans_dropped())
        jax.profiler.start_trace(trace_dir)
        try:
            for name, dep, method, payload in (
                    ("native", native, "Parked", small),
                    ("tcp", tcp, "Parked", small),
                    ("bulk", bulk, "Echo", big)):
                since = span.layer_mark().ns
                cntl = _call(dep.channel, method, payload)
                # the server stamps its write stage once the respond call
                # has returned, which the caller's wake-up may beat; a
                # parked handler's completion ends its callback span after
                # that again
                last = "brpc.server.write" if name == "bulk" \
                    else "brpc.poller.callback"
                deadline = time.monotonic() + 10
                while not _named(span.layer_spans(since), last) \
                        and time.monotonic() < deadline:
                    time.sleep(0.005)
                out[name] = span.layer_spans(since)
                if name == "bulk":
                    assert cntl.response_attachment.to_bytes() == bytes(
                        np.asarray(big))
        finally:
            jax.profiler.stop_trace()
        out["dir"] = trace_dir
        out["native_requests"] = native.server._native_ici.requests()
    finally:
        _flags.set_flag("ici_socket_window_bytes", old_window)
        for dep in (native, tcp, bulk):
            dep.close()
        span.layer_spans_reset()
    return out


def _named(spans, name):
    return [s for s in spans if s.name == name]


def _one(spans, name):
    found = _named(spans, name)
    assert len(found) == 1, (name, found)
    return found[0]


# ---- the switch ------------------------------------------------------------

def test_span_module_imports_without_jax():
    code = ("import sys; import brpc_tpu.rpc.span as s; "
            "assert 'jax' not in sys.modules; assert s.layer_on() is False")
    subprocess.run([sys.executable, "-c", code], cwd=REPO, check=True,
                   timeout=120)


def test_the_poller_reaches_the_recorder_without_the_rpc_layer():
    code = ("import sys; import brpc_tpu.bthread.device_waiter; "
            "import brpc_tpu.butil.layer_span as s; "
            "assert not [m for m in sys.modules if m.startswith("
            "'brpc_tpu.rpc') or m == 'jax']; assert s.layer_on() is False")
    subprocess.run([sys.executable, "-c", code], cwd=REPO, check=True,
                   timeout=120)


@pytest.mark.parametrize("mode,session,want", [
    ("on", False, True), ("sampled", True, True), ("sampled", False, False),
    ("off", True, False),       # "off" disables everything, a session too
])
def test_a_session_decomposes_every_request_unless_the_flag_is_off(
        monkeypatch, mode, session, want):
    from brpc_tpu.policy import tpu_std
    monkeypatch.setattr(tpu_std._stage_flag, "value", mode)
    monkeypatch.setattr(layer_span, "layer_on", lambda: session)
    assert tpu_std._stages_on() is want


def test_no_profiler_session_no_record(traced):
    spans, dropped = traced["before"]
    assert spans == [] and dropped == 0
    assert traced["native_requests"] >= 100     # the traffic was real
    assert span.layer_on() is False


# ---- one call, layer by layer ----------------------------------------------

@pytest.mark.parametrize("plane", ["native", "tcp"])
def test_call_span_encloses_its_wait(traced, plane):
    spans = traced[plane]
    call, wait = _one(spans, "brpc.call"), _one(spans, "brpc.call.wait")
    assert call.start_ns <= wait.start_ns <= wait.end_ns <= call.end_ns
    assert wait.cause_id == call.span_id and call.cause_id == 0
    assert wait.thread == call.thread
    if plane == "tcp":
        assert call.call_id != 0 and wait.call_id == call.call_id
    else:                       # native/rpc.cpp correlates: Python sees no id
        assert call.call_id == 0


@pytest.mark.parametrize("stage", STAGES)
@pytest.mark.parametrize("plane", ["native", "tcp"])
def test_server_stage_is_recorded_under_the_calls_id(traced, plane, stage):
    spans = traced[plane]
    call = _one(spans, "brpc.call")
    got = _one(spans, f"brpc.server.{stage}")
    assert call.start_ns <= got.start_ns <= got.end_ns <= call.end_ns
    ids = {_one(spans, f"brpc.server.{s}").call_id for s in STAGES}
    assert len(ids) == 1 and got.call_id != 0
    if plane == "tcp":          # the correlation id rides RpcMeta
        assert got.call_id == call.call_id


@pytest.mark.parametrize("plane", ["native", "tcp"])
def test_stages_follow_each_other(traced, plane):
    q, p, h, e, w = (_one(traced[plane], f"brpc.server.{s}") for s in STAGES)
    assert q.end_ns <= p.end_ns <= h.end_ns <= e.end_ns <= w.end_ns
    # one clock read per boundary: the handler's end IS the encode's start
    assert h.end_ns == e.start_ns and e.end_ns <= w.start_ns


@pytest.mark.parametrize("plane", ["native", "tcp"])
def test_poller_spans_in_order_caused_by_the_handler(traced, plane):
    spans = traced[plane]
    handler = _one(spans, "brpc.server.handler")
    queue, block, callback = (_one(spans, f"brpc.poller.{s}")
                              for s in ("queue", "block", "callback"))
    assert handler.start_ns <= queue.start_ns <= queue.end_ns \
        <= block.start_ns <= block.end_ns <= callback.start_ns \
        <= callback.end_ns
    assert {queue.cause_id, block.cause_id, callback.cause_id} == \
        {handler.span_id}
    assert {queue.call_id, block.call_id, callback.call_id} == \
        {handler.call_id}
    # a handler's completion: parked, then taken by a scheduler worker
    assert {queue.thread, block.thread, callback.thread} == {queue.thread}
    assert queue.thread.startswith("bthread_worker_")
    assert queue.n == 0                 # nothing was parked ahead of it
    # done() ran in the callback: the handler stage ends inside it, and the
    # response's encode and write are its children
    assert callback.start_ns <= handler.end_ns <= callback.end_ns
    assert _one(spans, "brpc.server.write").cause_id == callback.span_id


# ---- the ici plane's window -------------------------------------------------

def test_three_pieces_each_way(traced):
    spans = traced["bulk"]
    pieces = _named(spans, "brpc.ici.piece")
    assert len(pieces) == 6
    frame = BULK + 12                   # attachment and header, at least
    assert sum(p.n for p in pieces) >= 2 * frame
    # n is the piece's real length: a frame's first piece is its header
    # and a whole window of the attachment, every other piece the window
    # or the attachment's end, so no piece is a header's remainder
    sizes = sorted(p.n for p in pieces)
    assert sizes[:4] == [BULK - 2 * WINDOW] * 2 + [WINDOW] * 2
    assert all(WINDOW + 12 <= n < WINDOW + 1024 for n in sizes[4:])
    call = _one(spans, "brpc.call")
    assert call.call_id != 0            # the Python plane: a correlation id
    first = min(pieces, key=lambda p: p.start_ns)
    assert first.call_id == call.call_id
    assert first.cause_id == _one(spans, "brpc.call.wait").span_id


@pytest.mark.parametrize("child", ["brpc.ici.relocate", "brpc.ici.gate"])
def test_every_piece_has_one(traced, child):
    spans = traced["bulk"]
    pieces = _named(spans, "brpc.ici.piece")
    kids = _named(spans, child)
    assert sorted(k.cause_id for k in kids) == \
        sorted(p.span_id for p in pieces)
    by_id = {p.span_id: p for p in pieces}
    for k in kids:
        p = by_id[k.cause_id]
        assert p.start_ns <= k.start_ns
        if child == "brpc.ici.relocate":
            assert k.end_ns <= p.end_ns and k.thread == p.thread


@pytest.fixture
def three_piece_write(mesh, monkeypatch):
    """One write of three windows of bytes to a socket whose peer reads
    only after the writer has met the closed window: the spans of it, the
    sites switched on without a profiler session."""
    from brpc_tpu.butil.iobuf import IOBuf, IOPortal
    from brpc_tpu.ici.transport import IciSocket
    import jax.profiler  # noqa: F401
    layer_span.layer_on()   # binds the annotation class
    span.layer_spans_reset()
    monkeypatch.setattr(layer_span, "layer_on", lambda: True)
    monkeypatch.setattr(_flags.flag_object("ici_socket_window_bytes"),
                        "value", 4096)
    a, b = IciSocket(0, 0, mesh), IciSocket(0, 0, mesh)
    a.peer, b.peer = b, a
    try:
        done = []
        assert a.write(IOBuf(b"x" * (3 * 4096)), on_done=done.append) == 0
        deadline = time.monotonic() + 10
        while not _named(span.layer_spans(), "brpc.ici.piece") \
                and time.monotonic() < deadline:
            time.sleep(0.005)
        time.sleep(0.05)                # the writer is parked on the window
        got, portal = 0, IOPortal()
        while got < 3 * 4096 and time.monotonic() < deadline:
            n = b._do_read(portal, 1 << 20)
            got += max(n, 0)
            if n <= 0:
                time.sleep(0.005)
        while not done and time.monotonic() < deadline:
            time.sleep(0.005)
        assert got == 3 * 4096 and done == [0]
        yield span.layer_spans()
    finally:
        a.set_failed()
        b.set_failed()
        monkeypatch.undo()
        span.layer_spans_reset()


def test_three_piece_write_stalls_on_the_closed_window(three_piece_write):
    spans = three_piece_write
    pieces = _named(spans, "brpc.ici.piece")
    assert [p.n for p in pieces] == [4096] * 3
    # a window of one piece: each was cut once the one before was consumed
    assert [p.m for p in pieces] == [0] * 3
    for child in ("brpc.ici.relocate", "brpc.ici.gate"):
        assert sorted(k.cause_id for k in _named(spans, child)) == \
            sorted(p.span_id for p in pieces)
    stalls = _named(spans, "brpc.ici.stall")
    assert stalls and all(s.n == 4096 for s in stalls)  # unacked bytes
    assert max(s.end_ns - s.start_ns for s in stalls) >= 40e6
    # a stalled writer resumes with the next piece
    assert all(any(p.start_ns >= s.end_ns for p in pieces) for s in stalls)


def test_a_piece_says_what_was_unconsumed_when_it_was_cut(mesh, monkeypatch):
    """``brpc.ici.piece``'s second integer: the bytes of its socket still
    un-consumed at the peer at the cut (0: nothing was ahead of it)."""
    from brpc_tpu.butil.iobuf import IOBuf, IOPortal
    from brpc_tpu.ici.transport import IciSocket, ici_piece_stats
    import jax.profiler  # noqa: F401
    layer_span.layer_on()   # binds the annotation class
    span.layer_spans_reset()
    monkeypatch.setattr(layer_span, "layer_on", lambda: True)
    a, b = IciSocket(0, 0, mesh), IciSocket(0, 0, mesh)
    a.peer, b.peer = b, a
    before = ici_piece_stats()["pipelined_pieces"]
    try:
        for n in (1000, 500, 250):      # nobody reads
            assert a.write(IOBuf(b"x" * n)) == 0
        pieces = _named(span.layer_spans(), "brpc.ici.piece")
        assert [(p.n, p.m) for p in pieces] == [
            (1000, 0), (500, 1000), (250, 1500)]
        assert ici_piece_stats()["pipelined_pieces"] == before + 2
        assert b._do_read(IOPortal(), 1 << 20) == 1750
        # every other span's second integer is 0
        assert all(s.m == 0 for s in span.layer_spans()
                   if s.name != "brpc.ici.piece")
    finally:
        a.set_failed()
        b.set_failed()
        monkeypatch.undo()
        span.layer_spans_reset()


def test_an_ungated_delivery_still_records_its_gate(mesh, monkeypatch):
    """A DEVICE ref that is on the target chip already waits for nothing,
    ready or not; its entry still records ``brpc.ici.gate`` — no waits
    (``n`` 0), opened and closed by the writer's thread inside the piece —
    so ``delivery_gate_ms_per_call`` reads about 0 there, and not nothing.
    No span of the poller is left."""
    import jax.profiler  # noqa: F401
    from brpc_tpu.butil.iobuf import IOBuf
    from brpc_tpu.ici import transport
    layer_span.layer_on()   # binds the annotation class
    span.layer_spans_reset()
    monkeypatch.setattr(layer_span, "layer_on", lambda: True)
    monkeypatch.setattr(transport, "_all_ready", lambda arrays: False)
    a, b = transport.IciSocket(0, 0, mesh), transport.IciSocket(0, 0, mesh)
    a.peer, b.peer = b, a
    b.start_input_event = lambda inline=False: None
    try:
        buf = IOBuf()
        buf.append_device_array(_payload(mesh, 0, 4096))
        assert a.write(buf) == 0
        spans = span.layer_spans()
        piece, gate = _one(spans, "brpc.ici.piece"), _one(spans,
                                                          "brpc.ici.gate")
        assert gate.cause_id == piece.span_id and gate.n == 0
        assert gate.thread == piece.thread
        assert piece.start_ns <= gate.start_ns <= gate.end_ns <= piece.end_ns
        assert not [s for s in spans if s.name.startswith("brpc.poller.")]
    finally:
        a.set_failed()
        b.set_failed()
        monkeypatch.undo()
        span.layer_spans_reset()


def test_bulk_call_also_records_server_stages(traced):
    spans = traced["bulk"]
    call = _one(spans, "brpc.call")
    for s in STAGES:
        assert _one(spans, f"brpc.server.{s}").call_id == call.call_id


def test_the_planes_own_completions_never_leave_the_poller(traced):
    """Delivery gates ride the inline entry: whatever of them met the
    poller was served on its thread, callback and all."""
    assert all(s.thread.startswith("device_poller_")
               for s in traced["bulk"] if s.name.startswith("brpc.poller."))


@pytest.fixture
def sites_on(monkeypatch, empty_store):
    """The sites switched on without a profiler session."""
    monkeypatch.setattr(layer_span, "layer_on", lambda: True)
    yield
    monkeypatch.undo()


@pytest.mark.parametrize("entry,thread", [
    ("on_ready", "device_poller_"), ("device_on_ready", "bthread_worker_")])
def test_each_entry_point_records_the_three_spans_on_its_own_thread(
        sites_on, entry, thread):
    import threading
    import jax.numpy as jnp
    from brpc_tpu.bthread.device_waiter import DeviceEventDispatcher
    submit = device_on_ready if entry == "device_on_ready" \
        else DeviceEventDispatcher.instance().on_ready
    fired = threading.Event()
    outer = span.layer_begin("brpc.submitter", 41)
    submit([jnp.arange(8) + 1], fired.set)
    outer.end()
    assert fired.wait(10)
    deadline = time.monotonic() + 10
    while not _named(span.layer_spans(), "brpc.poller.callback") \
            and time.monotonic() < deadline:
        time.sleep(0.005)
    got = [s for s in span.layer_spans() if s.name.startswith("brpc.poller.")]
    assert [s.name for s in got] == [
        "brpc.poller.queue", "brpc.poller.block", "brpc.poller.callback"]
    assert {(s.cause_id, s.call_id) for s in got} == {(outer.span_id, 41)}
    assert len({s.thread for s in got}) == 1
    assert got[0].thread.startswith(thread)
    assert got[0].end_ns <= got[1].start_ns <= got[1].end_ns \
        <= got[2].start_ns


# ---- the same clock as the device trace -------------------------------------

@pytest.mark.parametrize("name", ["brpc.call", "brpc.call.wait",
                                  "brpc.server.handler",
                                  "brpc.poller.block", "brpc.poller.callback",
                                  "brpc.ici.piece"])
def test_lexical_span_is_on_the_profilers_host_plane(traced, name):
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(traced["dir"], "plugins", "profile", "*",
                                   "*.xplane.pb"))
    on_host = [e for plane in ProfileData.from_file(path).planes
               if plane.name.startswith("/host:")
               for line in plane.lines for e in line.events
               if e.name == name]
    recorded = sum(len(_named(traced[s], name))
                   for s in ("native", "tcp", "bulk"))
    assert recorded and len(on_host) == recorded


# ---- the store ---------------------------------------------------------------

@pytest.fixture
def empty_store():
    import jax.profiler  # noqa: F401
    span.layer_on()     # binds the annotation class, as any site does first
    span.layer_spans_reset()
    yield
    span.layer_spans_reset()


def test_the_bound_drops_and_counts(monkeypatch, empty_store):
    monkeypatch.setattr(layer_span, "LAYER_SPAN_CAP", 10)
    for i in range(8):
        span.layer_record("brpc.test", i, i + 1)
    kept = [span.layer_begin("brpc.test") for _ in range(5)]
    assert [k is not None for k in kept] == [True, True, False, False, False]
    for k in reversed(kept[:2]):    # lexical: the inner one ends first
        k.end()
    span.layer_record("brpc.test", 100, 101)
    assert len(span.layer_spans()) == 10
    assert span.layer_spans_dropped() == 4
    span.layer_spans_reset()
    assert span.layer_spans() == [] and span.layer_spans_dropped() == 0
    span.layer_record("brpc.test", 1, 2)
    assert len(span.layer_spans()) == 1


def test_nesting_gives_cause_and_call_id(empty_store):
    outer = span.layer_begin("brpc.outer", 7)
    inner = span.layer_begin("brpc.inner", n=3)
    mark = span.layer_mark(5)
    inner.end()
    span.layer_record("brpc.stamped", 10, 20)
    outer.end()
    span.layer_waited("brpc.waited", mark)
    after = span.layer_begin("brpc.after")
    after.end()
    by = {s.name: s for s in span.layer_spans()}
    assert by["brpc.inner"].cause_id == by["brpc.outer"].span_id
    assert by["brpc.inner"].call_id == 7 and by["brpc.inner"].n == 3
    assert by["brpc.stamped"].cause_id == by["brpc.outer"].span_id
    assert by["brpc.waited"].cause_id == by["brpc.inner"].span_id
    assert by["brpc.waited"].call_id == 7 and by["brpc.waited"].n == 5
    assert by["brpc.after"].cause_id == 0 and by["brpc.after"].call_id == 0


@pytest.mark.parametrize("since,until,name,want", [
    (0, None, None, ["a", "b", "c"]),
    (15, None, None, ["b", "c"]),       # a ended before
    (0, 25, None, ["a", "b"]),          # c began after
    (12, 22, None, ["b"]),              # b straddles both ends: in
    (0, None, "c", ["c"]),
])
def test_layer_spans_selects_by_overlap_and_name(empty_store, since, until,
                                                 name, want):
    span.layer_record("a", 0, 10)
    span.layer_record("b", 11, 30)
    span.layer_record("c", 31, 40)
    got = span.layer_spans(since, until, name)
    assert [s.name for s in got] == want


def test_finish_on_another_thread_keeps_the_opening_threads_name(empty_store):
    import threading
    opened = span.layer_begin("brpc.server.handler", 9)
    opened.leave()
    t = threading.Thread(target=opened.finish, name="finisher")
    t.start()
    t.join(10)
    assert not t.is_alive()
    got, = span.layer_spans()
    assert got.thread == threading.current_thread().name
    assert got.call_id == 9 and got.end_ns >= got.start_ns


def test_device_trace_hook_starts_with_an_empty_store(tmp_path, empty_store):
    from brpc_tpu.rpc import profiler
    span.layer_record("brpc.stale", 1, 2)
    assert profiler.start_device_trace(str(tmp_path))
    try:
        assert span.layer_on() is True
        assert span.layer_spans() == []
    finally:
        assert profiler.stop_device_trace()
    assert span.layer_on() is False


@pytest.mark.parametrize("between", ["a_site_ran", "idle_and_the_hook"])
def test_each_session_has_the_whole_cap_and_starts_empty(
        tmp_path, monkeypatch, empty_store, between):
    """Two sessions in one process: what the first recorded and dropped is
    gone when the second's first site runs."""
    import jax
    from brpc_tpu.rpc import profiler
    monkeypatch.setattr(layer_span, "LAYER_SPAN_CAP", 4)
    jax.profiler.start_trace(str(tmp_path / "one"))
    try:
        assert span.layer_on() is True
        for i in range(6):
            span.layer_record("brpc.first", i, i + 1)
    finally:
        jax.profiler.stop_trace()
    assert len(span.layer_spans()) == 4 and span.layer_spans_dropped() == 2
    if between == "a_site_ran":
        assert span.layer_on() is False
        jax.profiler.start_trace(str(tmp_path / "two"))
    else:
        assert profiler.start_device_trace(str(tmp_path / "two"))
    try:
        assert span.layer_on() is True
        assert span.layer_spans() == [] and span.layer_spans_dropped() == 0
        for i in range(3):
            span.layer_record("brpc.second", i, i + 1)
        assert span.layer_on() is True      # only the first site resets
    finally:
        jax.profiler.stop_trace()
    assert [s.name for s in span.layer_spans()] == ["brpc.second"] * 3
    assert span.layer_spans_dropped() == 0


def test_a_thread_with_no_span_open_adopts_no_call(empty_store):
    span.layer_adopt_call(77)           # a retry issued from a timer thread
    span.layer_record("brpc.before", 1, 2)
    outer = span.layer_begin("brpc.outer")
    span.layer_adopt_call(77)           # the caller, inside its brpc.call
    span.layer_record("brpc.inside", 3, 4)
    outer.end()
    span.layer_record("brpc.after", 5, 6)
    by = {s.name: s.call_id for s in span.layer_spans()}
    assert by == {"brpc.before": 0, "brpc.inside": 77, "brpc.outer": 0,
                  "brpc.after": 0}


# ---- a lexical span's CPU time (ISSUE 37) ------------------------------------

def _spent(how, ms=20):
    """One lexical span around ``ms`` of sleeping or of spinning."""
    opened = span.layer_begin(f"brpc.{how}", cpu=True)
    until = time.perf_counter_ns() + ms * 1_000_000
    if how == "sleeps":
        time.sleep(ms / 1e3)
    else:
        while time.perf_counter_ns() < until:
            pass
    opened.end()
    got = span.layer_spans(name=f"brpc.{how}")[-1]
    return got.cpu_ns, got.end_ns - got.start_ns


def test_a_span_that_sleeps_ran_little_and_one_that_spins_ran_it_all(
        empty_store):
    cpu, wall = _spent("sleeps")
    assert wall >= 20_000_000 and 0 <= cpu < 5_000_000
    # a spinning thread can lose its core on a shared host: the best of a
    # few tries is the thread's own; every try holds 0 <= cpu <= wall
    tries = [_spent("spins") for _ in range(5)]
    assert all(0 <= c <= w and w >= 20_000_000 for c, w in tries)
    assert max(c / w for c, w in tries) > 0.8


def test_only_a_span_that_asks_and_ends_where_it_began_has_cpu_time(
        empty_store):
    import threading
    crossing = span.layer_begin("brpc.server.handler", cpu=True)
    crossing.leave()
    t = threading.Thread(target=crossing.finish)
    t.start()
    t.join(10)
    assert not t.is_alive()
    left_then_finished = span.layer_begin("brpc.fanout", cpu=True)
    left_then_finished.leave()
    left_then_finished.finish()         # this thread, but not by end()
    span.layer_record("brpc.stamped", 10, 20)
    span.layer_waited("brpc.waited", span.layer_mark(3))
    unasked = span.layer_begin("brpc.unasked")   # the read is a system call
    unasked.end()
    lexical = span.layer_begin("brpc.lexical", cpu=True)
    lexical.end()
    by = {s.name: s.cpu_ns for s in span.layer_spans()}
    assert by.pop("brpc.lexical") >= 0
    assert by == {"brpc.server.handler": -1, "brpc.fanout": -1,
                  "brpc.stamped": -1, "brpc.waited": -1,
                  "brpc.unasked": -1}


def test_a_span_that_does_not_ask_reads_no_cpu_clock(monkeypatch,
                                                     empty_store):
    reads = []
    real = time.thread_time_ns

    class Clock:
        perf_counter_ns = staticmethod(time.perf_counter_ns)

        @staticmethod
        def thread_time_ns():
            reads.append(1)
            return real()
    monkeypatch.setattr(layer_span, "time", Clock)
    span.layer_begin("brpc.unasked").end()
    assert reads == []
    span.layer_begin("brpc.asked", cpu=True).end()
    assert len(reads) == 2


@pytest.mark.parametrize("fields, m", [(8, 0), (9, 5)])
def test_a_record_of_the_older_shape_still_reads(empty_store, fields, m):
    """Eight fields (stamped spans, waits) or nine (a lexical span before
    ``cpu_ns``): ``m`` and ``cpu_ns`` take their defaults."""
    st = layer_span._thread()
    st.records.append(("brpc.old", 1, 2, 3, 4, 5, "t", 6, 5)[:fields])
    got, = span.layer_spans()
    assert got == span.LayerSpan("brpc.old", 1, 2, 3, 4, 5, "t", 6, m, -1)
    assert got.cpu_ns == -1 and got.m == m


def test_outside_a_session_a_cut_asks_the_predicate_and_reads_no_clock(
        mesh, monkeypatch, empty_store):
    import jax
    from brpc_tpu.ici import transport as tr
    host = np.arange(64 * 1024, dtype=np.uint32).astype(np.uint8)
    arr = jax.block_until_ready(jax.device_put(host, mesh.device(1)))
    ref = type("Ref", (), {"offset": 4096, "length": 4096})()
    tr._cut(arr, ref)                   # the program and the start exist
    asked = []

    class NoClock:
        def __getattr__(self, name):
            raise AssertionError(f"time.{name} read outside a session")
    monkeypatch.setattr(layer_span, "time", NoClock())
    monkeypatch.setattr(layer_span, "_thread",
                        lambda: pytest.fail("a record outside a session"))
    really_on = tr._span.layer_on

    def on():
        asked.append(really_on())
        return asked[-1]
    monkeypatch.setattr(tr._span, "layer_on", on)
    got = tr._cut(arr, ref)
    assert asked == [False]
    assert bytes(np.asarray(got)) == bytes(host[4096:8192])


# ---- the stream layer's four spans (ISSUE 33) --------------------------------

STREAM_CHUNK = 100
STREAM_SPANS = ("brpc.stream.write", "brpc.stream.stall",
                "brpc.stream.queue", "brpc.stream.handler")
_stream_names = iter(range(1 << 30))


def _stream_exchange(chunks):
    """An echo over one stream under a two-chunk window each way, the
    server's handler 20 ms a message: the third write really waits.
    Returns when every chunk is back."""
    import threading
    from brpc_tpu.butil.iobuf import IOBuf
    got, lock = [], threading.Lock()

    class Collect(rpc.StreamInputHandler):
        def on_received_messages(self, sid, msgs):
            with lock:
                got.extend(m.to_bytes() for m in msgs)

    class StreamLayerService(rpc.Service):
        @rpc.method(EchoRequest, EchoResponse)
        def StartStream(self, cntl, request, response, done):
            class Back(rpc.StreamInputHandler):
                stream = None

                def on_received_messages(self, sid, msgs):
                    for m in msgs:
                        time.sleep(0.02)
                        self.stream.write(m, timeout=10)

            h = Back()
            h.stream = rpc.stream_accept(cntl, rpc.StreamOptions(
                handler=h, max_buf_size=2 * STREAM_CHUNK))
            done()

    server = rpc.Server()
    server.add_service(StreamLayerService())
    target = f"mem://layer-stream-{next(_stream_names)}"
    assert server.start(target) == 0
    try:
        ch = rpc.Channel()
        ch.init(target)
        cntl = rpc.Controller()
        stream = rpc.stream_create(cntl, rpc.StreamOptions(
            handler=Collect(), max_buf_size=2 * STREAM_CHUNK))
        ch.call_method("StreamLayerService.StartStream", cntl,
                       EchoRequest(message="s"), EchoResponse)
        assert not cntl.failed() and stream.wait_connected(5)
        for i in range(chunks):
            assert stream.write(IOBuf(b"x" * STREAM_CHUNK), timeout=10) == 0
        deadline = time.monotonic() + 10
        while len(got) < chunks and time.monotonic() < deadline:
            time.sleep(0.005)
        assert len(got) == chunks
        # the client's consumer leaves its handler span after `got` grows
        while stream._n_delivered < chunks and time.monotonic() < deadline:
            time.sleep(0.005)
        stream.close()
    finally:
        server.stop()


@pytest.fixture
def stream_spans(monkeypatch):
    """The spans of a six-chunk exchange, the sites switched on without a
    profiler session (as ``three_piece_write``)."""
    import jax.profiler  # noqa: F401
    layer_span.layer_on()   # binds the annotation class
    span.layer_spans_reset()
    monkeypatch.setattr(layer_span, "layer_on", lambda: True)
    try:
        _stream_exchange(6)
        yield [s for s in span.layer_spans()
               if s.name.startswith("brpc.stream.")]
    finally:
        monkeypatch.undo()
        span.layer_spans_reset()


def test_no_session_no_stream_span(empty_store):
    _stream_exchange(3)
    assert [s for s in span.layer_spans()
            if s.name.startswith("brpc.stream.")] == []


@pytest.mark.parametrize("name", STREAM_SPANS)
def test_stream_span_is_recorded_with_its_n_and_its_cause(stream_spans,
                                                         name):
    spans = stream_spans
    assert {s.name for s in spans} == set(STREAM_SPANS)
    mine = _named(spans, name)
    by_id = {s.span_id: s for s in spans}
    handlers = _named(spans, "brpc.stream.handler")
    writes = _named(spans, "brpc.stream.write")
    if name == "brpc.stream.write":
        # six by the caller, six by the server's handler writing back
        assert len(mine) == 12 and all(s.n == STREAM_CHUNK for s in mine)
        back = [s for s in mine if s.cause_id]
        assert len(back) == 6
        for s in back:                  # the handler is their cause
            h = by_id[s.cause_id]
            assert h.name == "brpc.stream.handler" and h.thread == s.thread
            assert h.start_ns <= s.start_ns <= s.end_ns <= h.end_ns
    if name == "brpc.stream.stall":
        # only a write that really parked has one, inside it; n = the bytes
        # the window was short of
        assert 1 <= len(mine) < len(writes)
        for s in mine:
            w = by_id[s.cause_id]
            assert w.name == "brpc.stream.write" and w.thread == s.thread
            assert w.start_ns <= s.start_ns <= s.end_ns <= w.end_ns
            assert 0 < s.n <= STREAM_CHUNK
        assert max(s.end_ns - s.start_ns for s in mine) >= 10e6
    if name == "brpc.stream.queue":
        # one a message, each way; n = messages ahead of it
        assert len(mine) == 12 and all(0 <= s.n < 6 for s in mine)
        for s in mine:                  # it ends where a batch's handler
            assert any(abs(h.start_ns - s.end_ns) < 5e6   # begins
                       and h.start_ns >= s.end_ns for h in handlers), s
    if name == "brpc.stream.handler":
        # n = the batch's messages: twelve were delivered in all
        assert sum(s.n for s in mine) == 12 and all(s.n >= 1 for s in mine)
        assert all(s.cause_id == 0 for s in mine)
        # the server's take 20 ms a message
        assert max(s.end_ns - s.start_ns for s in mine) >= 20e6
