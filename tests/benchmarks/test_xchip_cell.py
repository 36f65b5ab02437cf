"""The cross-chip deployment ``rdma_perf_xchip`` and its cell
``xchip_bulk_64m`` at CPU size: the echo through the device plane against
the plain reference, the plane's three layer spans, the reader of
``xchip_roofline`` on a synthetic reduction, and the cell's route check.

The cell sets no flag of the program, and on CPU devices the plane is off
unless ``ici_device_plane_host_mesh`` is set: the tests that need the plane
set it, as tests/test_device_plane.py does.  The parametrised tests of
test_benchmark_harness.py pick the cell up from BENCHMARK.json by themselves.
"""
import os
import sys
import time
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks.harness import counters, loader, readers  # noqa: E402
from benchmarks.harness.resident import make_set  # noqa: E402
from benchmarks.harness.xplane import Reduction  # noqa: E402
from benchmarks.reference import Echo as reference_echo  # noqa: E402
from benchmarks.reference import payload  # noqa: E402
from brpc_tpu.butil import flags, layer_span  # noqa: E402
from brpc_tpu.rpc import span  # noqa: E402
from test_benchmark_harness import rehearse, restore_mesh  # noqa: E402,F401

CELL = "xchip_bulk_64m"
WINDOW = 1 << 20                # the Python ici plane's send window here
BLOCK = 4 * WINDOW              # four whole windows each way; at 4 MiB the
#                                 frame no longer fits the native tier's window
SEED = 2 ** 31 + 29
MS = 1_000_000


@pytest.fixture
def host_mesh_plane():
    from brpc_tpu.ici import device_plane
    before = flags.get_flag("ici_device_plane_host_mesh")
    flags.set_flag("ici_device_plane_host_mesh", True)
    yield device_plane.plane()
    flags.set_flag("ici_device_plane_host_mesh", before)


@pytest.fixture
def echo_across_chips(host_mesh_plane, monkeypatch):
    """An Echo server on ici://1 and a channel whose caller lives on chip 0,
    over a send window of 1 MiB; gives call(block) -> (controller, reply)."""
    import jax
    import brpc_tpu.policy  # noqa: F401  (registers the protocols)
    from brpc_tpu import rpc
    from brpc_tpu.ici.mesh import IciMesh
    from benchmarks.services.messages import Request, Response
    monkeypatch.setattr(flags.flag_object("ici_socket_window_bytes"),
                        "value", WINDOW)
    before = IciMesh._default
    IciMesh.set_default(IciMesh(jax.devices()[:4]))
    mod = loader.service_module("Echo")
    opts = rpc.ServerOptions()
    for k, v in mod.SERVER_OPTIONS.items():
        setattr(opts, k, v)
    server = rpc.Server(opts)
    service = mod.build(None)
    server.add_service(service)
    assert server.start("ici://1") == 0
    channel = rpc.Channel()
    assert channel.init("ici://1", options=rpc.ChannelOptions(
        ici_local_device=0, max_retry=0, timeout_ms=60000,
        connection_type="pooled")) == 0

    def call(block, message="k"):
        cntl = rpc.Controller()
        cntl.request_attachment.append_device_array(block)
        resp = channel.call_method(f"{service.service_name()}.Echo", cntl,
                                   Request(message=message), Response)
        return cntl, resp

    try:
        yield call
    finally:
        channel.close()
        server.stop()
        IciMesh.set_default(before)


def _plane_spans():
    return [s for s in span.layer_spans() if s.name.startswith("brpc.plane.")]


def test_echo_crosses_through_the_plane_and_equals_the_reference(
        echo_across_chips, host_mesh_plane):
    import jax
    from benchmarks.harness.check import attachment_bytes
    blocks = make_set(SEED, 0, 3, BLOCK, jax.devices()[0])
    span.layer_spans_reset()
    before = host_mesh_plane.stats()
    for i, block in enumerate(blocks):
        cntl, resp = echo_across_chips(block, message=f"call{i}")
        assert not cntl.failed(), cntl.error_text
        att = cntl.response_attachment
        refs = att.device_refs()
        jax.block_until_ready([r.block.data for r in refs])
        want, message = reference_echo.expected(
            payload.block(SEED, 0, i, BLOCK), f"call{i}")
        assert resp.message == message
        assert len(att) == att.device_bytes() == BLOCK
        assert np.array_equal(attachment_bytes(att), want)
        # it has crossed twice: the reply is resident on the CALLER's chip
        assert all(set(r.block.data.devices()) == {jax.devices()[0]}
                   for r in refs)
    after = host_mesh_plane.stats()
    # a frame is its header and the block: the header rides with the first
    # piece (host bytes, no transfer of their own), so four whole windows of
    # the block each way go through the plane and nothing else does
    assert after["transfers"] - before["transfers"] == 3 * 2 * BLOCK // WINDOW
    assert after["bytes_sent"] - before["bytes_sent"] == 3 * 2 * BLOCK
    # the request's pieces are cut by the transfer program out of the caller's
    # block; the reply's are the blocks the server received, whole
    assert after["sliced_in_program"] - before["sliced_in_program"] \
        == 3 * BLOCK // WINDOW
    for k in ("fallbacks", "build_failures", "match_timeouts"):
        assert after[k] == before[k], k
    # no profiler session, no span of the plane
    assert _plane_spans() == []


def test_plane_spans_in_a_session_name_their_piece_and_their_bytes(
        echo_across_chips, monkeypatch):
    import jax
    import jax.profiler  # noqa: F401
    block = make_set(SEED, 1, 1, BLOCK, jax.devices()[0])[0]
    echo_across_chips(block)            # the programs are built
    layer_span.layer_on()               # binds the annotation class
    span.layer_spans_reset()
    monkeypatch.setattr(layer_span, "layer_on", lambda: True)
    try:
        cntl, _ = echo_across_chips(block)
        assert not cntl.failed(), cntl.error_text
        deadline = time.monotonic() + 10
        want = 2 * BLOCK // WINDOW      # transfers of one call
        while sum(s.name == "brpc.plane.complete" for s in _plane_spans()) \
                < want and time.monotonic() < deadline:
            time.sleep(0.005)
    finally:
        monkeypatch.undo()
    spans = span.layer_spans()
    pieces = {s.span_id: s for s in spans if s.name == "brpc.ici.piece"}
    for name in ("brpc.plane.post", "brpc.plane.run", "brpc.plane.complete"):
        mine = [s for s in spans if s.name == name]
        assert len(mine) == want, (name, mine)
        for s in mine:
            piece = pieces[s.cause_id]          # the piece is its cause
            assert WINDOW - 4096 < s.n <= WINDOW and s.n <= piece.n
            assert piece.start_ns <= s.start_ns <= s.end_ns
            if name != "brpc.plane.complete":   # lexical: inside the piece
                assert s.end_ns <= piece.end_ns and s.thread == piece.thread
    # a transfer is posted, then run, then completes
    by_piece = {}
    for s in _plane_spans():
        by_piece.setdefault(s.cause_id, {})[s.name] = s
    for got in by_piece.values():
        post, run, done = (got[f"brpc.plane.{k}"]
                           for k in ("post", "run", "complete"))
        assert post.end_ns <= run.start_ns <= run.end_ns <= done.start_ns
    span.layer_spans_reset()


# ---- the three metrics that read the plane's spans ---------------------------

PLANE_METRICS = {"plane_post_ms_per_call": "brpc.plane.post",
                 "plane_run_ms_per_call": "brpc.plane.run",
                 "plane_complete_ms": "brpc.plane.complete"}


@pytest.mark.parametrize("metric", sorted(PLANE_METRICS))
def test_plane_metric_reads_its_span_and_nothing_else(metric):
    """60 calls of 50 ms fill a 3 s slice; one span of 3 ms in each."""
    span.layer_spans_reset()
    entry = next(m for m in loader.manifest()["per_layer"]
                 if m["name"] == metric)
    assert entry["source"] == "program_span"
    assert entry["layer"] == "device plane"
    m = loader._metric(entry)
    assert m.reader == {"span": PLANE_METRICS[metric]}
    calls = [((1000 + i * 50) * MS, (1050 + i * 50) * MS, 0, 1, True, f"k{i}")
             for i in range(60)]
    window = types.SimpleNamespace(calls=lambda: iter(calls),
                                   trace_slice_ns=(1000 * MS, 4000 * MS))
    view = readers.View(window=window, reduction=None, peaks=None)
    assert readers.read(m, view) is None        # the parent: no such span
    for name in PLANE_METRICS.values():
        if name != PLANE_METRICS[metric]:
            span.layer_record(name, 2000 * MS, 2007 * MS)
    assert readers.read(m, view) is None
    for i in range(60):
        span.layer_record(PLANE_METRICS[metric], (1010 + i * 50) * MS,
                          (1013 + i * 50) * MS)
    assert readers.read(m, view) == pytest.approx(3.0)
    span.layer_spans_reset()


# ---- xchip_roofline's reader ------------------------------------------------

def _roofline():
    cell = loader.load_cell(CELL)
    return next(m for m in cell.per_layer if m.name == "xchip_roofline")


def _view(ops, busy_s=0.3, window_s=3.0, calls=None, chip=0):
    """Three callers, 150 ms a call, back to back over a 3 s slice: 60 calls
    of 2 x 64 MiB."""
    if calls is None:
        calls = [((1000 + i * 150) * MS, (1150 + i * 150) * MS, 0, 1 << 26,
                  True, f"k{t}.{i}") for t in range(3) for i in range(20)]
    window = types.SimpleNamespace(
        calls=lambda: iter(calls), trace_slice_ns=(1000 * MS, 4000 * MS),
        caller_device=types.SimpleNamespace(id=0))
    red = Reduction(window_s=window_s, busy_s={chip: busy_s},
                    ops={chip: ops})
    return readers.View(window=window, reduction=red,
                        peaks={"ici_gbs": 200.0})


PERMUTE = [("%collective-permute-done = u8[1,4194304]{1,0:T(4,128)(4,1)S(1)} "
            "collective-permute-done((u8[1,4194304]{1,0:T(4,128)(4,1)}, ...", 0.19),
           ("%copy-done = u8[67108864]{0:T(1024)(128)(4,1)S(1)} copy-done(...",
            0.06),
           # a copy OF the transfer's result is not the transfer
           ("%copy.3 = u8[1,4194304]{1,0:T(4,128)(4,1)} copy(u8[1,4194304]"
            "{1,0:T(4,128)(4,1)S(1)} %collective-permute-done)", 0.04),
           ("%collective-permute-start = (u8[1,4194304]{1,0:T(4,128)(4,1)}, "
            "...) collective-permute-start(...", 0.01)]
# the two program shapes of a call (the request's cuts, the reply's whole
# blocks) give instruction names that differ in their suffix alone
TWO_SHAPES = [("%collective-permute-done.1 = u8[4194304]{0:T(1024)(128)(4,1)"
               "S(1)} collective-permute-done(...", 0.1),
              ("%collective-permute-done = u8[4194304]{0:T(1024)(128)(4,1)"
               "S(1)} collective-permute-done(...", 0.09),
              ("%collective-permute-start.1 = (u8[4194304]{0:T(1024)(128)"
               "(4,1)}, ...) collective-permute-start(...", 0.01),
              ("%dynamic_slice.3 = u8[4194304]{0:T(1024)(128)(4,1)S(1)} "
               "dynamic-slice(...", 0.1)]


@pytest.mark.parametrize("ops", [PERMUTE, TWO_SHAPES],
                         ids=["ppermute", "two_program_shapes"])
def test_roofline_is_bytes_over_seconds_over_the_peak_whatever_the_kernel(
        ops):
    m = _roofline()
    # 60 calls x 2 x 64 MiB in 3 s = 2.684 GB/s on a chip that spends 0.2 of
    # its 0.3 busy seconds (every one of them named) in transfer operations:
    # 40.27 GB/s while it transfers, 20.13 % of 200 GB/s
    want = 100 * (60 * 2 * (1 << 26) / 3.0) / (0.2 / 3.0) / 200e9
    assert m.module.read(_view(ops), m.reader) == pytest.approx(want)
    assert m.module.transfer_bytes(_view(ops)) == 60 * 2 * (1 << 26)


def test_roofline_counts_the_seconds_the_kept_names_leave_out():
    """Ten names are kept: busy seconds beyond their sum may hide a transfer
    operation, so they are added and the share can only read lower."""
    m = _roofline()
    whole = m.module.read(_view(PERMUTE, busy_s=0.3), m.reader)
    hidden = m.module.read(_view(PERMUTE, busy_s=0.4), m.reader)
    assert hidden == pytest.approx(whole * 0.2 / 0.3)


def test_roofline_takes_each_rate_over_its_own_window():
    """The profiler's window is wider than the host's slice: the transfer
    seconds are a share of the trace's window, the bytes a second of the
    slice."""
    m = _roofline()
    a = m.module.read(_view(PERMUTE, window_s=3.0), m.reader)
    wider = [(n, s * 3.1 / 3.0) for n, s in PERMUTE]
    b = m.module.read(_view(wider, busy_s=0.31, window_s=3.1), m.reader)
    assert a == pytest.approx(b)


@pytest.mark.parametrize("view", [
    _view([("%fusion.3 = u8[4194304] fusion(...)", 0.2),
           ("%copy.3 = u8[1,4194304] copy(u8[1,4194304] "
            "%collective-permute-done)", 0.03)]),       # no transfer ran
    _view(PERMUTE, calls=[]),                           # no call in the slice
    _view(PERMUTE, chip=2),                             # another chip's trace
    readers.View(window=_view(PERMUTE).window, reduction=None, peaks=None),
], ids=["no_transfer_operation", "no_calls", "not_the_callers_chip",
        "no_trace"])
def test_roofline_reads_nothing_rather_than_zero(view):
    m = _roofline()
    assert m.module.read(view, m.reader) is None


# ---- the configuration and the cell's route ---------------------------------

def test_the_configuration_states_its_source_and_its_cut():
    cfg = loader.load_cell(CELL).config
    local = loader.load_cell("local_bulk_64m").config
    entry = next(c for c in loader.manifest()["configs"]
                 if c["name"] == "rdma_perf_xchip")
    assert cfg["source"] == entry["source"] and len(cfg["source"]) <= 200
    assert cfg["guarantees"] == local["guarantees"]     # word for word
    assert cfg["reduced"] == entry["reduced"] == ["servers", "resident_bytes"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    assert set(cfg["assumed"]) == {"attachment_64m", "threads"}
    assert cfg["source_notes"] and cfg["deployment"] and cfg["reference"]
    assert [s["endpoint"] for s in cfg["servers"]] == \
        ["ici://1", "ici://2", "ici://3"]
    assert cfg["caller_device"] == 0 and cfg["chips"] == 4
    route = {r["counter"]: r["per_call_min"]
             for r in loader.load_cell(CELL).workload["route"]}
    # the route is held in bytes through the plane, not in pieces: a larger
    # piece or window moves the same bytes in fewer transfers
    assert route == {"plane_bytes_sent": 2 << 26, "ici_device_bytes": 2 << 26}
    assert "plane_transfers" not in route
    assert "4 MB" in cfg["deployment"] and "kernel" not in cfg["deployment"]


@pytest.mark.parametrize("blinded", [False, True],
                         ids=["plane_taken", "count_blinded"])
@pytest.mark.parametrize("counter,a_call", [("plane_transfers", 4),
                                            ("plane_bytes_sent", 2 << 23)])
def test_route_check_holds_the_plane_to_its_transfers(
        capsys, restore_mesh, host_mesh_plane, monkeypatch, counter, a_call,
        blinded):
    """The rehearsal's 8 MiB blocks are two windows each way: four transfers
    and 16 MiB through the plane a call (the cell's file holds the bytes).
    With the plane's count blinded the run is not correct."""
    real_load, real_snapshot = loader.load_cell, counters.snapshot

    def with_the_planes_count(name, rehearse=False):
        cell = real_load(name, rehearse)
        cell.workload["route"] = cell.workload["route"] + [
            {"counter": counter, "per_call_min": a_call}]
        return cell

    def blind(servers):
        out = real_snapshot(servers)
        out[counter] = 0
        return out

    monkeypatch.setattr(loader, "load_cell", with_the_planes_count)
    if blinded:
        monkeypatch.setattr(counters, "snapshot", blind)
    rc, line, err = rehearse(capsys, CELL, "--trace", "0")
    assert line is not None, err[-2000:]
    got = line["checks"][f"{counter}_per_call"]
    if blinded:
        assert line["correct"] is False and got["value"] == 0
        assert f"check {counter}_per_call: 0.0 >= {a_call} NOT OK" in err
    else:
        assert line["correct"] is True, err[-2000:]
        assert got["value"] == float(a_call) and line["checks"][
            "second_route_events"]["value"] == 0


def test_wrong_chip_control_comes_out_not_correct(capsys, restore_mesh):
    """The control that needs a second chip, on the cell that has one."""
    mod = loader.control_module("wrong_chip")
    assert mod.GUARANTEE in loader.load_cell(CELL).config["guarantees"]
    rc, line, err = rehearse(capsys, CELL, "--trace", "0",
                             "--control", "wrong_chip")
    assert line is not None, err[-2000:]
    assert line["correct"] is False
    assert line["checks"]["misplaced_replies"]["value"] > 0
