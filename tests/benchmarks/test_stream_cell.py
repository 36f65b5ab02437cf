"""The streaming deployment ``streaming_echo`` and its cell ``stream_1m`` at
CPU size: the cell resolves from BENCHMARK.json alone (no fixture root), a
rehearsal is correct with its route and its two zero-counters held, each of
its three controls comes out not correct by the number it is meant to move,
the traced rehearsal's line has the seven metrics of the ``stream`` layer,
and their readers read the program's four ``brpc.stream.*`` spans.

``fixture_stream`` (test_client_and_counter_seams.py) stays the harness's own
test of the client and counter seams; what is here is the cell's.  The
parametrised tests of test_benchmark_harness.py pick the cell up from
BENCHMARK.json by themselves (the end of tests/conftest.py says which of their cases cannot
hold for a cell whose client is not a unary call).
"""
import json
import os
import re
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks.harness import counters, loader, readers  # noqa: E402
from benchmarks.reference import payload  # noqa: E402
from brpc_tpu.rpc import span  # noqa: E402
from test_benchmark_harness import rehearse, restore_mesh  # noqa: E402,F401

CELL, CONFIG, METHOD = "stream_1m", "streaming_echo", "StartStream"
CHUNKS, CHUNK, HEADER = 16, 65536, 32       # the rehearsal's operation
MS = 1_000_000
STREAM_METRICS = {
    "stream_frames_per_call": "stream_data_frames_sent",
    "stream_feedback_per_call": "stream_feedback_frames_sent",
    "stream_batches_per_call": "stream_batches_delivered",
    "stream_write_ms_per_call": "brpc.stream.write",
    "stream_stall_ms_per_call": "brpc.stream.stall",
    "stream_queue_ms": "brpc.stream.queue",
    "stream_handler_ms_per_call": "brpc.stream.handler",
}
SPAN_METRICS = {k: v for k, v in STREAM_METRICS.items()
                if v.startswith("brpc.")}
COUNT_METRICS = {k: v for k, v in STREAM_METRICS.items()
                 if not v.startswith("brpc.")}
# control -> the number it moves, and what it reads for each compared reply
CONTROLS = {"flipped_chunk": "byte_mismatches",
            "swapped_chunks": "byte_mismatches",
            "host_chunk": "short_replies"}


@pytest.fixture(autouse=True)
def no_call_id_ageing(monkeypatch):
    """A stream's operations draw no call id; the 600 reuses a slot that
    every rehearsal spends on ageing the pool only bring the process's id
    space nearer its end (ROADMAP 1.1), so this file's rehearsals spare
    them."""
    from benchmarks.harness import driver
    monkeypatch.setattr(driver, "age_call_ids", lambda slots: None)


def _source(*parts):
    with open(os.path.join(REPO, *parts), encoding="utf-8") as f:
        return f.read()


def _entry(group, name):
    return next(e for e in loader.manifest()[group] if e["name"] == name)


# ---- the cell is files and manifest entries, found with no fixture root ----

def test_cell_resolves_from_the_manifest_alone():
    assert len(loader.ROOTS) == 1           # no fixture root in this file
    cell = loader.load_cell(CELL)
    assert (cell.config_name, cell.chips) == (CONFIG, 1)
    assert cell.methods() == [METHOD] and cell.clients() == ["stream"]
    assert cell.workload["counters"] == ["stream"]
    for mod, name in ((loader.client_module("stream"), "clients.stream"),
                      (loader.service_module(METHOD), "services.StartStream"),
                      (loader.reference_module(METHOD),
                       "reference.StartStream"),
                      (loader.counter_module("stream"), "counters.stream")):
        assert mod.__name__ == f"benchmarks.{name}"
    for control in CONTROLS:
        mod = loader.control_module(control)
        assert mod.__name__ == f"benchmarks.controls.{control}"
        assert mod.GUARANTEE in cell.config["guarantees"]
    assert counters.second_route(cell) == list(counters.SECOND_ROUTE) + [
        "stream_window_overruns", "stream_write_failures"]
    assert {m.name for m in cell.end_to_end} == {
        "goodput_gbs", "latency_p50_ms", "latency_p95_ms", "setup_s"}
    assert set(STREAM_METRICS) <= {m.name for m in cell.per_layer}


def test_the_traffic_is_the_issues_table_letter_for_letter():
    wl = loader.load_cell(CELL).workload
    cfg = loader.load_cell(CELL).config
    assert wl["threads"] == 2 and cfg["queue_depth"] == 1
    assert wl["sets"]["resident"]["block_bytes"] == 1 << 26
    assert wl["sets"]["resident"]["bytes"] == 6 << 30       # 96 blocks
    (mix,) = wl["mix"]
    assert mix["client_options"] == {
        "chunk_bytes": 1 << 20, "header_bytes": 32,
        "max_buf_size": 2 * ((1 << 20) + 32), "timeout_s": 60}
    assert wl["sample_per_thread"] == 16
    assert (wl["warmup_seconds"], wl["trace_seconds"]) == (1.0, 3.0)
    assert wl["channel_options"] == {"connection_type": "pooled"}
    route = {r["counter"]: r["per_call_min"] for r in wl["route"]}
    # 64 chunks each way: 128 MiB of device memory, and 128 headers besides
    assert route == {"ici_device_bytes": 2 << 26,
                     "stream_data_bytes_sent": 128 * ((1 << 20) + 32)}
    tiny = loader.load_cell(CELL, rehearse=True).workload
    assert tiny["mix"][0]["client_options"]["chunk_bytes"] == CHUNK
    assert tiny["sets"]["resident"]["block_bytes"] == CHUNKS * CHUNK


def test_the_configuration_states_its_source_and_its_cut():
    cfg = loader.load_cell(CELL).config
    entry = _entry("configs", CONFIG)
    assert cfg["source"] == entry["source"] and len(cfg["source"]) <= 200
    for word in ("streaming_echo_c++", "client.cpp", "server.cpp",
                 "StreamOptions"):
        assert word in cfg["source"], word
    assert any("from memory" in n for n in cfg["source_notes"])
    assert cfg["reduced"] == entry["reduced"] == [
        "servers", "resident_bytes", "server_handler"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    assert cfg["assumed"] == {"chunk_bytes": 1 << 20,
                              "operation_bytes": 1 << 26,
                              "max_buf_size": 2097216, "header_bytes": 32,
                              "threads": 2}
    assert set(cfg["assumed_why"]) == set(cfg["assumed"])
    local = loader.load_cell("local_bulk_64m").config
    for key in ("chips", "caller_device", "servers", "channel_options",
                "queue_depth", "resident_bytes"):
        assert cfg[key] == local[key], key      # rdma_perf_local's layout
    assert {"reply_attachment", "order", "exactly_once", "flow_control",
            "single_route", "no_retry"} == set(cfg["guarantees"])
    assert cfg["second_route_counters"] == ["stream_window_overruns",
                                            "stream_write_failures"]


def test_the_manifest_gains_one_configuration_one_cell_seven_metrics():
    man = loader.manifest()
    assert [c["name"] for c in man["configs"]][-1] == CONFIG
    assert [w["name"] for w in man["workloads"]][-1] == CELL
    assert _entry("workloads", CELL) == {
        "name": CELL, "config": CONFIG, "traffic": "stream_64x1m",
        "chips": 1, "why": loader.load_cell(CELL).workload["why"]}
    assert [m["name"] for m in man["per_layer"]][-7:] == list(STREAM_METRICS)
    for name in STREAM_METRICS:
        e = _entry("per_layer", name)
        assert e["layer"] == "stream" and e["workloads"] == [CELL]
        assert e["moves"] == ("latency_p50_ms" if name == "stream_queue_ms"
                              else "goodput_gbs")
        assert e["source"] == ("program_span" if name in SPAN_METRICS
                               else "program_counter")
    # the metrics of the unary path that a stream never opens keep to the
    # cells that had them; those of the socket the stream rides take it in
    accepted = ["local_compute_1m", "local_bulk_64m", "xchip_bulk_64m"]
    for name in ("server_queue_ms", "server_parse_ms", "server_encode_ms",
                 "server_write_ms", "client_self_ms"):
        assert _entry("per_layer", name)["workloads"] == accepted
    for name in ("device_busy_ms_per_call", "window_pieces_per_call",
                 "slice_dispatch_ms_per_call", "delivery_gate_ms_per_call",
                 "window_stall_ms_per_call"):
        assert _entry("per_layer", name)["workloads"][-1] == CELL
    assert _entry("end_to_end", "goodput_gbs")["workloads"][-1] == CELL


# ---- sources: who may name what ---------------------------------------------

def test_no_file_of_the_harness_names_the_cell_a_stream_or_the_method():
    harness = os.path.join(REPO, "benchmarks", "harness")
    files = [os.path.join(harness, f) for f in sorted(os.listdir(harness))
             if f.endswith(".py")] + [os.path.join(REPO, "benchmarks",
                                                   "run.py")]
    assert len(files) >= 10
    for path in files:
        text = _source(path)
        for word in (CELL, CONFIG, METHOD, "stream_64x1m"):
            assert word not in text, (path, word)
        assert not re.search("stream", text, re.IGNORECASE), path


def test_the_reference_is_plain_numpy():
    text = _source("benchmarks", "reference", "StartStream.py")
    imports = re.findall(r"^\s*(?:from|import)\s+(\S+)", text, re.MULTILINE)
    assert set(imports) == {"__future__", "numpy"}
    want, message = loader.reference_module(METHOD).expected(
        np.arange(256, dtype=np.uint8), "w00.000000001")
    assert message == "w00.000000001"
    assert np.array_equal(want, np.arange(256, dtype=np.uint8) ^ 0x5A)


@pytest.mark.parametrize("parts", [("clients", "stream.py"),
                                   ("services", "StartStream.py"),
                                   ("counters", "stream.py")])
def test_the_cells_code_uses_the_streams_public_path_and_copies_nothing(
        parts):
    text = _source("benchmarks", *parts)
    # no copy of a buffer the handler is handed, no private attribute of a
    # Stream, no clock and no verdict of its own
    for word in ("IOBuf(m)", "live_streams", "._local", "._produced",
                 "._remote", "set_remote_consumed", "append_if_not_full",
                 "perf_counter", "time.time", "block_until_ready",
                 "set_flag", "os.environ"):
        assert word not in text, (parts, word)
    if parts[0] == "counters":
        assert "stream_stats" in text
    else:
        assert ".cut(" in text and ".write(" in text
        assert ("stream_create" if parts[0] == "clients"
                else "stream_accept") in text


# ---- a rehearsal on CPU devices ----------------------------------------------

def test_rehearsal_is_correct_with_route_and_zero_counters_held(
        capsys, restore_mesh):
    rc, line, err = rehearse(capsys, CELL, "--trace", "0")
    assert rc == 0 and line["correct"] is True, err[-2000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    checks = line["checks"]
    assert checks["replies_compared"] == {"value": 4, "limit": 4}
    assert checks["byte_mismatches"] == {"value": 0, "limit": 0}
    # both zero-counters are in the sum that is held at zero
    assert checks["second_route_events"] == {"value": 0, "limit": 0}
    # the route: every chunk each way by reference on the chip, and through
    # the stream: exactly an operation's frames with their headers
    assert checks["ici_device_bytes_per_call"] == {
        "value": float(2 * CHUNKS * CHUNK), "limit": 2 * CHUNKS * CHUNK}
    assert checks["stream_data_bytes_sent_per_call"] == {
        "value": float(2 * CHUNKS * (CHUNK + HEADER)),
        "limit": 2 * CHUNKS * (CHUNK + HEADER)}
    assert set(line["metrics"]) == {"goodput_gbs", "latency_p50_ms",
                                    "latency_p95_ms", "setup_s"}


def test_each_stream_rides_a_connection_of_its_own(capsys, restore_mesh,
                                                  monkeypatch):
    """What the chip met first: the two callers' streams on ONE pooled
    connection stack four chunks and their headers on one socket window of
    four chunks, the fourth frame is cut in mid-block, and a slice of an odd
    size compiles inside the window.  With the socket window in the cell's
    own proportion (4 chunks, as 4 MB to 1 MiB) the rehearsal cuts no chunk
    and compiles nothing."""
    from brpc_tpu.butil import flags
    from brpc_tpu.ici import transport
    monkeypatch.setattr(flags.flag_object("ici_socket_window_bytes"),
                        "value", 4 * CHUNK)
    cut, real = [], transport.CreditWindow._consume_window

    def spy(self, want, lead=0):
        n = real(self, want, lead)
        if 0 <= n < want:
            cut.append((want, n))
        return n

    monkeypatch.setattr(transport.CreditWindow, "_consume_window", spy)
    rc, line, err = rehearse(capsys, CELL, "--trace", "0")
    assert rc == 0 and line["correct"] is True, err[-2000:]
    assert cut == []
    assert "in the window 0 programs compiled" in err


def test_traced_rehearsal_has_every_metric_of_the_stream_layer(
        capsys, restore_mesh):
    rc, line, err = rehearse(capsys, CELL, "--trace", "1", seconds="1.0")
    assert rc == 0 and line["correct"] is True, err[-2000:]
    got = line["metrics"]
    listed = {m["name"]: m for m in loader.manifest()["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    # none it lists is missing (a device number is null off the chip)
    assert set(got) == set(listed)
    for name in STREAM_METRICS:
        assert got[name]["value"] is not None, name
        assert got[name]["unit"] == listed[name]["unit"]
    # 16 chunks each way: 32 DATA frames an operation, by construction
    assert got["stream_frames_per_call"]["value"] == 2.0 * CHUNKS
    assert 0 < got["stream_batches_per_call"]["value"] <= 2.0 * CHUNKS
    assert 0 < got["stream_feedback_per_call"]["value"] <= 2.0 * CHUNKS
    assert got["stream_write_ms_per_call"]["value"] >= \
        got["stream_stall_ms_per_call"]["value"] >= 0
    assert got["stream_handler_ms_per_call"]["value"] > 0
    assert got["stream_queue_ms"]["value"] > 0
    # the outside stamps are the service's: first chunk in, last chunk out
    assert got["req_path_ms"]["value"] > 0 and \
        got["resp_path_ms"]["value"] > 0
    # one socket piece a frame: the stream's frames and its feedback
    assert got["window_pieces_per_call"]["value"] >= 2.0 * CHUNKS
    for name in ("server_queue_ms", "server_parse_ms", "client_self_ms"):
        assert name not in listed


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_control_is_not_correct_and_says_by_which_number(
        capsys, restore_mesh, control):
    rc, line, err = rehearse(capsys, CELL, "--trace", "0", "--control",
                             control)
    assert line is not None and line["correct"] is False, err[-2000:]
    number = CONTROLS[control]
    checks = line["checks"]
    assert checks[number]["value"] > checks[number]["limit"] == 0
    assert f"check {number}:" in err and "NOT OK" in err
    compared = checks["replies_compared"]["value"]
    if control == "flipped_chunk":      # one byte of one chunk an operation
        assert checks["byte_mismatches"]["value"] == compared == 4
    if control == "swapped_chunks":     # two whole chunks out of place
        assert checks["byte_mismatches"]["value"] > compared * CHUNK
    if control == "host_chunk":         # every operation, sampled or not
        assert checks["short_replies"]["value"] == line["attempted"]
    # the control broke the one thing: nothing failed, nothing was re-sent
    assert checks["failed_calls"]["value"] == 0
    assert checks["second_route_events"]["value"] == 0


@pytest.mark.parametrize("counter", ["stream_window_overruns",
                                     "stream_write_failures"])
def test_a_zero_counter_that_moves_fails_the_run(capsys, restore_mesh,
                                                 monkeypatch, counter):
    """``flow_control``: one overrun of a window, or one failed write, over
    the window is a second-route event."""
    mod = loader.counter_module("stream")
    real, reads = mod.snapshot, []

    def one_more_each_read(servers):
        out = real(servers)
        reads.append(1)
        out[counter] += len(reads)
        return out

    monkeypatch.setattr(mod, "snapshot", one_more_each_read)
    rc, line, err = rehearse(capsys, CELL, "--trace", "0")
    assert line is not None and line["correct"] is False, err[-2000:]
    assert line["checks"]["second_route_events"] == {"value": 1, "limit": 0}
    assert len(reads) == 2


def test_a_reply_chunk_too_many_is_not_correct(capsys, restore_mesh,
                                               monkeypatch):
    """``exactly_once``: a server that writes one chunk twice leaves a chunk
    that no operation was waiting for; the operation that got it is wrong,
    the next one says so."""
    mod = loader.service_module(METHOD)
    real = mod.build

    def build_twice(spans):
        service = real(spans)
        service.mutate = lambda k, head, out: \
            [out, out] if k == CHUNKS - 1 else [out]
        return service

    monkeypatch.setattr(mod, "build", build_twice)
    try:
        rc, line, err = rehearse(capsys, CELL, "--trace", "0")
    except RuntimeError as e:           # already in the warm-up
        capsys.readouterr()
        assert "no operation was waiting for" in str(e)
        return
    assert line is not None and line["correct"] is False, err[-2000:]
    assert [k for k in ("failed_calls", "misordered_replies",
                        "byte_mismatches")
            if line["checks"][k]["value"] > 0], line["checks"]


def test_the_counter_module_reads_the_programs_totals():
    from brpc_tpu.rpc.stream import stream_stats
    mod = loader.counter_module("stream")
    assert set(mod.KEYS) == {f"stream_{k}" for k in stream_stats()}
    assert {"stream_data_frames_sent", "stream_data_bytes_sent",
            "stream_feedback_frames_sent", "stream_batches_delivered",
            "stream_write_failures", "stream_window_overruns"} <= \
        set(mod.KEYS)
    assert not set(mod.KEYS) & counters.TABLE_KEYS
    merged = counters.read([], ["stream"])
    assert set(merged) == counters.TABLE_KEYS | set(mod.KEYS)


# ---- the seven metrics' readers ---------------------------------------------

@pytest.fixture
def empty_store():
    span.layer_spans_reset()
    yield
    span.layer_spans_reset()


def _view(counters_=None):
    """One caller, 60 operations of 50 ms back to back over a 3 s slice."""
    calls = [((1000 + i * 50) * MS, (1050 + i * 50) * MS, 0, 1, True, f"k{i}")
             for i in range(60)]
    window = types.SimpleNamespace(calls=lambda: iter(calls),
                                   trace_slice_ns=(1000 * MS, 4000 * MS),
                                   counters=counters_ or {})
    return readers.View(window=window, reduction=None, peaks=None)


@pytest.mark.parametrize("metric", sorted(SPAN_METRICS))
def test_span_metric_reads_its_span_and_nothing_else(empty_store, metric):
    m = loader._metric(_entry("per_layer", metric))
    assert m.reader["span"] == SPAN_METRICS[metric]
    view = _view()
    assert readers.read(m, view) is None    # the parent: no such span
    for name in SPAN_METRICS.values():
        if name != SPAN_METRICS[metric] and not (
                metric == "stream_stall_ms_per_call"
                and name == "brpc.stream.write"):
            span.layer_record(name, 2000 * MS, 2007 * MS)
    assert readers.read(m, view) is None
    for i in range(60):                     # 3 ms in each operation
        span.layer_record(SPAN_METRICS[metric], (1010 + i * 50) * MS,
                          (1013 + i * 50) * MS)
    assert readers.read(m, view) == pytest.approx(3.0)


def test_no_stall_beside_writes_reads_zero(empty_store):
    m = loader._metric(_entry("per_layer", "stream_stall_ms_per_call"))
    span.layer_record("brpc.stream.write", 2000 * MS, 2001 * MS)
    assert readers.read(m, _view()) == 0.0


@pytest.mark.parametrize("metric", sorted(COUNT_METRICS))
def test_count_metric_is_its_counter_over_the_correct_operations(metric):
    m = loader._metric(_entry("per_layer", metric))
    assert m.module is None and m.reader == {
        "kind": "counter_per_call", "counter": COUNT_METRICS[metric]}
    view = _view({COUNT_METRICS[metric]: 60 * 128})
    assert readers.read(m, view) == 128.0


# ---- the system against the reference, chunk by chunk -----------------------

def test_a_stream_of_chunks_equals_the_reference_in_the_order_written():
    """The client and the service of the cell, driven directly: one
    operation's reply is the seeded block xored, in the order written."""
    import jax
    import brpc_tpu.policy  # noqa: F401  (registers the protocols)
    from brpc_tpu import rpc
    from brpc_tpu.ici.mesh import IciMesh
    from benchmarks.harness.check import attachment_bytes
    from benchmarks.harness.driver import ClientContext
    from benchmarks.harness.resident import make_set
    seed = 2 ** 31 + 33
    before = IciMesh._default
    IciMesh.set_default(IciMesh(jax.devices()[:1]))
    service = loader.service_module(METHOD).build(None)
    server = rpc.Server(rpc.ServerOptions())
    server.add_service(service)
    assert server.start("ici://0") == 0
    channel = rpc.Channel()
    assert channel.init("ici://0", options=rpc.ChannelOptions(
        ici_local_device=0, max_retry=0, timeout_ms=60000,
        connection_type="pooled")) == 0
    client = None
    try:
        client = loader.client_module("stream").open(ClientContext(
            rpc=rpc, channel=channel,
            method=f"{service.service_name()}.{METHOD}", thread=0,
            options={"chunk_bytes": CHUNK, "header_bytes": HEADER,
                     "max_buf_size": 2 * (CHUNK + HEADER), "timeout_s": 30}))
        blocks = make_set(seed, 0, 2, CHUNKS * CHUNK, jax.devices()[0])
        for i, block in enumerate(blocks):
            message, att = client.call(f"op{i}", block)
            want, key = loader.reference_module(METHOD).expected(
                payload.block(seed, 0, i, CHUNKS * CHUNK), f"op{i}")
            assert message == key
            assert len(att) == att.device_bytes() == CHUNKS * CHUNK
            assert att.backing_block_num() == CHUNKS    # as they came
            assert np.array_equal(attachment_bytes(att), want)
    finally:
        if client is not None:
            client.close()
        channel.close()
        server.stop()
        IciMesh.set_default(before)
