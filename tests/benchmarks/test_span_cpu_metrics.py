"""The readers of the CPU time in the program's layer spans
(benchmarks/harness/span_cpu.py) on synthetic records put straight into the
program's store, the nine metrics of BENCHMARK.json that PR 37 reads through
them, and a traced rehearsal of the two cells that cut pieces.

Also here, by entry and not by place: every clause of the two cases of
test_fanout_cell.py that hold the fan-out's five metrics to be the LAST of
``per_layer`` (skipped at the end of tests/conftest.py since this PR's nine
entries follow them)."""
import os
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks.harness import loader, program_spans, readers  # noqa: E402
from benchmarks.harness import span_cpu  # noqa: E402
from brpc_tpu.butil import layer_span  # noqa: E402
from brpc_tpu.rpc import span  # noqa: E402
from test_benchmark_harness import rehearse, restore_mesh  # noqa: E402,F401
from test_fanout_cell import FANOUT_METRICS, TAKEN_IN  # noqa: E402
from test_program_spans import MS, _calls, _view  # noqa: E402

CHANNEL = "channel, codec, dispatch and planes"
BULK = ["local_bulk_64m", "xchip_bulk_64m", "stream_1m", "fanout_4x16m"]
# metric -> (span, reader of span_cpu / program_spans, layer, moves, cells)
NINE = {
    "piece_cut_ms_per_call": (
        "brpc.ici.cut", "wall", CHANNEL, "goodput_gbs",
        ["local_bulk_64m", "fanout_4x16m"]),
    "piece_cut_cpu_ms_per_call": (
        "brpc.ici.cut", "cpu", CHANNEL, "goodput_gbs",
        ["local_bulk_64m", "fanout_4x16m"]),
    "piece_cpu_ms_per_call": (
        "brpc.ici.piece", "cpu", CHANNEL, "goodput_gbs", BULK),
    "piece_offcpu_ms_per_call": (
        "brpc.ici.piece", "off", CHANNEL, "goodput_gbs", BULK),
    "call_cpu_ms": (
        "brpc.call", "median", CHANNEL, "latency_p50_ms",
        ["local_compute_1m", "local_bulk_64m", "xchip_bulk_64m",
         "fanout_4x16m"]),
    "plane_run_cpu_ms_per_call": (
        "brpc.plane.run", "cpu", "device plane", "goodput_gbs",
        ["xchip_bulk_64m"]),
    "poller_callback_cpu_ms": (
        "brpc.poller.callback", "median", "device completion",
        "latency_p50_ms", ["local_compute_1m"] + BULK),
    "fanout_issue_cpu_ms_per_call": (
        "brpc.fanout.issue", "cpu", "fan-out", "goodput_gbs",
        ["fanout_4x16m"]),
    "stream_handler_cpu_ms_per_call": (
        "brpc.stream.handler", "cpu", "stream", "goodput_gbs",
        ["stream_1m"]),
}
FANOUT = list(FANOUT_METRICS)
STREAM_COUNTED = ["stream_frames_per_call", "stream_feedback_per_call",
                  "stream_batches_per_call"]
STREAM_TIMED = ["stream_write_ms_per_call", "stream_stall_ms_per_call",
                "stream_queue_ms", "stream_handler_ms_per_call"]


@pytest.fixture(autouse=True)
def empty_store():
    span.layer_spans_reset()
    yield
    span.layer_spans_reset()


@pytest.fixture(autouse=True)
def no_call_id_ageing(monkeypatch):
    """As test_fanout_cell.py: the ageing matters on the chip alone."""
    from benchmarks.harness import driver
    monkeypatch.setattr(driver, "age_call_ids", lambda slots: None)


_ids = iter(range(1, 1 << 30))


def _record(name, start_ms, length_ms, cpu_ms=None, fields=10):
    """A record straight into this thread's list: a lexical span's ten
    fields, or the eight / nine of a record with no ``cpu_ns``."""
    cpu = -1 if cpu_ms is None else int(cpu_ms * MS)
    rec = (name, int(start_ms * MS), int((start_ms + length_ms) * MS), 0,
           next(_ids), 0, "t", 0, 0, cpu)
    layer_span._thread().records.append(rec[:fields])


def _entry(group, name):
    return next(e for e in loader.manifest()[group] if e["name"] == name)


# ---- the arithmetic ---------------------------------------------------------

def test_per_call_cpu_and_offcpu_cut_a_straddling_span_in_proportion():
    """32 pieces of 1 ms in each of the 60 calls, each a quarter of it on
    the CPU, and one piece of 10 ms (4 on the CPU) that straddles the
    slice's end by half: its half counts, in both parts."""
    for c in range(60):
        for p in range(32):
            _record("brpc.ici.piece", 1000 + c * 50 + p, 1, 0.25)
    _record("brpc.ici.piece", 3995, 10, 4)
    view = _view(_calls())
    cpu = span_cpu.cpu_per_call_ms(view, "brpc.ici.piece")
    off = span_cpu.offcpu_per_call_ms(view, "brpc.ici.piece")
    assert cpu == pytest.approx((60 * 32 * 0.25 + 2) / 60)
    assert off == pytest.approx((60 * 32 * 0.75 + 3) / 60)
    # with the one above it sums to the pieces' time a call
    assert cpu + off == pytest.approx(
        program_spans.per_call_ms(view, "brpc.ici.piece"))


def test_a_span_that_begins_before_the_slice_is_cut_the_same_way():
    _record("brpc.plane.run", 990, 40, 10)      # three quarters inside
    view = _view(_calls())
    assert span_cpu.cpu_per_call_ms(view, "brpc.plane.run") \
        == pytest.approx(7.5 / 60)
    assert span_cpu.offcpu_per_call_ms(view, "brpc.plane.run") \
        == pytest.approx(22.5 / 60)


def test_calls_are_counted_by_their_overlap_with_the_slice():
    """Calls of 50 ms that begin 25 ms before the slice: the first counts a
    half, sixty calls are 59.5."""
    for c in range(60):
        _record("brpc.fanout.issue", 1000 + c * 50, 10, 6)
    view = _view(_calls(start_ms=975))
    assert span_cpu.cpu_per_call_ms(view, "brpc.fanout.issue") \
        == pytest.approx(60 * 6 / 59.5)


def test_a_record_with_no_cpu_reading_is_left_out():
    view = _view(_calls())
    for fields in (8, 9, 10):           # stamped, pre-PR lexical, cpu_ns -1
        _record("brpc.ici.piece", 2000, 5, fields=fields)
    assert [s.cpu_ns for s in span.layer_spans()] == [-1, -1, -1]
    for read in (span_cpu.cpu_per_call_ms, span_cpu.offcpu_per_call_ms,
                 span_cpu.cpu_of_median_ms):
        assert read(view, "brpc.ici.piece") is None
    _record("brpc.ici.piece", 2100, 6, 3)
    assert span_cpu.cpu_per_call_ms(view, "brpc.ici.piece") \
        == pytest.approx(3 / 60)
    assert span_cpu.offcpu_per_call_ms(view, "brpc.ici.piece") \
        == pytest.approx(3 / 60)
    assert span_cpu.cpu_of_median_ms(view, "brpc.ici.piece") \
        == pytest.approx(3.0)       # the one reading: 6 ms, half of it CPU
    # the span's own time counts all four
    assert program_spans.per_call_ms(view, "brpc.ici.piece") \
        == pytest.approx(21 / 60)


def test_cpu_of_the_median_span_is_its_length_times_the_slices_cpu_share():
    """A clock that moves in steps of 10 ms reads 0 in most short spans and
    10 ms in a few: the median of the readings says nothing, the share of
    the spans' time that was CPU time does."""
    for start, length, cpu in ((900, 50, 40), (1100, 2, 0), (2000, 12, 10),
                               (3000, 6, 0), (3100, 4, 0), (3999, 40, 30),
                               (4500, 1, 1)):
        _record("brpc.poller.callback", start, length, cpu)
    # 900+50 ends before the slice, 3999+40 and 4500+1 after it: the median
    # of 2, 12, 6 and 4 ms is 5, and 10 of their 24 ms were CPU time
    assert span_cpu.cpu_of_median_ms(_view(_calls()),
                                     "brpc.poller.callback") \
        == pytest.approx(5.0 * 10 / 24)


@pytest.mark.parametrize("why", ["no_span", "no_slice", "no_calls",
                                 "a_program_without_the_field",
                                 "a_program_without_layer_spans"])
def test_nothing_to_read_is_none(monkeypatch, why):
    """What the parent of this PR gives: the line then lacks the metric."""
    view = _view(_calls())
    if why != "no_span":
        _record("brpc.call", 2000, 5, 1)
    if why == "no_slice":
        view = _view(_calls(), trace_slice=None)
    elif why == "no_calls":
        view = _view([])
    elif why == "a_program_without_the_field":
        old = types.SimpleNamespace(name="brpc.call", start_ns=2000 * MS,
                                    end_ns=2005 * MS)
        monkeypatch.setattr(span, "layer_spans", lambda *a: [old])
    elif why == "a_program_without_layer_spans":
        monkeypatch.delattr(span, "layer_spans")
    assert span_cpu.cpu_per_call_ms(view, "brpc.call") is None
    assert span_cpu.offcpu_per_call_ms(view, "brpc.call") is None
    if why != "no_calls":               # a figure a span asks for no call
        assert span_cpu.cpu_of_median_ms(view, "brpc.call") is None


# ---- the nine metrics --------------------------------------------------------

@pytest.mark.parametrize("metric", list(NINE))
def test_the_entry_and_its_files(metric):
    name, _, layer, moves, cells = NINE[metric]
    assert _entry("per_layer", metric) == {
        "name": metric, "unit": "ms", "better": "lower",
        "source": "program_span", "layer": layer, "moves": moves,
        "workloads": cells}
    # a cell of the metric reports the end-to-end metric it moves
    moved = _entry("end_to_end", moves)
    assert set(cells) <= set(moved.get(
        "workloads", [w["name"] for w in loader.manifest()["workloads"]]))
    m = loader._metric(_entry("per_layer", metric))
    assert m.reader == {"span": name} and m.module is not None
    for cell in cells:
        assert metric in {x.name for x in loader.load_cell(cell).per_layer}


@pytest.mark.parametrize("metric", list(NINE))
def test_metric_reads_its_span_and_nothing_else(metric):
    name, how, *_ = NINE[metric]
    m = loader._metric(_entry("per_layer", metric))
    view = _view(_calls())
    assert readers.read(m, view) is None
    for other, *_ in NINE.values():
        if other != name:
            _record(other, 2000, 7, 5)
    assert readers.read(m, view) is None
    for i in range(60):
        _record(name, 1010 + i * 50, 3, 1)
    want = {"wall": 3.0, "cpu": 1.0, "off": 2.0, "median": 1.0}[how]
    assert readers.read(m, view) == pytest.approx(want)


def test_the_nine_follow_the_fanouts_five_and_change_no_entry_before_them():
    names = [m["name"] for m in loader.manifest()["per_layer"]]
    assert names[-9:] == list(NINE)
    assert names[-14:-9] == FANOUT
    at = names.index("stream_frames_per_call")
    assert names[at:at + 7] == STREAM_COUNTED + STREAM_TIMED
    assert names[at + 7:at + 12] == FANOUT
    assert len(names) == len(set(names)) == 45
    # the spans the nine read are the seven of the issue, no more
    assert sorted({v[0] for v in NINE.values()}) == [
        "brpc.call", "brpc.fanout.issue", "brpc.ici.cut", "brpc.ici.piece",
        "brpc.plane.run", "brpc.poller.callback", "brpc.stream.handler"]


# ---- the two skipped cases of test_fanout_cell.py, by entry ------------------

def test_the_fanout_cells_entries_are_as_they_were():
    """``test_the_manifest_gains_one_configuration_one_cell_five_metrics``
    less its one clause of place."""
    cell, config = "fanout_4x16m", "parallel_echo_local"
    man = loader.manifest()
    assert [c["name"] for c in man["configs"]][-1] == config
    assert [w["name"] for w in man["workloads"]][-1] == cell
    for line in (_entry("configs", config)["why"],
                 _entry("configs", config)["source"],
                 _entry("workloads", cell)["why"]):
        assert 1 <= len(line) <= 200 and line.isprintable(), line
    assert len(man["workloads"]) == 5
    assert sum(w["chips"] == 4 for w in man["workloads"]) == 1
    assert _entry("workloads", cell) == {
        "name": cell, "config": config, "traffic": "shard_4x16m",
        "chips": 1, "why": loader.load_cell(cell).workload["why"]}
    for name in FANOUT:
        e = _entry("per_layer", name)
        assert e["layer"] == "fan-out" and e["workloads"] == [cell]
        assert e["moves"] == ("latency_p50_ms" if name == "fanout_overlap"
                              else "goodput_gbs")
        assert e["source"] == ("program_counter"
                               if name == "fanout_subcalls_per_call"
                               else "program_span")
    for name in TAKEN_IN:
        assert _entry("per_layer", name)["workloads"][-1] == cell
    assert _entry("end_to_end", "goodput_gbs")["workloads"][-1] == cell


def test_the_stream_cells_entries_are_as_they_were():
    """``test_the_streaming_cells_entries_are_as_they_were`` of
    test_fanout_cell.py less its one clause of place
    (what follows the stream's seven is held above)."""
    cell, config = "fanout_4x16m", "parallel_echo_local"
    man = loader.manifest()
    configs = [c["name"] for c in man["configs"]]
    cells = [w["name"] for w in man["workloads"]]
    assert configs[configs.index(config) - 1] == "streaming_echo"
    assert cells[cells.index(cell) - 1] == "stream_1m"
    assert _entry("workloads", "stream_1m") == {
        "name": "stream_1m", "config": "streaming_echo",
        "traffic": "stream_64x1m", "chips": 1,
        "why": loader.load_cell("stream_1m").workload["why"]}
    for name in STREAM_COUNTED + STREAM_TIMED:
        e = _entry("per_layer", name)
        assert e["layer"] == "stream" and e["workloads"] == ["stream_1m"]
        assert e["moves"] == ("latency_p50_ms" if name == "stream_queue_ms"
                              else "goodput_gbs")
        assert e["source"] == ("program_span" if name in STREAM_TIMED
                               else "program_counter")
    for name in ("server_queue_ms", "server_parse_ms", "server_encode_ms",
                 "server_write_ms", "client_self_ms"):
        assert _entry("per_layer", name)["workloads"] == [
            "local_compute_1m", "local_bulk_64m", "xchip_bulk_64m", cell]
    for name in ("device_busy_ms_per_call", "window_pieces_per_call",
                 "slice_dispatch_ms_per_call", "delivery_gate_ms_per_call",
                 "window_stall_ms_per_call"):
        assert _entry("per_layer", name)["workloads"][-2:] == [
            "stream_1m", cell]
    assert _entry("end_to_end", "goodput_gbs")["workloads"][-2:] == [
        "stream_1m", cell]


def test_the_hook_skips_those_two_cases_of_that_file_and_no_other():
    import conftest

    class Item:
        def __init__(self, name):
            self.name, self.nodeid, self.marks = \
                name, f"tests/benchmarks/test_fanout_cell.py::{name}", []

        def add_marker(self, mark):
            self.marks.append(mark)

    import test_fanout_cell
    items = [Item(n) for n in dir(test_fanout_cell) if n.startswith("test_")]
    assert len(items) > 20
    conftest.pytest_collection_modifyitems(None, items)
    assert sorted(i.name for i in items if i.marks) == [
        "test_the_manifest_gains_one_configuration_one_cell_five_metrics",
        "test_the_streaming_cells_entries_are_as_they_were"]


# ---- a traced rehearsal on CPU devices ---------------------------------------

@pytest.mark.parametrize("cell", ["local_bulk_64m", "fanout_4x16m"])
def test_traced_rehearsal_carries_the_cells_new_metrics(capsys, restore_mesh,
                                                        cell):
    """Every metric of the nine that the cell lists is in the line wherever
    the accepted metric of the same span is (a rehearsal's small shards ride
    the native tier and cut no piece; CPU arrays are ready and park no
    completion), and no CPU figure passes its span's own time."""
    rc, line, err = rehearse(capsys, cell, "--trace", "1", seconds="1.0")
    assert rc == 0 and line["correct"] is True, err[-2000:]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    beside = {"piece_cut_ms_per_call": "slice_dispatch_ms_per_call",
              "piece_cut_cpu_ms_per_call": "slice_dispatch_ms_per_call",
              "piece_cpu_ms_per_call": "window_pieces_per_call",
              "piece_offcpu_ms_per_call": "window_pieces_per_call",
              "call_cpu_ms": "client_self_ms",
              "poller_callback_cpu_ms": "poller_callback_ms",
              "fanout_issue_cpu_ms_per_call": "fanout_issue_ms_per_call"}
    mine = [m for m, v in NINE.items() if cell in v[4]]
    assert sorted(mine) == sorted(beside if cell == "fanout_4x16m" else
                                  set(beside) - {"fanout_issue_cpu_ms_"
                                                 "per_call"})
    for metric in mine:
        assert (metric in got) == (beside[metric] in got), metric
        assert got.get(metric, 0.0) >= 0.0
    assert "call_cpu_ms" in got
    if cell == "local_bulk_64m":        # its 1 MiB blocks are cut in pieces
        assert 0 < got["piece_cut_cpu_ms_per_call"] \
            <= got["piece_cut_ms_per_call"] \
            <= got["slice_dispatch_ms_per_call"]
        assert got["piece_cut_ms_per_call"] <= got["piece_cpu_ms_per_call"] \
            + got["piece_offcpu_ms_per_call"]
    else:
        assert 0 < got["fanout_issue_cpu_ms_per_call"] \
            <= got["fanout_issue_ms_per_call"]
    # the store still holds the session's records: each reading by itself,
    # and only the spans a metric reads pay for the CPU clock
    read = {v[0] for v in NINE.values()}
    spans = span.layer_spans()
    lexical = [s for s in spans if s.cpu_ns >= 0]
    assert {"brpc.call"} <= {s.name for s in lexical} <= read
    assert all(s.cpu_ns <= s.end_ns - s.start_ns for s in lexical)
    assert all(s.cpu_ns >= 0 for s in spans if s.name in read)
    assert {"brpc.call.wait", "brpc.server.handler"} <= {
        s.name for s in spans if s.cpu_ns == -1}
