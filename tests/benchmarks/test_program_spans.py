"""The readers of the program's layer spans (benchmarks/harness/
program_spans.py) on synthetic spans put straight into the program's store,
and the twelve metrics of BENCHMARK.json that are read through them."""
import os
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks.harness import loader, program_spans, readers  # noqa: E402
from brpc_tpu.rpc import span  # noqa: E402

MS = 1_000_000
SLICE = (1000 * MS, 4000 * MS)
SPAN_METRICS = {
    "poller_queue_ms": "brpc.poller.queue",
    "poller_block_ms": "brpc.poller.block",
    "poller_callback_ms": "brpc.poller.callback",
    "server_queue_ms": "brpc.server.queue",
    "window_pieces_per_call": "brpc.ici.piece",
    "slice_dispatch_ms_per_call": "brpc.ici.relocate",
    "delivery_gate_ms_per_call": "brpc.ici.gate",
    "window_stall_ms_per_call": "brpc.ici.stall",
    "server_parse_ms": "brpc.server.parse",
    "server_encode_ms": "brpc.server.encode",
    "server_write_ms": "brpc.server.write",
    "client_self_ms": "brpc.call",
}


@pytest.fixture(autouse=True)
def empty_store():
    span.layer_spans_reset()
    yield
    span.layer_spans_reset()


def _view(calls, trace_slice=SLICE):
    window = types.SimpleNamespace(calls=lambda: iter(calls),
                                   trace_slice_ns=trace_slice)
    return readers.View(window=window, reduction=None, peaks=None)


def _calls(n=60, length_ms=50, start_ms=1000):
    """One caller, back to back, from the slice's start: 60 x 50 ms = the
    3 s of the slice."""
    return [((start_ms + i * length_ms) * MS,
             (start_ms + (i + 1) * length_ms) * MS, 0, 1, True, f"k{i}")
            for i in range(n)]


def _record(name, start_ms, length_ms):
    span.layer_record(name, int(start_ms * MS),
                      int((start_ms + length_ms) * MS))


def test_median_is_of_the_spans_that_ended_in_the_slice():
    for start, length in ((900, 50), (1100, 2), (2000, 4), (3000, 6),
                          (3999, 40), (4500, 1)):
        _record("brpc.poller.queue", start, length)
    _record("brpc.poller.block", 2000, 9)
    view = _view(_calls())
    # 900+50 ends before the slice, 3999+40 and 4500+1 after it
    assert program_spans.median_ms(view, "brpc.poller.queue") == \
        pytest.approx(4.0)
    assert program_spans.median_ms(view, "brpc.poller.block") == \
        pytest.approx(9.0)


def test_per_call_time_and_count_cut_a_straddling_span_to_the_slice():
    """32 pieces of 1 ms in each of the 60 calls, and one piece of 10 ms
    that straddles the slice's end by half: 32 ms and 32 pieces a call plus
    that half."""
    for c in range(60):
        for p in range(32):
            _record("brpc.ici.piece", 1000 + c * 50 + p, 1)
    _record("brpc.ici.piece", 3995, 10)
    view = _view(_calls())
    assert program_spans.per_call_ms(view, "brpc.ici.piece") == \
        pytest.approx((60 * 32 + 5) / 60)
    assert program_spans.per_call_count(view, "brpc.ici.piece") == \
        pytest.approx((60 * 32 + 0.5) / 60)


def test_calls_are_counted_by_their_share_inside_the_slice():
    """A call of 100 ms that is half inside counts a half."""
    _record("brpc.ici.gate", 1010, 30)
    calls = [(950 * MS, 1050 * MS, 0, 1, True, "a")]
    assert program_spans.per_call_ms(_view(calls), "brpc.ici.gate") == \
        pytest.approx(30 / 0.5)


@pytest.mark.parametrize("reader", ["median_ms", "per_call_ms",
                                    "per_call_count"])
@pytest.mark.parametrize("case", ["no_span_of_the_name", "no_call_in_slice",
                                  "not_traced"])
def test_nothing_to_read_is_none(reader, case):
    calls, trace_slice = _calls(), SLICE
    if case != "no_span_of_the_name":
        _record("brpc.ici.piece", 2000, 1)
    if case == "no_call_in_slice":
        calls = _calls(start_ms=5000)
    if case == "not_traced":
        trace_slice = None
    view = _view(calls, trace_slice)
    got = getattr(program_spans, reader)(view, "brpc.ici.piece")
    if case == "no_call_in_slice" and reader == "median_ms":
        assert got == pytest.approx(1.0)    # a median needs no call
    else:
        assert got is None


def test_no_stall_beside_pieces_reads_zero_and_beside_nothing_none():
    view = _view(_calls())
    assert program_spans.per_call_ms(view, "brpc.ici.stall",
                                     zero_beside="brpc.ici.piece") is None
    _record("brpc.ici.piece", 2000, 1)
    assert program_spans.per_call_ms(view, "brpc.ici.stall",
                                     zero_beside="brpc.ici.piece") == 0.0
    assert program_spans.per_call_ms(view, "brpc.ici.stall") is None


def _call_with_wait(start_ms, length_ms, wait_ms):
    """A ``brpc.call`` that caused a ``brpc.call.wait`` (none for 0)."""
    import jax.profiler  # noqa: F401
    span.layer_on()     # binds the annotation class, as any site does first
    call = span.layer_begin("brpc.call")
    call.start_ns = start_ms * MS
    if wait_ms:
        _record("brpc.call.wait", start_ms + 1, wait_ms)
    call.leave()
    call.finish((start_ms + length_ms) * MS)


def test_self_time_is_the_span_less_the_children_it_caused():
    view = _view(_calls())
    assert program_spans.self_ms(view, "brpc.call", "brpc.call.wait") is None
    _record("brpc.call.wait", 2000, 500)        # another span's child
    assert program_spans.self_ms(view, "brpc.call", "brpc.call.wait") is None
    for start, length, wait in ((1100, 10, 7), (1200, 12, 7), (1300, 4, 0),
                                (3995, 100, 1)):    # the last ends outside
        _call_with_wait(start, length, wait)
    assert program_spans.self_ms(view, "brpc.call", "brpc.call.wait") == \
        pytest.approx(4.0)                      # of 3, 5 and 4


def test_a_program_without_layer_spans_reads_none(monkeypatch):
    """The parent of the PR that brought the spans has no ``layer_spans``:
    the benchmark's files run over it and leave the metrics out."""
    _record("brpc.ici.piece", 2000, 1)
    monkeypatch.delattr(span, "layer_spans")
    view = _view(_calls())
    assert program_spans.spans(view, "brpc.ici.piece") == []
    assert program_spans.per_call_count(view, "brpc.ici.piece") is None
    assert program_spans.self_ms(view, "brpc.ici.piece", "brpc.x") is None


@pytest.mark.parametrize("metric", sorted(SPAN_METRICS))
def test_metric_reads_its_span_and_nothing_else(metric):
    entry = next(m for m in loader.manifest()["per_layer"]
                 if m["name"] == metric)
    assert entry["source"] == "program_span"
    m = loader._metric(entry)
    assert m.reader["span"] == SPAN_METRICS[metric]
    view = _view(_calls())
    if metric != "window_stall_ms_per_call":
        assert readers.read(m, view) is None
    for name in SPAN_METRICS.values():
        if name != SPAN_METRICS[metric]:
            _record(name, 2000, 7)
    want_nothing = 0.0 if metric == "window_stall_ms_per_call" else None
    assert readers.read(m, view) == want_nothing
    for i in range(60):
        _record(SPAN_METRICS[metric], 1010 + i * 50, 3)
    want = 1.0 if metric == "window_pieces_per_call" else 3.0
    assert readers.read(m, view) == pytest.approx(want)
