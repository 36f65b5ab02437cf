"""The streaming cell's traced rehearsal after PR 38: a chunk that is on the
server's chip already meets no delivery gate, so no span of the device poller
is left in the window and the four metrics that read those spans fall silent
— and the cell no longer lists them — while every metric the cell lists
is there, ``delivery_gate_ms_per_call`` among them.

tests/benchmarks/test_stream_cell.py holds the same of the listed metrics;
this file holds the route besides: no chunk met the gate.
"""
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import loader  # noqa: E402

CELL, CHUNKS = "stream_1m", 16              # the rehearsal's operation
POLLER_METRICS = {"poller_queue_ms", "poller_block_ms", "poller_callback_ms",
                  "poller_callback_cpu_ms"}
STREAM_METRICS = ("stream_frames_per_call", "stream_feedback_per_call",
                  "stream_batches_per_call", "stream_write_ms_per_call",
                  "stream_stall_ms_per_call", "stream_queue_ms",
                  "stream_handler_ms_per_call")


@pytest.fixture
def traced_line(capsys, monkeypatch):
    """(the last line of a traced rehearsal of the cell, what
    ``ici_piece_stats`` counted over it)."""
    from benchmarks.harness import driver
    from brpc_tpu.ici import transport
    from brpc_tpu.ici.mesh import IciMesh
    # a stream's operations draw no call id (tests/conftest.py, ROADMAP 1.1)
    monkeypatch.setattr(driver, "age_call_ids", lambda slots: None)
    mesh_before, before = IciMesh._default, transport.ici_piece_stats()
    try:
        rc = bench_run.main(["--workload", CELL, "--seed", "2147483693",
                             "--seconds", "1.0", "--rehearse",
                             "--trace", "1"])
    finally:
        IciMesh.set_default(mesh_before)
    out, err = capsys.readouterr()
    lines = [ln for ln in out.splitlines() if ln.strip()]
    assert rc == 0 and lines, err[-2000:]
    after = transport.ici_piece_stats()
    return json.loads(lines[-1]), {k: after[k] - before[k] for k in after}


def test_the_streams_chunks_meet_no_gate_and_the_poller_metrics_fall_silent(
        traced_line):
    line, counted = traced_line
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    listed = {m["name"]: m for m in loader.manifest()["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    # every chunk was on the server's chip: none waited for the poller
    assert counted["gated_arrays"] == 0
    assert counted["resident_refs_ungated"] >= 2 * CHUNKS * line["attempted"]
    # ... so the cell lists none of the poller's four metrics, and
    # nothing it lists is missing
    assert not POLLER_METRICS & set(listed)
    assert set(listed) <= set(got)
    assert set(got) <= set(listed)
    # the gate's span is still recorded for every entry: about 0, not absent
    gate = got["delivery_gate_ms_per_call"]["value"]
    assert gate is not None and 0 <= gate < \
        got["stream_write_ms_per_call"]["value"]
    # what test_stream_cell.py's skipped case held besides
    for name in STREAM_METRICS:
        assert got[name]["value"] is not None, name
        assert got[name]["unit"] == listed[name]["unit"]
    assert got["stream_frames_per_call"]["value"] == 2.0 * CHUNKS
    assert 0 < got["stream_batches_per_call"]["value"] <= 2.0 * CHUNKS
    assert 0 < got["stream_feedback_per_call"]["value"] <= 2.0 * CHUNKS
    assert got["stream_write_ms_per_call"]["value"] >= \
        got["stream_stall_ms_per_call"]["value"] >= 0
    assert got["stream_handler_ms_per_call"]["value"] > 0
    assert got["stream_queue_ms"]["value"] > 0
    assert got["req_path_ms"]["value"] > 0 and \
        got["resp_path_ms"]["value"] > 0
    assert got["window_pieces_per_call"]["value"] >= 2.0 * CHUNKS
