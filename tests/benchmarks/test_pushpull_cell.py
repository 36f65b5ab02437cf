"""The reducing deployment ``param_server_local`` and its cell
``pushpull_4x16m`` at CPU size: the cell resolves from BENCHMARK.json alone, a
rehearsal is correct with its route and its three zero-counters held, each of
its controls comes out not correct by the number it is meant to move, the
traced rehearsal's line has the four metrics of the reduce beside the fan-out
layer's, their readers read the program's ``brpc.fanout.reduce`` span, its
counter and the device's operations, and the cell's client copies and converts
nothing.

Also here, BY ENTRY AND BY ORDER and never by last place or by a count, every
clause that can still hold of the cases the end of tests/conftest.py skips
since this cell, its configuration and its four metrics were appended
(``test_span_cpu_metrics.py``: ``test_the_entry_and_its_files`` for a metric
whose list the cell joined, ``test_the_nine_follow_...``, the two
``..._entries_are_as_they_were``, and the two tests of the hook itself; and
``test_control_comes_out_not_correct`` for the three unary controls, whose
``GUARANTEE`` this configuration states under another name).  They are written
for the cells up to this one: a cell appended later changes none of them.
"""
import os
import re
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks.harness import counters, loader, readers, xplane  # noqa: E402
from benchmarks.reference import payload  # noqa: E402
from brpc_tpu.rpc import span  # noqa: E402
from test_benchmark_harness import rehearse, restore_mesh  # noqa: E402,F401
from test_fanout_cell import FANOUT_METRICS, TAKEN_IN  # noqa: E402
from test_span_cpu_metrics import (  # noqa: E402
    NINE, STREAM_COUNTED, STREAM_TIMED)

CELL, CONFIG, METHOD = "pushpull_4x16m", "param_server_local", "PushPull"
WIDTH, RANGE = 4, 1 << 20               # the rehearsal's operation
MS = 1_000_000
ACCEPTED = ["local_compute_1m", "local_bulk_64m", "xchip_bulk_64m",
            "stream_1m", "fanout_4x16m"]
SIX = ACCEPTED + [CELL]
CONFIGS = ["rdma_perf_local", "rdma_perf_xchip", "streaming_echo",
           "parallel_echo_local", CONFIG]
# metric -> (source, what it reads)
REDUCE_METRICS = {
    "fanout_reduce_ms_per_call": ("program_span", "brpc.fanout.reduce"),
    "fanout_reduce_cpu_ms_per_call": ("program_span", "brpc.fanout.reduce"),
    "fanout_reduce_programs_per_call": ("program_counter",
                                        "fanout_reduce_programs"),
    "fanout_reduce_roofline": ("device_trace", "hbm_gbs"),
}
FANOUT_SIX = list(FANOUT_METRICS) + ["fanout_issue_cpu_ms_per_call"]
PIECES = ["window_pieces_per_call", "slice_dispatch_ms_per_call",
          "delivery_gate_ms_per_call", "window_stall_ms_per_call",
          "piece_cut_ms_per_call", "piece_cut_cpu_ms_per_call",
          "piece_cpu_ms_per_call", "piece_offcpu_ms_per_call"]
UNARY_PATH = ["server_queue_ms", "server_parse_ms", "server_encode_ms",
              "server_write_ms", "client_self_ms", "call_cpu_ms"]
JOINED = FANOUT_SIX + PIECES + UNARY_PATH + ["device_busy_ms_per_call"]
POLLER = ["poller_queue_ms", "poller_block_ms", "poller_callback_ms"]
# control -> the guarantee it breaks here, the number it moves
CONTROLS = {"dropped_worker": ("exactly_once", "byte_mismatches"),
            "doubled_worker": ("exactly_once", "byte_mismatches"),
            "host_sum": ("device_resident", "second_route_events"),
            "failed_worker": ("all_or_nothing", "failed_calls"),
            # the three unary controls reach every worker's reply
            "flipped_byte": ("result", "byte_mismatches"),
            "stale_reply": ("result", "byte_mismatches"),
            "host_reply": ("device_resident", "second_route_events")}
OWN = ("dropped_worker", "doubled_worker", "host_sum", "failed_worker")


@pytest.fixture(autouse=True)
def no_call_id_ageing(monkeypatch):
    """As test_fanout_cell.py: the ageing matters on the chip alone."""
    from benchmarks.harness import driver
    monkeypatch.setattr(driver, "age_call_ids", lambda slots: None)


def _source(*parts):
    with open(os.path.join(REPO, *parts), encoding="utf-8") as f:
        return f.read()


def _entry(group, name):
    return next(e for e in loader.manifest()[group] if e["name"] == name)


def _names(group):
    return [e["name"] for e in loader.manifest()[group]]


def _in_order(some, of):
    """``some`` is a subsequence of ``of``."""
    it = iter(of)
    return all(x in it for x in some)


def _joined_later(listed, accepted):
    """``listed`` is ``accepted`` as it was, then only cells that the
    manifest appended after the last of them, in the manifest's order."""
    cells = _names("workloads")
    tail = listed[len(accepted):]
    later = cells[max(cells.index(c) for c in accepted) + 1:]
    return listed[:len(accepted)] == accepted and _in_order(tail, later) \
        and len(set(tail)) == len(tail)


# ---- the cell is files and manifest entries -------------------------------

def test_cell_resolves_from_the_manifest_alone():
    assert len(loader.ROOTS) == 1           # no fixture root in this file
    cell = loader.load_cell(CELL)
    assert (cell.config_name, cell.chips) == (CONFIG, 1)
    assert cell.methods() == [METHOD] and cell.clients() == ["pushpull"]
    assert cell.workload["counters"] == ["fanout", "fanout_reduce"]
    for mod, name in ((loader.client_module("pushpull"), "clients.pushpull"),
                      (loader.service_module(METHOD), "services.PushPull"),
                      (loader.reference_module(METHOD), "reference.PushPull"),
                      (loader.counter_module("fanout_reduce"),
                       "counters.fanout_reduce")):
        assert mod.__name__ == f"benchmarks.{name}"
    for control in OWN:
        mod = loader.control_module(control)
        assert mod.__name__ == f"benchmarks.controls.{control}"
        assert mod.GUARANTEE == CONTROLS[control][0]
        assert control in cell.config["controls"]
    for guarantee, _ in CONTROLS.values():
        assert guarantee in cell.config["guarantees"]
    assert counters.second_route(cell) == list(counters.SECOND_ROUTE) + [
        "fanout_host_operand_bytes", "fanout_partial_results",
        "fanout_reduce_host_merges"]
    assert {m.name for m in cell.end_to_end} == {
        "goodput_gbs", "latency_p50_ms", "latency_p95_ms", "setup_s"}
    listed = {m.name for m in cell.per_layer}
    assert set(REDUCE_METRICS) | set(JOINED) <= listed
    assert not set(POLLER) & listed         # nothing here enters the poller
    assert "poller_callback_cpu_ms" not in listed


def test_the_traffic_is_the_issues_letter_for_letter():
    cell = loader.load_cell(CELL)
    wl, cfg = cell.workload, cell.config
    assert wl["threads"] == 2 and cfg["queue_depth"] == 1
    assert wl["sets"] == {"resident": {"id": 0, "block_bytes": 1 << 24,
                                       "bytes": 6 << 30}}     # 384 ranges
    (mix,) = wl["mix"]
    assert mix == {"method": METHOD, "set": "resident", "weight": 1,
                   "client": "pushpull",
                   "client_options": {"sub_channels": 4, "fail_limit": 1}}
    assert wl["sample_per_thread"] == 16 and wl["schedule_cycle"] == 1
    assert (wl["warmup_seconds"], wl["trace_seconds"]) == (1.0, 3.0)
    assert wl["bind"] == "round_robin"
    assert wl["channel_options"] == {"connection_type": "pooled"}
    route = {r["counter"]: r["per_call_min"] for r in wl["route"]}
    assert route == {"fanout_sub_calls": 4.0,
                     "fanout_device_operand_bytes": 4 << 24,
                     "ici_device_bytes": 8 << 24,
                     "fanout_reduce_programs": 1.0,
                     "fanout_reduce_input_bytes": 4 << 24}
    tiny = loader.load_cell(CELL, rehearse=True).workload
    assert tiny["sets"]["resident"]["block_bytes"] == RANGE
    # a 1 MiB range fits the native window: the rehearsal's sub-calls ride
    # the native tier, and its route says so
    assert {r["counter"]: r["per_call_min"] for r in tiny["route"]} == {
        "fanout_sub_calls": 4.0,
        "fanout_device_operand_bytes": WIDTH * RANGE,
        "native_requests": 4.0, "fanout_reduce_programs": 1.0,
        "fanout_reduce_input_bytes": WIDTH * RANGE}
    # the same layer under the merge as fanout_4x16m: one window a sub-call
    shard = loader.load_cell("fanout_4x16m").config["assumed"]["shard_bytes"]
    assert cfg["assumed"]["range_bytes"] == shard == 1 << 24


def test_the_configuration_states_its_source_and_its_cut():
    cfg = loader.load_cell(CELL).config
    entry = _entry("configs", CONFIG)
    assert cfg["source"] == entry["source"] and len(cfg["source"]) <= 200
    for word in ("docs/cn/combo_channel.md", "ParallelChannel",
                 "default CallMapper", "example/parallel_echo_c++"):
        assert word in cfg["source"], word
    assert cfg["source"] != _entry("configs", "parallel_echo_local")["source"]
    assert any("from memory" in n for n in cfg["source_notes"])
    assert cfg["reduced"] == entry["reduced"] == [
        "servers", "resident_bytes", "server_handler"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    assert cfg["assumed"] == {
        "sub_channels": 4, "range_bytes": 1 << 24, "dtype": "float32",
        "merge": "sum", "fail_limit": 1, "threads": 2,
        "connection_type": "pooled",
        "contribution": "g_i[j] = float32((w[j] >> 8i) & 0xFF) * 4**i"}
    assert set(cfg["assumed_why"]) == set(cfg["assumed"])
    local = loader.load_cell("fanout_4x16m").config
    for key in ("chips", "caller_device", "servers", "channel_options",
                "queue_depth", "resident_bytes"):
        assert cfg[key] == local[key], key      # rdma_perf_local's layout
    assert list(cfg["guarantees"]) == [
        "result", "exactly_once", "precision", "all_or_nothing",
        "device_resident", "single_route", "no_retry"]
    assert cfg["second_route_counters"] == [
        "fanout_host_operand_bytes", "fanout_partial_results",
        "fanout_reduce_host_merges"]


def test_the_manifest_holds_the_cell_its_configuration_and_four_metrics():
    man = loader.manifest()
    configs, cells = _names("configs"), _names("workloads")
    assert configs[configs.index(CONFIG) - 1] == "parallel_echo_local"
    assert cells[cells.index(CELL) - 1] == "fanout_4x16m"
    # the driver refuses a line of more than 200 characters before any run
    for line in (_entry("configs", CONFIG)["why"],
                 _entry("configs", CONFIG)["source"],
                 _entry("workloads", CELL)["why"]):
        assert 1 <= len(line) <= 200 and line.isprintable(), line
    assert _entry("configs", CONFIG) == {
        "name": CONFIG, "source": loader.load_cell(CELL).config["source"],
        "file": "benchmarks/configs/param_server_local.json",
        "reduced": ["servers", "resident_bytes", "server_handler"],
        "why": _entry("configs", CONFIG)["why"]}
    assert _entry("workloads", CELL) == {
        "name": CELL, "config": CONFIG, "traffic": "replicate_sum_4x16m",
        "chips": 1, "why": loader.load_cell(CELL).workload["why"]}
    # of the cells up to this one, one asks for four chips: a quarter of six,
    # rounded down, is one
    upto = man["workloads"][:cells.index(CELL) + 1]
    assert [w["name"] for w in upto] == SIX
    assert [w["name"] for w in upto if w["chips"] == 4] == ["xchip_bulk_64m"]
    names = _names("per_layer")
    at = names.index("fanout_reduce_ms_per_call")
    assert names[at:at + 4] == list(REDUCE_METRICS)
    assert names[at - 1] == "stream_handler_cpu_ms_per_call"
    for name, (source, _) in REDUCE_METRICS.items():
        assert _entry("per_layer", name) == {
            "name": name,
            "unit": {"fanout_reduce_programs_per_call": "programs/call",
                     "fanout_reduce_roofline": "%"}.get(name, "ms"),
            "better": "higher" if name.endswith("roofline") else "lower",
            "source": source, "layer": "fan-out", "moves": "goodput_gbs",
            "workloads": _entry("per_layer", name)["workloads"]}
        assert _entry("per_layer", name)["workloads"][0] == CELL
    for name in JOINED + ["goodput_gbs"]:
        group = "end_to_end" if name == "goodput_gbs" else "per_layer"
        listed = _entry(group, name)["workloads"]
        assert listed[listed.index(CELL) - 1] == "fanout_4x16m", name
    # every cell of a metric reports the end-to-end metric it moves
    for m in man["per_layer"]:
        moved = _entry("end_to_end", m["moves"])
        assert set(m.get("workloads", cells)) <= set(
            moved.get("workloads", cells)), m["name"]


def test_the_three_listless_poller_metrics_list_the_five_accepted_cells():
    """"No list" meant every cell, which was these five; the cell, where
    every ref is resident and nothing enters the device poller, is not asked
    for them.  ``poller_callback_cpu_ms`` had its list and keeps it."""
    for name in POLLER:
        e = _entry("per_layer", name)
        assert e["workloads"][:5] == ACCEPTED and CELL not in e["workloads"]
        assert e == {"name": name, "unit": "ms", "better": "lower",
                     "source": "program_span", "layer": "device completion",
                     "moves": "latency_p50_ms", "workloads": e["workloads"]}
    assert _entry("per_layer", "poller_callback_cpu_ms")["workloads"][:5] \
        == ACCEPTED
    # and no accepted metric is list-less but those every cell reports
    for m in loader.manifest()["per_layer"]:
        if "workloads" not in m:
            assert m["name"] in ("req_path_ms", "resp_path_ms",
                                 "device_idle_pct", "compiles_in_window")


# ---- what the hook skips since this cell, clause by clause, by entry --------

def test_the_accepted_entries_keep_their_order():
    """``test_the_nine_follow_the_fanouts_five_and_change_no_entry_before_
    them`` without its two clauses of place (``names[-9:]``, 45 entries):
    the stream's seven, the fan-out's five and PR 37's nine follow one
    another, this PR's four follow them, and no name comes twice."""
    names = _names("per_layer")
    at = names.index("stream_frames_per_call")
    assert names[at:at + 7] == STREAM_COUNTED + STREAM_TIMED
    assert names[at + 7:at + 12] == list(FANOUT_METRICS)
    assert names[at + 12:at + 21] == list(NINE)
    assert names[at + 21:at + 25] == list(REDUCE_METRICS)
    assert len(names) == len(set(names))
    assert names[:names.index("poller_queue_ms")] == [
        "req_path_ms", "resp_path_ms", "device_wait_ms",
        "device_busy_ms_per_call", "device_idle_pct", "compiles_in_window"]
    assert sorted({v[0] for v in NINE.values()}) == [
        "brpc.call", "brpc.fanout.issue", "brpc.ici.cut", "brpc.ici.piece",
        "brpc.plane.run", "brpc.poller.callback", "brpc.stream.handler"]
    assert _names("configs")[:5] == CONFIGS
    assert _names("workloads")[:6] == SIX
    assert _names("end_to_end") == ["calls_per_s", "goodput_gbs",
                                    "latency_p50_ms", "latency_p95_ms",
                                    "setup_s"]


@pytest.mark.parametrize("metric", list(NINE))
def test_the_nines_entries_and_files_with_their_lists_by_order(metric):
    """``test_the_entry_and_its_files``, the list of cells held by order: the
    cells PR 37 wrote, then only cells appended since, in the manifest's
    order."""
    name, _, layer, moves, accepted = NINE[metric]
    e = _entry("per_layer", metric)
    assert e == {"name": metric, "unit": "ms", "better": "lower",
                 "source": "program_span", "layer": layer, "moves": moves,
                 "workloads": e["workloads"]}
    assert _joined_later(e["workloads"], accepted), e["workloads"]
    assert (CELL in e["workloads"]) == (metric in JOINED)
    moved = _entry("end_to_end", moves)
    assert set(e["workloads"]) <= set(moved.get("workloads",
                                                _names("workloads")))
    m = loader._metric(e)
    assert m.reader == {"span": name} and m.module is not None
    for cell in e["workloads"]:
        assert metric in {x.name for x in loader.load_cell(cell).per_layer}


def test_the_fanout_and_stream_cells_entries_are_as_they_were():
    """Every clause of test_span_cpu_metrics.py's two
    ``..._entries_are_as_they_were`` but those of last place and the count of
    five: the two configurations and cells follow one another, their entries
    are what they were, their metrics keep layer, source and what they move,
    and every list that took a cell in still has it where it was, followed
    by later cells only."""
    configs, cells = _names("configs"), _names("workloads")
    assert configs[configs.index("parallel_echo_local") - 1] \
        == "streaming_echo"
    assert cells[cells.index("fanout_4x16m") - 1] == "stream_1m"
    for cell, config, traffic in (
            ("stream_1m", "streaming_echo", "stream_64x1m"),
            ("fanout_4x16m", "parallel_echo_local", "shard_4x16m")):
        assert _entry("workloads", cell) == {
            "name": cell, "config": config, "traffic": traffic, "chips": 1,
            "why": loader.load_cell(cell).workload["why"]}
        for line in (_entry("configs", config)["why"],
                     _entry("configs", config)["source"],
                     _entry("workloads", cell)["why"]):
            assert 1 <= len(line) <= 200 and line.isprintable(), line
    for name in FANOUT_METRICS:
        e = _entry("per_layer", name)
        assert e["layer"] == "fan-out"
        assert _joined_later(e["workloads"], ["fanout_4x16m"])
        assert e["moves"] == ("latency_p50_ms" if name == "fanout_overlap"
                              else "goodput_gbs")
        assert e["source"] == ("program_counter"
                               if name == "fanout_subcalls_per_call"
                               else "program_span")
    for name in STREAM_COUNTED + STREAM_TIMED:
        e = _entry("per_layer", name)
        assert e["layer"] == "stream" and e["workloads"] == ["stream_1m"]
        assert e["moves"] == ("latency_p50_ms" if name == "stream_queue_ms"
                              else "goodput_gbs")
        assert e["source"] == ("program_span" if name in STREAM_TIMED
                               else "program_counter")
    for name in ("server_queue_ms", "server_parse_ms", "server_encode_ms",
                 "server_write_ms", "client_self_ms"):
        assert _joined_later(_entry("per_layer", name)["workloads"], [
            "local_compute_1m", "local_bulk_64m", "xchip_bulk_64m",
            "fanout_4x16m"])
    for name in TAKEN_IN:
        listed = _entry("per_layer", name)["workloads"]
        assert "fanout_4x16m" in listed
        assert _joined_later(listed,
                             listed[:listed.index("fanout_4x16m") + 1])
    for name in ("device_busy_ms_per_call", "window_pieces_per_call",
                 "slice_dispatch_ms_per_call", "delivery_gate_ms_per_call",
                 "window_stall_ms_per_call"):
        assert _joined_later(_entry("per_layer", name)["workloads"], [
            "local_bulk_64m", "xchip_bulk_64m", "stream_1m", "fanout_4x16m"])
    assert _joined_later(_entry("end_to_end", "goodput_gbs")["workloads"], [
        "local_bulk_64m", "xchip_bulk_64m", "stream_1m", "fanout_4x16m"])


class _Item:
    def __init__(self, name, file="x.py"):
        self.name, self.nodeid, self.marks = \
            name, f"tests/benchmarks/{file}::{name}", []

    def add_marker(self, mark):
        self.marks.append(mark)


def test_the_hook_skips_these_ids_of_the_six_cells_and_no_others():
    """What the end of tests/conftest.py takes out of the
    manifest-parametrised tests for the cells up to this one, id by id
    (PERF.md section 7 row 1c lists the same for the ``benchmark`` issue that
    un-skips them)."""
    import conftest
    tests = ["test_accepted_workload_names_no_client_and_resolves_to_unary",
             "test_control_comes_out_not_correct",
             "test_broken_timed_path_is_not_correct"]
    cases = ["flipped_byte", "stale_reply", "host_reply",
             "corrupted_byte-byte_mismatches", "wrong_chip-misplaced_replies",
             "dropped_reply-short_replies"]
    items = [_Item(f"{t}[{c}]") for t in tests for c in SIX] \
        + [_Item(f"{t}[{c}-{k}]") for t in tests for c in SIX for k in cases]
    conftest.pytest_collection_modifyitems(None, items)
    unary = "test_accepted_workload_names_no_client_and_resolves_to_unary"
    assert sorted(i.name for i in items if i.marks) == sorted(
        [f"{unary}[{c}]" for c in ("stream_1m", "fanout_4x16m", CELL)]
        + [f"test_control_comes_out_not_correct[{c}-{k}]"
           for c in ("stream_1m", CELL)
           for k in ("flipped_byte", "stale_reply", "host_reply")]
        + [f"test_broken_timed_path_is_not_correct[{c}-{k}]"
           for c in ("fanout_4x16m", CELL)
           for k in ("corrupted_byte-byte_mismatches",
                     "wrong_chip-misplaced_replies")])
    assert all("tests/conftest.py" in i.marks[0].kwargs["reason"]
               for i in items if i.marks)


def test_the_hook_skips_these_tests_of_the_accepted_files_and_no_others():
    """The whole tests, by file: those that hold a LAST place, a count or
    the hook itself to what it was before a cell was appended."""
    import conftest
    import test_fanout_cell
    import test_span_cpu_metrics
    import test_stream_cell
    want = {
        "test_fanout_cell.py": [
            "test_the_hook_skips_these_cases_and_no_others",
            "test_the_manifest_gains_one_configuration_one_cell_five_metrics",
            "test_the_streaming_cells_entries_are_as_they_were"],
        "test_span_cpu_metrics.py": [
            "test_the_fanout_cells_entries_are_as_they_were",
            "test_the_hook_skips_those_two_cases_of_that_file_and_no_other",
            "test_the_nine_follow_the_fanouts_five_and_change_no_entry_"
            "before_them",
            "test_the_stream_cells_entries_are_as_they_were"],
        "test_stream_cell.py": [
            "test_the_manifest_gains_one_configuration_one_cell_seven_"
            "metrics",
            "test_traced_rehearsal_has_every_metric_of_the_stream_layer"]}
    for module in (test_fanout_cell, test_span_cpu_metrics, test_stream_cell):
        file = os.path.basename(module.__file__)
        items = [_Item(n, file) for n in dir(module) if n.startswith("test_")]
        assert len(items) > 10
        conftest.pytest_collection_modifyitems(None, items)
        assert sorted(i.name for i in items if i.marks) == want[file], file
    # and of the parametrised entry test, the metrics whose list the cell
    # joined: six of the nine
    items = [_Item(f"test_the_entry_and_its_files[{m}]",
                   "test_span_cpu_metrics.py") for m in NINE]
    conftest.pytest_collection_modifyitems(None, items)
    assert [i.name for i in items if i.marks] == [
        f"test_the_entry_and_its_files[{m}]" for m in NINE if m in JOINED]
    # nothing of this file is skipped
    me = sys.modules[__name__]
    items = [_Item(n, "test_pushpull_cell.py") for n in dir(me)
             if n.startswith("test_")]
    conftest.pytest_collection_modifyitems(None, items)
    assert not [i.name for i in items if i.marks]


# ---- sources: who may name what -------------------------------------------

def test_no_file_of_the_harness_names_the_cell_a_reduce_or_the_method():
    harness = os.path.join(REPO, "benchmarks", "harness")
    files = [os.path.join(harness, f) for f in sorted(os.listdir(harness))
             if f.endswith(".py")] + [os.path.join(REPO, "benchmarks",
                                                   "run.py")]
    assert len(files) >= 10
    for path in files:
        text = _source(path)
        for word in (CELL, CONFIG, METHOD, "replicate_sum", "pushpull",
                     "float32", "MERGE_SUM"):
            assert word not in text, (path, word)


def test_the_reference_is_plain_numpy():
    text = _source("benchmarks", "reference", "PushPull.py")
    imports = re.findall(r"^\s*(?:from|import)\s+(\S+)", text, re.MULTILINE)
    assert set(imports) == {"__future__", "numpy"}
    words = np.array([0x04030201, 0xFFFFFFFF, 0], dtype="<u4")
    want, message = loader.reference_module(METHOD).expected(
        words.view(np.uint8), "w00.000000001")
    assert message == "w00.000000001"
    assert want.dtype == np.uint8 and want.size == words.size * 4
    assert want.view("<f4").tolist() == [
        1 + 2 * 4 + 3 * 16 + 4 * 64, 255 * 85, 0.0]


@pytest.mark.parametrize("parts", [("clients", "pushpull.py"),
                                   ("services", "PushPull.py"),
                                   ("counters", "fanout_reduce.py")])
def test_the_cells_code_uses_the_public_path_and_copies_nothing(parts):
    text = _source("benchmarks", *parts)
    # no host copy, no private state of the channel, no clock, no verdict and
    # no flag of its own
    for word in ("to_bytes", "np.asarray", "import numpy", "tobytes",
                 "device_put", "._subs", "__dict__", "_fanout_",
                 "perf_counter", "time.time", "block_until_ready",
                 "set_flag", "os.environ"):
        assert word not in text, (parts, word)
    if parts[0] == "services":
        # ONE program a sub-call, whatever the transport's piece is: the join
        # of the pieces is inside the jitted program, and no size is spelled
        assert text.count("jax.jit") == 1 and "PIECE_BYTES" not in text
        assert not re.search(r"\b(4194304|16777216)\b|<< 2[0-9]", text)
    else:
        assert "concatenate" not in text, parts
    if parts[0] == "counters":
        assert "fanout_reduce_stats" in text
    if parts[0] == "clients":
        for word in ("ParallelChannel", "ReplicateFanoutMapper",
                     "CollectiveMerger", "MERGE_SUM", '"float32"',
                     "fanout_operand", "fanout_result"):
            assert word in text, word
        # no device program of its own, and no conversion of the result: the
        # array is handed back as it is
        for word in ("jax", ".view(", "astype", "bitcast", "reshape"):
            assert word not in text, word


def test_the_counter_module_reads_the_programs_totals():
    from brpc_tpu.channels import fanout_reduce_stats, fanout_stats
    mod = loader.counter_module("fanout_reduce")
    assert list(mod.KEYS) == [f"fanout_reduce_{k}" for k in (
        "programs", "input_bytes", "output_bytes", "input_blocks",
        "host_merges", "lazy_reads")]
    assert set(mod.KEYS) == {f"fanout_reduce_{k}"
                             for k in fanout_reduce_stats()}
    taken = counters.TABLE_KEYS | set(loader.counter_module("fanout").KEYS)
    assert not set(mod.KEYS) & taken
    assert len(fanout_stats()) == 9         # its set is not this PR's to grow
    merged = counters.read([], ["fanout", "fanout_reduce"])
    assert set(merged) == taken | set(mod.KEYS)


def test_a_program_without_the_totals_cannot_load_the_cell():
    """How the parent of this PR fails the cell with this PR's benchmark
    files laid over it: the loader resolves the counter module before a
    device is touched, and its import fails."""
    import subprocess
    code = ("import sys, types; sys.path.insert(0, %r); "
            "import brpc_tpu.channels as c; del c.fanout_reduce_stats; "
            "from benchmarks.harness import loader; "
            "loader.load_cell(%r)" % (REPO, CELL))
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert res.returncode != 0
    assert "cannot import name 'fanout_reduce_stats'" in res.stderr
    assert "import jax" not in res.stderr


# ---- a rehearsal on CPU devices --------------------------------------------

def test_rehearsal_is_correct_with_route_and_zero_counters_held(
        capsys, restore_mesh):
    rc, line, err = rehearse(capsys, CELL, "--trace", "0")
    assert rc == 0 and line["correct"] is True, err[-2000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    checks = line["checks"]
    assert checks["replies_compared"] == {"value": 4, "limit": 4}
    assert checks["byte_mismatches"] == {"value": 0, "limit": 0}
    assert checks["message_mismatches"] == {"value": 0, "limit": 0}
    # the three zero-counters are in the sum that is held at zero
    assert checks["second_route_events"] == {"value": 0, "limit": 0}
    assert checks["fanout_sub_calls_per_call"] == {"value": 4.0,
                                                   "limit": 4.0}
    assert checks["fanout_device_operand_bytes_per_call"] == {
        "value": float(WIDTH * RANGE), "limit": WIDTH * RANGE}
    assert checks["native_requests_per_call"] == {"value": 4.0, "limit": 4.0}
    assert checks["fanout_reduce_programs_per_call"] == {"value": 1.0,
                                                         "limit": 1.0}
    assert checks["fanout_reduce_input_bytes_per_call"] == {
        "value": float(WIDTH * RANGE), "limit": WIDTH * RANGE}
    assert set(line["metrics"]) == {"goodput_gbs", "latency_p50_ms",
                                    "latency_p95_ms", "setup_s"}


def test_traced_rehearsal_has_every_metric_of_the_reduce(capsys,
                                                         restore_mesh):
    rc, line, err = rehearse(capsys, CELL, "--trace", "1", seconds="1.0")
    assert rc == 0 and line["correct"] is True, err[-2000:]
    got = line["metrics"]
    listed = {m["name"]: m for m in loader.manifest()["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    for name in list(REDUCE_METRICS) + FANOUT_SIX:
        assert got[name]["unit"] == listed[name]["unit"], name
        if name != "fanout_reduce_roofline":    # a rehearsal has no device
            assert got[name]["value"] is not None, name
    assert got["fanout_reduce_roofline"]["value"] is None
    assert got["fanout_reduce_programs_per_call"]["value"] == 1.0
    assert got["fanout_subcalls_per_call"]["value"] == float(WIDTH)
    # the reduce lies inside the finalizing merge, and what its thread ran
    # inside it is no more than it lasted
    assert 0 < got["fanout_reduce_cpu_ms_per_call"]["value"] \
        <= got["fanout_reduce_ms_per_call"]["value"] \
        <= got["fanout_merge_ms_per_call"]["value"]
    assert 0 < got["fanout_overlap"]["value"] <= float(WIDTH)
    # each sub-call is an ordinary unary call
    for name in ("server_queue_ms", "server_parse_ms", "server_encode_ms",
                 "server_write_ms", "client_self_ms", "call_cpu_ms",
                 "req_path_ms", "resp_path_ms"):
        assert got[name]["value"] > 0, name
    # nothing of it enters the device poller
    assert not set(POLLER) & set(got)
    reduces = [s for s in span.layer_spans()
               if s.name == "brpc.fanout.reduce"]
    assert reduces and all(s.n == WIDTH * RANGE and s.m == WIDTH
                           and s.cpu_ns >= 0 for s in reduces)


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_control_is_not_correct_and_says_by_which_number(
        capsys, restore_mesh, control):
    mod = loader.control_module(control)
    if control in OWN:
        assert mod.GUARANTEE == CONTROLS[control][0]
    rc, line, err = rehearse(capsys, CELL, "--trace", "0", "--control",
                             control)
    assert line is not None and line["correct"] is False, err[-2000:]
    number = CONTROLS[control][1]
    checks = line["checks"]
    assert checks[number]["value"] > checks[number]["limit"] == 0
    assert f"check {number}:" in err and "NOT OK" in err
    compared = checks["replies_compared"]["value"]
    if number == "byte_mismatches":
        # every reply arrived whole, on the chip, under its own message:
        # only the sum says that a contribution is wrong
        for k in ("failed_calls", "short_replies", "misplaced_replies",
                  "misordered_replies", "message_mismatches",
                  "second_route_events"):
            assert checks[k]["value"] == 0, k
        assert checks["fanout_reduce_programs_per_call"]["value"] == 1.0
        assert line["failed"] == compared == 4
    if control in ("dropped_worker", "doubled_worker"):
        # a word of the sum is wrong wherever the worker's byte of it is not
        # zero: all but one word in 256
        assert checks["byte_mismatches"]["value"] > compared * RANGE // 8
    if control in ("host_sum", "host_reply"):   # every operation
        assert checks["short_replies"]["value"] == line["attempted"]
        assert checks["fanout_reduce_programs_per_call"]["value"] == 0.0
        # host_merges (one an operation) and the bytes the merger took to
        # the host: every worker's, once one of them answered from there
        assert checks["second_route_events"]["value"] == line["attempted"] \
            * (1 + WIDTH * RANGE)
    if control == "failed_worker":      # every operation of the window
        assert checks["failed_calls"]["value"] == line["attempted"]
        assert checks["short_replies"]["value"] == 0    # nothing partial
        assert checks["second_route_events"]["value"] == 0
        assert checks["fanout_reduce_programs_per_call"]["value"] == 0.0


def _break_the_merge(monkeypatch, fault):
    import jax
    from brpc_tpu.butil.iobuf import IOBuf
    from brpc_tpu.channels import CollectiveMerger
    real = CollectiveMerger.merge_sub

    def broken(self, parent_cntl, index, sub_cntl, response):
        att = sub_cntl._peek_response_attachment()
        if att is not None and att.device_refs() and index == 1:
            out = IOBuf()
            for r in att.device_refs():
                z = r.block.data.reshape(-1)[r.offset:r.offset + r.length]
                if fault == "corrupted_byte":
                    z = z.at[len(z) // 3].set(z[len(z) // 3] ^ 0x40)
                else:
                    z = jax.device_put(z, jax.devices()[1])
                out.append_device_array(z)
            att.clear()
            att.append(out)
        return real(self, parent_cntl, index, sub_cntl, response)

    monkeypatch.setattr(CollectiveMerger, "merge_sub", broken)


def test_a_sub_reply_corrupted_where_it_is_merged_is_not_correct(
        capsys, restore_mesh, monkeypatch):
    """test_benchmark_harness.py's ``test_broken_timed_path_is_not_correct``
    for a cell whose replies arrive after ``call_method`` has returned: one
    bit of one worker's contribution changes one word of the sum."""
    _break_the_merge(monkeypatch, "corrupted_byte")
    rc, line, err = rehearse(capsys, CELL, "--trace", "0")
    assert line is not None, err[-2000:]
    assert line["correct"] is False and line["failed"] > 0
    compared = line["checks"]["replies_compared"]["value"]
    assert 0 < line["checks"]["byte_mismatches"]["value"] <= 4 * compared
    assert "check byte_mismatches:" in err and "NOT OK" in err


def test_a_sub_reply_on_another_chip_still_sums_on_the_callers(
        capsys, restore_mesh, monkeypatch):
    """Where ``fanout_4x16m`` hands back the refs as they arrived (a ref on
    another chip is a misplaced reply), a reduce brings every contribution
    to the operand's chip before it adds: the result is on the caller's
    chip and right."""
    _break_the_merge(monkeypatch, "wrong_chip")
    rc, line, err = rehearse(capsys, CELL, "--trace", "0")
    assert rc == 0 and line["correct"] is True, err[-2000:]
    assert line["checks"]["misplaced_replies"]["value"] == 0


def test_a_result_on_another_chip_is_a_misplaced_reply(capsys, restore_mesh,
                                                       monkeypatch):
    import jax
    from brpc_tpu.channels import collective_fanout as cf
    real = cf._gather

    def elsewhere(*args, **kwargs):
        return jax.device_put(real(*args, **kwargs), jax.devices()[1])

    monkeypatch.setattr(cf, "_gather", elsewhere)
    rc, line, err = rehearse(capsys, CELL, "--trace", "0")
    assert line is not None and line["correct"] is False, err[-2000:]
    assert line["checks"]["misplaced_replies"]["value"] == line["attempted"]
    assert line["checks"]["byte_mismatches"]["value"] == 0


def test_a_sum_made_in_bfloat16_is_not_correct(capsys, restore_mesh,
                                               monkeypatch):
    """``precision``: a merger that adds in the nearest precision below
    float32 gives other bytes (bfloat16 cannot hold 21,675)."""
    import jax
    import jax.numpy as jnp
    from brpc_tpu.channels import collective_fanout as cf

    @jax.jit
    def low(blocks):
        total = None
        for bs in blocks:
            a = (bs[0] if len(bs) == 1 else jnp.concatenate(bs)).view(
                jnp.float32).astype(jnp.bfloat16)
            total = a if total is None else total + a
        return total.astype(jnp.float32)

    monkeypatch.setattr(cf, "_gather_jit", lambda: (
        lambda blocks, merge, dtype, shard_shape: low(blocks)))
    rc, line, err = rehearse(capsys, CELL, "--trace", "0")
    assert line is not None and line["correct"] is False, err[-2000:]
    checks = line["checks"]
    compared = checks["replies_compared"]["value"]
    assert checks["byte_mismatches"]["value"] > compared * RANGE // 8
    assert checks["failed_calls"]["value"] == 0
    assert checks["second_route_events"]["value"] == 0


@pytest.mark.parametrize("counter", ["fanout_host_operand_bytes",
                                     "fanout_partial_results",
                                     "fanout_reduce_host_merges"])
def test_a_zero_counter_that_moves_fails_the_run(capsys, restore_mesh,
                                                 monkeypatch, counter):
    """``device_resident`` and ``all_or_nothing``: one byte through the host,
    one partial result or one merge made by numpy over the window is a
    second-route event."""
    mod = loader.counter_module(
        "fanout_reduce" if "reduce" in counter else "fanout")
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


def test_a_narrower_fanout_fails_the_route_and_the_sum(capsys, restore_mesh,
                                                       monkeypatch):
    """The route holds the width, and so does the sum: two workers'
    contributions are not four's."""
    mod = loader.client_module("pushpull")
    real = mod.open

    def narrower(ctx):
        ctx.options = dict(ctx.options, sub_channels=2)
        return real(ctx)

    monkeypatch.setattr(mod, "open", narrower)
    rc, line, err = rehearse(capsys, CELL, "--trace", "0")
    assert line is not None and line["correct"] is False, err[-2000:]
    assert line["checks"]["fanout_sub_calls_per_call"] == {"value": 2.0,
                                                           "limit": 4.0}
    assert line["checks"]["fanout_reduce_programs_per_call"]["value"] == 1.0
    assert line["checks"]["byte_mismatches"]["value"] > 0


# ---- the four metrics' readers ---------------------------------------------

@pytest.fixture
def empty_store():
    span.layer_spans_reset()
    yield
    span.layer_spans_reset()


def _view(counters_=None, reduction=None, nbytes=1 << 24):
    """One caller, 60 operations of 50 ms back to back over a 3 s slice."""
    calls = [((1000 + i * 50) * MS, (1050 + i * 50) * MS, 0, nbytes, True,
              f"k{i}") for i in range(60)]
    window = types.SimpleNamespace(
        calls=lambda: iter(calls), trace_slice_ns=(1000 * MS, 4000 * MS),
        counters=counters_ or {},
        caller_device=types.SimpleNamespace(id=0))
    return readers.View(window=window, reduction=reduction,
                        peaks={"hbm_gbs": 819.0})


def _reduce_span(start_ms, ms, cpu_ms):
    from brpc_tpu.butil import layer_span as ls
    st = ls._thread()
    st.records.append(("brpc.fanout.reduce", start_ms * MS,
                       (start_ms + ms) * MS, 0, next(ls._ids), 0, st.name,
                       4 << 24, 16, cpu_ms * MS))


@pytest.mark.parametrize("metric,want", [
    ("fanout_reduce_ms_per_call", 3.0),
    ("fanout_reduce_cpu_ms_per_call", 1.0)])
def test_span_metric_reads_the_reduce_span_and_nothing_else(
        empty_store, metric, want):
    m = loader._metric(_entry("per_layer", metric))
    assert m.reader == {"span": "brpc.fanout.reduce"}
    view = _view()
    assert readers.read(m, view) is None    # the parent: no such span
    span.layer_record("brpc.fanout.merge", 2000 * MS, 2007 * MS)
    assert readers.read(m, view) is None
    for i in range(60):                     # 3 ms, 1 of them CPU, an operation
        _reduce_span(1010 + i * 50, 3, 1)
    assert readers.read(m, view) == pytest.approx(want)


def test_the_count_metric_is_its_counter_over_the_correct_operations():
    m = loader._metric(_entry("per_layer",
                              "fanout_reduce_programs_per_call"))
    assert m.module is None and m.reader == {
        "kind": "counter_per_call", "counter": "fanout_reduce_programs"}
    assert readers.read(m, _view({"fanout_reduce_programs": 60})) == 1.0
    assert readers.read(m, _view({"fanout_reduce_programs": 120})) == 2.0


def _reduction(ops, busy_s, window_s=3.0):
    return xplane.Reduction(window_s=window_s, busy_s={0: busy_s},
                            ops={0: ops})


def test_the_roofline_is_the_traffics_bytes_over_the_reduces_seconds():
    m = loader._metric(_entry("per_layer", "fanout_reduce_roofline"))
    how = m.reader
    assert how["workers"] == 4 and how["peak"] == "hbm_gbs"
    assert how["reduce_ops"] and all(isinstance(s, str) and s
                                     for s in how["reduce_ops"])
    mod = m.module
    view = _view()
    # bytes the traffic defines: 4 contributions read and the sum written,
    # of the result's size, for each of the slice's 60 operations
    assert mod.reduce_bytes(view, 4) == 60 * 5 * (1 << 24)
    op, other = how["reduce_ops"][0], "%fusion.9 = u8[16]{0} fusion(%p)"
    named = [(f"%x = f32[4]{{0}} {op} rest", 0.012), (other, 0.5)]
    assert mod.reduce_seconds(_reduction(named, 0.512), 0,
                              how["reduce_ops"]) == pytest.approx(0.012)
    # busy seconds that the kept names do not account for are the reduce's
    assert mod.reduce_seconds(_reduction(named, 0.6), 0,
                              how["reduce_ops"]) == pytest.approx(0.1)
    # no kept name is an operation of the reduce: nothing, never 0 — unless
    # the program's count says reduces ran: then the seconds that no kept
    # name accounts for hold all of them
    assert mod.reduce_seconds(_reduction([(other, 0.5)], 0.6), 0,
                              how["reduce_ops"]) is None
    assert mod.reduce_seconds(_reduction([(other, 0.5)], 0.6), 0,
                              how["reduce_ops"], True) == pytest.approx(0.1)
    assert mod.reduce_seconds(_reduction([(other, 0.5)], 0.5), 0,
                              how["reduce_ops"], True) is None
    assert mod.reduce_seconds(_reduction(named, 0.0), 0,
                              how["reduce_ops"]) is None
    # 60 x 83.9 MB in 3 s is 1.678 GB/s; in 12 ms of the chip's 3 s that is
    # 419.4 GB/s while the reduce ran: 51.2 % of 819
    view = _view(reduction=_reduction(named, 0.512))
    assert readers.read(m, view) == pytest.approx(
        100 * (60 * 5 * (1 << 24) / 3.0) / (0.012 / 3.0) / 819e9)
    assert 51.0 < readers.read(m, view) < 51.5
    # the parent, an untraced run, a trace with no operation of the reduce
    assert readers.read(m, _view()) is None
    assert readers.read(m, _view(
        reduction=_reduction([(other, 0.5)], 0.6))) is None
    assert how["counter"] == "fanout_reduce_programs"
    assert readers.read(m, _view({"fanout_reduce_programs": 60}, _reduction(
        [(other, 0.5)], 0.512))) == pytest.approx(readers.read(m, view))
    assert readers.read(m, _view(reduction=xplane.Reduction(0.0))) is None


# ---- the system against the reference, through the cell's client -----------

def test_a_push_pull_equals_the_reference_and_meets_no_host():
    """The client and the service of the cell, driven directly: an
    operation's result is the reference's sum bit for bit, ONE float32 array
    on the caller's chip behind the methods the harness reads a reply by,
    and no byte met the host."""
    import jax
    import brpc_tpu.policy  # noqa: F401  (registers the protocols)
    from brpc_tpu import channels, rpc
    from brpc_tpu.ici.mesh import IciMesh
    from benchmarks.harness.check import attachment_bytes
    from benchmarks.harness.driver import ClientContext
    from benchmarks.harness.resident import make_set
    seed = 2 ** 31 + 39
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
        client = loader.client_module("pushpull").open(ClientContext(
            rpc=rpc, channel=channel,
            method=f"{service.service_name()}.{METHOD}", thread=0,
            options={"sub_channels": WIDTH, "fail_limit": 1}))
        blocks = make_set(seed, 0, 2, RANGE, jax.devices()[0])
        stats = channels.fanout_stats(), channels.fanout_reduce_stats()
        for i, block in enumerate(blocks):
            message, att = client.call(f"op{i}", block)
            want, key = loader.reference_module(METHOD).expected(
                payload.block(seed, 0, i, RANGE), f"op{i}")
            assert message == key
            assert len(att) == att.device_bytes() == RANGE
            assert att.backing_block_num() == 1
            (ref,) = att.device_refs()
            assert ref.block.data is att.array          # as it is
            assert (ref.offset, ref.length) == (0, RANGE)
            assert att.array.dtype == np.float32
            assert att.array.shape == (RANGE // 4,)
            assert set(att.array.devices()) == {jax.devices()[0]}
            assert np.array_equal(attachment_bytes(att), want)
        fan, red = channels.fanout_stats(), channels.fanout_reduce_stats()
        assert fan["host_operand_bytes"] == stats[0]["host_operand_bytes"]
        assert fan["device_operand_bytes"] \
            - stats[0]["device_operand_bytes"] == 2 * WIDTH * RANGE
        assert fan["sub_calls"] - stats[0]["sub_calls"] == 2 * WIDTH
        assert red["programs"] - stats[1]["programs"] == 2
        assert red["host_merges"] == stats[1]["host_merges"]
        assert red["lazy_reads"] == stats[1]["lazy_reads"]
    finally:
        if client is not None:
            client.close()
        channel.close()
        server.stop()
        IciMesh.set_default(before)


def test_the_result_reads_as_a_reply_whatever_it_holds():
    """What ``clients/pushpull.py`` hands the harness: a host array (the
    merge made by numpy) is a reply with no device byte, no result at all an
    empty one."""
    Result = loader.client_module("pushpull").Result
    host = Result(np.zeros(8, np.float32))
    assert len(host) == 32 and host.device_bytes() == 0
    assert host.device_refs() == [] and host.backing_block_num() == 1
    none = Result(None)
    assert len(none) == 0 and none.device_bytes() == 0
    assert none.device_refs() == [] and none.backing_block_num() == 0
