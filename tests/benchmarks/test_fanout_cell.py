"""The fan-out deployment ``parallel_echo_local`` and its cell
``fanout_4x16m`` at CPU size: the cell resolves from BENCHMARK.json alone, a
rehearsal is correct with its route and its two zero-counters held, each of
its five controls comes out not correct by the number it is meant to move,
the traced rehearsal's line has the five metrics of the ``fan-out`` layer,
their readers read the program's four ``brpc.fanout.*`` spans, and the cell's
client copies nothing through the host.

The parametrised tests of test_benchmark_harness.py pick the cell up from
BENCHMARK.json by themselves; the end of tests/conftest.py says which of
their cases cannot hold for it (the break that alters a reply as
``Channel.call_method`` returns: here the same two things are broken where a
sub-reply is merged) and which test of test_stream_cell.py held that the
streaming cell is the manifest's last (here: that its entries are as they
were).
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

from benchmarks.harness import counters, loader, readers  # noqa: E402
from benchmarks.reference import payload  # noqa: E402
from brpc_tpu.rpc import span  # noqa: E402
from test_benchmark_harness import rehearse, restore_mesh  # noqa: E402,F401

CELL, CONFIG, METHOD = "fanout_4x16m", "parallel_echo_local", "EchoShard"
WIDTH, BLOCK = 4, 1 << 20               # the rehearsal's operation
MS = 1_000_000
FANOUT_METRICS = {
    "fanout_subcalls_per_call": "fanout_sub_calls",
    "fanout_issue_ms_per_call": "brpc.fanout.issue",
    "fanout_wait_ms_per_call": "brpc.fanout.wait",
    "fanout_merge_ms_per_call": "brpc.fanout.merge",
    "fanout_overlap": "brpc.fanout",
}
SPAN_SUMS = {k: v for k, v in FANOUT_METRICS.items()
             if k.endswith("_ms_per_call")}
TAKEN_IN = ("device_busy_ms_per_call", "window_pieces_per_call",
            "slice_dispatch_ms_per_call", "delivery_gate_ms_per_call",
            "window_stall_ms_per_call", "server_queue_ms", "server_parse_ms",
            "server_encode_ms", "server_write_ms", "client_self_ms")
# control -> the guarantee it breaks, the number it moves
CONTROLS = {"flipped_byte": ("reply_attachment", "byte_mismatches"),
            "stale_reply": ("order", "byte_mismatches"),
            "host_reply": ("reply_attachment", "short_replies"),
            "swapped_shards": ("order", "byte_mismatches"),
            "dropped_shard": ("all_or_nothing", "failed_calls")}


@pytest.fixture(autouse=True)
def no_call_id_ageing(monkeypatch):
    """The ageing matters on the chip alone (a frame's length, ROADMAP 1.1);
    the 600 reuses a slot that every rehearsal spends on it only bring this
    worker's id space nearer its end, so this file's rehearsals spare them
    (the manifest-parametrised rehearsals of the cell age as every unary
    cell's do)."""
    from benchmarks.harness import driver
    monkeypatch.setattr(driver, "age_call_ids", lambda slots: None)


def _source(*parts):
    with open(os.path.join(REPO, *parts), encoding="utf-8") as f:
        return f.read()


def _entry(group, name):
    return next(e for e in loader.manifest()[group] if e["name"] == name)


# ---- the cell is files and manifest entries -------------------------------

def test_cell_resolves_from_the_manifest_alone():
    assert len(loader.ROOTS) == 1           # no fixture root in this file
    cell = loader.load_cell(CELL)
    assert (cell.config_name, cell.chips) == (CONFIG, 1)
    assert cell.methods() == [METHOD] and cell.clients() == ["fanout"]
    assert cell.workload["counters"] == ["fanout"]
    for mod, name in ((loader.client_module("fanout"), "clients.fanout"),
                      (loader.service_module(METHOD), "services.EchoShard"),
                      (loader.reference_module(METHOD),
                       "reference.EchoShard"),
                      (loader.counter_module("fanout"), "counters.fanout")):
        assert mod.__name__ == f"benchmarks.{name}"
    for control, (guarantee, _) in CONTROLS.items():
        mod = loader.control_module(control)
        assert mod.__name__ == f"benchmarks.controls.{control}"
        assert mod.GUARANTEE == guarantee
        assert guarantee in cell.config["guarantees"]
    assert counters.second_route(cell) == list(counters.SECOND_ROUTE) + [
        "fanout_host_operand_bytes", "fanout_partial_results"]
    assert {m.name for m in cell.end_to_end} == {
        "goodput_gbs", "latency_p50_ms", "latency_p95_ms", "setup_s"}
    assert set(FANOUT_METRICS) | set(TAKEN_IN) <= {
        m.name for m in cell.per_layer}


def test_the_traffic_is_the_issues_letter_for_letter():
    cell = loader.load_cell(CELL)
    wl, cfg = cell.workload, cell.config
    assert wl["threads"] == 2 and cfg["queue_depth"] == 1
    assert wl["sets"]["resident"]["block_bytes"] == 1 << 26
    assert wl["sets"]["resident"]["bytes"] == 6 << 30       # 96 blocks
    (mix,) = wl["mix"]
    assert mix["client_options"] == {"sub_channels": 4, "fail_limit": 1}
    assert wl["sample_per_thread"] == 16
    assert (wl["warmup_seconds"], wl["trace_seconds"]) == (1.0, 3.0)
    assert wl["channel_options"] == {"connection_type": "pooled"}
    route = {r["counter"]: r["per_call_min"] for r in wl["route"]}
    assert route == {"fanout_sub_calls": 4.0,
                     "fanout_device_operand_bytes": 1 << 26,
                     "ici_device_bytes": 2 << 26}
    tiny = loader.load_cell(CELL, rehearse=True).workload
    assert tiny["sets"]["resident"]["block_bytes"] == BLOCK  # 4 x 256 KiB
    # a 256 KiB shard fits the native window: the rehearsal's sub-calls ride
    # the native tier, and its route says so
    assert {r["counter"]: r["per_call_min"] for r in tiny["route"]} == {
        "fanout_sub_calls": 4.0, "fanout_device_operand_bytes": BLOCK,
        "native_requests": 4.0}


def test_the_configuration_states_its_source_and_its_cut():
    cfg = loader.load_cell(CELL).config
    entry = _entry("configs", CONFIG)
    assert cfg["source"] == entry["source"] and len(cfg["source"]) <= 200
    for word in ("parallel_echo_c++", "partition_echo_c++", "client.cpp",
                 "ParallelChannel", "combo_channel.md"):
        assert word in cfg["source"], word
    assert any("from memory" in n for n in cfg["source_notes"])
    assert cfg["reduced"] == entry["reduced"] == [
        "servers", "resident_bytes", "server_handler"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    assert cfg["assumed"] == {
        "sub_channels": 4, "operation_bytes": 1 << 26,
        "shard_bytes": 1 << 24, "mapping": "shard", "merge": "concat",
        "fail_limit": 1, "threads": 2, "connection_type": "pooled"}
    assert set(cfg["assumed_why"]) == set(cfg["assumed"])
    assert cfg["assumed"]["shard_bytes"] * cfg["assumed"]["sub_channels"] \
        == cfg["assumed"]["operation_bytes"]
    local = loader.load_cell("local_bulk_64m").config
    for key in ("chips", "caller_device", "servers", "channel_options",
                "queue_depth", "resident_bytes"):
        assert cfg[key] == local[key], key      # rdma_perf_local's layout
    assert list(cfg["guarantees"]) == [
        "reply_attachment", "order", "all_or_nothing", "device_resident",
        "single_route", "no_retry"]
    assert cfg["second_route_counters"] == ["fanout_host_operand_bytes",
                                            "fanout_partial_results"]


def test_the_manifest_gains_one_configuration_one_cell_five_metrics():
    man = loader.manifest()
    assert [c["name"] for c in man["configs"]][-1] == CONFIG
    assert [w["name"] for w in man["workloads"]][-1] == CELL
    # the driver refuses a line of more than 200 characters before any run
    for line in (_entry("configs", CONFIG)["why"],
                 _entry("configs", CONFIG)["source"],
                 _entry("workloads", CELL)["why"]):
        assert 1 <= len(line) <= 200 and line.isprintable(), line
    assert len(man["workloads"]) == 5
    assert sum(w["chips"] == 4 for w in man["workloads"]) == 1
    assert _entry("workloads", CELL) == {
        "name": CELL, "config": CONFIG, "traffic": "shard_4x16m",
        "chips": 1, "why": loader.load_cell(CELL).workload["why"]}
    assert [m["name"] for m in man["per_layer"]][-5:] == list(FANOUT_METRICS)
    for name in FANOUT_METRICS:
        e = _entry("per_layer", name)
        assert e["layer"] == "fan-out" and e["workloads"] == [CELL]
        assert e["moves"] == ("latency_p50_ms" if name == "fanout_overlap"
                              else "goodput_gbs")
        assert e["source"] == ("program_counter"
                               if name == "fanout_subcalls_per_call"
                               else "program_span")
    for name in TAKEN_IN:
        assert _entry("per_layer", name)["workloads"][-1] == CELL
    assert _entry("end_to_end", "goodput_gbs")["workloads"][-1] == CELL


def test_the_streaming_cells_entries_are_as_they_were():
    """Every clause of test_stream_cell.py's
    ``test_the_manifest_gains_one_configuration_one_cell_seven_metrics``
    (skipped at the end of tests/conftest.py since a cell follows
    ``stream_1m``), by entry and not by place: the configuration, the cell
    and its seven metrics follow one another as PR 33 appended them, each
    metric with the layer, the end-to-end metric and the source it had, and
    every list that took ``stream_1m`` in still has it, now followed by this
    cell or by nothing."""
    man = loader.manifest()
    configs = [c["name"] for c in man["configs"]]
    cells = [w["name"] for w in man["workloads"]]
    assert configs[configs.index(CONFIG) - 1] == "streaming_echo"
    assert cells[cells.index(CELL) - 1] == "stream_1m"
    assert _entry("workloads", "stream_1m") == {
        "name": "stream_1m", "config": "streaming_echo",
        "traffic": "stream_64x1m", "chips": 1,
        "why": loader.load_cell("stream_1m").workload["why"]}
    names = [m["name"] for m in man["per_layer"]]
    at = names.index("stream_frames_per_call")
    counted = ["stream_frames_per_call", "stream_feedback_per_call",
               "stream_batches_per_call"]
    timed = ["stream_write_ms_per_call", "stream_stall_ms_per_call",
             "stream_queue_ms", "stream_handler_ms_per_call"]
    assert names[at:at + 7] == counted + timed
    assert names[at + 7:] == list(FANOUT_METRICS)
    for name in counted + timed:
        e = _entry("per_layer", name)
        assert e["layer"] == "stream" and e["workloads"] == ["stream_1m"]
        assert e["moves"] == ("latency_p50_ms" if name == "stream_queue_ms"
                              else "goodput_gbs")
        assert e["source"] == ("program_span" if name in timed
                               else "program_counter")
    # the metrics of the unary path that a stream never opens: the cells
    # that had them, and this one, whose sub-calls are unary calls
    for name in ("server_queue_ms", "server_parse_ms", "server_encode_ms",
                 "server_write_ms", "client_self_ms"):
        assert _entry("per_layer", name)["workloads"] == [
            "local_compute_1m", "local_bulk_64m", "xchip_bulk_64m", CELL]
    for name in ("device_busy_ms_per_call", "window_pieces_per_call",
                 "slice_dispatch_ms_per_call", "delivery_gate_ms_per_call",
                 "window_stall_ms_per_call"):
        assert _entry("per_layer", name)["workloads"][-2:] == [
            "stream_1m", CELL]
    assert _entry("end_to_end", "goodput_gbs")["workloads"][-2:] == [
        "stream_1m", CELL]


def test_the_hook_skips_these_cases_and_no_others():
    """What the end of tests/conftest.py takes out of the
    manifest-parametrised tests, id by id (PERF.md section 7 row 1c lists
    the same for the ``benchmark`` issue that un-skips them)."""
    import conftest

    class Item:
        def __init__(self, name):
            self.name, self.nodeid, self.marks = \
                name, f"tests/benchmarks/x.py::{name}", []

        def add_marker(self, mark):
            self.marks.append(mark)

    cells = conftest._manifest_cells()
    tests = ["test_accepted_workload_names_no_client_and_resolves_to_unary",
             "test_control_comes_out_not_correct",
             "test_broken_timed_path_is_not_correct"]
    cases = ["flipped_byte", "stale_reply", "host_reply",
             "corrupted_byte-byte_mismatches", "wrong_chip-misplaced_replies",
             "dropped_reply-short_replies"]
    items = [Item(f"{t}[{c}]") for t in tests for c in cells] \
        + [Item(f"{t}[{c}-{k}]") for t in tests for c in cells
           for k in cases] \
        + [Item("test_the_manifest_gains_one_configuration_one_cell_seven_"
                "metrics")]
    conftest.pytest_collection_modifyitems(None, items)
    assert sorted(i.name for i in items if i.marks) == sorted([
        "test_accepted_workload_names_no_client_and_resolves_to_unary"
        "[stream_1m]",
        "test_accepted_workload_names_no_client_and_resolves_to_unary"
        f"[{CELL}]",
        "test_control_comes_out_not_correct[stream_1m-flipped_byte]",
        "test_control_comes_out_not_correct[stream_1m-stale_reply]",
        "test_control_comes_out_not_correct[stream_1m-host_reply]",
        f"test_broken_timed_path_is_not_correct[{CELL}-corrupted_byte-"
        "byte_mismatches]",
        f"test_broken_timed_path_is_not_correct[{CELL}-wrong_chip-"
        "misplaced_replies]",
        "test_the_manifest_gains_one_configuration_one_cell_seven_metrics"])


# ---- sources: who may name what -------------------------------------------

def test_no_file_of_the_harness_names_the_cell_a_fanout_or_the_method():
    harness = os.path.join(REPO, "benchmarks", "harness")
    files = [os.path.join(harness, f) for f in sorted(os.listdir(harness))
             if f.endswith(".py")] + [os.path.join(REPO, "benchmarks",
                                                   "run.py")]
    assert len(files) >= 10
    for path in files:
        text = _source(path)
        for word in (CELL, CONFIG, METHOD, "shard_4x16m", "ParallelChannel"):
            assert word not in text, (path, word)
        assert not re.search("fan.?out", text, re.IGNORECASE), path


def test_the_reference_is_plain_numpy():
    text = _source("benchmarks", "reference", "EchoShard.py")
    imports = re.findall(r"^\s*(?:from|import)\s+(\S+)", text, re.MULTILINE)
    assert set(imports) == {"__future__", "numpy"}
    want, message = loader.reference_module(METHOD).expected(
        np.arange(256, dtype=np.uint8), "w00.000000001")
    assert message == "w00.000000001"
    assert np.array_equal(want, np.arange(256, dtype=np.uint8) ^ 0x5A)


@pytest.mark.parametrize("parts", [("clients", "fanout.py"),
                                   ("services", "EchoShard.py"),
                                   ("counters", "fanout.py")])
def test_the_cells_code_uses_the_public_path_and_copies_nothing(parts):
    text = _source("benchmarks", *parts)
    # no host copy, no device program of the client's own, no private state
    # of the channel, no clock, no verdict and no flag of its own
    for word in ("to_bytes", "np.asarray", "import numpy", "tobytes",
                 "device_put", "reshape(4", "._subs", "__dict__",
                 "_fanout_", "perf_counter", "time.time",
                 "block_until_ready", "set_flag", "os.environ"):
        assert word not in text, (parts, word)
    if parts[0] == "services":
        # ONE program a shard, whatever the transport's piece is: the join
        # of the pieces is inside the jitted xor, and no size is spelled
        assert text.count("jax.jit") == 1 and "PIECE_BYTES" not in text
        assert not re.search(r"\b(4194304|16777216)\b|<< 2[0-9]", text)
    else:
        assert "concatenate" not in text, parts
    if parts[0] == "counters":
        assert "fanout_stats" in text
    if parts[0] == "clients":
        for word in ("ParallelChannel", "ShardingCallMapper", "MERGE_CONCAT",
                     "fanout_operand", "fanout_attachment", ".cut("):
            assert word in text, word
        assert "jax" not in text        # no device program of its own


def test_the_counter_module_reads_the_programs_totals():
    from brpc_tpu.channels import fanout_stats
    mod = loader.counter_module("fanout")
    assert set(mod.KEYS) == {f"fanout_{k}" for k in fanout_stats()}
    assert {"fanout_calls", "fanout_sub_calls", "fanout_sub_calls_failed",
            "fanout_merges", "fanout_partial_results",
            "fanout_device_operand_bytes", "fanout_host_operand_bytes",
            "fanout_route_rpc", "fanout_route_collective"} == set(mod.KEYS)
    assert not set(mod.KEYS) & counters.TABLE_KEYS
    merged = counters.read([], ["fanout"])
    assert set(merged) == counters.TABLE_KEYS | set(mod.KEYS)


def test_a_program_without_the_totals_cannot_load_the_cell():
    """How the parent of this PR fails the cell: the loader resolves the
    counter module before a device is touched, and its import fails."""
    import subprocess
    code = ("import sys, types; sys.path.insert(0, %r); "
            "import brpc_tpu.channels as c; del c.fanout_stats; "
            "from benchmarks.harness import loader; "
            "loader.load_cell(%r)" % (REPO, CELL))
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert res.returncode != 0
    assert "cannot import name 'fanout_stats'" in res.stderr
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
    # both zero-counters are in the sum that is held at zero
    assert checks["second_route_events"] == {"value": 0, "limit": 0}
    assert checks["fanout_sub_calls_per_call"] == {"value": 4.0,
                                                   "limit": 4.0}
    assert checks["fanout_device_operand_bytes_per_call"] == {
        "value": float(BLOCK), "limit": BLOCK}
    assert checks["native_requests_per_call"] == {"value": 4.0, "limit": 4.0}
    assert set(line["metrics"]) == {"goodput_gbs", "latency_p50_ms",
                                    "latency_p95_ms", "setup_s"}


def test_traced_rehearsal_has_every_metric_of_the_fanout_layer(
        capsys, restore_mesh):
    rc, line, err = rehearse(capsys, CELL, "--trace", "1", seconds="1.0")
    assert rc == 0 and line["correct"] is True, err[-2000:]
    got = line["metrics"]
    listed = {m["name"]: m for m in loader.manifest()["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    for name in FANOUT_METRICS:
        assert got[name]["value"] is not None, name
        assert got[name]["unit"] == listed[name]["unit"]
    assert got["fanout_subcalls_per_call"]["value"] == float(WIDTH)
    assert got["fanout_issue_ms_per_call"]["value"] > 0
    assert got["fanout_wait_ms_per_call"]["value"] > 0
    assert got["fanout_merge_ms_per_call"]["value"] > 0
    assert 0 < got["fanout_overlap"]["value"] <= float(WIDTH)
    # each sub-call is an ordinary unary call: the server's stages and the
    # client's own time are there, and the outside stamps are the service's
    for name in ("server_queue_ms", "server_parse_ms", "server_encode_ms",
                 "server_write_ms", "client_self_ms", "req_path_ms",
                 "resp_path_ms"):
        assert got[name]["value"] > 0, name


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_control_is_not_correct_and_says_by_which_number(
        capsys, restore_mesh, control):
    rc, line, err = rehearse(capsys, CELL, "--trace", "0", "--control",
                             control)
    assert line is not None and line["correct"] is False, err[-2000:]
    number = CONTROLS[control][1]
    checks = line["checks"]
    assert checks[number]["value"] > checks[number]["limit"] == 0
    assert f"check {number}:" in err and "NOT OK" in err
    compared = checks["replies_compared"]["value"]
    if control == "flipped_byte":       # one byte of every shard
        assert checks["byte_mismatches"]["value"] == compared * WIDTH == 16
    if control == "swapped_shards":     # two whole shards out of place
        assert checks["byte_mismatches"]["value"] > compared * BLOCK // 4
        assert checks["misordered_replies"]["value"] == 0
    if control == "host_reply":         # every operation, sampled or not
        assert checks["short_replies"]["value"] == line["attempted"]
        # and the bytes that met the host are counted by the program
        assert checks["second_route_events"]["value"] \
            == line["attempted"] * BLOCK
    if control == "dropped_shard":      # every operation of the window
        assert checks["failed_calls"]["value"] == line["attempted"]
        assert checks["short_replies"]["value"] == 0    # nothing partial
    if control not in ("host_reply", "dropped_shard"):
        assert checks["failed_calls"]["value"] == 0
        assert checks["second_route_events"]["value"] == 0


@pytest.mark.parametrize("fault,number", [
    ("corrupted_byte", "byte_mismatches"),
    ("wrong_chip", "misplaced_replies")])
def test_a_sub_reply_broken_where_it_is_merged_is_not_correct(
        capsys, restore_mesh, monkeypatch, fault, number):
    """test_benchmark_harness.py's ``test_broken_timed_path_is_not_correct``
    for a cell whose replies arrive after ``call_method`` has returned: the
    program's client side hands the merger something else than the server
    sent."""
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
    rc, line, err = rehearse(capsys, CELL, "--trace", "0")
    assert line is not None, err[-2000:]
    assert line["correct"] is False and line["failed"] > 0
    assert line["checks"][number]["value"] > line["checks"][number]["limit"]
    assert f"check {number}:" in err and "NOT OK" in err


@pytest.mark.parametrize("counter", ["fanout_host_operand_bytes",
                                     "fanout_partial_results"])
def test_a_zero_counter_that_moves_fails_the_run(capsys, restore_mesh,
                                                 monkeypatch, counter):
    """``device_resident`` and ``all_or_nothing``: one byte through the host,
    or one partial result, over the window is a second-route event."""
    mod = loader.counter_module("fanout")
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


def test_a_narrower_fanout_fails_the_route(capsys, restore_mesh,
                                           monkeypatch):
    """The route holds the width: a client that fans out over two
    sub-channels answers every byte and is not the cell."""
    mod = loader.client_module("fanout")
    real = mod.open

    def narrower(ctx):
        ctx.options = dict(ctx.options, sub_channels=2)
        return real(ctx)

    monkeypatch.setattr(mod, "open", narrower)
    rc, line, err = rehearse(capsys, CELL, "--trace", "0")
    assert line is not None and line["correct"] is False, err[-2000:]
    assert line["checks"]["fanout_sub_calls_per_call"] == {"value": 2.0,
                                                           "limit": 4.0}
    assert line["checks"]["byte_mismatches"]["value"] == 0


# ---- the five metrics' readers ---------------------------------------------

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


@pytest.mark.parametrize("metric", sorted(SPAN_SUMS))
def test_span_metric_reads_its_span_and_nothing_else(empty_store, metric):
    m = loader._metric(_entry("per_layer", metric))
    assert m.reader == {"span": SPAN_SUMS[metric]}
    view = _view()
    assert readers.read(m, view) is None    # the parent: no such span
    for name in SPAN_SUMS.values():
        if name != SPAN_SUMS[metric]:
            span.layer_record(name, 2000 * MS, 2007 * MS)
    assert readers.read(m, view) is None
    for i in range(60):                     # 3 ms in each operation
        span.layer_record(SPAN_SUMS[metric], (1010 + i * 50) * MS,
                          (1013 + i * 50) * MS)
    assert readers.read(m, view) == pytest.approx(3.0)


def test_the_count_metric_is_its_counter_over_the_correct_operations():
    m = loader._metric(_entry("per_layer", "fanout_subcalls_per_call"))
    assert m.module is None and m.reader == {
        "kind": "counter_per_call", "counter": "fanout_sub_calls"}
    assert readers.read(m, _view({"fanout_sub_calls": 240})) == 4.0


def _fanout(start, subs, end):
    """One recorded fan-out: ``subs`` rows of (issue start, merge end) in ms
    after ``start``, by index; every span as the program records it."""
    from brpc_tpu.butil import layer_span as ls
    ids = ls._ids
    st = ls._thread()
    parent = next(ids)
    for i, (a, b) in enumerate(subs):
        st.records.append(("brpc.fanout.issue", (start + a) * MS,
                           (start + a + 1) * MS, 0, next(ids), parent,
                           st.name, 0, 0))
        st.records.append(("brpc.fanout.merge", (start + b) * MS - 1000,
                           (start + b) * MS, 0, next(ids), parent, st.name,
                           i, 0))
    st.records.append(("brpc.fanout.merge", (start + end) * MS - 1000,
                       (start + end) * MS, 0, next(ids), parent, st.name,
                       len(subs), 1))      # the finalize: not a sub-call
    st.records.append(("brpc.fanout", start * MS, (start + end) * MS, 0,
                       parent, 0, st.name, len(subs), 0))


@pytest.mark.parametrize("subs,end,want", [
    ([(0, 10), (10, 20), (20, 30), (30, 40)], 40, 1.0),     # one by one
    ([(0, 40), (0, 40), (0, 40), (0, 40)], 40, 4.0),        # side by side
    ([(0, 12), (5, 17), (10, 22), (15, 27)], 27, 48 / 27),
], ids=["one_after_another", "side_by_side", "staggered"])
def test_overlap_is_the_sub_calls_lifetimes_over_the_fanouts(
        empty_store, subs, end, want):
    m = loader._metric(_entry("per_layer", "fanout_overlap"))
    view = _view()
    assert readers.read(m, view) is None    # the parent: no such span
    for k in range(5):
        _fanout(1100 + 100 * k, subs, end)
    _fanout(3990, subs, end)                # ends after the slice: left out
    assert readers.read(m, view) == pytest.approx(want)


# ---- the system against the reference, shard by shard ----------------------

def test_a_fanout_of_shards_equals_the_reference_and_meets_no_host():
    """The client and the service of the cell, driven directly: an
    operation's gathered reply is the seeded block xored, its device
    blocks in sub-channel order, and no byte met the host."""
    import jax
    import brpc_tpu.policy  # noqa: F401  (registers the protocols)
    from brpc_tpu import channels, rpc
    from brpc_tpu.ici.mesh import IciMesh
    from benchmarks.harness.check import attachment_bytes
    from benchmarks.harness.driver import ClientContext
    from benchmarks.harness.resident import make_set
    seed = 2 ** 31 + 35
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
        client = loader.client_module("fanout").open(ClientContext(
            rpc=rpc, channel=channel,
            method=f"{service.service_name()}.{METHOD}", thread=0,
            options={"sub_channels": WIDTH, "fail_limit": 1}))
        blocks = make_set(seed, 0, 2, BLOCK, jax.devices()[0])
        stats = channels.fanout_stats()
        for i, block in enumerate(blocks):
            message, att = client.call(f"op{i}", block)
            want, key = loader.reference_module(METHOD).expected(
                payload.block(seed, 0, i, BLOCK), f"op{i}")
            assert message == key
            assert len(att) == att.device_bytes() == BLOCK
            assert att.backing_block_num() == WIDTH     # one a shard here
            assert all(set(r.block.data.devices()) == {jax.devices()[0]}
                       for r in att.device_refs())
            assert np.array_equal(attachment_bytes(att), want)
        after = channels.fanout_stats()
        assert after["host_operand_bytes"] == stats["host_operand_bytes"]
        assert after["device_operand_bytes"] \
            - stats["device_operand_bytes"] == 2 * BLOCK
        assert after["sub_calls"] - stats["sub_calls"] == 2 * WIDTH
        assert after["route_rpc"] - stats["route_rpc"] == 2
    finally:
        if client is not None:
            client.close()
        channel.close()
        server.stop()
        IciMesh.set_default(before)
