"""The expert-parallel deployment ``deepep_ep8_local`` and its cell
``ep8_dispatch_combine_4096`` at CPU size: the cell resolves from
BENCHMARK.json alone and its entries are appended after every accepted one,
a rehearsal is correct with its route and its zero-counters held, each of its
controls comes out not correct by the number it is meant to move, the traced
rehearsal's line has the five metrics of the dispatch and the combine beside
the fan-out layer's, and their readers read the program's two spans, its
counters and the device's two programs.

Also here, for the cases the end of tests/conftest.py skips for this cell
because its client is made of unary sub-calls (``ep_dispatch``): the three
unary controls come out not correct against the guarantees this configuration
states, and a reply corrupted or moved where it is merged (not where
``Channel.call_method`` returns, before an asynchronous sub-call's reply is
there) is not correct either.
"""
import json
import os
import re
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks.harness import loader, readers, xplane  # noqa: E402
from brpc_tpu.rpc import span  # noqa: E402
from test_benchmark_harness import rehearse, restore_mesh  # noqa: E402,F401

CELL, CONFIG, METHOD = "ep8_dispatch_combine_4096", "deepep_ep8_local", \
    "ExpertRank"
TRAFFIC = "dispatch_combine_4096x7168"
RANKS = 8
TOKENS, HIDDEN = 64, 7168               # the rehearsal's operation
MS = 1_000_000
ACCEPTED = ["local_compute_1m", "local_bulk_64m", "xchip_bulk_64m",
            "stream_1m", "fanout_4x16m", "pushpull_4x16m"]
CONFIGS = ["rdma_perf_local", "rdma_perf_xchip", "streaming_echo",
           "parallel_echo_local", "param_server_local"]
# metric -> (unit, better, source)
EP_METRICS = {
    "ep_dispatch_ms_per_call": ("ms", "lower", "program_span"),
    "ep_combine_ms_per_call": ("ms", "lower", "program_span"),
    "ep_pad_share": ("%", "lower", "program_counter"),
    "ep_pack_roofline": ("%", "higher", "device_trace"),
    "ep_combine_roofline": ("%", "higher", "device_trace"),
}
# the accepted metrics this cell reports: every one that lists
# pushpull_4x16m, but the reduce's own
JOINED = ["device_busy_ms_per_call", "server_queue_ms",
          "window_pieces_per_call", "slice_dispatch_ms_per_call",
          "delivery_gate_ms_per_call", "window_stall_ms_per_call",
          "server_parse_ms", "server_encode_ms", "server_write_ms",
          "client_self_ms", "fanout_subcalls_per_call",
          "fanout_issue_ms_per_call", "fanout_wait_ms_per_call",
          "fanout_merge_ms_per_call", "fanout_overlap",
          "piece_cut_ms_per_call", "piece_cut_cpu_ms_per_call",
          "piece_cpu_ms_per_call", "piece_offcpu_ms_per_call",
          "call_cpu_ms", "fanout_issue_cpu_ms_per_call"]
# control -> the guarantee it breaks here, the number it moves
CONTROLS = {"dropped_rank": ("exactly_once", "byte_mismatches"),
            "next_rank": ("exactly_once", "byte_mismatches"),
            "doubled_rank": ("exactly_once", "byte_mismatches"),
            "host_combine": ("device_resident", "second_route_events"),
            # the three unary controls reach every rank's reply
            "flipped_byte": ("result", "byte_mismatches"),
            "stale_reply": ("result", "byte_mismatches"),
            "host_reply": ("device_resident", "second_route_events")}
OWN = ("dropped_rank", "next_rank", "doubled_rank", "host_combine")


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


# ---- the cell is files and manifest entries -------------------------------

def test_cell_resolves_from_the_manifest_alone():
    assert len(loader.ROOTS) == 1
    cell = loader.load_cell(CELL)
    assert (cell.config_name, cell.chips) == (CONFIG, 1)
    assert cell.methods() == [METHOD] and cell.clients() == ["ep_dispatch"]
    assert cell.workload["counters"] == ["fanout", "expert_dispatch"]
    assert cell.workload["traffic"] == TRAFFIC
    assert cell.workload["sets"]["resident"]["block_bytes"] \
        == 4096 * HIDDEN * 2
    assert cell.workload["threads"] == 2
    assert cell.workload["mix"][0]["client_options"] == {
        "ranks": 8, "experts": 256, "top_k": 8, "hidden": 7168,
        "fail_limit": 1}
    for control in OWN:
        mod = loader.control_module(control)
        assert mod.__name__ == f"benchmarks.controls.{control}"
        assert mod.GUARANTEE == CONTROLS[control][0]
    assert {m.name for m in cell.per_layer} >= set(EP_METRICS) | set(JOINED)


def test_the_configuration_states_its_widths_source_and_cut():
    cfg = json.loads(_source("benchmarks", "configs", f"{CONFIG}.json"))
    e = _entry("configs", CONFIG)
    assert e["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert cfg["source"] == e["source"] and "DeepEP" in e["source"]
    assert (cfg["tokens"], cfg["hidden"], cfg["experts"], cfg["top_k"],
            cfg["ranks"], cfg["scales_per_token"], cfg["record_bytes"]) \
        == (4096, 7168, 256, 8, 8, 56, 7488)
    assert sorted(cfg["reduced"]) == sorted(e["reduced"]) \
        == sorted(cfg["reduced_why"])
    assert set(cfg["assumed"]) == set(cfg["assumed_why"])
    assert set(cfg["controls"]) - {"tests_only"} == set(OWN)
    for control in OWN:
        assert CONTROLS[control][0] in cfg["guarantees"]
    assert cfg["resident_bytes"] / 16e9 >= 0.25 * 1.07 ** -1
    assert set(cfg["second_route_counters"]) == {
        "fanout_host_operand_bytes", "fanout_partial_results",
        "fanout_ep_host_fallbacks"}
    for line in (e["why"], e["source"], _entry("workloads", CELL)["why"]):
        assert 1 <= len(line) <= 200 and line.isprintable(), line


def test_the_entries_are_appended_after_every_accepted_one():
    assert _names("configs")[:len(CONFIGS)] == CONFIGS
    assert _names("configs")[len(CONFIGS)] == CONFIG
    cells = _names("workloads")
    assert cells[:len(ACCEPTED) + 1] == ACCEPTED + [CELL]
    assert _entry("workloads", CELL) == {
        "name": CELL, "config": CONFIG, "traffic": TRAFFIC, "chips": 1,
        "why": loader.load_cell(CELL).workload["why"]}
    names = _names("per_layer")
    at = names.index("fanout_reduce_roofline")
    assert names[at + 1:at + 6] == list(EP_METRICS)
    for name, (unit, better, source) in EP_METRICS.items():
        assert _entry("per_layer", name) == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": "fan-out", "moves": "goodput_gbs",
            "workloads": [CELL]}
    # a list the cell joined ends with it where pushpull_4x16m ended it
    for name in JOINED:
        listed = _entry("per_layer", name)["workloads"]
        assert listed.index(CELL) == listed.index("pushpull_4x16m") + 1
    listed = _entry("end_to_end", "goodput_gbs")["workloads"]
    assert listed.index(CELL) == listed.index("pushpull_4x16m") + 1
    for name in ("fanout_reduce_ms_per_call", "fanout_reduce_roofline",
                 "poller_queue_ms", "device_wait_ms"):
        assert CELL not in _entry("per_layer", name)["workloads"]


class _Item:
    def __init__(self, name, file="x.py"):
        self.name, self.nodeid, self.marks = \
            name, f"tests/benchmarks/{file}::{name}", []

    def add_marker(self, mark):
        self.marks.append(mark)


def test_the_hook_skips_these_ids_of_this_cell_and_no_others():
    """What the end of tests/conftest.py takes out of the
    manifest-parametrised tests for this cell: the unary client's test, the
    three unary controls (whose guarantees this configuration states under
    other names: CONTROLS here holds them) and the two breaks at the call's
    return (the merge's are here)."""
    import conftest
    tests = ["test_accepted_workload_names_no_client_and_resolves_to_unary",
             "test_control_comes_out_not_correct",
             "test_broken_timed_path_is_not_correct"]
    cases = ["flipped_byte", "stale_reply", "host_reply",
             "corrupted_byte-byte_mismatches", "wrong_chip-misplaced_replies",
             "dropped_reply-short_replies"]
    items = [_Item(f"{t}[{CELL}]") for t in tests] \
        + [_Item(f"{t}[{CELL}-{k}]") for t in tests for k in cases]
    conftest.pytest_collection_modifyitems(None, items)
    assert sorted(i.name for i in items if i.marks) == sorted(
        [f"test_accepted_workload_names_no_client_and_resolves_to_unary"
         f"[{CELL}]"]
        + [f"test_control_comes_out_not_correct[{CELL}-{k}]"
           for k in ("flipped_byte", "stale_reply", "host_reply")]
        + [f"test_broken_timed_path_is_not_correct[{CELL}-{k}]"
           for k in ("corrupted_byte-byte_mismatches",
                     "wrong_chip-misplaced_replies")])
    me = sys.modules[__name__]
    items = [_Item(n, os.path.basename(__file__)) for n in dir(me)
             if n.startswith("test_")]
    conftest.pytest_collection_modifyitems(None, items)
    assert not [i.name for i in items if i.marks]


# ---- sources: who may name what -------------------------------------------

def test_no_file_of_the_harness_names_the_cell_or_the_method():
    harness = os.path.join(REPO, "benchmarks", "harness")
    files = [os.path.join(harness, f) for f in sorted(os.listdir(harness))
             if f.endswith(".py")] + [os.path.join(REPO, "benchmarks",
                                                   "run.py")]
    for path in files:
        text = _source(path)
        for word in (CELL, CONFIG, METHOD, "ep_dispatch", "expert"):
            assert word not in text, (path, word)


def test_the_reference_is_numpy_and_ml_dtypes():
    text = _source("benchmarks", "reference", f"{METHOD}.py")
    imports = re.findall(r"^\s*(?:from|import)\s+(\S+)", text, re.MULTILINE)
    assert set(imports) == {"__future__", "numpy", "ml_dtypes"}


def test_the_client_packs_and_sums_nothing_itself():
    text = _source("benchmarks", "clients", "ep_dispatch.py")
    for word in ("ParallelChannel", "ExpertDispatchMapper",
                 "ExpertCombineMerger", "ExpertBatch", "fanout_operand",
                 "fanout_result"):
        assert word in text, word
    for word in ("to_bytes", "np.asarray", "import numpy", "tobytes",
                 "device_put", "._subs", "__dict__", "_fanout_", "_ep_",
                 "concatenate", "scatter", ".at[", "perf_counter",
                 "block_until_ready", "set_flag", "os.environ"):
        assert word not in text, word
    assert text.count("jax.jit") == 1           # the gate alone
    service = _source("benchmarks", "services", f"{METHOD}.py")
    assert service.count("jax.jit") == 1 and "PIECE_BYTES" not in service
    assert ".view(" not in service


def test_a_program_without_the_mapper_cannot_load_the_cell():
    """How the parent of this PR fails the cell with this PR's benchmark
    files laid over it: the loader resolves the client and the counter
    module before a device is touched, and their imports fail."""
    import subprocess
    code = ("import sys; sys.path.insert(0, %r); "
            "import brpc_tpu.channels as c; del c.ExpertDispatchMapper; "
            "del c.expert_dispatch_stats; "
            "from benchmarks.harness import loader; "
            "loader.load_cell(%r)" % (REPO, CELL))
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert res.returncode != 0
    assert "ExpertDispatchMapper" in res.stderr


# ---- a rehearsal on CPU devices --------------------------------------------

def test_rehearsal_is_correct_with_route_and_zero_counters_held(
        capsys, restore_mesh):
    rc, line, err = rehearse(capsys, CELL, "--trace", "0")
    assert rc == 0 and line["correct"] is True, err[-2000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    checks = line["checks"]
    assert checks["replies_compared"] == {"value": 4, "limit": 4}
    assert checks["byte_mismatches"] == {"value": 0, "limit": 0}
    assert checks["second_route_events"] == {"value": 0, "limit": 0}
    assert checks["fanout_sub_calls_per_call"] == {"value": 8.0,
                                                   "limit": 8.0}
    for k in ("pack_programs", "count_reads", "combine_programs"):
        assert checks[f"fanout_ep_{k}_per_call"] == {"value": 1.0,
                                                     "limit": 1.0}
    assert checks["fanout_ep_rows_dispatched_per_call"]["value"] > 256
    assert set(line["metrics"]) == {"goodput_gbs", "latency_p50_ms",
                                    "latency_p95_ms", "setup_s"}


def test_traced_rehearsal_has_every_metric_of_the_dispatch(capsys,
                                                           restore_mesh):
    span.layer_spans_reset()
    rc, line, err = rehearse(capsys, CELL, "--trace", "1", seconds="1.0")
    assert rc == 0 and line["correct"] is True, err[-2000:]
    got = line["metrics"]
    # the rehearsal's 64 tokens ride the native tier: no window piece
    pieces = [n for n in JOINED if n.startswith(("window_", "slice_",
                                                 "delivery_", "piece_"))]
    for name in list(EP_METRICS) + ["compiles_in_window", "device_idle_pct",
                                    "req_path_ms", "resp_path_ms"] \
            + [n for n in JOINED if n not in pieces]:
        assert name in got, name
        if EP_METRICS.get(name, ("", "", ""))[2] != "device_trace" \
                and name not in ("device_idle_pct",
                                 "device_busy_ms_per_call"):
            assert got[name]["value"] is not None, name
    assert got["ep_pack_roofline"]["value"] is None     # no device here
    assert got["fanout_subcalls_per_call"]["value"] == float(RANKS)
    assert got["compiles_in_window"]["value"] == 0.0
    assert 0 < got["ep_dispatch_ms_per_call"]["value"] \
        <= got["fanout_issue_ms_per_call"]["value"]
    assert 0 < got["ep_combine_ms_per_call"]["value"] \
        <= got["fanout_merge_ms_per_call"]["value"]
    # the rehearsal's 64 tokens fit a buffer of 64: padding, but not 0
    assert 0 < got["ep_pad_share"]["value"] < 100
    assert 0 < got["fanout_overlap"]["value"] <= float(RANKS)


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
    if control in OWN and number == "byte_mismatches":
        # every reply arrived whole, on the chip, under its own message:
        # only the combine says that a rank's rows are wrong
        for k in ("failed_calls", "short_replies", "misplaced_replies",
                  "misordered_replies", "message_mismatches",
                  "second_route_events"):
            assert checks[k]["value"] == 0, k
        assert line["failed"] == checks["replies_compared"]["value"] == 4
    if control in ("host_combine", "host_reply"):
        assert checks["fanout_ep_combine_programs_per_call"]["value"] == 0.0


def _break_the_merge(monkeypatch, fault):
    import jax
    from brpc_tpu.butil.iobuf import IOBuf
    from brpc_tpu.channels import ExpertCombineMerger
    real = ExpertCombineMerger.merge_sub

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

    monkeypatch.setattr(ExpertCombineMerger, "merge_sub", broken)


@pytest.mark.parametrize("fault", ["corrupted_byte", "wrong_chip"])
def test_a_reply_broken_where_it_is_merged(capsys, restore_mesh,
                                           monkeypatch, fault):
    """test_benchmark_harness.py's ``test_broken_timed_path_is_not_correct``
    for replies that arrive after ``call_method`` has returned: one bit of
    one rank's rows changes the tokens it got; a reply on another chip is
    brought to the operand's chip before the combine, which stays right."""
    _break_the_merge(monkeypatch, fault)
    rc, line, err = rehearse(capsys, CELL, "--trace", "0")
    assert line is not None, err[-2000:]
    if fault == "corrupted_byte":
        assert line["correct"] is False and line["failed"] > 0
        assert line["checks"]["byte_mismatches"]["value"] > 0
    else:
        assert line["correct"] is True, err[-2000:]
        assert line["checks"]["misplaced_replies"]["value"] == 0


# ---- the readers ---------------------------------------------------------------

@pytest.fixture
def empty_store():
    span.layer_spans_reset()
    yield
    span.layer_spans_reset()


BLOCK = 4096 * HIDDEN * 2


def _view(counters_=None, reduction=None, nbytes=BLOCK):
    """One caller, 60 operations of 50 ms back to back over a 3 s slice."""
    calls = [((1000 + i * 50) * MS, (1050 + i * 50) * MS, 0, nbytes, True,
              f"k{i}") for i in range(60)]
    window = types.SimpleNamespace(
        calls=lambda: iter(calls), trace_slice_ns=(1000 * MS, 4000 * MS),
        counters=counters_ or {},
        caller_device=types.SimpleNamespace(id=0))
    return readers.View(window=window, reduction=reduction,
                        peaks={"hbm_gbs": 819.0})


@pytest.mark.parametrize("metric,name", [
    ("ep_dispatch_ms_per_call", "brpc.fanout.dispatch"),
    ("ep_combine_ms_per_call", "brpc.fanout.combine")])
def test_span_metric_reads_its_span_and_nothing_else(empty_store, metric,
                                                     name):
    from brpc_tpu.butil import layer_span as ls
    m = loader._metric(_entry("per_layer", metric))
    assert m.reader == {"span": name}
    view = _view()
    assert readers.read(m, view) is None    # the parent: no such span
    st = ls._thread()
    for i in range(60):
        st.records.append((name, (1010 + i * 50) * MS, (1013 + i * 50) * MS,
                           0, next(ls._ids), 0, st.name, 0, 0, -1))
    assert readers.read(m, view) == pytest.approx(3.0)


def test_the_pad_share_is_padding_over_the_real_bytes_on_the_wire():
    m = loader._metric(_entry("per_layer", "ep_pad_share"))
    assert readers.read(m, _view()) is None
    c = {"fanout_ep_padded_bytes": 10, "fanout_device_operand_bytes": 70,
         "fanout_ep_combine_input_bytes": 140}
    assert readers.read(m, _view(c)) == pytest.approx(100 * 10 / 200)


def _reduction(programs, busy_s=0.6, window_s=3.0, chip=0):
    return xplane.Reduction(window_s=window_s, busy_s={chip: busy_s},
                            ops={chip: [("%fusion.9 = u8[16]{0}", busy_s)]},
                            programs={chip: programs})


ROWS = 21700 * 60                        # a window's rows, 60 operations


@pytest.mark.parametrize("metric,program,per_op", [
    ("ep_pack_roofline", "jit_brpc_ep_pack",
     BLOCK + 4096 * 8 * 8 + 21700 * 7488),
    ("ep_combine_roofline", "jit_brpc_ep_combine",
     21700 * HIDDEN * 2 + BLOCK)])
def test_the_roofline_is_the_traffics_bytes_over_its_programs_seconds(
        metric, program, per_op):
    m = loader._metric(_entry("per_layer", metric))
    assert m.reader["program"] == program[len("jit_"):]
    rows = {"fanout_ep_rows_dispatched": ROWS}
    # 60 operations in the 3 s slice; the program ran 0.1 s of the chip's 3
    want = 100 * (60 * per_op / 3.0) / (0.1 / 3.0) / 819e9
    got = readers.read(m, _view(rows, _reduction({program: 0.1})))
    assert got == pytest.approx(want)
    # nothing to read: no trace, no such program, no count of rows
    assert readers.read(m, _view(rows, None)) is None
    assert readers.read(m, _view(rows, _reduction({"jit_other": 0.1}))) \
        is None
    assert readers.read(m, _view({}, _reduction({program: 0.1}))) is None
