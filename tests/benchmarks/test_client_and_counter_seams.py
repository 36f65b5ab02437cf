"""The harness's two seams for a cell whose client is not a unary call:
``clients/<name>.py`` (what an operation does) and ``counters/<name>.py``
(counts of the program the closed table does not read), both found by name
under every root of ``loader.ROOTS``.

The accepted cells take the seams by default (``unary``, no further counter)
with no key in their files.  ``fixtures/``'s ``fixture_stream`` is the second
client: a long-lived stream a caller, an operation of 16 chunks of 64 KiB of
device memory xored and written back on the stream.  It is in no
BENCHMARK.json and costs no chip time; the parametrised tests of
test_benchmark_harness.py run it beside the manifest's cells (contract line,
broken timed path, its own control), and what only it shows is here.
"""
import json
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import counters, loader  # noqa: E402
from test_benchmark_harness import (  # noqa: E402,F401
    CELLS, FIXTURES, rehearse, restore_mesh, with_fixture_cells)

STREAM = "fixture_stream"
CHUNKS, CHUNK, HEADER = 16, 65536, 32
HARNESS = [os.path.join(REPO, "benchmarks", "run.py")] + [
    os.path.join(REPO, "benchmarks", "harness", f)
    for f in sorted(os.listdir(os.path.join(REPO, "benchmarks", "harness")))
    if f.endswith(".py")]
EVERY_CALL = {"failed_calls", "short_replies", "misplaced_replies",
              "misordered_replies", "byte_mismatches", "message_mismatches",
              "replies_compared", "second_route_events"}


def _source(path):
    with open(path, encoding="utf-8") as f:
        return f.read()


# ---- the accepted cells take the seams by default -------------------------

@pytest.mark.parametrize("cell", CELLS)
def test_accepted_workload_names_no_client_and_resolves_to_unary(cell):
    raw = json.loads(_source(os.path.join(
        REPO, "benchmarks", "workloads", f"{cell}.json")))
    assert "counters" not in raw
    assert all("client" not in m and "client_options" not in m
               for m in raw["mix"])
    loaded = loader.load_cell(cell)
    assert loaded.clients() == ["unary"] * len(raw["mix"])
    assert counters.second_route(loaded) == list(counters.SECOND_ROUTE)
    unary = loader.client_module("unary")
    assert unary.__name__ == "benchmarks.clients.unary"
    assert callable(unary.open)


@pytest.mark.parametrize("cell", CELLS)
def test_unary_rehearsal_compares_the_numbers_it_always_did(
        capsys, restore_mesh, cell):
    rc, line, err = rehearse(capsys, cell, "--trace", "0")
    assert rc == 0 and line["correct"] is True, err[-2000:]
    route = {f"{r['counter']}_per_call" for r in
             loader.load_cell(cell, rehearse=True).workload["route"]}
    assert set(line["checks"]) == EVERY_CALL | route
    assert list(line["checks"])[:8] == [
        "failed_calls", "short_replies", "misplaced_replies",
        "misordered_replies", "byte_mismatches", "message_mismatches",
        "replies_compared", "second_route_events"]


def test_the_clock_and_the_checks_are_the_drivers_alone():
    """What a latency is cannot differ between cells: ``driver.py`` holds the
    clock, the annotation, the four per-operation checks and the reservoir,
    and none of the call; a client holds the call and none of those."""
    driver = _source(os.path.join(REPO, "benchmarks", "harness", "driver.py"))
    for word in ("rpc.Controller", "call_method", "response_attachment"):
        assert word not in driver, word
    for word in ("perf_counter_ns", "TraceAnnotation(\"bench.call.\"",
                 "block_until_ready", "short_replies", "misplaced_replies",
                 "misordered_replies", "failed_calls", "_maybe_keep"):
        assert word in driver, word
    unary = _source(os.path.join(REPO, "benchmarks", "clients", "unary.py"))
    for word in ("Controller", "call_method", "response_attachment",
                 "append_device_array", "cntl.failed()"):
        assert word in unary, word
    clients = [os.path.join(REPO, "benchmarks", "clients", "unary.py"),
               os.path.join(FIXTURES, "benchmarks", "clients",
                            "fixture_stream.py")]
    for path in clients:
        text = _source(path)
        for word in ("perf_counter", "time.time", "TraceAnnotation",
                     "block_until_ready", "_replies", "CallerLog"):
            assert word not in text, (path, word)


@pytest.mark.parametrize("path", HARNESS,
                         ids=[os.path.basename(p) for p in HARNESS])
def test_the_harness_names_no_method_no_client_and_no_cell(path):
    text = _source(path)
    names = ["Echo", "Transform", "FixtureStream", "fixture_"] \
        + CELLS + [c["name"] for c in loader.manifest()["configs"]]
    for word in names:
        assert word not in text, word
    assert not re.search(r"stream", text, re.IGNORECASE)
    # the one client the harness knows by name is the default of an entry
    # that names none, and only the loader knows it
    if not path.endswith("loader.py"):
        assert "unary" not in text


# ---- the second client: a stream a caller ----------------------------------

def test_stream_fixture_resolves_through_the_roots():
    cell = loader.load_cell(STREAM)
    assert cell.clients() == ["fixture_stream"]
    assert cell.workload["counters"] == ["fixture_stream"]
    assert counters.second_route(cell) == list(counters.SECOND_ROUTE) + [
        "fixture_stream_write_failures"]
    for mod in (loader.client_module("fixture_stream"),
                loader.service_module("FixtureStream"),
                loader.reference_module("FixtureStream"),
                loader.counter_module("fixture_stream"),
                loader.control_module("fixture_flipped_chunk")):
        assert mod.__file__.startswith(FIXTURES)
        assert not mod.__name__.startswith("benchmarks.")
    # loaded once: a control and the run it breaks see one module
    assert loader.counter_module("fixture_stream") is \
        loader.counter_module("fixture_stream")


@pytest.mark.parametrize("trace", [0, 1])
def test_stream_cell_rehearses_to_a_correct_line(capsys, restore_mesh, trace):
    rc, line, err = rehearse(capsys, STREAM, "--trace", str(trace))
    assert rc == 0 and line["correct"] is True, err[-2000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    checks = line["checks"]
    # two callers' reservoirs of two were due, and were compared
    assert checks["replies_compared"] == {"value": 4, "limit": 4}
    assert set(checks) == EVERY_CALL | {
        "fixture_stream_consumed_bytes_per_call"}
    # the counter module's key is a WINDOW delta: exactly an operation's
    # chunks with their headers, the warm-up's operations not among them
    assert checks["fixture_stream_consumed_bytes_per_call"]["value"] == \
        CHUNKS * (CHUNK + HEADER)
    if trace:
        got = line["metrics"]["fixture_stream_bytes_per_call"]
        assert got == {"value": float(CHUNKS * (CHUNK + HEADER)),
                       "unit": "bytes/call"}
        # the outside stamps are the service's, so a stream has them too
        assert line["metrics"]["req_path_ms"]["value"] > 0
        assert line["metrics"]["resp_path_ms"]["value"] > 0
    else:
        assert set(line["metrics"]) == {"latency_p50_ms", "latency_p95_ms",
                                        "setup_s"}


def test_stream_cells_own_control_flips_every_chunk(capsys, restore_mesh):
    assert loader.control_module("fixture_flipped_chunk").GUARANTEE in \
        loader.load_cell(STREAM).config["guarantees"]
    rc, line, err = rehearse(capsys, STREAM, "--trace", "0", "--control",
                             "fixture_flipped_chunk")
    assert line is not None and line["correct"] is False, err[-2000:]
    compared = line["checks"]["replies_compared"]["value"]
    assert line["checks"]["byte_mismatches"]["value"] == CHUNKS * compared
    assert line["failed"] == compared
    assert "check byte_mismatches:" in err and "NOT OK" in err


def test_a_route_limit_on_a_modules_key_that_is_not_met_reads_not_ok(
        capsys, restore_mesh, monkeypatch):
    real_load = loader.load_cell

    def with_a_limit_out_of_reach(name, rehearse=False):
        cell = real_load(name, rehearse)
        cell.workload["route"] = [{"counter": "fixture_stream_consumed_bytes",
                                   "per_call_min": 1 << 40}]
        return cell

    monkeypatch.setattr(loader, "load_cell", with_a_limit_out_of_reach)
    rc, line, err = rehearse(capsys, STREAM, "--trace", "0")
    assert line is not None and line["correct"] is False, err[-2000:]
    got = line["checks"]["fixture_stream_consumed_bytes_per_call"]
    assert got == {"value": float(CHUNKS * (CHUNK + HEADER)),
                   "limit": 1 << 40}
    assert (f"check fixture_stream_consumed_bytes_per_call: {got['value']} "
            f">= {1 << 40} NOT OK") in err


def test_a_counter_the_configuration_holds_at_zero_fails_the_run(
        capsys, restore_mesh, monkeypatch):
    """``second_route_counters`` of the configuration: one failed stream
    write in the window is a second-route event."""
    mod = loader.counter_module("fixture_stream")
    real, reads = mod.snapshot, []

    def one_more_failure_each_read(servers):
        out = real(servers)
        reads.append(1)
        out["fixture_stream_write_failures"] += len(reads)
        return out

    monkeypatch.setattr(mod, "snapshot", one_more_failure_each_read)
    rc, line, err = rehearse(capsys, STREAM, "--trace", "0")
    assert line is not None and line["correct"] is False, err[-2000:]
    assert line["checks"]["second_route_events"] == {"value": 1, "limit": 0}
    assert len(reads) == 2              # before the window and after it


def test_the_tables_keys_are_the_ones_it_says():
    """``TABLE_KEYS`` is what the loader refuses a module's key against
    before a device is touched: it has to be the table as it is read."""
    assert set(counters.snapshot([])) == counters.TABLE_KEYS
    merged = counters.read([], ["fixture_stream"])
    assert set(merged) == counters.TABLE_KEYS | set(
        loader.counter_module("fixture_stream").KEYS)
    assert set(counters.SECOND_ROUTE) <= counters.TABLE_KEYS


# ---- files that do not describe a runnable cell: exit 2, no device touched --

def _a_root(tmp_path, monkeypatch, name, change, counter_files=()):
    """One more root: the stream fixture's workload under another name, with
    ``change`` applied to it, and counter modules of its own."""
    bench = tmp_path / "benchmarks"
    (bench / "workloads").mkdir(parents=True)
    (bench / "counters").mkdir()
    workload = json.loads(_source(os.path.join(
        FIXTURES, "benchmarks", "workloads", f"{STREAM}.json")))
    change(workload)
    (bench / "workloads" / f"{name}.json").write_text(json.dumps(workload))
    for stem, text in counter_files:
        (bench / "counters" / f"{stem}.py").write_text(text)
    (tmp_path / "manifest.json").write_text(json.dumps({
        "configs": [], "end_to_end": [], "per_layer": [],
        "workloads": [{"name": name, "config": workload["config"],
                       "traffic": workload["traffic"], "chips": 1,
                       "why": "a cell whose files do not add up"}]}))
    monkeypatch.setattr(loader, "ROOTS", loader.ROOTS + [
        (str(tmp_path / "manifest.json"), str(bench))])


def _module(keys):
    return (f"KEYS = {keys!r}\n\n\ndef snapshot(servers):\n"
            f"    return {{k: 0 for k in KEYS}}\n")


BAD_FILES = {
    "no_such_client": (
        lambda wl: wl["mix"][0].update(client="no_such_client"), (),
        "missing file benchmarks/clients/no_such_client.py"),
    "no_such_counter_module": (
        lambda wl: wl.update(counters=["fixture_stream", "no_such_counters"]),
        (), "missing file benchmarks/counters/no_such_counters.py"),
    "key_of_the_table_given_again": (
        lambda wl: wl.update(counters=["fixture_stream", "takes_the_tables"]),
        [("takes_the_tables", _module(("mine", "plane_transfers")))],
        "'plane_transfers', which harness/counters.py gives already"),
    "key_of_another_module_given_again": (
        lambda wl: wl.update(counters=["fixture_stream", "takes_a_modules"]),
        [("takes_a_modules", _module(("fixture_stream_consumed_bytes",)))],
        "which counters/fixture_stream.py gives already"),
    "held_at_zero_and_given_by_nobody": (
        lambda wl: wl.update(counters=[]), (),
        "holds 'fixture_stream_write_failures' at zero and no counter"),
}


@pytest.mark.parametrize("case", sorted(BAD_FILES))
def test_files_that_do_not_add_up_end_with_exit_2_before_any_device(
        tmp_path, monkeypatch, capsys, case):
    change, counter_files, says = BAD_FILES[case]
    _a_root(tmp_path, monkeypatch, case, change, counter_files)
    with pytest.raises(loader.BenchmarkError) as e:
        loader.load_cell(case)
    assert says in str(e.value)

    def no_device(*a, **kw):
        raise AssertionError("a device was looked for")

    monkeypatch.setattr(bench_run, "device_check", no_device)
    rc = bench_run.main(["--workload", case, "--seed", "1", "--seconds", "1",
                         "--rehearse"])
    out, err = capsys.readouterr()
    assert rc == bench_run.EXIT_NO_DEVICE == 2
    assert out.strip() == "" and says in err


def test_a_key_that_turns_up_twice_when_read_is_refused(tmp_path,
                                                        monkeypatch):
    """A module whose ``KEYS`` do not say all it gives is caught where the
    counters are read."""
    _a_root(tmp_path, monkeypatch, "says_less_than_it_gives",
            lambda wl: wl.update(counters=["fixture_stream", "says_less"]),
            [("says_less", "KEYS = ('mine',)\n\n\ndef snapshot(servers):\n"
                           "    return {'mine': 0, 'ici_bytes': 0}\n")])
    cell = loader.load_cell("says_less_than_it_gives")
    with pytest.raises(loader.BenchmarkError) as e:
        counters.read([], cell.workload["counters"])
    assert "'ici_bytes', which is read already" in str(e.value)
