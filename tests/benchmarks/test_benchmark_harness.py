"""The benchmark's harness on the CPU: every cell of BENCHMARK.json resolves
to its files, a rehearsal of each cell ends in the contract's last line, and
a timed path that is broken underneath comes out not correct.

No topology is described and no chip is looked for here: the rehearsals run
``benchmarks/run.py --rehearse`` in this process, on the CPU devices that
tests/conftest.py gives every test (eight, so a four-chip cell rehearses on
four of them).

``fixtures/`` holds two more cells, added the way a later PR adds one: files
and manifest entries, no edit of the harness.  ``fixture_xchip`` (three
servers on three further chips, a metric with a reader of its own) kept the
harness's path over several chips under test until BENCHMARK.json had a
cross-chip cell.  ``fixture_stream`` has a client that is not a unary call (a
long-lived stream a caller, ``clients/fixture_stream.py``), a service, a
reference, a counter module and a control of its own: it keeps the client
and counter seams under test until a streaming cell is added
(test_client_and_counter_seams.py has what only that cell shows).
"""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import loader, readers, traffic  # noqa: E402
from benchmarks.reference import payload  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
MANIFEST = loader.manifest()            # BENCHMARK.json alone
CELLS = [w["name"] for w in MANIFEST["workloads"]]
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
FIXTURE_CELLS = [w["name"] for w in json.load(open(os.path.join(
    FIXTURES, "manifest.json")))["workloads"]]
ALL_CELLS = CELLS + FIXTURE_CELLS
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


def _cells_of(metric):
    return metric.get("workloads", CELLS)


@pytest.fixture(autouse=True)
def with_fixture_cells(monkeypatch):
    """Every test sees the fixtures' cell beside the manifest's."""
    monkeypatch.setattr(loader, "ROOTS", loader.ROOTS + [
        (os.path.join(FIXTURES, "manifest.json"),
         os.path.join(FIXTURES, "benchmarks"))])


@pytest.fixture
def restore_mesh():
    """A rehearsal binds the process's default mesh to its own chips."""
    from brpc_tpu.ici.mesh import IciMesh
    before = IciMesh._default
    yield
    IciMesh.set_default(before)


def rehearse(capsys, cell, *extra, seconds="0.6", seed="2147483659"):
    """run.py's main in this process; (exit code, last stdout line parsed,
    standard error)."""
    rc = bench_run.main(["--workload", cell, "--seed", seed, "--seconds",
                         seconds, "--rehearse", *extra])
    out, err = capsys.readouterr()
    lines = [ln for ln in out.splitlines() if ln.strip()]
    return rc, (json.loads(lines[-1]) if lines else None), err


# ---- the manifest and the files it names ---------------------------------

def _all_names():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in MANIFEST[group]:
            yield f"{group}:{e['name']}", e["name"]
    for w in MANIFEST["workloads"]:
        yield f"traffic:{w['traffic']}", w["traffic"]


@pytest.mark.parametrize("label,name", list(_all_names()),
                         ids=[x[0] for x in _all_names()])
def test_names_keep_to_the_allowed_characters(label, name):
    assert NAME.match(name), name


@pytest.mark.parametrize("metric", METRICS, ids=[m["name"] for m in METRICS])
def test_metric_file_holds_only_its_reader(metric):
    """What a metric is and which cells report it is said once, in the
    manifest: a cell is added without an edit of any metric's file."""
    how = json.load(open(os.path.join(
        REPO, "benchmarks", "metrics", f"{metric['name']}.json")))
    assert UNIT.match(metric["unit"])
    assert set(how) == {"reader"}
    has_code = os.path.exists(os.path.join(
        REPO, "benchmarks", "metrics", f"{metric['name']}.py"))
    assert has_code or how["reader"]["kind"] in readers.KINDS


@pytest.mark.parametrize("cell", ALL_CELLS)
def test_loader_resolves_cell(cell):
    c = loader.load_cell(cell)
    assert c.workload["config"] == c.config_name
    assert c.config["chips"] == c.chips
    assert len(c.config["servers"]) >= 1 and c.config["guarantees"]
    for m in c.methods():
        assert hasattr(loader.service_module(m), "build")
        assert hasattr(loader.reference_module(m), "expected")
    names = {m.name for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and c.per_layer
    for spec in traffic.set_specs(c.workload).values():
        assert spec.count >= c.workload["threads"]
    tiny = loader.load_cell(cell, rehearse=True)
    assert sum(s.count * s.block_bytes
               for s in traffic.set_specs(tiny.workload).values()) < 1 << 28


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=[m["name"] for m in MANIFEST["per_layer"]])
def test_per_layer_metric_cells_report_what_it_moves(metric):
    moved = next(m for m in MANIFEST["end_to_end"]
                 if m["name"] == metric["moves"])
    assert set(_cells_of(metric)) <= set(_cells_of(moved))


def test_every_cell_has_setup_another_end_to_end_and_a_layer_metric():
    for cell in CELLS:
        e2e = [m["name"] for m in MANIFEST["end_to_end"]
               if cell in _cells_of(m)]
        assert "setup_s" in e2e and len(e2e) >= 2, cell
        assert any(cell in _cells_of(m) for m in MANIFEST["per_layer"]), cell


def test_unknown_device_kind_has_no_peaks():
    assert loader.peaks("TPU v5 lite")["ici_gbs"] == 200.0
    with pytest.raises(loader.BenchmarkError):
        loader.peaks("TPU v9 imaginary")


def test_unknown_cell_is_refused(capsys):
    assert bench_run.main(["--workload", "no_such_cell", "--seed", "1",
                           "--seconds", "1", "--rehearse"]) != 0
    assert capsys.readouterr().out.strip() == ""


# ---- traffic and payload --------------------------------------------------

@pytest.mark.parametrize("cell", ALL_CELLS)
def test_schedule_repeats_for_a_seed_and_differs_for_another(cell):
    wl = loader.load_cell(cell).workload
    big = 2 ** 31 + 11
    a = [traffic.head(wl, big, t, 200) for t in range(wl["threads"])]
    b = [traffic.head(wl, big, t, 200) for t in range(wl["threads"])]
    c = [traffic.head(wl, big + 1, t, 200) for t in range(wl["threads"])]
    assert a == b and a != c
    # no two callers ever hold the same block of a set
    for m in range(len(wl["mix"])):
        held = [{blk for mix, blk in rows if mix == m} for rows in a]
        assert not set.intersection(*held) or wl["threads"] == 1
    # every seed offers the same multiset of calls per cycle
    cyc = [sorted(traffic.mix_cycle(wl, s, 0)) for s in (1, big)]
    assert cyc[0] == cyc[1]


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5, 2 ** 33 + 1])
def test_device_sets_equal_the_host_reference(seed):
    import jax
    from benchmarks.harness.resident import make_set
    blocks = make_set(seed, 3, 4, 4096, jax.devices()[0])
    for i, blk in enumerate(blocks):
        assert np.array_equal(np.asarray(blk),
                              payload.block(seed, 3, i, 4096))
    assert not np.array_equal(payload.block(seed, 3, 0, 4096),
                              payload.block(seed + 1, 3, 0, 4096))
    assert not np.array_equal(payload.block(seed, 3, 0, 4096),
                              payload.block(seed, 4, 0, 4096))


def test_a_metric_with_a_reader_of_its_own_is_found_by_name(capsys,
                                                            restore_mesh):
    """``metrics/<name>.py`` beside ``metrics/<name>.json`` (the fixtures'
    ``fixture_large_calls``) is loaded and read."""
    cell = loader.load_cell(FIXTURE_CELLS[0])
    mine = {m.name: m for m in cell.per_layer}
    assert mine["fixture_large_calls"].module is not None
    assert mine["fixture_transfers_per_call"].module is None
    rc, line, err = rehearse(capsys, FIXTURE_CELLS[0], "--trace", "1")
    assert rc == 0, err[-2000:]
    assert line["metrics"]["fixture_large_calls"]["value"] > 0
    assert line["metrics"]["fixture_large_calls"]["unit"] == "calls"


def _varint_len(n):
    return max(1, (n.bit_length() + 6) // 7)


def test_aged_call_ids_keep_one_length_for_the_window():
    """After the ageing step the slots that the callers will draw give ids
    whose varint keeps its length for the next 30,000 calls on a slot."""
    from brpc_tpu.bthread import id as call_id
    from benchmarks.harness.driver import age_call_ids
    age_call_ids(3)
    held = [call_id.create() for _ in range(3)]
    for cid in held:
        call_id.unlock_and_destroy(cid)
    now = {_varint_len(c) for c in held}
    later = {_varint_len(c + (2 * 30000 << 32)) for c in held}
    assert now == later == {7}


@pytest.mark.parametrize("within,want", [
    ((0, 100), 3.0),            # all three calls inside
    ((10, 30), 1.0 + 0.5),      # one whole, half of the second
    ((35, 38), 0.3),            # a slice inside one call
    ((100, 200), 0.0),          # a slice after the last call
], ids=["all", "straddled_end", "inside_one_call", "after"])
def test_calls_are_counted_by_their_overlap_with_a_slice(within, want):
    calls = [(10, 20, 0, 1, True, "a"), (20, 40, 0, 1, True, "b"),
             (30, 40, 0, 1, True, "c")]
    if within == (10, 30):
        calls = calls[:2]
    if within == (35, 38):
        calls = calls[2:]
    assert readers.overlap_count(calls, within) == pytest.approx(want)


@pytest.mark.parametrize("trace_window_s,want_ms", [(3.0, 1.0), (3.04, 1.0),
                                                   (0.0, None)],
                         ids=["same_window", "profiler_window_wider",
                              "no_trace_window"])
def test_busy_per_call_takes_each_rate_over_its_own_window(trace_window_s,
                                                           want_ms):
    """Two callers, 50 ms a call (40 calls a second), the chip busy 4 % of
    the trace's window (40 ms a second): 1 ms a call, however much wider
    the profiler's window is than the host's slice and whatever calls
    straddle the slice's ends."""
    import types
    from benchmarks.harness.xplane import Reduction
    ms = 1_000_000
    calls = [(t * 50 * ms + off, (t + 1) * 50 * ms + off, 0, 1, True, "k")
             for off in (0, 17 * ms) for t in range(100)]
    window = types.SimpleNamespace(
        calls=lambda: iter(calls), trace_slice_ns=(1000 * ms, 4000 * ms),
        caller_device=types.SimpleNamespace(id=0))
    red = Reduction(window_s=trace_window_s,
                    busy_s={0: 0.04 * trace_window_s})
    view = readers.View(window=window, reduction=red, peaks=None)
    got = readers.trace_busy_ms_per_call(view, {"chip": "caller"})
    assert got == (pytest.approx(want_ms) if want_ms else None)


@pytest.mark.parametrize("n,q,rank", [(100, 0.5, 50), (100, 0.95, 95),
                                      (10, 0.95, 10), (1, 0.5, 1),
                                      (20, 0.95, 19)])
def test_percentile_is_the_nearest_rank(n, q, rank):
    assert readers.percentile([float(i) for i in range(1, n + 1)], q) == rank


# ---- a rehearsal of each cell ends in the contract's last line ------------

@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", ALL_CELLS)
def test_rehearsal_prints_the_contract_line(capsys, restore_mesh, cell, trace):
    rc, line, err = rehearse(capsys, cell, "--trace", str(trace))
    merged = loader.manifest()
    assert rc == 0, err[-2000:]
    want = LINE_KEYS | ({"breakdown"} if trace else set())
    assert set(line) == want and list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0, err[-2000:]
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["memory_peak_bytes"] is None
    group = "per_layer" if trace else "end_to_end"
    listed = {m["name"]: m for m in merged[group]
              if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) <= set(listed)
    for name, m in line["metrics"].items():
        assert m["unit"] == listed[name]["unit"]
        if listed[name]["source"] == "device_trace":
            assert m["value"] is None       # no device number off the chip
        else:
            assert m["value"] is not None
    if trace:
        assert line["device"]["busy_s"] is None
        assert line["device"]["window_s"] is None
        assert {n for n, m in listed.items()
                if m["source"] == "device_trace"} <= set(line["metrics"])
    else:
        assert set(line["metrics"]) == set(listed)
    # each number compared is printed beside its limit, last on stderr
    tail = [ln for ln in err.splitlines() if ln.strip()][-len(line["checks"]):]
    assert all(ln.startswith("check ") for ln in tail)
    assert all(set(v) == {"value", "limit"} for v in line["checks"].values())
    # the reservoirs held exactly what was due: the sample, or every call
    held = line["checks"]["replies_compared"]
    assert held["value"] == held["limit"] > 0


# ---- a timed path broken underneath comes out not correct -----------------

def _break_replies(monkeypatch, how):
    """The program's client side hands on something else than the server
    sent: a unary call's reply attachment as ``call_method`` returns, a
    stream's chunk as it is delivered to the client's handler."""
    import jax
    from brpc_tpu import rpc
    from brpc_tpu.butil.iobuf import IOBuf
    from brpc_tpu.rpc import stream as stream_mod
    real_call, real_on_data = rpc.Channel.call_method, \
        stream_mod.Stream.on_data

    def altered(buf):
        """``buf`` with its device blocks broken, its host bytes as sent."""
        out, first = IOBuf(), True
        for i in range(buf.backing_block_num()):
            r = buf.backing_block(i)
            if not hasattr(r.block.data, "devices"):
                out.append(bytes(r.block.host_view(r.offset, r.length)))
                continue
            z = r.block.data.reshape(-1)[r.offset:r.offset + r.length]
            if how == "corrupted_byte" and first:
                z = z.at[len(z) // 3].set(z[len(z) // 3] ^ 0x40)
            if how == "wrong_chip":
                z = jax.device_put(z, jax.devices()[1])
            first = False
            out.append_device_array(z)
        return out

    def broken_call(self, method, cntl, request, response_cls, *a, **kw):
        resp = real_call(self, method, cntl, request, response_cls, *a, **kw)
        att = cntl.response_attachment
        if cntl.failed() or not att.device_refs():
            return resp
        broken = altered(att)
        att.clear()
        att.append(broken)
        return resp

    def broken_on_data(self, data):
        if self.is_client and data.device_refs():
            data = altered(data)
        return real_on_data(self, data)

    monkeypatch.setattr(rpc.Channel, "call_method", broken_call)
    monkeypatch.setattr(stream_mod.Stream, "on_data", broken_on_data)


@pytest.mark.parametrize("fault,number", [
    ("corrupted_byte", "byte_mismatches"),
    ("wrong_chip", "misplaced_replies")])
@pytest.mark.parametrize("cell", ALL_CELLS)
def test_broken_timed_path_is_not_correct(capsys, restore_mesh, monkeypatch,
                                          cell, fault, number):
    _break_replies(monkeypatch, fault)
    rc, line, err = rehearse(capsys, cell, "--trace", "0")
    assert line is not None, err[-2000:]
    assert line["correct"] is False and line["failed"] > 0
    assert line["checks"][number]["value"] > line["checks"][number]["limit"]
    assert f"check {number}:" in err and "NOT OK" in err


# the controls of ``benchmarks/controls/`` alter a unary reply's attachment as
# the handler answers, which a stream's chunks never pass: a cell whose client
# is not unary brings a control of its own
UNARY_CONTROLS = ["flipped_byte", "stale_reply", "host_reply"]
OWN_CONTROLS = {"fixture_xchip": UNARY_CONTROLS + ["wrong_chip"],
                "fixture_stream": ["fixture_flipped_chunk"]}


@pytest.mark.parametrize("cell,control", [
    (c, k) for c in ALL_CELLS for k in OWN_CONTROLS.get(c, UNARY_CONTROLS)])
def test_control_comes_out_not_correct(capsys, restore_mesh, cell, control):
    """The control: the cell with one stated guarantee broken."""
    mod = loader.control_module(control)
    assert mod.GUARANTEE in loader.load_cell(cell).config["guarantees"]
    rc, line, err = rehearse(capsys, cell, "--trace", "0",
                             "--control", control)
    assert line is not None, err[-2000:]
    assert line["correct"] is False
    broken = [k for k, v in line["checks"].items()
              if k != "replies_compared" and not k.endswith("_per_call")
              and v["value"] > v["limit"]]
    assert broken, line["checks"]


def test_route_check_fails_when_the_route_is_not_taken(capsys, restore_mesh,
                                                       monkeypatch):
    """A cell whose calls leave the route its ``why`` names is not correct:
    the native tier's request count is held at one per call."""
    from benchmarks.harness import counters
    real = counters.snapshot

    def blind(servers):
        out = real(servers)
        out["native_requests"] = 0
        return out

    monkeypatch.setattr(counters, "snapshot", blind)
    cell = next(c for c in CELLS if any(
        r["counter"] == "native_requests"
        for r in loader.load_cell(c, rehearse=True).workload["route"]))
    rc, line, err = rehearse(capsys, cell, "--trace", "0")
    assert line["correct"] is False
    assert line["checks"]["native_requests_per_call"]["value"] == 0


# ---- no chip, no result ---------------------------------------------------

def test_without_a_chip_and_without_rehearse_there_is_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=120, cwd=REPO)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
    assert "no TPU" in res.stderr
