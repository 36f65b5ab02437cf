"""The xplane reduction's interval arithmetic on synthetic events: busy
union, idle share, ranking and idle gaps.  Pure functions, no jax."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness import xplane  # noqa: E402

E = [("a", 0, 10), ("b", 5, 10), ("c", 30, 5)]          # overlap + disjoint


@pytest.mark.parametrize("events,window,want", [
    ([("a", 0, 10), ("b", 20, 10)], None, 20),            # disjoint
    ([("a", 0, 10), ("b", 5, 10)], None, 15),             # overlapping
    ([("a", 0, 100), ("b", 10, 5), ("c", 20, 30)], None, 100),   # nested
    ([("a", 0, 10), ("b", 10, 10)], None, 20),            # touching
    ([], None, 0),                                        # an empty plane
    ([("a", 0, 10)], (0, 1000), 10),       # a window wider than the events
    ([("a", 0, 10), ("b", 5, 10)], (8, 12), 4),           # cut to the window
    ([("z", 5, 0)], None, 0),                             # zero-length event
], ids=["disjoint", "overlapping", "nested", "touching", "empty",
        "wide_window", "cut_to_window", "zero_length"])
def test_busy_union(events, window, want):
    assert xplane.busy_ns(events, window) == want


@pytest.mark.parametrize("busy,window,want", [
    (25, 100, 0.75), (0, 100, 1.0), (100, 100, 0.0), (0, 0, None)],
    ids=["quarter_busy", "empty_plane", "always_busy", "no_window"])
def test_idle_share(busy, window, want):
    assert xplane.idle_share(busy, window) == want


def test_ranking_sums_by_name_longest_first():
    events = [("x", 0, 5), ("y", 10, 30), ("x", 50, 50), ("w", 0, 30)]
    assert xplane.rank_ops(events) == [
        ("x", 55e-9), ("w", 30e-9), ("y", 30e-9)]
    assert xplane.rank_ops(events, top=1) == [("x", 55e-9)]
    assert xplane.rank_ops([]) == []


def test_gaps_are_the_window_less_the_union():
    window = (0, 50)
    found = xplane.gaps(E, window, top=None)
    assert sorted(found) == [(15, 30), (35, 50)]
    assert sum(b - a for a, b in found) + xplane.busy_ns(E, window) == 50
    assert xplane.gaps([], window) == [(0, 50)]
    assert xplane.gaps(E, window, top=1) == [(15, 30)]


def test_gaps_go_to_the_annotation_that_covers_them():
    host = [("bench.call.Echo", 0, 40), ("bench.handler.Echo", 16, 6)]
    got = dict(xplane.attribute_gaps([(15, 30), (35, 50)], host))
    # the middle of (15, 30) lies past the handler's end: the call covers
    # it; the middle of (35, 50) is past the call's end: nothing is open
    assert got == {"bench.call.Echo": 15e-9, "no benchmark span open": 15e-9}
    got = dict(xplane.attribute_gaps([(16, 20)], host))
    assert got == {"bench.handler.Echo": 4e-9}


CALL = ("bench.call.Echo", 0, 1000)


@pytest.mark.parametrize("host,idle,want", [
    # a span of the program inside the benchmark's names the layer waited for
    ([CALL, ("brpc.ici.stall", 100, 50)], [(110, 130)],
     {"brpc.ici.stall": 20e-9}),
    # the innermost of the program's: the one that began last and is open
    ([CALL, ("brpc.ici.piece", 100, 200), ("brpc.plane.run", 150, 40)],
     [(160, 180), (200, 220)],
     {"brpc.plane.run": 20e-9, "brpc.ici.piece": 20e-9}),
    # a span of the program wins over a benchmark annotation that began later
    ([CALL, ("brpc.call.wait", 10, 900), ("bench.handler.Echo", 400, 100)],
     [(420, 440)], {"brpc.call.wait": 20e-9}),
    # under the benchmark's annotation alone a gap keeps its name
    ([CALL, ("brpc.ici.stall", 100, 50)], [(500, 540)],
     {"bench.call.Echo": 40e-9}),
    # a span of the program that has ended covers nothing; nothing open
    ([("bench.call.Echo", 0, 100), ("brpc.ici.stall", 10, 20)], [(200, 260)],
     {"no benchmark span open": 60e-9}),
    # gaps are taken in any order, each by its own middle
    ([CALL, ("brpc.ici.gate", 700, 100), ("brpc.ici.stall", 100, 50)],
     [(710, 730), (110, 130), (400, 410)],
     {"brpc.ici.gate": 20e-9, "brpc.ici.stall": 20e-9,
      "bench.call.Echo": 10e-9}),
], ids=["program_span_inside_wins", "innermost_program_span",
        "program_span_over_later_bench", "bench_alone_keeps_its_name",
        "ended_span_covers_nothing", "gaps_in_any_order"])
def test_gaps_name_the_layer_the_chip_waited_for(host, idle, want):
    assert dict(xplane.attribute_gaps(idle, host)) == pytest.approx(want)


@pytest.mark.parametrize("between", [0, 63, 64, 500, 20000])
def test_a_cover_is_found_however_many_events_began_since(between):
    """A bulk call records some two hundred layer spans: the enclosing
    annotation can lie any number of events back."""
    host = [("bench.call.Echo", 0, 10 ** 9)] + [
        ("brpc.ici.piece", 10 + 3 * i, 2) for i in range(between)]
    middle = 10 + 3 * between + 50
    got = dict(xplane.attribute_gaps([(middle - 5, middle + 5)], host))
    assert got == {"bench.call.Echo": 10e-9}
    inside = [("brpc.call.wait", 5, 10 ** 9)] + host
    got = dict(xplane.attribute_gaps([(middle - 5, middle + 5)], inside))
    assert got == {"brpc.call.wait": 10e-9}


def test_the_programs_spans_widen_no_traced_window():
    chips = {0: [("op", 100, 10), ("op", 300, 20)], 1: [("op", 90, 5)]}
    bench = [("bench.call.Echo", 80, 100), ("bench.handler.Echo", 310, 30)]
    assert xplane.traced_window(chips, bench) == (80, 340)
    program = [("brpc.poller.block", 0, 5000), ("brpc.call.wait", 335, 900)]
    assert xplane.traced_window(chips, bench + program) == (80, 340)
    assert xplane.traced_window({}, program) is None
    assert xplane.traced_window({0: []}, []) is None


def test_reduction_mean_busy_over_chips():
    red = xplane.Reduction(window_s=2.0, busy_s={0: 0.5, 1: 1.5})
    assert red.mean_busy_s() == 1.0
    assert xplane.Reduction(window_s=0.0).mean_busy_s() is None
