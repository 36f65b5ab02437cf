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


def test_reduction_mean_busy_over_chips():
    red = xplane.Reduction(window_s=2.0, busy_s={0: 0.5, 1: 1.5})
    assert red.mean_busy_s() == 1.0
    assert xplane.Reduction(window_s=0.0).mean_busy_s() is None
