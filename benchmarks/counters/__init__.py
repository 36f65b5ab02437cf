"""Counters of the program that ``harness/counters.py``'s closed table does
not read, one file a module, named by a workload file's ``"counters"`` list.

A module gives ``KEYS`` (the keys it adds, none of them the table's or another
module's of the cell) and ``snapshot(servers) -> {key: count}`` with a
cumulative count under each.  The harness reads it beside the table before and
after the window, so ``Window.counters[key]`` is the window's delta: ``route``
entries, ``counter_per_call`` readers and a configuration's
``second_route_counters`` name such a key as they name the table's.
"""
