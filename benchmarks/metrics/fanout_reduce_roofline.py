"""The fan-out's reduce as a share of the HBM peak, on the callers' chip:
bytes the traffic had to move through memory, over the seconds that chip
spent in the reduce program's operations, over ``peaks.json``'s ``hbm_gbs``.

Bytes are what the traffic defines, whatever implements the reduce: every
correct operation reads ``workers`` contributions of the result's size and
writes the result once, so (``workers`` + 1) x its result bytes (an
operation's result is as long as the range it sent), each operation counted
by the share of it that lies in the traced slice (``readers.overlap_count``).
A reduce that also relays its inputs out moves more and earns no more.

Seconds are those of the reduce program's operations on the chip's operation
line, named in ``reduce_ops`` from a kept trace of the cell (an event is named
by its HLO text; an operation is the reduce's where the text holds one of the
listed strings: on that trace the program's sixteen ``reshape``, sixteen
``copy``, four ``shift-left_reduce_fusion`` and its ``bitcast-convert_add_
fusion``, names that no other program of the cell has — the cuts'
``copy-start`` / ``copy-done`` are other names, and the handler views
nothing).  A ``Reduction`` keeps the ten longest names of a chip, so an
operation of the reduce may have fallen out of them; every busy second the ten
names do not account for is therefore added to the reduce's seconds.  That can
only understate the share.  Where NONE of the ten kept names is the reduce's
(its operations run once an operation, the handler's and the cuts' four and
thirty-two times) and the program's own count says that reduces ran in the
window (``counter``), all of the reduce's seconds are among those busy
seconds that no kept name accounts for, and they alone are taken: again too
many seconds, never too few.  The handler's program is the benchmark's own
service and not a kernel of the system: it gets no share.  A window in which
no reduce ran gives nothing, never 0.
"""
from benchmarks.harness import readers


def reduce_bytes(view, workers: int) -> float:
    """Bytes the reduces of the traced slice's operations read and wrote."""
    within = view.window.trace_slice_ns
    return sum((workers + 1.0) * c[3] * readers.overlap_count([c], within)
               for c in view.good_calls())


def reduce_seconds(reduction, chip, reduce_ops, ran: bool = False):
    """Seconds ``chip`` spent in operations whose event name holds one of
    ``reduce_ops``, plus the busy seconds no kept name accounts for; ``None``
    where no kept name is an operation of the reduce, unless reduces ``ran``
    (then those unaccounted seconds hold all of them) — and where they come
    to nothing."""
    named = reduction.ops.get(chip, [])
    found = [s for name, s in named if any(op in name for op in reduce_ops)]
    if not (found or ran) or not reduction.busy_s.get(chip):
        return None
    unnamed = max(0.0, reduction.busy_s[chip] - sum(s for _, s in named))
    return sum(found) + unnamed or None


def read(view, reader):
    red, within = view.reduction, view.window.trace_slice_ns
    if red is None or not within or red.window_s <= 0:
        return None
    ran = view.window.counters.get(reader["counter"], 0) > 0
    seconds = reduce_seconds(red, view.window.caller_device.id,
                             reader["reduce_ops"], ran)
    moved = reduce_bytes(view, reader["workers"]) if seconds else 0.0
    if not moved:
        return None
    bytes_per_s = moved / ((within[1] - within[0]) / 1e9)   # the host's slice
    share_of_time = seconds / red.window_s                  # the trace's own
    return 100.0 * bytes_per_s / share_of_time \
        / (view.peaks[reader["peak"]] * 1e9)
