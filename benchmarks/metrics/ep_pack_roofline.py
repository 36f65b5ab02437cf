"""The expert dispatch's pack as a share of the HBM peak, on the callers'
chip: bytes the traffic defines, over the seconds that chip spent in the
program named in ``program`` (module ``jit_<program>`` on the trace's
modules line, as ``fanout_reduce_roofline``), over ``peaks.json``'s
``hbm_gbs``.

An operation's block is its BF16 hidden states (``tokens x hidden x 2``
bytes).  The pack reads them and the routing (int32 ids and float32 weights,
``tokens x top_k`` each) and writes each real record once (7,488 B at the
cell's widths); records a rank got are the window's
``fanout_ep_rows_dispatched`` over its correct operations.  Padding records
are written too and earn nothing, so the share can only understate.
Operations count by the share of them inside the traced slice.  ``None``
where the chip shows no such program or the program keeps no such count,
never 0."""
from benchmarks.harness import readers
from benchmarks.metrics.fanout_reduce_roofline import reduce_seconds

ROWS = "fanout_ep_rows_dispatched"


def record_bytes(hidden: int, top_k: int) -> int:
    return hidden + 4 * (hidden // 128) + 12 * top_k


def rows_per_operation(view):
    n = sum(1 for _ in view.good_calls())
    rows = view.window.counters.get(ROWS)
    return rows / n if n and rows else None


def share(view, reader, bytes_of):
    """``bytes_of(block bytes, rows per operation)`` an operation, over the
    seconds of ``reader["program"]``, as % of the peak."""
    red, within = view.reduction, view.window.trace_slice_ns
    if red is None or not within or red.window_s <= 0:
        return None
    rows = rows_per_operation(view)
    seconds = reduce_seconds(red, view.window.caller_device.id,
                             reader["program"])
    if not rows or not seconds:
        return None
    moved = sum(bytes_of(c[3], rows) * readers.overlap_count([c], within)
                for c in view.good_calls())
    if not moved:
        return None
    bytes_per_s = moved / ((within[1] - within[0]) / 1e9)   # the host's slice
    return 100.0 * bytes_per_s / (seconds / red.window_s) \
        / (view.peaks[reader["peak"]] * 1e9)


def read(view, reader):
    hidden, top_k = reader["hidden"], reader["top_k"]

    def pack_bytes(block, rows):
        tokens = block // (2 * hidden)
        return block + tokens * top_k * 8 + rows * record_bytes(hidden,
                                                                top_k)

    return share(view, reader, pack_bytes)
