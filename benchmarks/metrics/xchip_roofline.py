"""The device plane's transfer program as a share of the ICI peak, on the
callers' chip: bytes the traffic had to move across the link, over the seconds
that chip spent in transfer operations, over ``peaks.json``'s ``ici_gbs``.

Bytes are what the traffic defines, whatever implements the transfer: every
correct call carries its attachment to the server's chip and the echo back, so
2 x its attachment bytes, each call counted by the share of it that lies in
the traced slice (``readers.overlap_count``).

Seconds are those of the transfer operations on the chip's operation line:
the transfer program's ``collective-permute-start`` and ``-done`` instructions
(on a kept trace of the cell one name each whatever the piece's size: 0.005 s
and 0.548 s of chip 0's 0.786 busy seconds, first and eighth of its fifteen
names; PERF.md section 5).  A ``Reduction`` keeps the ten longest names of a
chip, so a transfer operation may have fallen out of them; every busy second
the ten names do not account for is therefore added to the transfer seconds.
That can only understate the share, by nothing where the ten names hold every
transfer operation.  A trace in which the chip ran no transfer operation gives
nothing, never 0.
"""
from benchmarks.harness import readers


def transfer_bytes(view) -> float:
    """Attachment bytes that crossed the link, both ways, for the calls of
    the traced slice."""
    within = view.window.trace_slice_ns
    return sum(2.0 * c[3] * readers.overlap_count([c], within)
               for c in view.good_calls())


def transfer_seconds(reduction, chip, transfer_ops):
    """Seconds ``chip`` spent in operations whose instruction name (an
    event is named by its HLO text, ``%name = shape op(...)``) starts with
    one of ``transfer_ops``, plus the busy seconds no kept name accounts
    for; ``None`` where no kept name is a transfer operation."""
    named = reduction.ops.get(chip, [])
    found = [s for name, s in named
             if name.lstrip("%").split(" = ", 1)[0].startswith(
                 tuple(transfer_ops))]
    if not found or not reduction.busy_s.get(chip):
        return None
    unnamed = max(0.0, reduction.busy_s[chip] - sum(s for _, s in named))
    return sum(found) + unnamed


def read(view, reader):
    red, within = view.reduction, view.window.trace_slice_ns
    if red is None or not within or red.window_s <= 0:
        return None
    seconds = transfer_seconds(red, view.window.caller_device.id,
                               reader["transfer_ops"])
    moved = transfer_bytes(view) if seconds else 0.0
    if not moved:
        return None
    bytes_per_s = moved / ((within[1] - within[0]) / 1e9)   # the host's slice
    share_of_time = seconds / red.window_s                  # the trace's own
    return 100.0 * bytes_per_s / share_of_time \
        / (view.peaks[reader["peak"]] * 1e9)
