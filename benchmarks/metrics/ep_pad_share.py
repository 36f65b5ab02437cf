"""The expert dispatch's padding as a share of what it had to move: the
bytes of padding records and of their reply rows over the window, over the
real bytes on the wire (the sub-calls' request bytes and the replies the
combines read, less that padding), in %.  ``None`` where the program keeps
no such counts, or moved nothing."""


def read(view, reader):
    counts = view.window.counters
    if reader["padded"] not in counts or reader["answered"] not in counts:
        return None
    padded = counts[reader["padded"]]
    real = counts[reader["sent"]] + counts[reader["answered"]] - padded
    return 100.0 * padded / real if real > 0 else None
