"""A metric with a reader of its own: the window's correct calls of one mix
entry.  Shows that a metric is added as files: this .py, its .json and one
manifest entry."""


def read(view, reader):
    n = sum(1 for c in view.good_calls() if c[2] == reader["mix"])
    return float(n) if n else None
