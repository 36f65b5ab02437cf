"""The expert combine as a share of the HBM peak, on the callers' chip:
each real row the ranks answered read once (``rows x hidden x 2`` bytes,
rows as ``ep_pack_roofline`` counts them) and the ONE bfloat16 result
written (as long as the block), over the seconds of the program named in
``program``, over ``hbm_gbs``.  Padding rows are read too and earn nothing:
the share can only understate.  ``None`` where there is nothing to read."""
from benchmarks.metrics.ep_pack_roofline import share


def read(view, reader):
    hidden = reader["hidden"]
    return share(view, reader,
                 lambda block, rows: rows * 2 * hidden + block)
