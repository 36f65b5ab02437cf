"""One file per kind of client operation, found by the ``client`` of a mix
entry (``unary`` where the entry names none).

A module gives ``open(ctx)``, called once per caller and mix entry after the
deployment's channels exist and before the warm-up (inside ``setup_s``).
``ctx`` is a ``harness.driver.ClientContext``: the ``rpc`` module, the
caller's ``channel``, the full ``method`` name, the caller's ``thread`` and
the mix entry's ``client_options``.  What ``open``
returns has ``call(key, block) -> (reply message, reply attachment)``, which
raises where the operation failed, and ``close()``, called before the
deployment is closed.  The reply attachment is an ``IOBuf``; the clock, the
``bench.call.<Method>`` annotation and the four per-operation checks are
``harness/driver.py``'s, around ``call``: a client neither times nor judges
itself.
"""
