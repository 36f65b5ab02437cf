"""Controls: the cell with ONE stated guarantee broken underneath.  A run
with ``--control <name>`` has to come out not correct; the benchmark's own
runs never use one.  A control module gives ``GUARANTEE`` (the key of the
configuration's ``guarantees`` it breaks) and ``wrap_service(service)`` and/or
``channel_options(options)``.
"""
from __future__ import annotations

from typing import Callable


def answer_through(service, mutate: Callable) -> object:
    """Every method of ``service`` passes its controller to ``mutate`` just
    before it answers: the answer is altered where it is produced."""
    for name, desc in service.methods().items():
        def wrapped(cntl, request, response, done, _fn=desc.fn):
            def done_after():
                mutate(cntl)
                done()
            return _fn(cntl, request, response, done_after)
        wrapped._rpc_method = (desc.request_cls, desc.response_cls)
        setattr(service, name, wrapped)
    return service


def reply_as_one_array(cntl):
    """The reply attachment's device bytes as one device array."""
    import jax.numpy as jnp
    parts = [r.block.data.reshape(-1)[r.offset:r.offset + r.length]
             for r in cntl.response_attachment.device_refs()]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)
