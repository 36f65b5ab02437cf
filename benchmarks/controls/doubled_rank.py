"""Breaks ``exactly_once`` by one too many: rank 1 of every operation
answers twice its rows (computed on the chip from its own answer: each
bfloat16 doubled, which is exact), as a combine does that takes one rank's
reply in twice.  Every reply arrives whole, every message is right and
nothing meets the host."""
import jax
import jax.numpy as jnp

from . import reply_as_one_array
from .worker_reply import answer_of_worker

GUARANTEE = "exactly_once"
RANK = 1


@jax.jit
def _twice(flat):
    rows = jax.lax.bitcast_convert_type(flat.view(jnp.uint16), jnp.bfloat16)
    return jax.lax.bitcast_convert_type(rows + rows, jnp.uint16) \
        .view(jnp.uint8)


def _double(cntl):
    z = _twice(reply_as_one_array(cntl))
    cntl.response_attachment.clear()
    cntl.response_attachment.append_device_array(z)


def wrap_service(service):
    return answer_of_worker(service, RANK, _double)
