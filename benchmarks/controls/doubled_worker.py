"""Breaks ``exactly_once`` by one too many: worker 1 of every operation
answers twice its contribution (computed on the chip from its own answer), as
a sum does that takes one reply in twice.  Every reply arrives whole, every
message is right and nothing meets the host; each word of the sum is 4 x its
byte 1 over."""
import jax
import jax.numpy as jnp

from . import reply_as_one_array
from .worker_reply import answer_of_worker

GUARANTEE = "exactly_once"
WORKER = 1


@jax.jit
def _twice(flat):
    g = jax.lax.bitcast_convert_type(flat.view(jnp.uint32), jnp.float32)
    return jax.lax.bitcast_convert_type(g + g, jnp.uint32).view(jnp.uint8)


def _double(cntl):
    z = _twice(reply_as_one_array(cntl))
    cntl.response_attachment.clear()
    cntl.response_attachment.append_device_array(z)


def wrap_service(service):
    return answer_of_worker(service, WORKER, _double)
