"""Breaks ``exactly_once`` by one too few: worker 2 of every operation
answers a contribution of zeros (whole, on the chip, under its own message),
so its part is left out of the sum.  Every reply arrives, every message is
right and nothing meets the host; each word of the sum is 16 x its byte 2
short."""
import jax.numpy as jnp

from .worker_reply import answer_of_worker

GUARANTEE = "exactly_once"
WORKER = 2


def _zeros(cntl):
    att = cntl.response_attachment
    n = len(att)
    att.clear()
    att.append_device_array(jnp.zeros(n, jnp.uint8))


def wrap_service(service):
    return answer_of_worker(service, WORKER, _zeros)
