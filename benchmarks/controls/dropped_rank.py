"""Breaks ``exactly_once`` by one too few: rank 3 of every operation
answers rows of zeros (whole, on the chip, under its own message), so its
part of every token it got is left out of the combine.  Every reply arrives,
every message is right and nothing meets the host."""
import jax.numpy as jnp

from .worker_reply import answer_of_worker

GUARANTEE = "exactly_once"
RANK = 3


def _zeros(cntl):
    att = cntl.response_attachment
    n = len(att)
    att.clear()
    att.append_device_array(jnp.zeros(n, jnp.uint8))


def wrap_service(service):
    return answer_of_worker(service, RANK, _zeros)
