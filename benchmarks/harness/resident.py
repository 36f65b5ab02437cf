"""Payload sets made on the device from --seed, one small program per block
size; byte for byte what ``reference/payload.py`` computes on the host."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.payload import M32, seed_mix


# blocks made by one program call: many small blocks to a call, so that a set
# of thousands costs tens of dispatches; a block of a MiB or more alone (on
# the chip a program with 64 outputs of 1 MiB took 100 s to compile)
CALL_BYTES = 1 << 20
CALL_BLOCKS = 64


@functools.partial(jax.jit, static_argnames=("nbytes", "k"))
def _blocks(mix, base, *, nbytes: int, k: int):
    """``k`` consecutive blocks of ``nbytes`` bytes, the first starting at
    word ``base`` of its set.  Elementwise over the bytes: byte ``j`` is byte
    ``j % 4`` (low first) of the hash of word ``j // 4``."""
    j = jnp.arange(nbytes, dtype=jnp.uint32)
    shift = (j & 3) * 8
    out = []
    for b in range(k):
        x = (j >> 2) + (base + jnp.uint32(b * (nbytes // 4)))
        x = x * jnp.uint32(0x9E3779B1) + mix
        x = x ^ (x >> 16)
        x = x * jnp.uint32(0x85EBCA6B)
        x = x ^ (x >> 13)
        x = x * jnp.uint32(0xC2B2AE35)
        x = x ^ (x >> 16)
        out.append((x >> shift).astype(jnp.uint8))
    return out


def make_set(seed: int, set_id: int, count: int, nbytes: int, device):
    """``count`` separate device arrays of ``nbytes`` uint8 on ``device``."""
    if nbytes % 4:
        raise ValueError(f"block size {nbytes} is not a multiple of 4")
    words = nbytes // 4
    mix = jax.device_put(np.uint32(seed_mix(seed, set_id)), device)
    per_call = max(1, min(CALL_BLOCKS, CALL_BYTES // nbytes))
    blocks = []
    for first in range(0, count, per_call):
        k = min(per_call, count - first)
        blocks.extend(_blocks(mix, np.uint32((first * words) & M32),
                              nbytes=nbytes, k=k))
    jax.block_until_ready(blocks)
    return blocks
