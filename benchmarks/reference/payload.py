"""What every block of a payload set holds, worked out on the host.

A set is ``count`` blocks of ``nbytes`` bytes; byte ``j`` of block ``i`` is a
pure function of (seed, set id, i, j), so the reference regenerates any block
it has to judge without asking the device (or the program) for it.
"""
from __future__ import annotations

import numpy as np

M32 = 0xFFFFFFFF


def seed_mix(seed: int, set_id: int) -> int:
    """The 32-bit word that ties a block's bytes to --seed and to its set."""
    return (seed * 0x27D4EB2F + (seed >> 32) * 0x9E3779B1
            + (set_id + 1) * 0x165667B1) & M32


def block(seed: int, set_id: int, index: int, nbytes: int) -> np.ndarray:
    """Block ``index`` of a set as uint8; ``nbytes`` is a multiple of 4."""
    words = nbytes // 4
    x = np.arange(words, dtype=np.uint32)
    x += np.uint32((index * words) & M32)
    x *= np.uint32(0x9E3779B1)
    x += np.uint32(seed_mix(seed, set_id))
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x85EBCA6B)
    x ^= x >> np.uint32(13)
    x *= np.uint32(0xC2B2AE35)
    x ^= x >> np.uint32(16)
    # byte k of each word, low first: the word's little-endian bytes
    return x.astype("<u4", copy=False).view(np.uint8)
