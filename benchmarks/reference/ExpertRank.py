"""ExpertRank: one operation of an expert-parallel layer, worked out with
numpy and ml_dtypes from the block alone, as independent of the program as
it can be.

The block is ``tokens x hidden`` 16-bit words (little-endian).  Word ``u``
gives the token's bfloat16 value: its sign bit and 7 mantissa bits as they
are, its exponent 124 + bits 7..8 of ``u`` (|x| in [2**-3, 2)).  A token's
routing comes from its first two words as the key ``k``: expert ``e``'s
score is ``mix(k ^ (e * 0x9E3779B9)) >> 16`` (ties broken by ``e``), the
top-8 of 256 by score are its experts, and ``(mix(.) >> 8 & 0xFF) + 1``
over 256 their weights.  Then, as DeepEP's normal mode with power-of-two
scales: each 128 channels of a token share the scale ``2**ceil(log2(amax /
448))``, the value over its scale is rounded to e4m3fn; rank ``r`` holds
experts ``32r .. 32r + 31`` and answers, for each token that has one of
them, the dequantized row times the sum of those experts' weights (float32,
rounded once), as bfloat16; the result is the ranks' rows added in rank
order in float32 and rounded once to bfloat16.  Every value is normal, so
no denormal is flushed on either side and the comparison is byte for byte.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

HIDDEN = 7168
EXPERTS = 256
TOP_K = 8
RANKS = 8
GROUP = 128


def _mix(h):
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> np.uint32(13))
    h = h * np.uint32(0xC2B2AE35)
    return h ^ (h >> np.uint32(16))


def gate(block: np.ndarray, hidden: int = HIDDEN, experts: int = EXPERTS,
         top_k: int = TOP_K):
    """(x float32 [tokens, hidden] of bfloat16 values, ids [tokens, top_k],
    weights float32 [tokens, top_k])."""
    u = block.view("<u2").reshape(-1, hidden).astype(np.uint32)
    bits = (u & 0x807F) | ((0x7C + ((u >> 7) & 3)) << 7)
    x = bits.astype(np.uint16).view(ml_dtypes.bfloat16).astype(np.float32)
    key = u[:, 0] | (u[:, 1] << np.uint32(16))
    e = np.arange(experts, dtype=np.uint32)
    h = _mix(key[:, None] ^ (e[None, :] * np.uint32(0x9E3779B9)))
    score = (h >> np.uint32(16)).astype(np.int64) * experts + e
    ids = np.argsort(-score, axis=1, kind="stable")[:, :top_k]
    hk = np.take_along_axis(h, ids, axis=1)
    weights = (((hk >> np.uint32(8)) & 0xFF) + 1).astype(np.float32) \
        / np.float32(256)
    return x, ids, weights


def dequantized(x: np.ndarray) -> np.ndarray:
    """Each token's row cast to e4m3fn under its power-of-two scales and
    read back, float32."""
    t, h = x.shape
    xg = x.reshape(t, h // GROUP, GROUP)
    amax = np.maximum(np.abs(xg).max(axis=2), np.float32(1e-4))
    scale = np.exp2(np.ceil(np.log2(amax.astype(np.float64) / 448.0)))
    y = (xg / scale[:, :, None]).astype(np.float32)
    fp8 = y.astype(ml_dtypes.float8_e4m3fn).astype(np.float32)
    return (fp8 * scale[:, :, None].astype(np.float32)).reshape(t, h)


def contributions(block: np.ndarray, hidden: int = HIDDEN,
                  experts: int = EXPERTS, top_k: int = TOP_K,
                  ranks: int = RANKS):
    """Each rank's float32 [tokens, hidden] part in token order (zero where
    the token has none of its experts), in rank order."""
    return contributions_of(*gate(block, hidden, experts, top_k),
                            experts=experts, ranks=ranks)


def contributions_of(x, ids, weights, experts: int = EXPERTS,
                     ranks: int = RANKS):
    """``contributions`` of a batch and its routing as they are given."""
    top_k = ids.shape[1]
    deq = dequantized(x)
    per = experts // ranks
    out = []
    for r in range(ranks):
        mine = ids // per == r
        wsum = np.zeros(ids.shape[0], np.float32)
        for k in range(top_k):
            wsum = wsum + np.where(mine[:, k], weights[:, k], np.float32(0))
        rows = (deq * wsum[:, None]).astype(ml_dtypes.bfloat16)
        out.append(np.where(mine.any(axis=1)[:, None],
                            rows.astype(np.float32), np.float32(0)))
    return out


def combined(parts) -> np.ndarray:
    acc = np.zeros_like(parts[0])
    for p in parts:                     # float32, in rank order
        acc = acc + p
    return acc.astype(ml_dtypes.bfloat16)


def expected(request: np.ndarray, message: str):
    result = combined(contributions(request))
    return result.view(np.uint8).reshape(-1), message
