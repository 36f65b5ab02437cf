"""PushPull: with ``w`` the range's little-endian uint32 words, worker ``i``
of four contributes ``float32((w >> 8i) & 0xFF) * 4**i`` and the operation's
result is the four contributions added in index order in float32: the sum's
bytes, and the operation's key as the merged message."""
from __future__ import annotations

import numpy as np

WORKERS = 4


def contributions(request: np.ndarray):
    """Each worker's float32 array, in index order."""
    words = request.view("<u4")
    return [((words >> np.uint32(8 * i)) & np.uint32(0xFF)).astype(np.float32)
            * np.float32(4 ** i) for i in range(WORKERS)]


def expected(request: np.ndarray, message: str):
    parts = contributions(request)
    total = parts[0]
    for g in parts[1:]:
        total = total + g               # float32 + float32, in index order
    return total.astype("<f4", copy=False).view(np.uint8), message
