"""Transform: every byte xor 0x5A; the reply message carries the sum of the
transformed bytes after the request message."""
from __future__ import annotations

import numpy as np


def expected(request: np.ndarray, message: str):
    want = request ^ np.uint8(0x5A)
    return want, f"{message}:{int(want.astype(np.uint64).sum())}"
