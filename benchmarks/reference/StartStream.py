"""StartStream: every chunk comes back xor 0x5A, chunk k of the reply from
chunk k of the request, so the reply, in the order written, is the whole
block xored byte for byte; the reply's message is the operation's key."""
from __future__ import annotations

import numpy as np


def expected(request: np.ndarray, message: str):
    return request ^ np.uint8(0x5A), message
