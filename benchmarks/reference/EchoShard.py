"""EchoShard: every member answers its shard xor 0x5A and the merge puts the
answers in sub-channel order, so the gathered reply is the whole block xored
byte for byte; the merged message is the operation's key."""
from __future__ import annotations

import numpy as np


def expected(request: np.ndarray, message: str):
    return request ^ np.uint8(0x5A), message
