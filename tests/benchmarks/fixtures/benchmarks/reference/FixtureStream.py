"""FixtureStream: every byte xor 0x5A, chunk by chunk in the order written,
so the reply is the whole block xored; the reply's message is the key."""
import numpy as np


def expected(request: np.ndarray, message: str):
    return request ^ np.uint8(0x5A), message
