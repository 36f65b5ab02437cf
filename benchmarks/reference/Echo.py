"""Echo: the reply attachment is the request attachment; the reply message
is the request message."""
from __future__ import annotations

import numpy as np


def expected(request: np.ndarray, message: str):
    return request, message
