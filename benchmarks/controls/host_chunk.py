"""Breaks ``reply_attachment`` (resident on the caller's chip): one reply
chunk of every operation is brought to host memory and written back from
there."""
import numpy as np

from ..services.StartStream import frame

GUARANTEE = "reply_attachment"
CHUNK = 1                       # the operation's second chunk


def _to_host(k, head, out):
    if k != CHUNK:
        return [out]
    return [frame(head, np.asarray(
        out.device_refs()[0].block.data).tobytes())]


def wrap_service(service):
    service.mutate = _to_host
    return service
