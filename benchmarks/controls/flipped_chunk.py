"""Breaks ``reply_attachment`` (byte-exact) where a stream's chunks are
produced: one bit of the middle byte of ONE chunk of every operation is
flipped on the device before the handler writes it back.
(``flipped_byte.py`` alters a unary reply in ``done``, which a stream's
chunks never pass.)"""
from ..services.StartStream import frame

GUARANTEE = "reply_attachment"
CHUNK = 1                       # the operation's second chunk


def _flip(k, head, out):
    if k != CHUNK:
        return [out]
    y = out.device_refs()[0].block.data
    mid = y.shape[0] // 2
    return [frame(head, y.at[mid].set(y[mid] ^ 1))]


def wrap_service(service):
    service.mutate = _flip
    return service
