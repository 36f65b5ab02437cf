"""Breaks ``reply_attachment`` (byte-exact) where a stream's chunks are
produced: one bit of the middle byte of EVERY chunk the handler writes back is
flipped on the device.  (``controls/flipped_byte.py`` alters the unary reply's
attachment in ``done``, which a stream's chunks never pass.)"""
GUARANTEE = "reply_attachment"


def _flip(y):
    mid = y.shape[0] // 2
    return y.at[mid].set(y[mid] ^ 1)


def wrap_service(service):
    service.mutate = _flip
    return service
