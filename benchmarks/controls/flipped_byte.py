"""Breaks ``reply_attachment`` (byte-exact): one bit of the middle byte of
every reply is flipped on the device before the handler answers."""
from . import answer_through, reply_as_one_array

GUARANTEE = "reply_attachment"


def _flip(cntl):
    z = reply_as_one_array(cntl)
    mid = z.shape[0] // 2
    z = z.at[mid].set(z[mid] ^ 1)
    cntl.response_attachment.clear()
    cntl.response_attachment.append_device_array(z)


def wrap_service(service):
    return answer_through(service, _flip)
