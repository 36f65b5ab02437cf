"""Breaks ``order`` and ``reply_attachment``: every handler answers with the
attachment of the call before it (a buffer reused too early)."""
from . import answer_through

GUARANTEE = "order"


def wrap_service(service):
    last = {}

    def _swap(cntl):
        from brpc_tpu.butil.iobuf import IOBuf
        mine = IOBuf(cntl.response_attachment)
        if "reply" in last:
            cntl.response_attachment.clear()
            cntl.response_attachment.append(last["reply"])
        last["reply"] = mine

    return answer_through(service, _swap)
