"""Breaks ``order``: of every operation's shards the server holds the answer
to the first that arrives until the second has its own, and sends each the
other's attachment.  Every byte that goes back is right and every message is
the operation's key; two shards are in each other's place."""
import threading

from brpc_tpu.butil.iobuf import IOBuf

GUARANTEE = "order"


def wrap_service(service):
    held, lock = {}, threading.Lock()

    for name, desc in service.methods().items():
        def wrapped(cntl, request, response, done, _fn=desc.fn):
            def done_after():
                with lock:
                    first = held.pop(request.message, None)
                    if first is None:
                        held[request.message] = (cntl, done)
                        return
                other, other_done = first
                mine = IOBuf(cntl.response_attachment)
                theirs = IOBuf(other.response_attachment)
                for c, att in ((cntl, theirs), (other, mine)):
                    c.response_attachment.clear()
                    c.response_attachment.append(att)
                other_done()
                done()
            return _fn(cntl, request, response, done_after)
        wrapped._rpc_method = (desc.request_cls, desc.response_cls)
        setattr(service, name, wrapped)
    return service
