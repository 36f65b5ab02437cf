"""Breaks ``exactly_once`` by misrouting: the server holds every rank's
answer of an operation until all eight are made, then answers each rank with
the rows of the next (rank r gets rank r + 1's, rank 7 rank 0's), as a
dispatch does that sends each rank's rows to its neighbour.  Every reply is
whole and on the chip, every message is right and nothing meets the host;
each token's rows are added where other tokens' belong."""
import threading

from brpc_tpu.butil.iobuf import IOBuf

GUARANTEE = "exactly_once"
RANKS = 8


def wrap_service(service):
    held, lock = {}, threading.Lock()

    for name, desc in service.methods().items():
        def wrapped(cntl, request, response, done, _fn=desc.fn):
            def done_after():
                key, _, rank = request.message.rpartition("#")
                with lock:
                    mine = held.setdefault(key, {})
                    mine[int(rank)] = (cntl, done)
                    if len(mine) < RANKS:
                        return
                    del held[key]
                rows = {r: IOBuf(c.response_attachment)
                        for r, (c, _) in mine.items()}
                for r, (c, _) in mine.items():
                    c.response_attachment.clear()
                    c.response_attachment.append(rows[(r + 1) % RANKS])
                for _, (_, d) in sorted(mine.items()):
                    d()
            return _fn(cntl, request, response, done_after)
        wrapped._rpc_method = (desc.request_cls, desc.response_cls)
        setattr(service, name, wrapped)
    return service
