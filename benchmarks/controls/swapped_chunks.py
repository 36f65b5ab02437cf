"""Breaks ``reply_attachment`` (in the order written) and ``order``: the
server holds the third reply chunk of every operation back and writes it
after the fourth.  Every byte that goes back is right; two chunks are in
each other's place."""
GUARANTEE = "order"
HELD = 2                        # the operation's third chunk


def wrap_service(service):
    held = {}

    def _swap(k, head, out):
        if k == HELD:
            held[head.to_bytes()] = out
            return []
        if k == HELD + 1:
            return [out, held.pop(head.to_bytes())]
        return [out]

    service.mutate = _swap
    return service
