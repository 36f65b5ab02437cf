"""Breaks ``all_or_nothing`` as far as a server can: one shard of every
operation of the window (the second to arrive; the warm-up's are spared, a
run has to reach its window) is failed by its member.  A sound program fails
the whole operation, so the run is not correct by its failed operations; one
that handed back what did arrive would show in ``fanout_partial_results`` and
in a short reply."""
import threading

GUARANTEE = "all_or_nothing"
FAILED = 2001                   # an error code of the service's own


def wrap_service(service):
    seen, lock = {}, threading.Lock()

    for name, desc in service.methods().items():
        def wrapped(cntl, request, response, done, _fn=desc.fn):
            def done_after():
                key = request.message
                with lock:
                    nth = seen[key] = seen.get(key, 0) + 1
                    if nth >= 4:
                        del seen[key]
                if key.startswith("w") and nth == 2:
                    cntl.response_attachment.clear()
                    cntl.set_failed(FAILED, "dropped_shard control")
                done()
            return _fn(cntl, request, response, done_after)
        wrapped._rpc_method = (desc.request_cls, desc.response_cls)
        setattr(service, name, wrapped)
    return service
