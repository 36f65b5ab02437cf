"""Breaks ``all_or_nothing`` as far as a server can: worker 0 of every
operation of the window (the warm-up's are spared, a run has to reach its
window) is failed by the server.  A sound program fails the whole operation,
so the run is not correct by its failed operations; one that summed what did
arrive would show in ``fanout_partial_results`` and in wrong bytes."""
from .worker_reply import answer_of_worker

GUARANTEE = "all_or_nothing"
WORKER = 0
FAILED = 2001                   # an error code of the service's own


def _fail(cntl):
    cntl.response_attachment.clear()
    cntl.set_failed(FAILED, "failed_worker control")


def wrap_service(service):
    return answer_of_worker(service, WORKER, _fail, window_only=True)
