"""Not a control: what the controls of a fan-out whose members are told who
they are (``<key>#<i>`` as the request's message) share.  One worker's answer
is altered where it is produced, every other worker's is left alone."""
from __future__ import annotations

from typing import Callable


def answer_of_worker(service, worker: int, mutate: Callable,
                     window_only: bool = False) -> object:
    """Every method of ``service`` passes its controller to ``mutate`` just
    before it answers, where the request's message ends in ``#<worker>``;
    with ``window_only`` the warm-up's operations (their keys do not start
    with ``w``) are spared."""
    for name, desc in service.methods().items():
        def wrapped(cntl, request, response, done, _fn=desc.fn):
            def done_after():
                said = request.message
                if said.endswith(f"#{worker}") and \
                        (said.startswith("w") or not window_only):
                    mutate(cntl)
                done()
            return _fn(cntl, request, response, done_after)
        wrapped._rpc_method = (desc.request_cls, desc.response_cls)
        setattr(service, name, wrapped)
    return service
