"""Echo: upstream rdma_performance's server with ``echo_attachment`` on.
The handler never blocks, so it runs inline on the delivering thread (the
fused native dispatch where the frame fits the native window)."""
from __future__ import annotations

import jax

from brpc_tpu import rpc

from .messages import Request, Response

SERVER_OPTIONS = {"usercode_inline": True}


def build(spans):
    class BenchEcho(rpc.Service):
        @rpc.method(Request, Response)
        def Echo(self, cntl, request, response, done):
            if spans is not None:
                spans.stamp("handler_entry", request.message)
            with jax.profiler.TraceAnnotation("bench.handler.Echo"):
                response.message = request.message
                cntl.response_attachment.append(cntl.request_attachment)
            if spans is not None:
                spans.stamp("done", request.message)
            done()

    return BenchEcho()
