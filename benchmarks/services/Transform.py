"""Transform: a handler that USES the attachment on the chip: one jitted
program over the payload (xor and sum), the reply parked on the device's
completion.  Default server options: the handler waits on the device."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from brpc_tpu import rpc
from brpc_tpu.bthread.device_waiter import device_on_ready

from .messages import Request, Response

SERVER_OPTIONS = {}


@jax.jit
def transform(x):
    y = x ^ jnp.uint8(0x5A)
    return y, jnp.sum(y.astype(jnp.uint32))


def build(spans):
    class BenchTransform(rpc.Service):
        @rpc.method(Request, Response)
        def Transform(self, cntl, request, response, done):
            key = request.message
            if spans is not None:
                spans.stamp("handler_entry", key)
            att = cntl.request_attachment
            refs = att.device_refs()
            if len(refs) != 1 or refs[0].length != len(att):
                cntl.set_failed(22, "expected one whole device block")
                done()
                return
            with jax.profiler.TraceAnnotation("bench.handler.Transform"):
                y, s = transform(refs[0].block.data)
                cntl.response_attachment.append_device_array(y)

            def reply():
                if spans is not None:
                    spans.stamp("callback_entry", key)
                response.message = f"{key}:{int(s)}"
                if spans is not None:
                    spans.stamp("done", key)
                done()

            if spans is not None:
                spans.stamp("registered", key)
            device_on_ready([y, s], reply)

    return BenchTransform()
