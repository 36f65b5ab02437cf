"""StartStream: upstream streaming_echo's server.  The establishing RPC
accepts a stream (its request's message says the window the caller keeps, and
the server keeps the same the other way); every chunk that arrives on the
stream is computed on the chip (one jitted xor) and written back on the same
stream behind the header it came with.  The handler cuts the buffers it is
handed and copies none.

``mutate`` is where a control alters what is written: it is given the chunk's
index in its operation and the frame about to go, and returns the frames to
write now.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp

from brpc_tpu import rpc
from brpc_tpu.butil.iobuf import IOBuf

from .messages import Request, Response

SERVER_OPTIONS = {}
WRITE_TIMEOUT_S = 30.0


@jax.jit
def transform(x):
    return x ^ jnp.uint8(0x5A)


def frame(head: IOBuf, payload) -> IOBuf:
    """A reply chunk: the header it came with, then the device array (or,
    from a control, host bytes)."""
    out = IOBuf(head)
    if hasattr(payload, "devices"):
        out.append_device_array(payload)
    else:
        out.append(payload)
    return out


def whole(ref):
    """The device array a block ref covers, with no dispatch where the ref is
    all of its block (every chunk the client cuts is a block of its own)."""
    data = ref.block.data
    if ref.offset == 0 and ref.length == data.size:
        return data.reshape(-1) if data.ndim != 1 else data
    return data.reshape(-1)[ref.offset:ref.offset + ref.length]


def build(spans):
    class XorBack(rpc.StreamInputHandler):
        def __init__(self, service, header: int):
            self.service = service
            self.header = header
            self.stream = None
            self.key = None             # of the operation in hand
            self.k = 0                  # chunks of it seen so far

        def on_received_messages(self, sid, messages):
            for m in messages:
                head = m.cut(self.header)
                key = head.to_bytes().decode().rstrip()
                if key != self.key:
                    self.key, self.k = key, 0
                    if spans is not None:
                        spans.stamp("handler_entry", key)
                refs = m.device_refs()
                with jax.profiler.TraceAnnotation(
                        "bench.handler.StartStream"):
                    if len(refs) == 1 and refs[0].length == len(m):
                        out = frame(head, transform(whole(refs[0])))
                    else:       # not one device block: the header alone
                        out = IOBuf(head)   # goes back, the reply is short
                    mutate = self.service.mutate
                    frames = [out] if mutate is None \
                        else mutate(self.k, head, out)
                self.k += 1
                if spans is not None:
                    spans.stamp("done", key)    # the last chunk's stays
                for f in frames:
                    self.stream.write(f, timeout=WRITE_TIMEOUT_S)

    class BenchStartStream(rpc.Service):
        def __init__(self):
            self.mutate = None

        @rpc.method(Request, Response)
        def StartStream(self, cntl, request, response, done):
            asked = json.loads(request.message)
            handler = XorBack(self, asked["header_bytes"])
            handler.stream = rpc.stream_accept(cntl, rpc.StreamOptions(
                handler=handler, max_buf_size=asked["max_buf_size"]))
            response.message = request.message
            done()

    return BenchStartStream()
