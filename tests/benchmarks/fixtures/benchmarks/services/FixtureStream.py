"""FixtureStream: the establishing RPC accepts a stream; every chunk that
arrives on it is xored on the device and written back on the stream behind the
header it came with.  ``mutate`` is where a control alters a chunk as it is
produced; ``write_failures`` is what ``counters/fixture_stream.py`` reads."""
import jax
import jax.numpy as jnp

from benchmarks.services.messages import Request, Response
from brpc_tpu import rpc
from brpc_tpu.butil.iobuf import IOBuf

SERVER_OPTIONS = {}
HEADER = 32                     # the operation's key, padded, on every chunk
MAX_BUF = 2 * (65536 + HEADER)  # the reply side's window: two chunks


@jax.jit
def transform(x):
    return x ^ jnp.uint8(0x5A)


def build(spans):
    class XorBack(rpc.StreamInputHandler):
        def __init__(self, service):
            self.service = service
            self.stream = None

        def on_received_messages(self, sid, messages):
            for m in messages:
                m = IOBuf(m)    # a copy of the references: the stream counts
                #                 what was consumed from the lengths it gave
                head = m.cut(HEADER)
                key = head.to_bytes().decode().rstrip()
                if spans is not None and \
                        key not in spans.at.get("handler_entry", {}):
                    spans.stamp("handler_entry", key)
                refs = m.device_refs()
                if len(refs) != 1 or refs[0].length != len(m):
                    self.service.write_failures += 1
                    continue
                r = refs[0]
                x = r.block.data.reshape(-1)[r.offset:r.offset + r.length]
                with jax.profiler.TraceAnnotation(
                        "bench.handler.FixtureStream"):
                    y = transform(x)
                    if self.service.mutate is not None:
                        y = self.service.mutate(y)
                    out = IOBuf(head)
                    out.append_device_array(y)
                if spans is not None:
                    spans.stamp("done", key)    # the last chunk's stays
                if self.stream.write(out, timeout=30.0) != 0:
                    self.service.write_failures += 1

    class BenchFixtureStream(rpc.Service):
        def __init__(self):
            self.mutate = None
            self.write_failures = 0

        @rpc.method(Request, Response)
        def FixtureStream(self, cntl, request, response, done):
            handler = XorBack(self)
            handler.stream = rpc.stream_accept(cntl, rpc.StreamOptions(
                handler=handler, max_buf_size=MAX_BUF))
            response.message = request.message
            done()

    return BenchFixtureStream()
