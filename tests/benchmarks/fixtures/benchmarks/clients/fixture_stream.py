"""fixture_stream: a client that is not a unary call.  ``open`` creates one
long-lived stream on the caller's channel (``stream_create``, the establishing
RPC, ``wait_connected``); an operation writes the block as chunks of device
memory under the stream's window, a fixed host header with the operation's key
in front of each, and returns when as many reply chunks have come back: their
device blocks as one attachment, the key the first of them carries as the
message.  It neither times nor judges itself: ``harness/driver.py`` does."""
import threading

from benchmarks.services.messages import Request, Response
from brpc_tpu.butil.iobuf import IOBuf


class Client:
    def __init__(self, ctx):
        rpc, opt = ctx.rpc, ctx.options
        self.chunk = opt["chunk_bytes"]
        self.header = opt["header_bytes"]
        self.timeout = opt["timeout_s"]
        self.replies = []               # of the operation in flight
        self.arrived = threading.Condition()
        client = self

        class Collect(rpc.StreamInputHandler):
            def on_received_messages(self, sid, messages):
                # copies (of references): the stream counts what was
                # consumed from the messages' lengths after this returns
                with client.arrived:
                    client.replies.extend(IOBuf(m) for m in messages)
                    client.arrived.notify_all()

        cntl = rpc.Controller()
        self.stream = rpc.stream_create(cntl, rpc.StreamOptions(
            handler=Collect(), max_buf_size=opt["max_buf_size"]))
        try:
            ctx.channel.call_method(
                ctx.method, cntl, Request(message=f"open{ctx.thread:02d}"),
                Response)
            if cntl.failed():
                raise RuntimeError(f"stream not accepted: {cntl.error_text}")
            if not self.stream.wait_connected(self.timeout):
                raise RuntimeError("stream never connected")
        except BaseException:
            self.stream.close()
            raise

    def call(self, key, block):
        head = key.encode().ljust(self.header)
        chunks = block.shape[0] // self.chunk
        with self.arrived:
            self.replies = []
        for i in range(chunks):
            frame = IOBuf(head)
            frame.append_device_array(
                block[i * self.chunk:(i + 1) * self.chunk])
            rc = self.stream.write(frame, timeout=self.timeout)
            if rc != 0:
                raise RuntimeError(f"stream write of chunk {i}: {rc}")
        with self.arrived:
            if not self.arrived.wait_for(
                    lambda: len(self.replies) >= chunks, self.timeout):
                raise TimeoutError(f"{len(self.replies)} of {chunks} reply "
                                   f"chunks after {self.timeout}s")
            replies, self.replies = self.replies, []
        attachment = IOBuf()
        message = None
        for reply in replies:
            head_of_it = reply.cut(self.header)
            if message is None:
                message = head_of_it.to_bytes().decode().rstrip()
            attachment.append(reply)
        return message, attachment

    def close(self):
        self.stream.close()


def open(ctx):
    return Client(ctx)
