"""stream: upstream streaming_echo's client.  ``open`` creates one long-lived
stream on the caller's channel (``stream_create``, the establishing RPC,
``wait_connected``): set-up, outside every operation's clock.  An operation
writes the block as chunks of device memory with the blocking
``Stream.write`` under the stream's window, a fixed host header with the
operation's key in front of each, and returns when as many reply chunks are
back: their device blocks, in the order they came, as one attachment, and as
the message the key they carry (the first header that differs from the first,
where one does).  The handler cuts the buffers it is handed and copies none.
"""
from __future__ import annotations

import json
import threading

from brpc_tpu.butil.iobuf import IOBuf

from ..services.messages import Request, Response


class Client:
    def __init__(self, ctx):
        rpc, opt = ctx.rpc, ctx.options
        self.chunk = opt["chunk_bytes"]
        self.header = opt["header_bytes"]
        self.timeout = opt["timeout_s"]
        self.arrived = threading.Condition()
        self._fresh()
        client = self

        class Collect(rpc.StreamInputHandler):
            def on_received_messages(self, sid, messages):
                with client.arrived:
                    for m in messages:
                        head = m.cut(client.header).to_bytes()
                        if client.message is None:
                            client.message = head
                        elif head != client.message and client.odd is None:
                            client.odd = head
                        client.attachment.append(m)
                        client.chunks += 1
                    client.arrived.notify_all()

        cntl = rpc.Controller()
        self.stream = rpc.stream_create(cntl, rpc.StreamOptions(
            handler=Collect(), max_buf_size=opt["max_buf_size"]))
        try:
            ctx.channel.call_method(ctx.method, cntl, Request(
                message=json.dumps({"caller": ctx.thread,
                                    "header_bytes": self.header,
                                    "max_buf_size": opt["max_buf_size"]})),
                Response)
            if cntl.failed():
                raise RuntimeError(f"stream not accepted: {cntl.error_text}")
            if not self.stream.wait_connected(self.timeout):
                raise RuntimeError("stream never connected")
        except BaseException:
            self.stream.close()
            raise

    def _fresh(self):
        """What an operation collects; under ``arrived``."""
        self.message = None
        self.odd = None
        self.attachment = IOBuf()
        self.chunks = 0

    def call(self, key, block):
        head = key.encode().ljust(self.header)
        chunks = block.shape[0] // self.chunk
        with self.arrived:
            if self.chunks:
                raise RuntimeError(f"{self.chunks} reply chunks that no "
                                   f"operation was waiting for")
            self._fresh()
        for i in range(chunks):
            out = IOBuf(head)
            out.append_device_array(
                block[i * self.chunk:(i + 1) * self.chunk])
            rc = self.stream.write(out, timeout=self.timeout)
            if rc != 0:
                raise RuntimeError(f"stream write of chunk {i}: {rc}")
        with self.arrived:
            if not self.arrived.wait_for(
                    lambda: self.chunks >= chunks, self.timeout):
                raise TimeoutError(f"{self.chunks} of {chunks} reply chunks "
                                   f"after {self.timeout}s")
            message = self.message if self.odd is None else self.odd
            attachment = self.attachment
            self._fresh()
        return message.decode().rstrip(), attachment

    def close(self):
        self.stream.close()


def open(ctx):
    return Client(ctx)
