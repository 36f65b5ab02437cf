"""unary: one ``rpc.Channel.call_method`` an operation, the device block as
the request's attachment and the key as its message: upstream
rdma_performance's client.  The client of every mix entry that names none."""
from __future__ import annotations

from ..services.messages import Request, Response


class Client:
    def __init__(self, ctx):
        self.Controller = ctx.rpc.Controller
        self.call_method = ctx.channel.call_method
        self.method = ctx.method

    def call(self, key, block):
        cntl = self.Controller()
        cntl.request_attachment.append_device_array(block)
        resp = self.call_method(self.method, cntl, Request(message=key),
                                Response)
        if cntl.failed():
            raise RuntimeError(cntl.error_text)
        return resp.message, cntl.response_attachment

    def close(self):
        pass                            # the channel is the deployment's


def open(ctx):
    return Client(ctx)
