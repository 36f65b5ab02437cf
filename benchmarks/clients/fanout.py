"""fanout: upstream parallel_echo's client.  ``open`` builds one
``ParallelChannel`` whose sub-channels are all the caller's own channel
(upstream's ``-same_channel``), each with a ``ShardingCallMapper`` and one
index-ordered CONCAT merger.  An operation is ONE ``call_method``: the block
is cut into as many equal rows as there are sub-channels — refs into the
block, no program — and handed over as ``cntl.fanout_operand``; it returns
the merged message and the gathered reply, ``cntl.fanout_attachment``: the
sub-replies' device refs in sub-channel order.  Nothing is copied, to or from
the host or on the chip."""
from __future__ import annotations

from brpc_tpu import channels
from brpc_tpu.butil.iobuf import IOBuf

from ..services.messages import Request, Response


class KeyedMerger(channels.CollectiveMerger):
    """The merged message is the key only if EVERY sub-reply says it."""

    def merge_sub(self, parent_cntl, index, sub_cntl, response):
        said = sub_cntl.response.message
        if not response.message:
            response.message = said
        elif said != response.message and \
                not response.message.startswith("mixed: "):
            response.message = f"mixed: {response.message} / {said}"
        return super().merge_sub(parent_cntl, index, sub_cntl, response)


class Client:
    def __init__(self, ctx):
        opt = ctx.options
        self.Controller = ctx.rpc.Controller
        self.method = ctx.method
        self.width = opt["sub_channels"]
        self.fanout = channels.ParallelChannel(fail_limit=opt["fail_limit"])
        mapper = channels.ShardingCallMapper()
        merger = KeyedMerger(merge=channels.MERGE_CONCAT, dtype="uint8")
        for _ in range(self.width):
            self.fanout.add_channel(ctx.channel, mapper=mapper,
                                    merger=merger)

    def call(self, key, block):
        whole = IOBuf()
        whole.append_device_array(block)
        shard = len(whole) // self.width
        cntl = self.Controller()
        cntl.fanout_operand = [whole.cut(shard) for _ in range(self.width)]
        resp = self.fanout.call_method(self.method, cntl,
                                       Request(message=key), Response())
        if cntl.failed():
            raise RuntimeError(cntl.error_text)
        return resp.message, cntl.fanout_attachment

    def close(self):
        pass                            # the channel is the deployment's


def open(ctx):
    return Client(ctx)
