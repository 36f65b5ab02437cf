"""pushpull: the aggregating side of a parameter server's push-pull over
upstream parallel_echo's client.  ``open`` builds one ``ParallelChannel``
whose sub-channels are all the caller's own channel, each with upstream's
default mapping (every sub-channel gets the whole request: a
``ReplicateFanoutMapper``, which hands the range over by reference) and one
``CollectiveMerger(MERGE_SUM, float32)``.  An operation is ONE
``call_method``: the range rides as ``cntl.fanout_operand`` (flat uint8
device memory: refs, no program, no copy); worker ``i`` is told who it is by
the request message ``<key>#<i>`` (upstream's ``CallMapper::Map`` may rewrite
a request); the operation returns the merged message and
``cntl.fanout_result`` — ONE float32 array on the caller's chip, summed by one
device program — as it is, behind the few methods the harness reads a reply
by.  Nothing is copied or converted, to or from the host or on the chip."""
from __future__ import annotations

from brpc_tpu import channels
from brpc_tpu.butil.iobuf import DEVICE, Block, BlockRef

from ..services.messages import Request, Response
from .fanout import KeyedMerger


class WorkerMapper(channels.ReplicateFanoutMapper):
    """Sub-call ``i`` carries the whole operand and the message
    ``<key>#<i>``."""

    def map_fanout(self, index, method_full_name, request, parent_cntl):
        sub = super().map_fanout(index, method_full_name, request,
                                 parent_cntl)
        sub.request = Request(message=f"{request.message}#{index}")
        return sub


class KeyedSumMerger(KeyedMerger):
    """``KeyedMerger``'s rule — the merged message is the key only if EVERY
    reply says it — over replies that each say it under their own index."""

    def merge_sub(self, parent_cntl, index, sub_cntl, response):
        reply = sub_cntl.response
        said, _, worker = reply.message.rpartition("#")
        reply.message = said if worker == str(index) else \
            f"worker {index} answered {reply.message!r}"
        return super().merge_sub(parent_cntl, index, sub_cntl, response)


class Result:
    """The operation's result as the harness reads a reply attachment: one
    block, the array as it is (any dtype), all of it device memory if a
    device holds it.  An ``IOBuf`` admits flat uint8 only, and a user reads
    the array, not its bytes."""

    def __init__(self, array):
        self.array = array
        self.nbytes = 0 if array is None else array.nbytes
        self.on_device = hasattr(array, "devices")

    def __len__(self):
        return self.nbytes

    def device_bytes(self):
        return self.nbytes if self.on_device else 0

    def device_refs(self):
        return [self.backing_block(0)] if self.on_device else []

    def backing_block_num(self):
        return 1 if self.nbytes else 0

    def backing_block(self, i):
        return BlockRef(Block(DEVICE, self.array, size=self.nbytes), 0,
                        self.nbytes)


class Client:
    def __init__(self, ctx):
        opt = ctx.options
        self.Controller = ctx.rpc.Controller
        self.method = ctx.method
        self.fanout = channels.ParallelChannel(fail_limit=opt["fail_limit"])
        mapper = WorkerMapper()
        merger = KeyedSumMerger(merge=channels.MERGE_SUM, dtype="float32")
        for _ in range(opt["sub_channels"]):
            self.fanout.add_channel(ctx.channel, mapper=mapper,
                                    merger=merger)

    def call(self, key, block):
        cntl = self.Controller()
        cntl.fanout_operand = block
        resp = self.fanout.call_method(self.method, cntl,
                                       Request(message=key), Response())
        if cntl.failed():
            raise RuntimeError(cntl.error_text)
        return resp.message, Result(cntl.fanout_result)

    def close(self):
        pass                            # the channel is the deployment's


def open(ctx):
    return Client(ctx)
