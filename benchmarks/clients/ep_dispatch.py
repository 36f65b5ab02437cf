"""ep_dispatch: one rank's dispatch and combine of an expert-parallel layer
(DeepEP's normal mode at EP8).  ``open`` builds one ``ParallelChannel``
whose sub-channels, one a rank, are all the caller's own channel, each with
the program's ``ExpertDispatchMapper`` and ``ExpertCombineMerger``
(``brpc_tpu.channels``).  An operation takes the block as the layer's input
and its gate: ONE jitted program of the benchmark's own (``gate``) makes the
``bfloat16[tokens, hidden]`` hidden states and the top-k routing from the
block on the chip, as ``reference/ExpertRank.py`` makes them with numpy; the
batch rides ONE ``call_method`` as ``cntl.fanout_operand``; the program
packs, sends, and combines; the operation returns the merged message and
``cntl.fanout_result`` — ONE ``bfloat16[tokens, hidden]`` on the caller's
chip — as it is.  Rank ``r`` is told who it is by the request message
``<key>#<r>``.  The client packs and sums nothing itself."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from brpc_tpu import channels
from brpc_tpu.channels import byte_words

from ..services.messages import Request, Response
from .pushpull import Result


def _mix(h):
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


@functools.partial(jax.jit, static_argnames=("hidden", "experts", "top_k"))
def gate(block, *, hidden, experts, top_k):
    """(x, topk_idx, topk_weights) of the block: see
    ``reference/ExpertRank.py`` for the rule."""
    u = byte_words.words(block, jnp.uint16).reshape(-1, hidden) \
        .astype(jnp.uint32)
    bits = (u & 0x807F) | ((0x7C + ((u >> 7) & 3)) << 7)
    x = jax.lax.bitcast_convert_type(bits.astype(jnp.uint16), jnp.bfloat16)
    key = u[:, 0] | (u[:, 1] << 16)
    e = jnp.arange(experts, dtype=jnp.uint32)
    h = _mix(key[:, None] ^ (e[None, :] * jnp.uint32(0x9E3779B9)))
    score = ((h >> 16) * experts + e).astype(jnp.float32)   # exact, distinct
    _, idx = jax.lax.top_k(score, top_k)
    hk = jnp.take_along_axis(h, idx, axis=1)
    weights = (((hk >> 8) & 0xFF) + 1).astype(jnp.float32) / 256.0
    return x, idx.astype(jnp.int32), weights


class RankMapper(channels.ExpertDispatchMapper):
    """Sub-call ``r`` carries rank ``r``'s records and the message
    ``<key>#<r>``."""

    def map_fanout(self, index, method_full_name, request, parent_cntl):
        sub = super().map_fanout(index, method_full_name, request,
                                 parent_cntl)
        if not sub.skip:
            sub.request = Request(message=f"{request.message}#{index}")
        return sub


class KeyedCombineMerger(channels.ExpertCombineMerger):
    """The merged message is the key only if EVERY rank's reply says it
    under its own index (``KeyedSumMerger``'s rule)."""

    def merge_sub(self, parent_cntl, index, sub_cntl, response):
        reply = sub_cntl.response
        said, _, rank = reply.message.rpartition("#")
        said = said if rank == str(index) else \
            f"rank {index} answered {reply.message!r}"
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
        self.shape = dict(hidden=opt["hidden"], experts=opt["experts"],
                          top_k=opt["top_k"])
        self.fanout = channels.ParallelChannel(fail_limit=opt["fail_limit"])
        mapper = RankMapper(experts=opt["experts"], top_k=opt["top_k"],
                            ranks=opt["ranks"])
        merger = KeyedCombineMerger()
        for _ in range(opt["ranks"]):
            self.fanout.add_channel(ctx.channel, mapper=mapper,
                                    merger=merger)

    def call(self, key, block):
        cntl = self.Controller()
        cntl.fanout_operand = channels.ExpertBatch(*gate(block, **self.shape))
        resp = self.fanout.call_method(self.method, cntl,
                                       Request(message=key), Response())
        if cntl.failed():
            raise RuntimeError(cntl.error_text)
        return resp.message, Result(cntl.fanout_result)

    def close(self):
        pass                            # the channel is the deployment's


def open(ctx):
    return Client(ctx)
