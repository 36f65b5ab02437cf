"""EchoShard: upstream parallel_echo's server, as one member of a fan-out
sees it: a sub-call brings its shard of the operation's block as device
memory, the handler computes on the shard on the chip — ONE jitted program a
shard, whatever blocks the shard crossed in as: they are joined and xored in
the same program, which gives one array of the shard's size — and answers
with the result before it is ready, under the operation's key.  The handler
copies nothing to the host and never waits for the device."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from brpc_tpu import rpc

from .messages import Request, Response

SERVER_OPTIONS = {}


@functools.partial(jax.jit, static_argnums=1)
def transform(blocks, cuts):
    """The shard xored: ``blocks`` are the device blocks its refs point
    into, ``cuts`` each ref's (offset, length) in its block."""
    rows = [b.reshape(-1)[at:at + n] for b, (at, n) in zip(blocks, cuts)]
    shard = rows[0] if len(rows) == 1 else jnp.concatenate(rows)
    return shard ^ jnp.uint8(0x5A)


def build(spans):
    class BenchEchoShard(rpc.Service):
        @rpc.method(Request, Response)
        def EchoShard(self, cntl, request, response, done):
            key = request.message
            if spans is not None and \
                    key not in spans.at.get("handler_entry", ()):
                spans.stamp("handler_entry", key)   # the first shard's
            att = cntl.request_attachment
            with jax.profiler.TraceAnnotation("bench.handler.EchoShard"):
                if len(att) and att.device_bytes() == len(att):
                    refs = att.device_refs()
                    cntl.response_attachment.append_device_array(transform(
                        tuple(r.block.data for r in refs),
                        tuple((r.offset, r.length) for r in refs)))
                # a shard that did not come as device memory gets no
                # attachment back: the operation's reply is short
                response.message = key
            if spans is not None:
                spans.stamp("done", key)            # the last shard's stays
            done()

    return BenchEchoShard()
