"""PushPull: one worker of a parameter server's push-pull, as a member of a
fan-out sees it: a sub-call brings the WHOLE range (every worker gets all of
it: upstream's default ``CallMapper``) as device memory under the message
``<key>#<i>``, and worker ``i`` answers its contribution — with ``w[j]`` the
range's little-endian uint32 words, ``g_i[j] = float32((w[j] >> 8i) & 0xFF) *
4**i`` — as the float32 array's bytes, flat uint8, before it is ready.  ONE
jitted program a sub-call, whatever blocks the range crossed in as: they are
joined and computed on in the same program.  Every value is
a non-negative integer and every partial sum of the four is at most 21,675,
exact in float32.  The handler copies nothing to the host and never waits for
the device."""
from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp

from brpc_tpu import rpc

from .messages import Request, Response

SERVER_OPTIONS = {}


@functools.partial(jax.jit, static_argnums=(1, 2))
def contribution(blocks, cuts, index):
    """Worker ``index``'s answer to the range: ``blocks`` are the device
    blocks its refs point into, ``cuts`` each ref's (offset, length).

    Computed byte by byte, in the layout the bytes arrive in.  A ``u8[n]``
    viewed as ``uint32`` is no bitcast on a TPU but a relayout (the four bytes
    of a word lie 128 elements apart in its tiling), and so is a float32
    array viewed as bytes: written with the two views this program took 8.6 ms
    of the chip a sub-call, as written here 0.24 (PERF.md section 6, PR 39).
    Byte ``k`` of answer word ``j`` is byte ``k`` of
    ``float32(range[4j + index]) * 4**index``: the range shifted by
    ``k - index`` places puts that input byte where its answer byte goes.
    A whole number under 2**8 times a power of two has a mantissa of eight
    bits at most, so bytes 0 and 1 of the float32 are zero."""
    rows = [b.reshape(-1)[at:at + n] for b, (at, n) in zip(blocks, cuts)]
    whole = rows[0] if len(rows) == 1 else jnp.concatenate(rows)
    place = jax.lax.iota(jnp.uint32, whole.shape[0]) & 3

    def answer_byte(k):
        g = jnp.roll(whole, k - index).astype(jnp.float32) \
            * jnp.float32(4 ** index)
        bits = jax.lax.bitcast_convert_type(g, jnp.uint32)
        return ((bits >> (8 * k)) & 0xFF).astype(jnp.uint8)

    return jnp.where(place == 2, answer_byte(2),
                     jnp.where(place == 3, answer_byte(3), jnp.uint8(0)))


def build(spans):
    # workers of an operation that have entered, for the two stamps an
    # operation keeps: the first worker's entry, the last worker's done
    entered, lock = {}, threading.Lock()

    class BenchPushPull(rpc.Service):
        @rpc.method(Request, Response)
        def PushPull(self, cntl, request, response, done):
            said = request.message
            key, _, worker = said.rpartition("#")
            if spans is not None:
                with lock:
                    first = key not in entered
                    entered[key] = entered.get(key, 0) + 1
                if first:
                    spans.stamp("handler_entry", key)
            att = cntl.request_attachment
            with jax.profiler.TraceAnnotation("bench.handler.PushPull"):
                if key and worker.isdigit() and len(att) \
                        and att.device_bytes() == len(att):
                    refs = att.device_refs()
                    cntl.response_attachment.append_device_array(
                        contribution(
                            tuple(r.block.data for r in refs),
                            tuple((r.offset, r.length) for r in refs),
                            int(worker)))
                # a range that did not come as device memory, or a message
                # that names no worker, gets no attachment back: the
                # operation's sum cannot be made and its reply is short
                response.message = said
            if spans is not None:
                spans.stamp("done", key)            # the last worker's stays
            done()

    return BenchPushPull()
