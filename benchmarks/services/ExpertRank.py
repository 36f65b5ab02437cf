"""ExpertRank: one rank of an expert-parallel layer, as a member of the
dispatch sees it: a sub-call brings the rank's records (DeepEP's per-token
FP8 row, its float32 scales, its top-k ids local to the rank and their
weights; ``brpc_tpu.channels.expert_dispatch``'s record) as device memory
under the message ``<key>#<r>``, and the rank answers one ``bfloat16`` row a
record, in the records' order: the row dequantized and scaled by the sum of
the token's weights over the rank's own experts (0 for a padding record,
whose ids are all -1).  There is no expert network: DeepEP's own test
combines what it dispatched, and this keeps the chip at work and makes a
misrouted row change the result.  ONE jitted program a sub-call; the rank
copies nothing to the host and never waits for the device."""
from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp

from brpc_tpu import rpc
from brpc_tpu.channels import expert_dispatch as ep

from .messages import Request, Response

SERVER_OPTIONS = {}
HIDDEN = 7168
TOP_K = 8


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def answer(blocks, cuts, hidden, top_k):
    """The rank's reply bytes: ``blocks`` the device blocks its request's
    refs point into, ``cuts`` each ref's (offset, length).  The scale is a
    power of two, so dequantizing is exact and the weight's product rounds
    once, then once more to bfloat16."""
    rows = [b.reshape(-1)[at:at + n] for b, (at, n) in zip(blocks, cuts)]
    flat = rows[0] if len(rows) == 1 else jnp.concatenate(rows)
    codes, scales, ids, weights = ep.split_records(flat, hidden, top_k)
    wsum = jnp.zeros(ids.shape[:1], jnp.float32)
    for k in range(top_k):                  # in the ids' order
        wsum = wsum + jnp.where(ids[:, k] >= 0, weights[:, k], 0.0)
    n = codes.shape[0]
    g = hidden // ep.SCALE_GROUP
    vals = ep.fp8_decode(codes).reshape(n, g, ep.SCALE_GROUP) \
        * scales[:, :, None]
    rows = (vals * wsum[:, None, None]).reshape(n, hidden)
    return ep.rows_as_bytes(rows.astype(jnp.bfloat16))


def build(spans, hidden: int = HIDDEN, top_k: int = TOP_K):
    # ranks of an operation that have entered, for the two stamps an
    # operation keeps: the first rank's entry, the last rank's done
    entered, lock = {}, threading.Lock()

    class BenchExpertRank(rpc.Service):
        @rpc.method(Request, Response)
        def ExpertRank(self, cntl, request, response, done):
            said = request.message
            key, _, rank = said.rpartition("#")
            if spans is not None:
                with lock:
                    first = key not in entered
                    entered[key] = entered.get(key, 0) + 1
                if first:
                    spans.stamp("handler_entry", key)
            att = cntl.request_attachment
            with jax.profiler.TraceAnnotation("bench.handler.ExpertRank"):
                if key and rank.isdigit() and len(att) \
                        and att.device_bytes() == len(att):
                    refs = att.device_refs()
                    cntl.response_attachment.append_device_array(answer(
                        tuple(r.block.data for r in refs),
                        tuple((r.offset, r.length) for r in refs),
                        hidden, top_k))
                # records that did not come as device memory get no rows
                # back: the combine cannot be made and the reply is short
                response.message = said
            if spans is not None:
                spans.stamp("done", key)            # the last rank's stays
            done()

    return BenchExpertRank()
