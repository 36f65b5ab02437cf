"""Expert-parallel dispatch and combine on a ``ParallelChannel``: the
fan-out of a mixture-of-experts layer, one sub-channel a rank of experts.

DeepEP's normal mode (``deepseek-ai/DeepEP``) is the model: each token goes
to every rank that holds one of its ``top_k`` experts as ONE record — its
hidden row cast per token to FP8 (e4m3) with one float32 scale per 128
channels, its ``top_k`` expert ids (int64, local to the rank, -1 where the
expert is another rank's) and weights (float32, 0 there) — and each rank's
answer, a ``bfloat16`` row a received token, is added back into token order.

``ExpertDispatchMapper`` takes ``cntl.fanout_operand = ExpertBatch(x,
topk_idx, topk_weights)`` (``x`` ``bfloat16[tokens, hidden]`` on a device).
At the first sub-call ONE device program, ``brpc_ep_pack``, casts and packs
each rank's records in token order into a buffer of ``capacity`` records
(zero records, ids -1, after the last), and the host reads the ranks' counts
once (DeepEP's normal mode syncs its counts to the CPU too).  Sub-call ``r``
carries rank ``r``'s buffer by reference; a rank with no token is skipped.
Every buffer has the one shape, so a ragged operation compiles nothing.

``ExpertCombineMerger`` keeps each rank's reply (``bfloat16[capacity,
hidden]`` as bytes, the rows of its buffer in order) and at the finalize ONE
program, ``brpc_ep_combine``, gives ``cntl.fanout_result``: ONE
``bfloat16[tokens, hidden]`` on the operand's chip, each token's rank rows
added in float32 in rank order and rounded once.  The replies' 2-byte words
are built in their byte tiling (``byte_words.words``).  A reply that came
back as host bytes is combined with numpy, and counted.

Scales are powers of two (DeepEP's ``round_scale``, UE8M0): ``2**ceil(
log2(amax / 448))`` per 128 channels, so the cast, and a scale times a
weight, are exact, and the FP8 codes are made and read by integer
arithmetic, bit for bit what ``ml_dtypes`` gives, whatever converts the
chip's compiler takes.

Counters (``expert_dispatch_stats()``, ``/vars`` ``rpc_fanout_ep_<key>``);
layer spans ``brpc.fanout.dispatch`` (the pack's dispatch and the count
read, with CPU time) and ``brpc.fanout.combine`` (the combine's dispatch).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple

from .. import bvar
from ..butil import layer_span as _span
from ..butil.iobuf import IOBuf
from . import parallel_channel as _pc

SCALE_GROUP = 128                # channels a scale
META_BYTES = 8 + 4               # an int64 id and a float32 weight

# ``pack_programs`` / ``combine_programs`` dispatches of the two programs;
# ``count_reads`` host reads of a pack's counts; ``rows_dispatched`` records
# sent (padding left out); ``padded_bytes`` bytes of padding records and of
# their reply rows that rode the sub-calls; ``skipped_ranks`` sub-calls
# skipped for a rank with no token; ``combine_input_bytes`` reply bytes the
# combines read; ``host_fallbacks`` an operand packed from the host, or a
# combine made with numpy
_STAT_KEYS = ("pack_programs", "count_reads", "rows_dispatched",
              "padded_bytes", "skipped_ranks", "combine_programs",
              "combine_input_bytes", "host_fallbacks")
_g = {k: bvar.Adder(f"rpc_fanout_ep_{k}") for k in _STAT_KEYS}


def expert_dispatch_stats() -> Dict[str, int]:
    """What this process's expert dispatches and combines have done."""
    return {k: v.get_value() for k, v in _g.items()}


class ExpertBatch(NamedTuple):
    """A dispatch's operand: ``x`` ``bfloat16[tokens, hidden]``,
    ``topk_idx`` integer ``[tokens, top_k]`` (global expert ids, -1 for
    none), ``topk_weights`` ``float32[tokens, top_k]``."""
    x: Any
    topk_idx: Any
    topk_weights: Any


def record_bytes(hidden: int, top_k: int) -> int:
    """One record: the FP8 row, its float32 scales, the ids and weights."""
    return hidden + 4 * (hidden // SCALE_GROUP) + META_BYTES * top_k


def rank_share(experts: int, top_k: int, ranks: int) -> float:
    """The chance that a rank holds one of a token's ``top_k`` experts
    under uniform routing: 1 - C(E - E/R, k) / C(E, k)."""
    return 1.0 - math.comb(experts - experts // ranks, top_k) \
        / math.comb(experts, top_k)


def capacity_for(tokens: int, hidden: int, experts: int, top_k: int,
                 ranks: int, sigmas: float = 8.0) -> int:
    """Records a rank's buffer holds: the mean under uniform routing plus
    ``sigmas`` standard deviations, rounded up so that a reply's bytes are
    whole 4 KiB tiles in rows of eight, and never more than ``tokens``.  A pack whose rank
    needs more is packed again at ``tokens`` (``ExpertDispatchMapper``)."""
    p = rank_share(experts, top_k, ranks)
    want = tokens * p + sigmas * math.sqrt(tokens * p * (1.0 - p))
    grain = math.lcm(8, 4096 // math.gcd(4096, 2 * hidden))
    return min(tokens, grain * math.ceil(want / grain))


# -- FP8 e4m3fn by integer arithmetic ---------------------------------------

def fp8_scale_exponents(amax):
    """``k`` with ``2**k`` the least power of two at or over ``amax /
    448`` (``amax`` float32, positive and normal): from the bits alone.
    ``amax = 1.f * 2**e``; 448 = 1.75 * 2**8, so ``k = e - 8``, one more
    where ``1.f`` passes 1.75."""
    import jax
    import jax.numpy as jnp
    bits = jax.lax.bitcast_convert_type(amax, jnp.int32)
    e = ((bits >> 23) & 0xFF) - 127
    return e - 8 + ((bits & 0x7FFFFF) > 0x600000).astype(jnp.int32)


def fp8_encode(y):
    """float32 ``y`` (|y| <= 448) to e4m3fn codes (``uint8``), rounded to
    nearest even: a normal value's 23 mantissa bits cut to 3, a subnormal's
    ``|y| * 2**9`` rounded; the top finite code where rounding passes it."""
    import jax
    import jax.numpy as jnp
    b = jax.lax.bitcast_convert_type(y, jnp.uint32)
    sign = (b >> 31) << 7
    a = b & 0x7FFFFFFF
    mant = a & 0x7FFFFF
    comb = (((a >> 23) - 120) << 3) | (mant >> 20)
    rem = mant & 0xFFFFF
    up = (rem > 0x80000) | ((rem == 0x80000) & ((comb & 1) == 1))
    normal = jnp.minimum(comb + up.astype(jnp.uint32), 0x7E)
    small = jnp.round(jnp.abs(y) * 512.0).astype(jnp.uint32)
    code = jnp.where(a < 0x3C800000, small, normal)      # |y| < 2**-6
    return (sign | code).astype(jnp.uint8)


def fp8_decode(codes):
    """e4m3fn codes (``uint8``) to float32, exact, NaN for the two NaN
    codes: by integer arithmetic, not a table gather or a convert."""
    import jax
    import jax.numpy as jnp
    c = codes.astype(jnp.uint32)
    sign = (c >> 7) << 31
    e = (c >> 3) & 0xF
    m = c & 7
    normal = jax.lax.bitcast_convert_type(
        sign | ((e + 120) << 23) | (m << 20), jnp.float32)
    small = m.astype(jnp.float32) * jnp.float32(2.0 ** -9)
    small = jnp.where(sign != 0, -small, small)
    nan = jax.lax.bitcast_convert_type(sign | 0x7FC00000, jnp.float32)
    return jnp.where(e == 0, small,
                     jnp.where((e == 15) & (m == 7), nan, normal))


def fp8_cast(x):
    """DeepEP's per-token cast with power-of-two scales: ``x``
    ``[tokens, hidden]`` to (codes ``uint8[tokens, hidden]``, scales
    ``float32[tokens, hidden / 128]``); amax is clamped at 1e-4 as DeepEP
    clamps it."""
    import jax
    import jax.numpy as jnp
    t, h = x.shape
    g = h // SCALE_GROUP
    xf = x.astype(jnp.float32).reshape(t, g, SCALE_GROUP)
    amax = jnp.maximum(jnp.max(jnp.abs(xf), axis=2), jnp.float32(1e-4))
    k = fp8_scale_exponents(amax)
    scale = jax.lax.bitcast_convert_type((k + 127) << 23, jnp.float32)
    inv = jax.lax.bitcast_convert_type((127 - k) << 23, jnp.float32)
    codes = fp8_encode(xf * inv[:, :, None])
    return codes.reshape(t, h), scale


# -- the record --------------------------------------------------------------

def _le_bytes(words, width: int):
    """Integer ``words[..., n]`` as ``uint8[..., n * width]``, little-endian
    (an int32 widened to 8 bytes by its sign)."""
    import jax.numpy as jnp
    u = words.astype(jnp.uint32)
    planes = [(u >> (8 * i)) & 0xFF for i in range(min(width, 4))]
    if width == 8:
        top = jnp.where(words < 0, 0xFF, 0).astype(jnp.uint32)
        planes += [top] * 4
    out = jnp.stack(planes, axis=-1).astype(jnp.uint8)
    return out.reshape(*words.shape[:-1], words.shape[-1] * width)


def _le_words(data, width: int):
    """``uint8[..., n * width]`` little-endian to ``uint32[..., n]`` (the
    low four bytes of each word)."""
    import jax.numpy as jnp
    out = jnp.zeros(data.shape[:-1] + (data.shape[-1] // width,), jnp.uint32)
    for i in range(4):
        out = out | (data[..., i::width].astype(jnp.uint32) << (8 * i))
    return out


def split_records(flat, hidden: int, top_k: int):
    """A rank's buffer (``uint8[n * record]``) as its fields: (codes
    ``uint8[n, hidden]``, scales ``float32[n, hidden / 128]``, local ids
    ``int32[n, top_k]``, weights ``float32[n, top_k]``).  Traced inside a
    jitted program."""
    import jax
    import jax.numpy as jnp
    rb = record_bytes(hidden, top_k)
    rec = flat.reshape(-1, rb)
    g = hidden // SCALE_GROUP
    at = hidden
    codes = rec[:, :at]
    scales = jax.lax.bitcast_convert_type(
        _le_words(rec[:, at:at + 4 * g], 4), jnp.float32)
    at += 4 * g
    ids = _le_words(rec[:, at:at + 8 * top_k], 8).astype(jnp.int32)
    at += 8 * top_k
    weights = jax.lax.bitcast_convert_type(
        _le_words(rec[:, at:at + 4 * top_k], 4), jnp.float32)
    return codes, scales, ids, weights


def rows_as_bytes(rows):
    """A rank's answer, ``bfloat16[n, hidden]``, as the flat ``uint8``
    block of a reply, built in the byte tiling where its length is whole
    tiles (``byte_words.word_bytes``).  Traced inside a jitted program."""
    import jax.numpy as jnp
    from . import byte_words
    flat = rows.reshape(-1)
    if byte_words.in_layout([flat.shape[0] * 2], 2):
        return byte_words.word_bytes(flat)
    return flat.view(jnp.uint8)


# -- the two programs ----------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _pack_program():
    """The one jitted ``brpc_ep_pack``: (each rank's buffer, flat ``uint8``
    of ``capacity`` records; ``int32[ranks]`` counts; the handle,
    ``int32[ranks, tokens]``: each token's row in each rank's buffer, or
    ``capacity`` where it was not sent).  A rank's rows are its tokens in
    token order; the rows after them are zero records whose ids are -1."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit,
                       static_argnames=("experts", "ranks", "capacity"))
    def brpc_ep_pack(x, topk_idx, topk_weights, *, experts, ranks,
                     capacity):
        t = x.shape[0]
        k = topk_idx.shape[1]
        per = experts // ranks
        codes, scales = fp8_cast(x)
        shared = jnp.concatenate(
            [codes, _le_bytes(jax.lax.bitcast_convert_type(
                scales, jnp.uint32), 4)], axis=1)
        shared = jnp.concatenate(
            [shared, jnp.zeros((1, shared.shape[1]), jnp.uint8)])
        idx = topk_idx.astype(jnp.int32)
        rank = jnp.arange(ranks, dtype=jnp.int32)
        owner = jnp.where(idx >= 0, idx // per, -1)
        hit = owner[:, None, :] == rank[None, :, None]         # [t, R, k]
        sent = jnp.any(hit, axis=2)                             # [t, R]
        counts = jnp.sum(sent, axis=0, dtype=jnp.int32)
        pos = jnp.cumsum(sent, axis=0, dtype=jnp.int32) - 1
        place = jnp.where(sent & (pos < capacity), pos, capacity).T
        src = jnp.full((ranks, capacity + 1), t, jnp.int32).at[
            rank[:, None], place].set(
                jnp.arange(t, dtype=jnp.int32)[None, :])[:, :capacity]
        local = jnp.where(hit, idx[:, None, :] - rank[None, :, None] * per,
                          -1)
        weight = jnp.where(hit, topk_weights[:, None, :].astype(jnp.float32),
                           jnp.float32(0))
        pad = jnp.concatenate([jnp.full((1, 8 * k), 0xFF, jnp.uint8),
                               jnp.zeros((1, 4 * k), jnp.uint8)], axis=1)
        bufs = []
        for r in range(ranks):
            meta = jnp.concatenate(
                [_le_bytes(local[:, r, :], 8),
                 _le_bytes(jax.lax.bitcast_convert_type(
                     weight[:, r, :], jnp.uint32), 4)], axis=1)
            meta = jnp.concatenate([meta, pad])
            rec = jnp.concatenate([jnp.take(shared, src[r], axis=0),
                                   jnp.take(meta, src[r], axis=0)], axis=1)
            bufs.append(rec.reshape(-1))
        return tuple(bufs), counts, place

    return brpc_ep_pack


def _reply_words(block):
    """A reply block's 2-byte words: in its byte tiling where it is whole
    tiles, else by ``.view``."""
    import jax.numpy as jnp
    from . import byte_words
    if byte_words.in_layout([block.shape[0]], 2):
        return byte_words.words(block, jnp.uint16)
    return block.view(jnp.uint16)


@functools.lru_cache(maxsize=None)
def _combine_program():
    """The one jitted ``brpc_ep_combine``: the ranks' replies (each a tuple
    of flat ``uint8`` blocks, the rows of its buffer in order) scatter-added
    into token order through the handle, in float32 in rank order, rounded
    once to ``bfloat16``."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("ranks", "hidden"))
    def brpc_ep_combine(parts, place, *, ranks, hidden):
        acc = jnp.zeros((place.shape[1], hidden), jnp.float32)
        for r, blocks in zip(ranks, parts):
            ws = [_reply_words(b) for b in blocks]
            w = ws[0] if len(ws) == 1 else jnp.concatenate(ws)
            rows = jax.lax.bitcast_convert_type(w, jnp.bfloat16).reshape(
                -1, hidden).astype(jnp.float32)
            acc = acc + jnp.take(rows, place[r], axis=0, mode="fill",
                                 fill_value=0)
        return acc.astype(jnp.bfloat16)

    return brpc_ep_combine


def combine_on_host(replies, place, hidden: int):
    """``brpc_ep_combine`` with numpy: ``replies`` {rank: reply bytes}."""
    import ml_dtypes
    import numpy as np
    place = np.asarray(place)
    acc = np.zeros((place.shape[1], hidden), np.float32)
    for r in sorted(replies):
        rows = np.frombuffer(replies[r], ml_dtypes.bfloat16).reshape(
            -1, hidden).astype(np.float32)
        rows = np.concatenate([rows, np.zeros((1, hidden), np.float32)])
        acc = acc + rows[np.minimum(place[r], rows.shape[0] - 1)]
    return acc.astype(ml_dtypes.bfloat16)


# -- the mapper and the merger ----------------------------------------------

class _Packed(NamedTuple):
    buffers: tuple                # each rank's flat uint8 buffer
    counts: Any                   # numpy int32[ranks]: the one host read
    place: Any                    # the handle, int32[ranks, tokens]
    capacity: int
    hidden: int
    record: int
    home: Any                     # the operand's device


class ExpertDispatchMapper:
    """CallMapper of an expert-parallel dispatch: sub-call ``r`` carries, by
    reference, rank ``r``'s records of ``cntl.fanout_operand`` (an
    ``ExpertBatch``) in token order; a rank with no token is skipped.
    ``ranks`` is the number of sub-channels, each holding ``experts /
    ranks`` consecutive experts; a rank's buffer holds ``capacity_for`` the
    operand's shape."""

    def __init__(self, experts: int, top_k: int, ranks: int):
        if experts % ranks:
            raise ValueError(f"{experts} experts do not split over "
                             f"{ranks} ranks")
        self.experts = experts
        self.top_k = top_k
        self.ranks = ranks

    def map(self, index: int, method_full_name: str, request: Any):
        return _pc.SubCall(request)

    def map_fanout(self, index: int, method_full_name: str, request: Any,
                   parent_cntl) -> "_pc.SubCall":
        packed = self.packed(parent_cntl)
        n = int(packed.counts[index])
        if n == 0:
            _g["skipped_ranks"] << 1
            return _pc.SubCall.skip_call()
        att = IOBuf()
        att.append_device_array(packed.buffers[index])
        _g["rows_dispatched"] << n
        _g["padded_bytes"] << (packed.capacity - n) * (
            packed.record + 2 * packed.hidden)
        _pc._g["device_operand_bytes"] << len(att)
        return _pc.SubCall(request, attachment=att)

    def packed(self, parent_cntl) -> _Packed:
        """The operation's pack: made at its first sub-call, kept on the
        parent controller for the rest and for the merger."""
        d = parent_cntl.__dict__
        packed = d.get("_ep_packed")
        if packed is not None:
            return packed
        import jax
        import numpy as np
        x, idx, weights = parent_cntl.fanout_operand
        if idx.shape[1] != self.top_k:
            raise ValueError(f"routing has top-{idx.shape[1]}, the mapper "
                             f"top-{self.top_k}")
        if not all(hasattr(a, "devices") for a in (x, idx, weights)):
            _g["host_fallbacks"] << 1
            _pc._g["host_operand_bytes"] << sum(
                np.asarray(a).nbytes for a in (x, idx, weights))
            x, idx, weights = jax.device_put((x, idx, weights))
        home = next(iter(x.devices()))
        tokens, hidden = x.shape
        capacity = capacity_for(tokens, hidden, self.experts, self.top_k,
                                self.ranks)
        ls = _span.layer_begin("brpc.fanout.dispatch", n=tokens, cpu=True) \
            if _span.layer_on() else None
        try:
            pack = _pack_program()
            bufs, counts, place = pack(x, idx, weights, experts=self.experts,
                                       ranks=self.ranks, capacity=capacity)
            counts = np.asarray(counts)         # the one read of the counts
            _g["pack_programs"] << 1
            _g["count_reads"] << 1
            if int(counts.max()) > capacity:    # a rank past its buffer
                capacity = tokens
                bufs, _, place = pack(x, idx, weights, experts=self.experts,
                                      ranks=self.ranks, capacity=capacity)
                _g["pack_programs"] << 1
        finally:
            if ls is not None:
                ls.end()
        d["_fanout_device"] = home
        packed = d["_ep_packed"] = _Packed(
            bufs, counts, place, capacity, hidden,
            record_bytes(hidden, self.top_k), home)
        return packed


class ExpertCombineMerger:
    """ResponseMerger of an expert-parallel combine: rank ``r``'s reply
    (``bfloat16[capacity, hidden]`` as bytes) kept by index, and at the
    finalize ONE ``brpc_ep_combine`` program makes ``cntl.fanout_result``.
    A skipped rank sent nothing and answers nothing: it is no missing part
    (``merges_skips``); a failed one fails the operation."""

    merges_skips = True

    def merge_sub(self, parent_cntl, index: int, sub_cntl,
                  response: Any) -> int:
        att = sub_cntl._peek_response_attachment()
        parent_cntl.__dict__.setdefault("_ep_replies", {})[index] = \
            IOBuf(att) if att is not None else IOBuf()
        return _pc.ResponseMerger.MERGED

    def finalize_fanout(self, parent_cntl) -> None:
        d = parent_cntl.__dict__
        packed: _Packed = d["_ep_packed"]
        replies = d.get("_ep_replies", {})
        want = packed.capacity * packed.hidden * 2
        for r, part in replies.items():
            if len(part) != want:
                raise ValueError(f"rank {r} answered {len(part)} bytes, "
                                 f"its {packed.capacity} rows are {want}")
        ranks = tuple(sorted(replies))
        if any(replies[r].device_bytes() != want for r in ranks):
            _g["host_fallbacks"] << 1
            _pc._g["host_operand_bytes"] << want * len(ranks)
            parent_cntl.fanout_result = combine_on_host(
                {r: replies[r].to_bytes() for r in ranks}, packed.place,
                packed.hidden)
            return
        import jax
        from ..ici.transport import _cut
        mark = d.pop("_fanout_mark", None)
        ls = _span.layer_begin("brpc.fanout.combine", n=want * len(ranks),
                               mark=mark, m=len(ranks)) \
            if _span.layer_on() else None
        try:
            parts = []
            for r in ranks:
                blocks = []
                for ref in replies[r].device_refs():
                    a = ref.block.data
                    a = _cut(a if a.ndim == 1 else a.reshape(-1), ref)
                    if packed.home not in a.devices():
                        a = jax.device_put(a, packed.home)
                    blocks.append(a)
                parts.append(tuple(blocks))
            out = _combine_program()(tuple(parts), packed.place, ranks=ranks,
                                     hidden=packed.hidden)
        finally:
            if ls is not None:
                ls.end()
        _g["combine_programs"] << 1
        _g["combine_input_bytes"] << want * len(ranks)
        parent_cntl.fanout_result = out
