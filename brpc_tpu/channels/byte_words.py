"""A flat ``uint8`` device block's 2- or 4-byte words, built in the byte
tiling the block already has on the TPU.

A ``u8[n]`` array lies on the TPU as ``T(1024)(128)(4,1)``: rows of 128
bytes, four consecutive rows packed into each 32-bit word of a lane.  The
bytes ``4j .. 4j+3`` of the stream's word ``j`` therefore sit in four
adjacent LANES of one row, and ``a.view(uint32)`` gathers them by a reshape
to ``u8[n/4, 4]``, whose minor dimension the chip pads to 128 lanes: a
relayout at ~5 GB/s, where the add it feeds costs nothing (PERF.md §5,
``pushpull_4x16m``).

``words`` never forms that shape.  It reads the block as ``u8[n/128, 128]``
(the same bytes, no copy) in a Pallas kernel, tile by tile:
``pltpu.bitcast`` gives the packed 32-bit words (word ``(a, l)`` holds the
bytes ``512a + 128q + l``, ``q`` = 0..3), their four byte planes go to the
MXU as bfloat16 (every byte value is exact there), and one 0/1 selection
matrix moves each byte to the lane of the word it belongs to, times 256
where it is the high byte of a 16-bit half.  Every output column sums at
most two nonzero products, below 2**16: exact in the float32 accumulator.
The two halves are joined by shift and or, and each row of the result is
128 words of the stream, in order.  Nothing is computed on a value: the
words are the block's bits, NaN payloads and denormals included.

``word_bytes`` is the way back for 2-byte words (a handler that answers
bfloat16 rows): the packed 32-bit words of ``u16[n/128, 128]`` hold two
rows of the stream each, and one 0/1 placement matrix puts each of their
bytes in the lane its output byte has; the kernel writes ``u8[n/64, 128]``.

Where the program is lowered for the CPU the same kernel runs in Pallas's
interpreter; which one is decided by the platform the program is lowered
for (``jax.lax.platform_dependent``), so a compile for a described TPU
takes the compiled kernel on a host with no chip.
"""
from __future__ import annotations

import functools
from typing import Iterable

LANES = 128
# the u8 tile (32 rows of 128 lanes): a block views in its tiling only if it
# is a whole number of them
TILE_BYTES = 32 * LANES
_MAX_ROWS = 2048                 # u8 rows a grid step: 256 KiB in, 256 KiB out


def in_layout(lengths: Iterable[int], itemsize: int) -> bool:
    """True where ``words`` can view every block of these byte lengths as
    ``itemsize``-byte words; anything else takes ``.view``."""
    lengths = list(lengths)
    return itemsize in (2, 4) and bool(lengths) and all(
        n > 0 and n % TILE_BYTES == 0 for n in lengths)


@functools.lru_cache(maxsize=None)
def _selection(itemsize: int):
    """``S[512, 256]`` over one 512-byte group (the four byte planes of a
    row of packed words, plane-major): column ``h * 128 + m`` builds 16-bit
    half ``h`` of output word ``(., m)``, whose byte ``p`` (``2h`` and
    ``2h + 1``) is the stream's byte ``f(m, p)`` of the group."""
    import numpy as np
    s = np.zeros((4 * LANES, 2 * LANES), np.float32)
    for m in range(LANES):
        for p in range(4):
            if itemsize == 4:         # output word m: bytes 4m .. 4m+3
                f = 4 * m + p
            else:                     # packed u16 rows 2a, 2a+1: 256h + 2m + t
                f = 256 * (p // 2) + 2 * m + p % 2
            s[f, LANES * (p // 2) + m] = 256.0 if p % 2 else 1.0
    return s


def _kernel(x_ref, s_ref, o_ref):
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu
    w = pltpu.bitcast(x_ref[...], jnp.int32)
    planes = [(jax.lax.shift_right_logical(w, 8 * q) & 0xFF)
              .astype(jnp.float32).astype(jnp.bfloat16) for q in range(4)]
    y = jnp.dot(jnp.concatenate(planes, axis=1), s_ref[...],
                preferred_element_type=jnp.float32)
    out = y[:, :LANES].astype(jnp.int32) | (y[:, LANES:].astype(jnp.int32)
                                            << 16)
    o_ref[...] = out if o_ref.dtype == jnp.int32 \
        else pltpu.bitcast(out, o_ref.dtype)


def words(block, carrier):
    """``block`` (flat ``uint8``, ``in_layout``) as ``carrier`` words, in
    the order ``block.view(carrier)`` gives them.  Traced inside a jitted
    program."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    carrier = jnp.dtype(carrier)
    size = carrier.itemsize
    rows = block.shape[0] // LANES
    step = _MAX_ROWS
    while rows % step:
        step //= 2
    out_dtype = jnp.int32 if size == 4 else jnp.uint16
    out_rows = step // size               # a step's words, rows of out_dtype

    def call(interpret):
        return pl.pallas_call(
            _kernel,
            out_shape=jax.ShapeDtypeStruct((rows // step * out_rows, LANES),
                                           out_dtype),
            grid=(rows // step,),
            in_specs=[pl.BlockSpec((step, LANES), lambda i: (i, 0)),
                      pl.BlockSpec((4 * LANES, 2 * LANES), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((out_rows, LANES), lambda i: (i, 0)),
            interpret=interpret,
            name="brpc_byte_words",
        )

    x = block.reshape(rows, LANES)
    s = jnp.asarray(_selection(size), jnp.bfloat16)
    out = jax.lax.platform_dependent(
        x, s, tpu=call(False), default=call(True))
    return jax.lax.bitcast_convert_type(out, carrier).reshape(-1)


# 2-byte words to a block: words a grid step (u16 rows of 128), 256 KiB in
_MAX_WORD_ROWS = 1024


@functools.lru_cache(maxsize=None)
def _placement():
    """``S[512, 512]``, the inverse of ``_selection(2)``: a packed 32-bit
    input word ``(a, m)`` holds words ``256a + m`` (low half) and
    ``256a + 128 + m`` (high half) of the stream, so its four byte planes
    (plane-major columns ``128p + m``) hold the low and high bytes of the
    words ``256a .. 256a + 255``; output column ``128q + l`` is byte ``q``
    of output word ``(a, l)``, the stream's byte ``512a + 128q + l``: word
    ``64q + l // 2`` of the group, its low byte where ``l`` is even."""
    import numpy as np
    s = np.zeros((4 * LANES, 4 * LANES), np.float32)
    for q in range(4):
        for l in range(LANES):
            o, t = 64 * q + l // 2, l % 2
            p, m = (t, o) if o < LANES else (2 + t, o - LANES)
            s[LANES * p + m, LANES * q + l] = 1.0
    return s


def _bytes_kernel(x_ref, s_ref, o_ref):
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu
    w = pltpu.bitcast(x_ref[...], jnp.int32)
    planes = [(jax.lax.shift_right_logical(w, 8 * p) & 0xFF)
              .astype(jnp.float32).astype(jnp.bfloat16) for p in range(4)]
    y = jnp.dot(jnp.concatenate(planes, axis=1), s_ref[...],
                preferred_element_type=jnp.float32).astype(jnp.int32)
    out = y[:, :LANES]
    for q in range(1, 4):
        out = out | (y[:, LANES * q:LANES * (q + 1)] << (8 * q))
    o_ref[...] = pltpu.bitcast(out, jnp.uint8)


def word_bytes(words):
    """The flat ``uint8`` block of ``words`` (a flat 2-byte array whose
    byte length ``in_layout`` admits), in the order ``words.view(uint8)``
    gives them, written in the byte tiling: the inverse of ``words(block,
    uint16)``.  Each byte goes to the MXU as bfloat16 and one 0/1 placement
    matrix puts it in the lane it belongs to; every output column holds one
    product, exact.  Traced inside a jitted program."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    rows = words.shape[0] // LANES
    step = _MAX_WORD_ROWS
    while rows % step:
        step //= 2

    def call(interpret):
        return pl.pallas_call(
            _bytes_kernel,
            out_shape=jax.ShapeDtypeStruct((2 * rows, LANES), jnp.uint8),
            grid=(rows // step,),
            in_specs=[pl.BlockSpec((step, LANES), lambda i: (i, 0)),
                      pl.BlockSpec((4 * LANES, 4 * LANES), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((2 * step, LANES), lambda i: (i, 0)),
            interpret=interpret,
            name="brpc_word_bytes",
        )

    x = jax.lax.bitcast_convert_type(words, jnp.uint16).reshape(rows, LANES)
    s = jnp.asarray(_placement(), jnp.bfloat16)
    out = jax.lax.platform_dependent(
        x, s, tpu=call(False), default=call(True))
    return out.reshape(-1)
