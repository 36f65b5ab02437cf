"""Pallas ring collectives: the kernel-level RdmaEndpoint.

Reference mapping (SURVEY.md §3.5): RdmaEndpoint posts zero-copy sends from
registered blocks with a sliding window and waits CQ completions.  On TPU
the same machinery is a Pallas kernel:

  * ``pltpu.make_async_remote_copy``  = ibv_post_send over ICI, HBM → HBM
  * send/recv DMA semaphores          = completion queue events
  * capacity semaphore (all-reduce)   = the receiver's credit window
  * neighbour barrier semaphore       = the QP handshake: no remote copy
    starts before both ring neighbours have entered the kernel

Two kernels, each one hop per step around the logical ring:

  * ``ring_all_gather(x)``  — every device ends with every chunk
  * ``ring_all_reduce(x)``  — every device ends with the sum of all chunks

Payloads stay in HBM: the kernels take ``pl.ANY`` refs and touch them only
through DMA.  The all-reduce's add streams HBM → VMEM → HBM in double-
buffered row tiles, so VMEM use is 6 MiB at most whatever the chunk.

A program built for a TPU mesh is compiled by Mosaic; built for any other
mesh (the CPU test mesh) it runs under the Pallas TPU interpreter, which
emulates remote DMA and the semaphores.  The choice follows the platform of
the mesh the program is built for (see :func:`interpret_for`) and is logged.
The lax.ppermute-based path in ring.py remains the XLA-scheduled
alternative; this module is the hand-scheduled one for when the compiler's
schedule is the bottleneck.
"""
from __future__ import annotations

import math
import threading
from typing import Callable, Dict, Optional, Tuple

from ..butil import logging as log
from .mesh import IciMesh

_cache: Dict[Tuple, Callable] = {}
_cache_lock = threading.Lock()

_LANES = 128
_TILE_BYTES = 1 << 20       # one VMEM pipeline buffer of the all-reduce add


def interpret_for(devices, what: str):
    """``interpret=`` for a pallas_call built for ``devices``: False on a
    TPU mesh (Mosaic compiles it), the TPU interpreter's params elsewhere.
    Decided from the devices the program is built for — never from the
    process's default backend, so a program built for a described TPU
    topology takes the compiled branch on a host that has no chip.

    An interpreted mesh should leave ``jax.devices()[0]`` out: the
    interpreter's host callbacks run their own small jax ops there, and
    a device parked inside such a callback cannot serve them."""
    platform = devices[0].platform
    if platform == "tpu":
        return False
    import jax.experimental.pallas.tpu as pltpu
    log.info("pallas %s: mesh platform is %r, running in the Pallas TPU "
             "interpreter", what, platform)
    return pltpu.InterpretParams()


def neighbour_barrier(left, right):
    """Both ring neighbours are inside the kernel (their output buffers
    are live) before the first remote copy targets them.  On a two-device
    ring both are the same peer, signalled twice."""
    import jax.experimental.pallas.tpu as pltpu
    barrier = pltpu.get_barrier_semaphore()
    for nb in (left, right):
        pltpu.semaphore_signal(barrier, 1, device_id=nb,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_wait(barrier, 2)


def _tile_plan(chunk_shape, dtype) -> Tuple[int, int, int]:
    """The chunk as the kernels see it: (rows, cols, tile_rows) with cols
    a multiple of the 128 lanes, rows a multiple of tile_rows, tile_rows a
    multiple of the dtype's sublane tiling, and one (tile_rows, cols) tile
    about ``_TILE_BYTES``.  A chunk whose last dim is already lane-aligned
    keeps it (the reshape is free); anything else is flattened and padded."""
    import numpy as np
    itemsize = np.dtype(dtype).itemsize
    sub = 8 * max(1, 4 // itemsize)
    n_elems = math.prod(chunk_shape)
    cols = chunk_shape[-1] if (len(chunk_shape) >= 2
                               and chunk_shape[-1] % _LANES == 0) else _LANES
    rows = -(-n_elems // cols)
    tile_rows = max(sub, _TILE_BYTES // (cols * itemsize) // sub * sub)
    tile_rows = min(tile_rows, -(-rows // sub) * sub)
    rows = -(-rows // tile_rows) * tile_rows
    return rows, cols, tile_rows


def _to_tiles(x_chunk, rows: int, cols: int):
    import jax.numpy as jnp
    flat = x_chunk.reshape(-1)
    pad = rows * cols - flat.shape[0]
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows, cols)


def _from_tiles(tiles, chunk_shape):
    n_elems = math.prod(chunk_shape)
    lead = tiles.shape[:-2]
    return tiles.reshape(lead + (-1,))[..., :n_elems].reshape(
        lead + tuple(chunk_shape))


def _build_all_gather(mesh: IciMesh, chunk_shape, dtype, interpret):
    import jax
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    n = mesh.size
    ax = mesh.axis_name
    rows, cols, _ = _tile_plan(chunk_shape, dtype)

    def kernel(local_ref, out_ref, copy_sem, send_sem, recv_sems):
        my_id = lax.axis_index(ax)
        right = lax.rem(my_id + 1, n)
        left = lax.rem(my_id + n - 1, n)
        neighbour_barrier(left, right)
        own = pltpu.make_async_copy(local_ref, out_ref.at[my_id], copy_sem)
        own.start()
        own.wait()
        # step s forwards the row received at step s-1; every row of
        # out_ref is written exactly once, so no slot is ever reused and
        # the only flow control needed is the per-step recv semaphore
        for step in range(n - 1):
            row = lax.rem(my_id - step + n, n)
            rdma = pltpu.make_async_remote_copy(
                src_ref=out_ref.at[row],
                dst_ref=out_ref.at[row],
                send_sem=send_sem,
                recv_sem=recv_sems.at[step],
                device_id=right,
                device_id_type=pltpu.DeviceIdType.LOGICAL,
            )
            rdma.start()
            rdma.wait()

    def per_device(x_local):            # (1, *chunk)
        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((n, rows, cols), dtype),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.SemaphoreType.DMA(()),
                pltpu.SemaphoreType.DMA(()),
                pltpu.SemaphoreType.DMA((n - 1,)),
            ],
            compiler_params=pltpu.CompilerParams(has_side_effects=True,
                                                 collective_id=0),
            interpret=interpret,
            name="brpc_ring_all_gather",
        )(_to_tiles(x_local[0], rows, cols))
        return _from_tiles(out, chunk_shape)[None]

    return jax.jit(shard_map(per_device, mesh=mesh.mesh, in_specs=P(ax),
                             out_specs=P(ax), check_vma=False))


def _build_all_reduce(mesh: IciMesh, chunk_shape, dtype, interpret):
    import jax
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    n = mesh.size
    ax = mesh.axis_name
    rows, cols, tile_rows = _tile_plan(chunk_shape, dtype)
    n_tiles = rows // tile_rows

    def add_hbm(a_hbm, b_hbm, o_hbm, a_v, b_v, o_v, sems):
        """o = a + b over HBM refs, streamed through two-slot VMEM tiles:
        tile i+1 loads and tile i-1 stores while tile i adds."""
        def rows_of(i):
            return pl.ds(pl.multiple_of(i * tile_rows, tile_rows), tile_rows)

        def loads(i, slot):
            return (pltpu.make_async_copy(a_hbm.at[rows_of(i)], a_v.at[slot],
                                          sems.at[0, slot]),
                    pltpu.make_async_copy(b_hbm.at[rows_of(i)], b_v.at[slot],
                                          sems.at[1, slot]))

        def store(i, slot):
            return pltpu.make_async_copy(o_v.at[slot], o_hbm.at[rows_of(i)],
                                         sems.at[2, slot])

        for c in loads(0, 0):
            c.start()

        def tile_body(i, _):
            slot = lax.rem(i, 2)

            @pl.when(i + 1 < n_tiles)
            def _():
                for c in loads(i + 1, 1 - slot):
                    c.start()

            for c in loads(i, slot):
                c.wait()

            @pl.when(i >= 2)
            def _():
                store(i - 2, slot).wait()       # o_v[slot] is free again

            o_v[slot] = a_v[slot] + b_v[slot]
            store(i, slot).start()
            return 0

        lax.fori_loop(0, n_tiles, tile_body, 0)
        for i in range(max(0, n_tiles - 2), n_tiles):
            store(i, i % 2).wait()

    def kernel(local_ref, out_ref, recv_ref, send_sem, recv_sems, cap_sem,
               a_v, b_v, o_v, tile_sems):
        """Ring accumulate: the carry (``out_ref``) moves one hop per step
        and adds the local chunk at every stop; after n-1 hops every carry
        holds the sum.  ``recv_ref`` is a two-slot landing buffer; the
        right neighbour returns a credit on ``cap_sem`` once it has
        consumed a slot, and a slot is refilled only against that credit."""
        my_id = lax.axis_index(ax)
        right = lax.rem(my_id + 1, n)
        left = lax.rem(my_id + n - 1, n)
        neighbour_barrier(left, right)
        for step in range(n - 1):
            slot = step % 2
            if step >= 2:
                pltpu.semaphore_wait(cap_sem, 1)
            rdma = pltpu.make_async_remote_copy(
                src_ref=local_ref if step == 0 else out_ref,
                dst_ref=recv_ref.at[slot],
                send_sem=send_sem,
                recv_sem=recv_sems.at[slot],
                device_id=right,
                device_id_type=pltpu.DeviceIdType.LOGICAL,
            )
            rdma.start()
            rdma.wait()                 # our send left AND left's arrived
            add_hbm(recv_ref.at[slot], local_ref, out_ref,
                    a_v, b_v, o_v, tile_sems)
            if step + 2 < n - 1:        # this slot will be refilled
                pltpu.semaphore_signal(
                    cap_sem, 1, device_id=left,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)

    def per_device(x_local):            # (1, *chunk)
        out, _ = pl.pallas_call(
            kernel,
            out_shape=(jax.ShapeDtypeStruct((rows, cols), dtype),
                       jax.ShapeDtypeStruct((2, rows, cols), dtype)),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec(memory_space=pl.ANY)),
            scratch_shapes=[
                pltpu.SemaphoreType.DMA(()),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.REGULAR,
                pltpu.VMEM((2, tile_rows, cols), dtype),
                pltpu.VMEM((2, tile_rows, cols), dtype),
                pltpu.VMEM((2, tile_rows, cols), dtype),
                pltpu.SemaphoreType.DMA((3, 2)),
            ],
            compiler_params=pltpu.CompilerParams(has_side_effects=True,
                                                 collective_id=1),
            interpret=interpret,
            name="brpc_ring_all_reduce",
        )(_to_tiles(x_local[0], rows, cols))
        return _from_tiles(out, chunk_shape)[None]

    return jax.jit(shard_map(per_device, mesh=mesh.mesh, in_specs=P(ax),
                             out_specs=P(ax), check_vma=False))


def _cached(key: Tuple, builder: Callable) -> Callable:
    with _cache_lock:
        fn = _cache.get(key)
        if fn is None:
            fn = builder()
            _cache[key] = fn
        return fn


def _program(kind: str, builder: Callable, x, mesh: IciMesh,
             interpret: Optional[bool]) -> Callable:
    chunk_shape = tuple(x.shape[1:])
    key = (kind, tuple(mesh.devices), chunk_shape, str(x.dtype), interpret)

    def build():
        if interpret is None:
            mode = interpret_for(mesh.devices, f"ring {kind}")
        elif interpret:
            import jax.experimental.pallas.tpu as pltpu
            mode = pltpu.InterpretParams()
        else:
            mode = False
        return builder(mesh, chunk_shape, x.dtype, mode)
    return _cached(key, build)


def ring_all_gather(x, mesh: Optional[IciMesh] = None,
                    interpret: Optional[bool] = None):
    """x: (n, *chunk) sharded one row per device → (n, n, *chunk) sharded:
    device d's row holds every device's chunk.  ``interpret=None`` follows
    the mesh's platform (see :func:`interpret_for`)."""
    mesh = mesh or IciMesh.default()
    if mesh.size == 1:
        return x[:, None]
    return _program("all_gather", _build_all_gather, x, mesh, interpret)(x)


def ring_all_reduce(x, mesh: Optional[IciMesh] = None,
                    interpret: Optional[bool] = None):
    """x: (n, *chunk) sharded → (n, *chunk) sharded where every row is the
    elementwise sum over all rows."""
    mesh = mesh or IciMesh.default()
    if mesh.size == 1:
        return x
    return _program("all_reduce", _build_all_reduce, x, mesh, interpret)(x)
