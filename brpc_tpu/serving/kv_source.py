"""Zero-copy KV handoff sources (ISSUE 15): the prefill attachment's
bytes land DIRECTLY in :class:`~brpc_tpu.serving.PagedKvPool` blocks.

The PR-14 loader paid one full host-side materialization per session at
the pool boundary: ``attachment.to_bytes()`` (copy 1) → the layer-major
→ token-major transpose reshape (copy 2) → the pool's block fill
(copy 3).  For a 1536-token session LoadKv was the single largest
byte-moving operation left on the host, and it runs once per
prefill→decode handoff AND once per re-prefill retry around a kill.

Here the wire segments are wrapped as read-only views and scattered
STRAIGHT into the block views ``PagedKvPool.load_into`` reserves —
every payload byte is copied exactly once, whatever the plane:

  * **adopted** — host-byte segments consumed in place: the shm ring
    claim (a USER block wrapping the ring slot itself — PR 10's
    consume-to-release credit is the custody model: the slot retires
    when the consumed claim's last ref dies, which the loader forces
    right after the fill) and plain HOST/bulk-claim blocks;
  * **scattered** — device segments: a parked ``NativeAttachment``
    handle's segs are TAKEN raw (:meth:`NativeAttachment.take_segments`
    — no IOBuf inflation, the PR-12 exactly-one-exit custody holds) and
    loopback/device blocks viewed via ``np.asarray``, then scattered
    block-wise.  Segment boundaries need not align with pool block (or
    token, or layer) boundaries — the scatter loop handles straddling;
  * **materialized** — the PR-14 fallback, kept byte-for-byte behind
    ``serving_kv_adopt=False`` for same-run A/B.

Per-route truth rides ``serving_kv_load_{adopted,scattered,
materialized}`` Adders plus ``serving_kv_load_copy_bytes`` (host copy
PASSES × payload bytes: ≤1× on the adopted/scattered routes, 3× on the
materialized one), snapshot via :func:`kv_load_stats` — the /status
serving block and the tests' route assertions read exactly this.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional

import numpy as np

from .. import bvar
from ..butil import debug_sync as _dbg
from ..butil import flags as _flags
from ..butil.iobuf import DEVICE, IOBuf
from ..ici import route as _route

_flags.define_flag(
    "serving_kv_adopt", True,
    "land prefill->decode KV attachment bytes directly in PagedKvPool "
    "blocks (shm claims consumed in place, native att segments taken "
    "block-wise; one copy pass).  False restores the PR-14 "
    "materialize-then-load path byte-for-byte for same-run A/B")

ADOPTED = "adopted"
SCATTERED = "scattered"
MATERIALIZED = "materialized"


def adopt_enabled() -> bool:
    return bool(_flags.get_flag("serving_kv_adopt"))


class _KvLoadStats:
    """Route-assertion surface for every KV load in the process: which
    path carried each session's bytes and how many host copy passes
    they paid.  Adders are write-local; the per-route byte ledger is
    the guarded half."""

    _GUARDED_BY = {"_route_bytes": "_lock"}

    def __init__(self):
        self._lock = _dbg.make_lock("kv_source._KvLoadStats._lock")
        self._route_bytes: Dict[str, int] = {}
        self.adopted = bvar.Adder("serving_kv_load_adopted")
        self.scattered = bvar.Adder("serving_kv_load_scattered")
        self.materialized = bvar.Adder("serving_kv_load_materialized")
        self.copy_bytes = bvar.Adder("serving_kv_load_copy_bytes")

    def record(self, route: str, payload_bytes: int,
               copy_passes: int) -> None:
        {ADOPTED: self.adopted, SCATTERED: self.scattered,
         MATERIALIZED: self.materialized}[route] << 1
        self.copy_bytes << payload_bytes * copy_passes
        with self._lock:
            self._route_bytes[route] = \
                self._route_bytes.get(route, 0) + payload_bytes

    def snapshot(self) -> dict:
        with self._lock:
            by_route = dict(self._route_bytes)
        return {
            "adopted": self.adopted.get_value(),
            "scattered": self.scattered.get_value(),
            "materialized": self.materialized.get_value(),
            "copy_bytes": self.copy_bytes.get_value(),
            "payload_bytes_by_route": by_route,
        }


stats = _KvLoadStats()


def kv_load_stats() -> dict:
    """{route: loads, copy_bytes, payload_bytes_by_route} — the /status
    serving block's ``kv_load`` field, rpc_press's serving summary, and
    the tests' per-call route assertion."""
    return stats.snapshot()


def _write_flat(dest: np.ndarray, off: int, chunk: np.ndarray) -> None:
    """Write a contiguous 1-D ``chunk`` into the strided 2-D ``dest``
    starting at row-major flat offset ``off`` — the straddle primitive:
    head partial row, vectorized middle, tail partial row."""
    ncols = dest.shape[1]
    n = chunk.shape[0]
    i = 0
    r, c = divmod(off, ncols)
    if c:
        take = min(ncols - c, n)
        dest[r, c:c + take] = chunk[:take]
        i = take
        r += 1
    full = (n - i) // ncols
    if full:
        dest[r:r + full] = chunk[i:i + full * ncols].reshape(full, ncols)
        i += full * ncols
        r += full
    if i < n:
        dest[r, :n - i] = chunk[i:]


class WireKvSource:
    """One LoadKv payload as ordered read-only uint8 views over the wire
    segments, plus the ``fill`` that scatters the layer-major wire
    layout ``(layers, seq_len, dmodel)`` into the pool's token-major
    block views — each payload byte read once, written once.

    The dominant single-segment shape (one device array / one ring
    claim) runs ONE strided transpose-assignment per pool block; the
    general shape walks (block × layer) destination slices through the
    segment list, splitting at segment boundaries wherever they fall
    (mid-block, mid-token, even mid-layer-row).  Instances are
    single-use: ``fill`` once, then the loader drops the object so
    claim credit / array refs release deterministically.  A ``fill``
    after :meth:`release` raises loudly — since ISSUE 16 the pool runs
    fills OUTSIDE its lock, so a stale callback invoked late must fail
    typed instead of scattering zero segments and publishing a table
    over stale arena bytes."""

    __slots__ = ("route", "layers", "seq_len", "dmodel", "_segs",
                 "_starts")

    def __init__(self, segments: List[np.ndarray], route: str,
                 layers: int, seq_len: int, dmodel: int):
        self.route = route
        self.layers = layers
        self.seq_len = seq_len
        self.dmodel = dmodel
        self._segs = segments
        starts = [0]
        for s in segments:
            starts.append(starts[-1] + s.shape[0])
        self._starts = starts

    @property
    def total(self) -> int:
        return self._starts[-1]

    def fill(self, views: List[np.ndarray]) -> None:
        """The ``PagedKvPool.load_into`` fill callback (may run outside
        the pool lock; it only writes the reserved views)."""
        if not self._segs:
            raise RuntimeError(
                "WireKvSource.fill after release(): sources are "
                "single-use — build a fresh source per load")
        L, D = self.layers, self.dmodel
        if len(self._segs) == 1:
            wire = self._segs[0].reshape(L, self.seq_len, D)
            t0 = 0
            for v in views:
                n = v.shape[0]
                # one strided copy per block: wire (L, n, D) slab →
                # token-major (n, L, D) rows, transposed in-assignment
                v.reshape(n, L, D)[...] = \
                    wire[:, t0:t0 + n, :].transpose(1, 0, 2)
                t0 += n
            return
        t0 = 0
        for v in views:
            n = v.shape[0]
            for layer in range(L):
                self._copy_rows(layer, t0, n,
                                v[:, layer * D:(layer + 1) * D])
            t0 += n

    def _copy_rows(self, layer: int, t0: int, n: int,
                   dest: np.ndarray) -> None:
        """Copy layer ``layer``'s bytes for tokens [t0, t0+n) into the
        strided dest (n, dmodel) view, walking the segment list."""
        D = self.dmodel
        pos = (layer * self.seq_len + t0) * D
        need = n * D
        i = bisect.bisect_right(self._starts, pos) - 1
        off = 0
        while need > 0:
            seg = self._segs[i]
            a = pos + off - self._starts[i]
            take = min(seg.shape[0] - a, need)
            _write_flat(dest, off, seg[a:a + take])
            off += take
            need -= take
            i += 1

    def release(self) -> None:
        """Drop the segment views NOW: the shm ring claim's
        consume-to-release credit returns (and taken device arrays
        free) at a deterministic point instead of a later GC."""
        self._segs = []
        self._starts = [0]


def _load_route(sock, cls: str, nbytes: int) -> str:
    """Adopt-vs-scatter through the SHARED route table (ISSUE 17) —
    the payload class here is the same HOST/DEVICE split that orders
    ``route.candidates()``, not a private kind ladder:

      * DEVICE-class bytes always scatter (the D2H crossing is the
        wire transfer itself, never a host copy pass);
      * HOST-class bytes adopt in place, UNLESS the carrying socket is
        known and its plane-health records say every descriptor plane
        (shm, bulk) has left UP — then the load is recorded SCATTERED,
        so the route-assertion surface never claims an in-place
        adoption rode a healthy plane it didn't.  Custody is safe on
        both labels (a retired ring keeps claimed slots alive until
        the last ref dies); what the consultation changes is that the
        counters tell the truth about plane state at load time.
    """
    if cls == _route.DEVICE:
        return SCATTERED
    if sock is not None and _route.SHM not in (
            planes := _route.candidates(sock, _route.HOST, nbytes)) \
            and _route.BULK not in planes:
        return SCATTERED
    return ADOPTED


def wire_source(att: IOBuf, layers: int, seq_len: int,
                dmodel: int, sock=None) -> WireKvSource:
    """Build the scatter source for one LoadKv attachment.  The VIEW
    mechanics stay per-block (custody is what the attachment is); the
    adopt-vs-scatter ROUTE comes from :func:`_load_route`, which asks
    ``route.candidates()`` / plane-health when ``sock`` (the fabric
    socket that carried the request) is supplied:

      * an untouched parked ``NativeAttachment`` → ``take_segments()``
        (the custody exit that never builds IOBuf blocks), DEVICE
        class;
      * a plain IOBuf → zero-copy views per backing block: HOST/USER
        blocks (shm ring claims, bulk claims, inline bytes) viewed via
        ``np.frombuffer``; DEVICE blocks (loopback / an
        already-materialized native view) via ``np.asarray`` (the D2H
        crossing is the wire transfer itself, not a host copy pass).
    """
    take = getattr(att, "take_segments", None)
    if take is not None and att.parked:
        segs = []
        # arrays re-emerging from native custody are FLAT UINT8 by
        # construction — append_device_array validates shape/dtype at
        # entry and the unchecked path only re-posts registry arrays
        # that entered through it — so element counts ARE byte counts
        for arr, nbytes in take():
            view = np.asarray(arr)
            if view.shape[0] != nbytes:
                view = view[:nbytes]
            segs.append(view)
        return WireKvSource(
            segs, _load_route(sock, _route.DEVICE, len(att)),
            layers, seq_len, dmodel)
    segs = []
    dev = False
    for i in range(att.backing_block_num()):
        r = att.backing_block(i)
        b = r.block
        if b.kind == DEVICE:
            # DEVICE blocks are flat uint8 (enforced at
            # append_device_array), so ref offset/length index bytes
            dev = True
            if r.offset == 0 and r.length == b.size:
                # whole-block (the dominant shape): asarray the array
                # itself so repeated sends hit jax's cached host value
                seg = np.asarray(b.data)
            else:
                # partial ref (IOBuf cut ops move refs, never bytes):
                # slice ON DEVICE first so only the referenced bytes
                # pay the D2H crossing, not the whole backing array
                seg = np.asarray(b.data[r.offset:r.offset + r.length])
        else:
            seg = np.frombuffer(b.data, np.uint8)[
                r.offset:r.offset + r.length]
        segs.append(seg)
    return WireKvSource(
        segs,
        _load_route(sock, _route.DEVICE if dev else _route.HOST,
                    len(att)),
        layers, seq_len, dmodel)


def load_wire_attachment(pool, att: IOBuf, session: str, seq_len: int,
                         layers: int, dmodel: int, *, last_token: int,
                         tenant: str = "",
                         priority: Optional[int] = None,
                         sock=None):
    """The whole zero-copy handoff in one call: build the source, let
    the pool reserve-and-fill (outside the pool lock by default since
    ISSUE 16, so concurrent LoadKv scatters proceed in parallel),
    record the route, and release the segment views (ring credit back,
    device refs dropped) whether the load committed or aborted.  Pool
    refusals (PoolSaturated / SessionBusy — the latter now also fired
    by the commit-time re-check when a raced loader's entry got
    pinned mid-fill) propagate for the RPC layer's shed mapping."""
    src = wire_source(att, layers, seq_len, dmodel, sock=sock)
    try:
        want = seq_len * layers * dmodel
        if src.total != want:
            raise ValueError(
                f"kv wire segments hold {src.total} bytes, "
                f"descriptor said {want}")
        s = pool.load_into(session, seq_len, src.fill,
                           last_token=last_token, tenant=tenant,
                           priority=priority)
    finally:
        src.release()
    stats.record(src.route, seq_len * layers * dmodel, 1)
    return s


def load_token_major_attachment(pool, att: IOBuf, session: str,
                                seq_len: int, *, last_token: int,
                                tenant: str = "",
                                priority: Optional[int] = None,
                                sock=None):
    """The KV MIGRATION ingest (ISSUE 19): the payload is already
    token-major ``(seq_len, bytes_per_token)`` — a pool-to-pool
    transfer ships the source pool's row layout verbatim, so there is
    no layer transpose to undo.  Declaring ``layers=1`` with
    ``dmodel=bytes_per_token`` makes the wire layout identical to the
    pool's block rows and the scatter one strided copy per extent;
    everything else (route accounting, segment custody, the pool's
    reserve/fill-outside-the-lock/commit with SessionBusy/saturation
    sheds) is byte-for-byte :func:`load_wire_attachment`."""
    return load_wire_attachment(
        pool, att, session, seq_len, 1, pool.options.bytes_per_token,
        last_token=last_token, tenant=tenant, priority=priority,
        sock=sock)
