"""Native ici:// plane — Python control plane over native/rpc.cpp's ici
datapath.

This is the fusion VERDICT r2/r3 task #1 demanded: the full unary hot path
(window reservation → TRPC frame encode → queue hop → dispatch →
correlation wake) runs in C++; Python appears on the datapath ONLY for
device-ref relocation (``jax.device_put``, the HBM→HBM ICI transfer), and
only when a ref is not already resident on the target chip.  Reference
anchors: the wait-free write discipline src/brpc/socket.cpp:1584-1596 and
the RDMA endpoint's zero-copy post + completion custody
src/brpc/rdma/rdma_endpoint.cpp:771,926.

Three pieces:

* **device-ref registry** — keeps jax arrays alive while their keys are in
  native custody.  Custody rules (must mirror native/rpc.cpp exactly):
  a key given to native exits custody either INTO Python (``take`` at an
  upcall or response boundary) or via the release upcall on drop paths.
* **ServerBinding** — attaches an ``rpc.Server``'s method table to a
  native listener; per-request upcall parses + dispatches user code
  (inline or on a tasklet, mirroring InputMessenger's dispatch).
* **ChannelBinding** — the client side used by ``rpc.Channel`` when the
  target device has a native listener in this process.
"""
from __future__ import annotations

import ctypes
import itertools
import threading
import time as _time
from typing import Any, Dict, List, Optional, Tuple

from .. import bvar
from ..butil import debug_sync as _dbg
from ..butil import flags as _flags
from ..butil import layer_span as _lspan
from ..butil import logging as log
from ..butil import native
from ..butil.iobuf import IOBuf, DEVICE
from ..butil.native import IciCallOut, IciRespC, IciSegC, _ICI_BATCH_FN, \
    _ICI_RELEASE_FN, _ICI_RELOCATE_FN
from ..rpc import errors
from ..rpc import request_context as _reqctx

_U8P = ctypes.POINTER(ctypes.c_uint8)

# the fused paths read the request-context slot without the
# current()/scope() call frames — same thread-local the module owns
_reqctx_tls = _reqctx._tls

# call_fused returns this when the call must re-route to the Python
# plane (frame too large / hedging configured / dead-conn fallback):
# distinct from None, which is a legitimate failed-call result
FUSED_FALLTHROUGH = object()

# the raw C string_at (stable since 2.5): the public wrapper is a
# Python frame per read, and the fused paths read 2-3 borrowed buffers
# per RPC
_string_at = ctypes._string_at

_fused_ffi = None


def _fused_call_binding():
    """Fused-path FFI binding for call4 whose payload/att-host argtypes
    are ``c_char_p`` — bytes objects pass straight through (ABI-identical
    pointer) instead of paying two ``ctypes.cast`` frames per call.
    Bound on a SEPARATE CDLL handle so the legacy ``call`` keeps its
    POINTER(c_uint8) binding byte-for-byte."""
    global _fused_ffi
    if _fused_ffi is None:
        lib = native.load()
        lib2 = ctypes.CDLL(lib._name)
        f4 = lib2.brpc_tpu_ici_call4
        f4.restype = ctypes.c_uint64
        f4.argtypes = [ctypes.c_uint64, ctypes.c_char_p, ctypes.c_char_p,
                       ctypes.c_uint64, ctypes.c_char_p, ctypes.c_uint64,
                       ctypes.POINTER(IciSegC), ctypes.c_uint64,
                       ctypes.c_int64, ctypes.c_int64, ctypes.c_char_p,
                       ctypes.c_int64, ctypes.POINTER(IciCallOut)]
        _fused_ffi = f4
    return _fused_ffi

# Batched one-struct upcall tuning (native/rpc.cpp enqueue_batch): the
# drainer takes up to max_batch requests per GIL crossing; an arrival
# whose queue head has aged past batch_age_us steals the queue and
# delivers concurrently, so p99 never pays more than the age bound for
# batching.  Applied to every new ServerBinding.
_flags.define_flag("ici_upcall_max_batch", 64,
                   "max Python-handler requests delivered per batched "
                   "upcall (one GIL crossing) on the native ici plane")
_flags.define_flag("ici_upcall_batch_age_us", 50,
                   "age bound (us) before a queued ici request is "
                   "stolen from a busy drainer and delivered "
                   "concurrently — bounds the p99 cost of batching")

# Native attachment custody (ISSUE 12): device-seg lists park in a
# NATIVE att table and move as one opaque handle — the handler tier
# receives a ready zero-copy IOBuf view (NativeAttachment) instead of
# walking seg descriptors through the registry twice per RPC.  A
# host-mixed attachment alone keeps the take-during-upcall walk
# (build_attachment_from_c): its host spans interleave with the device
# segs positionally.

# Fused dispatch (ISSUE 13): the per-RPC interpreter-frame chain on the
# native-ici hot path collapses into single flat code objects —
# _process/_execute/done fuse into ServerBinding._process_fused +
# _FusedDone on the server, Channel.call_method's native preamble +
# screens + ChannelBinding.call fuse into ChannelBinding.call_fused on
# the client, with per-method dispatch resolved ONCE per (listener,
# method) instead of per call.  Off = the PR-12 frame chain
# byte-for-byte (the A/B leg).  Snapshot at bind/connect time.
_flags.define_flag("ici_fused_dispatch", True,
                   "collapse the native-ici per-RPC dispatch chain "
                   "into fused code objects (server process/execute/"
                   "done and the client call path); off restores the "
                   "unfused PR-12 frame chain for A/B")

# hot-path module handles, resolved once at first call: the per-call
# `from x import y` dance measured ~1 us/call on the fast plane (the
# lazy-at-call-time form exists only to dodge import cycles at load)
_hot = None


def _hot_modules():
    global _hot
    if _hot is None:
        from ..bthread import scheduler
        from ..rpc import fault_injection
        from . import transport
        _hot = (fault_injection, scheduler, transport)
    return _hot


# tpu_std's stage-decomposition hooks (tpu_std_server_* recorders); the
# ici handler tier feeds the SAME recorders so the per-stage p50s
# decompose the deployed-common path (lazy: policy<->ici import cycle)
_stage_hot = None


def _stage_modules():
    global _stage_hot
    if _stage_hot is None:
        from ..policy.tpu_std import (_enter_handler, _record_stage,
                                      _stages_on)
        _stage_hot = (_stages_on, _record_stage, _enter_handler)
    return _stage_hot


_cntl_pool = None


def _controller_pool():
    global _cntl_pool
    if _cntl_pool is None:
        from ..rpc.controller import server_controller_pool
        _cntl_pool = server_controller_pool
    return _cntl_pool


# ---------------------------------------------------------------------
# device-ref registry
# ---------------------------------------------------------------------

class _DevRegistry:
    """key → jax.Array, alive while the key is in native custody.

    Lock-free by construction: keys come from itertools.count (atomic in
    CPython) and every table op is a single GIL-atomic dict operation —
    put/take pairs on the RPC hot path used to cost four lock
    acquisitions per attachment round trip.  A key is written exactly
    once and removed exactly once (the exactly-one-exit custody
    invariant), so there is no read-modify-write to race."""

    # fablint custody contract (ISSUE 20): every registered device ref
    # leaves through take (Python assumes custody) or release (drop);
    # keys parked in wire segments / IOBuf handles carry custody-moved
    # markers at the put site naming the structure that owes the exit.
    _CUSTODY = {"put": ("take", "release")}

    def __init__(self):
        self._m: Dict[int, Any] = {}
        self._next = itertools.count(1).__next__

    def put(self, arr) -> int:
        key = self._next()
        self._m[key] = arr
        return key

    def peek(self, key: int):
        return self._m.get(key)

    def take(self, key: int):
        """Remove and return — the Python side assumes custody."""
        return self._m.pop(key, None)

    def release(self, key: int) -> None:
        self._m.pop(key, None)

    def live(self) -> int:
        return len(self._m)


_registry = _DevRegistry()


def registry() -> _DevRegistry:
    return _registry


# ---------------------------------------------------------------------
# hooks (relocation = the only Python on the datapath)
# ---------------------------------------------------------------------

# relocations that threw: the RPC fails (native reads the 0), and the
# count stays visible in /vars after the log line scrolled away
_g_relocate_failures = bvar.Adder("ici_native_relocate_failures")

def _relocate(key: int, target_dev: int) -> int:
    """Move the array behind ``key`` to mesh device ``target_dev``; returns
    a NEW key for the moved array (native releases the old one) or the same
    key when already resident.  0 = failure (native fails the RPC).

    Payloads at/above ``ici_device_plane_threshold`` cross through the
    device plane's compiled transfer program (post_send + rendezvous —
    the no-host datapath); smaller or refused ones keep device_put."""
    try:
        import jax
        from .mesh import IciMesh
        arr = _registry.peek(key)
        if arr is None:
            return 0
        mesh = IciMesh.default()
        target = mesh.device(target_dev)
        if not hasattr(arr, "devices"):
            # host-delivered fabric bulk payload (a ctypes-backed numpy
            # view over the native receive buffer) being forwarded into
            # an in-process call: detach into an owned copy first —
            # device_put zero-copy ALIASES such views WITHOUT retaining
            # them, and the native pool recycles the buffer under the
            # alias (same discipline as transport.py _relocate)
            import numpy as np
            arr = np.array(arr, copy=True)
        else:
            try:
                if target in arr.devices():
                    return key                   # resident: pure ref pass
            except Exception:
                pass
            from . import device_plane as _dp
            nbytes = int(arr.shape[0]) if arr.ndim == 1 else 0
            if nbytes and _dp.eligible(nbytes):
                src_idx = _dp.mesh_index_of(arr, mesh)
                if src_idx >= 0 and src_idx != target_dev:
                    try:
                        t = _dp.plane().transfer_local(arr, src_idx,
                                                       target_dev)
                        return _registry.put(t.out)
                    except _dp.DevicePlaneError:
                        pass    # counted (and, for a build error, logged
                        #         at error) by the plane; device_put path
        moved = jax.device_put(arr, target)      # HBM→HBM over ICI
        return _registry.put(moved)
    except Exception as e:                       # never raise across ctypes
        _g_relocate_failures << 1
        log.error("ici relocate(key=%d, dev=%d) failed: %s: %s", key,
                  target_dev, type(e).__name__, e)
        return 0                                 # native fails the RPC


def _release(key: int) -> None:
    _registry.release(key)


_hooks_installed = False
_hooks_lock = threading.Lock()
_relocate_cb = None
_release_cb = None


def ensure_hooks() -> bool:
    """Install the relocate/release upcalls once per process."""
    global _hooks_installed, _relocate_cb, _release_cb, _att_fns
    lib = native.load()
    if lib is None:
        return False
    with _hooks_lock:
        if not _hooks_installed:
            _relocate_cb = _ICI_RELOCATE_FN(_relocate)
            _release_cb = _ICI_RELEASE_FN(_release)
            lib.brpc_tpu_ici_set_hooks(_relocate_cb, _release_cb)
            # att-custody handle ops, bound once (the view's custody
            # exits must not pay native.load()'s lock)
            _att_fns = (lib.brpc_tpu_ici_att_take,
                        lib.brpc_tpu_ici_att_dispose)
            _hooks_installed = True
    return True


def available() -> bool:
    return native.available()


def has_listener(device_id: int) -> bool:
    lib = native.load()
    return lib is not None and \
        lib.brpc_tpu_ici_has_listener(device_id) == 1


# Python-side view of live ServerBindings, for properties native cannot
# answer (dispatch mode).  device_id -> ServerBinding.
_server_bindings: Dict[int, "ServerBinding"] = {}
_server_bindings_lock = threading.Lock()


def listener_dispatch_inline(device_id: int,
                             method: Optional[str] = None) -> Optional[bool]:
    """True when the in-process listener at ``device_id`` answers
    ``method`` INLINE on the caller's thread — usercode_inline servers
    (every method), or the compiled echo tier (that method is served
    fully in C regardless of the server's dispatch mode).  False when
    the handler parks on a tasklet, None when unknown.  Fan-out issuers
    use this: against an inline answer a sub-call-per-tasklet buys no
    concurrency (the work runs in the caller's stack either way) and
    costs a scheduling hop."""
    with _server_bindings_lock:
        b = _server_bindings.get(device_id)
    if b is None:
        return None
    if method is not None and method in b._echo_methods:
        return True
    return bool(getattr(b._server.options, "usercode_inline", False))


# ---------------------------------------------------------------------
# IOBuf ⇄ (att_host, segs) marshalling
# ---------------------------------------------------------------------

def split_attachment(buf: IOBuf) -> Tuple[bytes, list]:
    """Decompose an attachment IOBuf into the host byte-stream plus the
    ordered segment descriptor list — PLAIN TUPLES (key, nbytes, dev,
    is_dev), not ctypes structs: a ctypes Structure construction per seg
    measured ~0.8 µs, and the FFI boundary fills its arrays from the
    tuples with plain field stores.  Device blocks are registered (native
    custody begins); host runs merge into one descriptor each."""
    if buf.backing_block_num() == 1:
        # the dominant fast-plane shape: one whole device block
        r = buf.backing_block(0)
        if (r.block.kind == DEVICE and not r.offset
                and r.length == r.block.size):
            arr = r.block.data
            return b"", [(_registry.put(arr), r.length,
                          _device_index(arr), 1)]
    host_parts: List[bytes] = []
    segs: list = []
    run = 0
    for i in range(buf.backing_block_num()):
        r = buf.backing_block(i)
        if r.block.kind == DEVICE:
            if run:
                segs.append((0, run, 0, 0))
                run = 0
            arr = r.block.data
            if r.offset or r.length != len(arr):
                arr = arr[r.offset:r.offset + r.length]
            dev = _device_index(arr)
            segs.append((_registry.put(arr), r.length, dev, 1))
        else:
            host_parts.append(bytes(r.block.host_view(r.offset, r.length)))
            run += r.length
    if run:
        segs.append((0, run, 0, 0))
    return b"".join(host_parts), segs


def fill_seg_array(segs) -> "ctypes.Array":
    """(IciSegC * n) array from split_attachment's tuple descriptors
    (tolerates IciSegC instances for callers that build their own)."""
    arr = (IciSegC * len(segs))()
    for j, sg in enumerate(segs):
        if type(sg) is tuple:
            e = arr[j]
            e.key, e.nbytes, e.dev, e.is_dev = sg
        else:
            arr[j] = sg
    return arr


def build_attachment_from_c(att_host: bytes, segs_p, nsegs: int) -> IOBuf:
    """build_attachment reading the ctypes seg array DIRECTLY — skips the
    per-seg IciSegC copy the list-based form needs (one ctypes Structure
    construction per seg measured ~0.8 µs on the handler tier).

    EXCEPTION-SAFE (ISSUE 12 satellite): the upcall contract says the
    walk TAKES every device key — native clears its seg list when the
    upcall returns, so a mid-walk failure used to strand every
    not-yet-walked key in the registry forever (already-taken keys ride
    the dropped buf; the REMAINING ones had no owner left).  On any
    failure the un-walked device keys are released before re-raising."""
    buf = IOBuf()
    off = 0
    take = _registry.take
    i = 0
    try:
        while i < nsegs:
            s = segs_p[i]
            n = s.nbytes
            if s.is_dev:
                arr = take(s.key)
                if arr is None:
                    raise KeyError(f"ici device ref {s.key} missing")
                buf.append_device_array_unchecked(arr, n)
            else:
                buf.append(att_host[off:off + n])
                off += n
            i += 1
    except BaseException:
        release = _registry.release
        for j in range(i + 1, nsegs):
            s = segs_p[j]
            if s.is_dev:
                release(s.key)
        raise
    return buf


# native att-custody handle ops, bound once at ensure_hooks (the hot
# path must not pay native.load()'s lock per call): (take, dispose)
_att_fns = None


class NativeAttachment(IOBuf):
    """Zero-copy attachment view backed by NATIVE custody (ISSUE 12).

    The device-seg list this buffer represents is PARKED in the native
    att table under ``_h``; the keys stay in the device-ref registry
    (arrays alive, custody native).  Construction costs one small
    object — no registry ops, no Block/BlockRef builds, no seg walk.
    The handle exits custody EXACTLY ONCE, by whichever happens first:

      * pass-through — ``cntl.response_attachment = view`` hands the
        handle back to native in the respond struct (the echo shape:
        zero Python walks end to end);
      * materialization — any structural touch (``backing_block_num``,
        ``to_bytes``, appending it into another IOBuf, ...) inflates
        real DEVICE blocks: the registry keys are taken into Python
        custody and the native entry is dropped without release;
      * dispose — Controller pool-recycle (server side), ``__del__``
        (client side / safety net): native releases every parked key.

    ``len()``/``size()``/``empty()`` answer from the descriptor total
    WITHOUT materializing — presence checks stay free.  Like IOBuf
    itself, instances are not thread-safe."""

    __slots__ = ("_h", "_total", "_seg_meta", "_mat")

    def __init__(self, handle: int, total: int, seg_meta: tuple):
        # deliberately NOT calling IOBuf.__init__: _refs/_size stay
        # unset until materialization — __getattr__ inflates on the
        # first structural touch
        self._h = handle
        self._total = total
        self._seg_meta = seg_meta      # ((key, nbytes, dev), ...)
        self._mat = False

    # ---- lazy inflation ----------------------------------------------
    def __getattr__(self, name):
        if name in ("_refs", "_size"):
            self._materialize()
            return object.__getattribute__(self, name)
        raise AttributeError(name)

    def _materialize(self) -> None:
        IOBuf.__init__(self)           # sets _refs/_size
        self._mat = True
        h = self._h
        if not h:
            return                     # surrendered/disposed: empty
        self._h = 0
        for arr, nbytes in self._take_parked(h):
            self.append_device_array_unchecked(arr, nbytes)

    def _take_parked(self, h: int) -> list:
        """Consume the parked native entry for ``h`` plus its registry
        keys, returning ``[(array, nbytes), ...]`` — the ONE custody
        walk behind both exits-into-Python (``_materialize`` and
        ``take_segments``).  On any failure every not-yet-taken key is
        released before the raise (the view can no longer exit, so a
        stranded key would pin its array forever); releasing keys a
        native dispose already dropped is a no-op, never a
        double-free."""
        fns = _att_fns
        metas = self._seg_meta
        if fns is None or fns[0](h) < 0:    # att_take consumes the entry
            release = _registry.release
            for key, _n, _d in metas:
                release(key)
            raise KeyError(f"ici native att handle {h} missing")
        take = _registry.take
        out = []
        for i, (key, nbytes, _dev) in enumerate(metas):
            arr = take(key)
            if arr is None:
                release = _registry.release
                for k2, _n2, _d2 in metas[i + 1:]:
                    release(k2)
                raise KeyError(f"ici device ref {key} missing")
            out.append((arr, nbytes))
        return out

    # ---- cheap overrides (no materialization) ------------------------
    def __len__(self) -> int:
        return self._total if not self._mat else self._size

    def size(self) -> int:
        return self.__len__()

    def empty(self) -> bool:
        return self.__len__() == 0

    def __repr__(self) -> str:
        if self._mat:
            return IOBuf.__repr__(self)
        return (f"NativeAttachment(size={self._total}, "
                f"handle={self._h:#x}, lazy)")

    @property
    def parked(self) -> bool:
        """True while the seg list is still in NATIVE custody (never
        materialized, handle not yet exited) — the predicate outside
        callers (the serving KV loader) route on instead of reaching
        into the view's slots."""
        return not self._mat and bool(self._h)

    # ---- custody exits -----------------------------------------------
    def take_segments(self) -> list:
        """Fourth custody exit (ISSUE 15): take the parked segs into
        Python as raw ``(array, nbytes)`` pairs WITHOUT building IOBuf
        blocks — the serving KV scatter-loader's surface (the bytes go
        straight into pool blocks, so Block/BlockRef construction would
        be pure overhead).  Consumes the handle and the registry keys
        (exactly-one-exit holds: afterwards the view reads as an EMPTY
        IOBuf and pool-recycle/GC disposes are no-ops).  On a custody
        bug mid-walk the remaining keys are released before the raise,
        same as materialization."""
        if self._mat or not self._h:
            raise ValueError(
                "take_segments: view already materialized or exited")
        IOBuf.__init__(self)           # _refs/_size: the view is now an
        self._mat = True               # inert empty buffer
        h = self._h
        self._h = 0
        return self._take_parked(h)

    def _surrender_native(self) -> int:
        """Hand the parked entry back to native (the response pass-
        through): returns the handle and forgets it — the respond
        struct now owns the exit.  0 when there is nothing to pass."""
        if self._mat:
            return 0
        h = self._h
        self._h = 0
        return h

    def _dispose_native(self) -> None:
        """Drop path (pool recycle / reject): native releases every
        parked key.  Idempotent — a surrendered or materialized view
        holds no handle."""
        h = self._h
        if h:
            self._h = 0
            fns = _att_fns
            if fns is not None:
                fns[1](h)

    def __del__(self):                 # noqa: D105 — safety net: a view
        try:                           # GC'd unexited must not strand
            self._dispose_native()     # keys in the registry forever
        except Exception:
            pass


class ResponseAttachment(NativeAttachment):
    """The server/client response-attachment default (installed as
    ``Controller.response_attachment``'s lazy factory once this module
    loads): a plain IOBuf until a WHOLE, untouched ``NativeAttachment``
    view is appended while this buffer is still empty — the PR-8 echo
    idiom ``cntl.response_attachment.append(cntl.request_attachment)``
    — which ADOPTS the parked handle instead of materializing it
    (ISSUE 13 satellite): the respond path then passes the handle back
    with zero Python seg walks, byte-identical to the assignment
    idiom.  Any structural touch after adoption inflates through the
    inherited lazy discipline; exactly-one-exit holds (pass-through at
    respond, or dispose at pool recycle / GC)."""

    __slots__ = ()

    def __init__(self):
        IOBuf.__init__(self)
        self._h = 0
        self._total = 0
        self._seg_meta = ()
        self._mat = True               # a real (empty) buffer until adopted

    def append(self, data) -> None:
        if (self._mat and isinstance(data, NativeAttachment)
                and not data._mat and data._h and not self._refs):
            # adopt: the handle moves here and THIS buffer becomes the
            # lazy view — the donor is left surrendered (same aliasing
            # the assignment idiom has).  The real refs/size slots are
            # deleted so the first structural touch re-inflates through
            # NativeAttachment.__getattr__.
            self._h = data._h
            data._h = 0
            self._total = data._total
            self._seg_meta = data._seg_meta
            self._mat = False
            del self._refs, self._size
            return
        IOBuf.append(self, data)


def _install_response_attachment_factory() -> None:
    """Swap Controller's lazy response-attachment factory to
    ResponseAttachment — process-wide, on every call plane (the wire
    and loopback planes see a plain IOBuf in all but the adoption
    shape, which only the native custody tier can produce)."""
    from ..rpc.controller import Controller
    vars(Controller)["response_attachment"].factory = ResponseAttachment


_install_response_attachment_factory()


def _seg_meta_from_req(r, nsegs: int):
    """((key, nbytes, dev), ...) + total bytes for a handle-carrying
    request struct: the dominant 1-seg shape reads the inline seg0
    mirror (plain struct fields); longer lists walk the parked segs."""
    if nsegs == 1:
        n = r.seg0_nbytes
        return ((r.seg0_key, n, r.seg0_dev),), n
    segs_p = r.segs
    total = 0
    meta = []
    for i in range(nsegs):
        s = segs_p[i]
        meta.append((s.key, s.nbytes, s.dev))
        total += s.nbytes
    return tuple(meta), total


def att_table_live() -> int:
    """Parked native att entries (census surface); 0 when the native
    core is unavailable."""
    lib = native.load()
    if lib is None or not hasattr(lib, "brpc_tpu_ici_att_count"):
        return 0
    return int(lib.brpc_tpu_ici_att_count())


# id(arr) -> (mesh generation, mesh index), evicted by a finalizer when
# the array dies (the id is unique until then).  A steady workload
# re-posts the same payload arrays, and arr.device + the mesh lookup
# costs microseconds per call (host clock).  An array cannot change
# residence in place, but the MESH can be swapped (IciMesh.set_default)
# — entries are keyed on the mesh generation so a swap invalidates them
# instead of silently stamping a wrong logical id (review finding r5).
# idx == -1 ("not in the mesh") is never cached: it usually means the
# mesh isn't configured yet, and pinning it would force a relocate
# upcall on every later send of that array.
_devidx_cache: Dict[int, Tuple[int, int]] = {}


_IciMesh = None


def _mesh_cls():
    global _IciMesh
    if _IciMesh is None:
        from .mesh import IciMesh
        _IciMesh = IciMesh
    return _IciMesh


def _device_index(arr) -> int:
    """Logical mesh id of the array's residence, or -1 when the device is
    not in the mesh.  -1 never equals a target id, so native relocation
    always upcalls for such refs — the relocate hook then does the real
    residency check/device_put, preserving Python-plane semantics instead
    of silently skipping relocation (review finding: a 0 default would
    alias device 0)."""
    IciMesh = _IciMesh
    if IciMesh is None:
        IciMesh = _mesh_cls()
    gen = IciMesh.generation
    key = id(arr)
    hit = _devidx_cache.get(key)
    if hit is not None and hit[0] == gen:
        return hit[1]
    mesh = IciMesh.default()
    idx = -1
    try:
        idx = mesh.device_index(arr.device)      # single-device fast path
    except Exception:
        pass
    if idx < 0:
        try:
            for d in arr.devices():
                i = mesh.device_index(d)
                if i >= 0:
                    idx = i
                    break
        except Exception:
            pass
    if idx >= 0:
        try:
            import weakref
            if hit is None:
                weakref.finalize(arr, _devidx_cache.pop, key, None)
            _devidx_cache[key] = (gen, idx)
        except TypeError:
            pass                 # not weakref-able: skip caching
    return idx


# ---------------------------------------------------------------------
# server binding
# ---------------------------------------------------------------------

class _RespondCollector:
    """Per-upcall response accumulator — the symmetric half of the
    batched ABI: every ``done()`` that fires while its delivery upcall
    is still open parks its packed response here, and ONE
    ``brpc_tpu_ici_respond_batch`` crossing flushes them all when the
    upcall closes.  A ``done()`` arriving later (async handler, tasklet,
    usercode pool) misses the window and responds as a batch of one."""

    __slots__ = ("_binding", "_lock", "_items", "_open")

    _GUARDED_BY = {"_items": "_lock", "_open": "_lock"}

    def __init__(self, binding: "ServerBinding"):
        self._binding = binding
        self._lock = _dbg.make_lock("_RespondCollector._lock")
        self._items: List[tuple] = []
        self._open = True

    def add(self, item: tuple) -> bool:
        with self._lock:
            if not self._open:
                return False
            self._items.append(item)
            return True

    def close_and_flush(self) -> None:
        with self._lock:
            self._open = False
            items, self._items = self._items, []
        if items:
            self._binding._respond_flush(items)


class ServerBinding:
    """Native listener for one device id, dispatching into an
    ``rpc.Server``'s method table (the Python-handler tier; echo-class
    methods can additionally be served fully native via
    ``register_native_echo``).

    Request boundary: the BATCHED one-struct upcall ABI — native
    accumulates ready requests and one ctypes crossing delivers an
    ``IciReqC`` array; responses accumulate in a _RespondCollector and
    one ``brpc_tpu_ici_respond_batch`` crossing writes them back.  Server
    Controllers come from the shared pool and recycle at response time.
    """

    def __init__(self, server, device_id: int):
        lib = native.load()
        if lib is None or not ensure_hooks():
            raise RuntimeError("native core unavailable")
        self._lib = lib
        self._server = server
        self.device_id = device_id
        self._echo_methods: set = set()   # served fully in C, inline
        self._peer_eps: Dict[int, Any] = {}
        self._method_names: Dict[bytes, str] = {}   # decode cache
        self._tenant_names: Dict[bytes, str] = {}   # decode cache
        self._mdcache: Dict[str, tuple] = {}   # full -> (md, status)
        self._tls = threading.local()          # reused respond array
        self._cb = _ICI_BATCH_FN(self._on_batch)   # pinned for lifetime
        # handler rides the listen call: the listener is never visible
        # half-initialized (a racing caller could otherwise ENOMETHOD)
        h = lib.brpc_tpu_ici_listen_batch(device_id, self._cb)
        if h == 0:
            raise OSError(errors.EINVAL,
                          f"ici://{device_id} already listening (native)")
        self._handle = h
        lib.brpc_tpu_ici_set_batch_params(
            h, int(_flags.get_flag("ici_upcall_max_batch")),
            int(_flags.get_flag("ici_upcall_batch_age_us")))
        # fused dispatch (ISSUE 13), snapshot at bind: the inline hot
        # path runs through _process_fused — one flat code object per
        # request — with the per-method dispatch tuple resolved once per
        # raw method key and every hot module handle bound HERE instead
        # of re-resolved per call
        self._fused = bool(_flags.get_flag("ici_fused_dispatch"))
        # the batch-of-1 fast lane's gate, snapshot at bind (options
        # are final once start() ran; a flag flipped later takes effect
        # with the next listener, never mid-listener)
        self._fused_inline1 = self._fused and bool(
            getattr(server.options, "usercode_inline", False))
        self._fcache: Dict[bytes, tuple] = {}   # mkey -> dispatch tuple
        self._stages_on, self._record_stage, self._enter_handler = \
            _stage_modules()
        self._pool = _controller_pool()
        # dispatch-route truth (OBSERVABILITY.md): how many requests ran
        # the fused body vs the legacy chain on this listener — plain
        # ints bumped on the hot path (an Adder op per RPC is real µs),
        # published by describe()
        self.fused_dispatched = 0
        self.legacy_dispatched = 0
        with _server_bindings_lock:
            _server_bindings[device_id] = self

    def register_native_echo(self, full_method: str) -> None:
        self._lib.brpc_tpu_ici_register_echo(self._handle,
                                             full_method.encode())
        self._echo_methods.add(full_method)

    def stop(self) -> None:
        if self._handle:
            self._lib.brpc_tpu_ici_unlisten(self._handle)
            self._handle = 0
            with _server_bindings_lock:
                if _server_bindings.get(self.device_id) is self:
                    del _server_bindings[self.device_id]

    def requests(self) -> int:
        return self._lib.brpc_tpu_ici_requests(self._handle)

    def batch_stats(self) -> Tuple[int, int, int]:
        """(upcalls, requests_delivered, max_batch_seen) — the batching
        amortization counters (native side)."""
        u = ctypes.c_uint64()
        r = ctypes.c_uint64()
        m = ctypes.c_uint64()
        self._lib.brpc_tpu_ici_batch_stats(
            self._handle, ctypes.byref(u), ctypes.byref(r),
            ctypes.byref(m))
        return u.value, r.value, m.value

    # ---- data-plane upcall (batched one-struct ABI) -------------------

    def _on_batch(self, reqs, n):
        """ONE ctypes crossing for up to ici_upcall_max_batch ready
        requests.  Inline servers process every request here and flush
        every ready response through one respond_batch crossing; other
        dispatch modes fan the requests out (tasklets / usercode pool —
        the queued counter counts BATCH CONTENTS, one per request, so
        the lame-duck drain gate sees each of them)."""
        # the idle/low-load fast lane: ONE fused inline request, no
        # collector, no loop setup — the dominant shape of a closed-loop
        # echo (the snapshot below is taken at bind; options are final
        # once the server started)
        if n == 1 and self._fused_inline1:
            try:
                self._process_fused(reqs[0], None)
            except Exception as e:
                self._batch_request_failed(reqs[0], e)
            return
        try:
            server = self._server
            inline = getattr(server.options, "usercode_inline", False)
            pool = getattr(server, "usercode_pool", None)
            fused = self._fused and inline
            # a batch of ONE (the idle/low-load shape) responds directly —
            # the collector only earns its lock when there is something
            # to amortize
            collector = _RespondCollector(self) if inline and n > 1 \
                else None
            names = self._method_names
            scheduler = None
            try:
                for i in range(n):
                    # per-request failure isolation: an unexpected error
                    # on request i must answer ITS token EINTERNAL and
                    # release ITS seg custody, never abandon the rest of
                    # the batch (their clients would block to timeout and
                    # their untaken device refs would pin HBM forever)
                    r = reqs[i]
                    token = r.token
                    try:
                        if fused:
                            # inline hot path: the whole request — method
                            # resolve, gates, controller setup, parse,
                            # invoke, completion — runs in ONE flat code
                            # object (custody exits inside match the
                            # legacy chain exactly; the except arm below
                            # still covers a failure here, and its
                            # dispose of an already-exited handle is a
                            # table-miss no-op)
                            self._process_fused(r, collector)
                            continue
                        mkey = r.method
                        full = names.get(mkey)
                        if full is None:
                            full = names[mkey] = mkey.decode()
                        payload = ctypes.string_at(r.payload,
                                                   r.payload_len) \
                            if r.payload_len else b""
                        att_host = ctypes.string_at(r.att_host,
                                                    r.att_host_len) \
                            if r.att_host_len else b""
                        nsegs = r.nsegs
                        if nsegs or att_host:
                            ah = r.att_handle
                            if ah:
                                # native custody: the seg list stays
                                # PARKED under ah — one small view
                                # object, zero registry ops, zero
                                # Block builds on this path
                                meta, total = _seg_meta_from_req(
                                    r, nsegs)
                                attachment = NativeAttachment(
                                    ah, total, meta)
                            else:
                                # legacy walk: the registry takes
                                # happen HERE, inside the upcall —
                                # native clears its seg lists when we
                                # return
                                try:
                                    attachment = \
                                        build_attachment_from_c(
                                            att_host, r.segs, nsegs)
                                except KeyError as e:
                                    self._respond_one(
                                        token, errors.EINTERNAL,
                                        str(e))
                                    continue
                        else:
                            attachment = None
                        # admission meta: (wire priority, tenant,
                        # deadline_left_ms) — decoded here once so every
                        # dispatch mode sees identical values
                        tb = r.tenant
                        if tb:
                            tenant = self._tenant_names.get(tb)
                            if tenant is None:
                                tenant = tb.decode()
                                # wire input: cap the decode cache so a
                                # caller cycling tenant names can't grow
                                # it without bound
                                if len(self._tenant_names) < 1024:
                                    self._tenant_names[tb] = tenant
                        else:
                            tenant = ""
                        adm_meta = (r.priority, tenant,
                                    r.deadline_left_ms)
                        if inline:
                            self._process(token, full, payload, attachment,
                                          r.log_id, r.peer_dev, r.recv_ns,
                                          collector, adm_meta)
                        elif pool is not None:
                            # usercode_in_pthread under batching: EVERY
                            # request in the batch is counted queued
                            # individually — the drain gate counts batch
                            # contents, not batches
                            server.on_usercode_queued()
                            reg = server._isolated.get(full) \
                                if server._isolated else None
                            try:
                                if reg is not None:
                                    # isolated method (usercode_pool):
                                    # the payload crosses as bytes to a
                                    # subinterpreter worker; gates —
                                    # admission included — and custody
                                    # run in _run_isolated on the
                                    # backup thread
                                    pool.submit(self._run_isolated,
                                                token, full, payload,
                                                attachment, reg,
                                                adm_meta, r.recv_ns)
                                else:
                                    pool.submit(self._run_usercode,
                                                token, full, payload,
                                                attachment, r.log_id,
                                                r.peer_dev, r.recv_ns,
                                                adm_meta)
                            except RuntimeError:
                                server.on_usercode_done()
                                if reg is not None:
                                    # isolation workers are gone too:
                                    # bounce retryable, like the drain
                                    self._release_attachment_custody(
                                        attachment)
                                    self._respond_one(
                                        token, errors.ELOGOFF,
                                        "server stopping")
                                else:
                                    # pool shut down mid-stop: run here
                                    self._process(token, full, payload,
                                                  attachment, r.log_id,
                                                  r.peer_dev, r.recv_ns,
                                                  None, adm_meta)
                        else:
                            if scheduler is None:
                                from ..bthread import scheduler
                            scheduler.start_background(
                                self._process, token, full, payload,
                                attachment, r.log_id, r.peer_dev,
                                r.recv_ns, None, adm_meta,
                                name=f"ici-req:{full}")
                    except Exception as e:
                        log.error("ici batch request failed: %s", e,
                                  exc_info=True)
                        try:
                            if r.att_handle:
                                # per-request failure isolation, handle
                                # mode: dispose the PARKED entry (a
                                # table miss is a no-op, so racing the
                                # view's own __del__ is safe — handles
                                # are never reused)
                                lib = self._lib
                                lib.brpc_tpu_ici_att_dispose(
                                    r.att_handle)
                            else:
                                for j in range(r.nsegs):  # custody rel.
                                    sg = r.segs[j]
                                    if sg.is_dev:
                                        _registry.release(sg.key)
                        except Exception:
                            pass
                        try:
                            self._respond_one(token, errors.EINTERNAL,
                                              f"{type(e).__name__}: {e}")
                        except Exception:
                            pass
            finally:
                # executed requests' parked responses flush even when a
                # later request in the batch blew up
                if collector is not None:
                    collector.close_and_flush()
        except Exception as e:       # never let an exception cross ctypes
            log.error("ici batch upcall failed: %s", e, exc_info=True)

    def _run_usercode(self, token, full, payload, attachment, log_id,
                      peer_dev, recv_ns, adm_meta=None) -> None:
        try:
            self._process(token, full, payload, attachment, log_id,
                          peer_dev, recv_ns, None, adm_meta)
        finally:
            self._server.on_usercode_done()

    def _run_isolated(self, token, full, payload, attachment, reg,
                      adm_meta=None, recv_ns=0) -> None:
        """A registered isolated method on a backup thread: gates
        (including the SAME admission decision tree every other plane
        runs), the share-nothing pool call (payload bytes →
        subinterpreter worker → response bytes), attachment custody,
        respond.  Isolated methods have no MethodDescriptor — the
        handler source lives in the pool's workers
        (Server.register_isolated)."""
        server = self._server
        try:
            if server._draining:
                self._release_attachment_custody(attachment)
                self._respond_one(token, errors.ELOGOFF,
                                  "server is draining (lame duck)")
                return
            pri_wire, tenant, ddl = adm_meta or (0, "", 0)
            adm = server.admission
            if adm is not None:
                from ..rpc import admission as admission_mod

                def _admitted(queued_us: int) -> None:
                    # the budget shrank while queued: bound the worker
                    # wait by what is LEFT, not the at-recv value
                    left = max(ddl - queued_us // 1000, 1) if ddl else 0
                    self._isolated_admitted(token, full, payload,
                                            attachment, reg, left)

                def _shed(code: int, text: str, retry_after: int) -> None:
                    self._release_attachment_custody(attachment)
                    self._respond_one(token, code, text,
                                      retry_after=retry_after)

                adm.submit(
                    priority=(pri_wire - 1) if pri_wire else None,
                    tenant=tenant,
                    deadline_left_ms=ddl or None,
                    recv_us=(recv_ns // 1000) if recv_ns else 0,
                    try_enter=admission_mod.server_method_gate(server,
                                                               None),
                    run=_admitted, shed=_shed)
                return
            if not server.on_request_in():
                self._release_attachment_custody(attachment)
                self._respond_one(token, errors.ELIMIT,
                                  "server max_concurrency reached")
                return
            self._isolated_admitted(token, full, payload, attachment,
                                    reg, ddl)
        finally:
            server.on_usercode_done()

    def _isolated_admitted(self, token, full, payload, attachment, reg,
                           deadline_left_ms) -> None:
        """Gates held: the pool round trip + custody + respond.  The
        wait on the isolation worker is bounded by the request's OWN
        remaining deadline when it carried one (a 100 ms client must
        not pin a backup thread for the pool's default bound)."""
        server = self._server
        _src, att_mode = reg
        start_ns = _time.monotonic_ns()
        pool = server.usercode_pool
        try:
            if pool is None:
                raise RuntimeError("usercode pool stopped")
            resp = pool.call_isolated(
                full, payload,
                timeout=(deadline_left_ms / 1000.0)
                if deadline_left_ms else None)
        except TimeoutError:
            # budget spent waiting on the worker: the same
            # ERPCTIMEDOUT every other plane reports for a spent
            # deadline, not an internal error
            self._release_attachment_custody(attachment)
            item = (token, errors.ERPCTIMEDOUT,
                    f"isolated handler exceeded deadline "
                    f"({deadline_left_ms}ms)".encode(), b"", b"",
                    (), (None, errors.ERPCTIMEDOUT, 0, server), 0, 0)
            self._respond_item(item)
            return
        except Exception as e:
            self._release_attachment_custody(attachment)
            item = (token, errors.EINTERNAL,
                    f"{type(e).__name__}: {e}".encode(), b"", b"",
                    (), (None, errors.EINTERNAL, 0, server), 0, 0)
            self._respond_item(item)
            return
        latency_us = (_time.monotonic_ns() - start_ns) // 1000
        pass_h = 0
        att_host = b""
        segs = ()
        if attachment is not None:
            if isinstance(attachment, NativeAttachment) \
                    and not attachment._mat:
                if att_mode == "echo":
                    pass_h = attachment._surrender_native()
                else:
                    attachment._dispose_native()
            elif att_mode == "echo" and attachment.backing_block_num():
                # legacy-walk attachment: the echo pays the split
                att_host, segs = split_attachment(attachment)
        item = (token, 0, b"", resp, att_host, segs,
                (None, 0, latency_us, server), 0, pass_h)
        self._respond_item(item)

    # ---- fused dispatch (ISSUE 13) -----------------------------------

    def _batch_request_failed(self, r, e) -> None:
        """Per-request failure isolation for the fused batch-of-1 fast
        lane — mirrors the loop's except arm: answer THIS token
        EINTERNAL and release THIS request's seg custody (a dispose of
        an already-exited handle is a table-miss no-op)."""
        log.error("ici batch request failed: %s", e, exc_info=True)
        try:
            if r.att_handle:
                self._lib.brpc_tpu_ici_att_dispose(r.att_handle)
            else:
                for j in range(r.nsegs):
                    sg = r.segs[j]
                    if sg.is_dev:
                        _registry.release(sg.key)
        except Exception:
            pass
        try:
            self._respond_one(r.token, errors.EINTERNAL,
                              f"{type(e).__name__}: {e}")
        except Exception:
            pass

    def _fused_entry(self, mkey: bytes):
        """Resolve + memoize the per-method dispatch tuple for a raw
        method key: (full, handler fn, request_cls, response_cls,
        status).  Everything per-method — name decode, method lookup,
        codec classes, the limiter handle — resolves ONCE per listener
        instead of per call.  Services cannot be added after start, so
        the cache never goes stale; a miss (unknown method) is NOT
        cached so a typo probe can't grow the table."""
        full = mkey.decode()
        md = self._server.find_method(full)
        if md is None:
            return None
        ent = (full, md.fn, md.request_cls, md.response_cls,
               self._server.method_status(full))
        self._fcache[mkey] = ent
        return ent

    def _process_fused(self, r, collector) -> None:
        """The whole inline request path as ONE flat code object —
        the fusion of _on_batch's extraction, _process's gates, and
        _execute's setup/parse/invoke (completion lives in _FusedDone).
        Semantics mirror the legacy chain exactly; admission-controlled
        servers delegate to it (the shed/WFQ decision tree is not a
        hot-path shape).  Custody: every exit point below matches the
        legacy chain's exactly-one-exit discipline."""
        server = self._server
        token = r.token
        mkey = r.method
        ent = self._fcache.get(mkey)
        if ent is None:
            ent = self._fused_entry(mkey)
        nsegs = r.nsegs
        ahl = r.att_host_len
        attachment = None
        if nsegs or ahl:
            ah = r.att_handle
            if ah:
                # native custody: the seg list stays PARKED under ah —
                # the dominant 1-seg shape reads the inline seg0 mirror
                if nsegs == 1:
                    total = r.seg0_nbytes
                    attachment = NativeAttachment(
                        ah, total, ((r.seg0_key, total, r.seg0_dev),))
                else:
                    meta, total = _seg_meta_from_req(r, nsegs)
                    attachment = NativeAttachment(ah, total, meta)
            else:
                att_host = _string_at(r.att_host, ahl) \
                    if ahl else b""
                try:
                    attachment = build_attachment_from_c(
                        att_host, r.segs, nsegs)
                except KeyError as e:
                    self._respond_one(token, errors.EINTERNAL, str(e),
                                      collector)
                    return
        if server._draining:
            # lame-duck bounce comes BEFORE method resolution, like the
            # legacy chain
            if attachment is not None and \
                    type(attachment) is NativeAttachment:
                attachment._dispose_native()
            self._respond_one(token, errors.ELOGOFF,
                              "server is draining (lame duck)", collector)
            return
        if ent is None:
            if attachment is not None and \
                    type(attachment) is NativeAttachment:
                attachment._dispose_native()
            self._respond_one(token, errors.ENOMETHOD,
                              f"no method {mkey.decode()}", collector)
            return
        full, fn, request_cls, response_cls, status = ent
        pri_wire = r.priority
        tb = r.tenant
        ddl = r.deadline_left_ms
        # the wire tenant decodes BEFORE any gate or pool acquire: a
        # malformed (non-UTF-8) tenant must fail in the pre-gate region
        # — _on_batch's except arm answers EINTERNAL and releases
        # custody, but cannot roll back a concurrency slot or a pooled
        # Controller (the legacy chain decoded in _on_batch for the
        # same reason)
        if tb:
            tenant = self._tenant_names.get(tb)
            if tenant is None:
                tenant = tb.decode()
                if len(self._tenant_names) < 1024:
                    self._tenant_names[tb] = tenant
        else:
            tenant = ""
        payload = _string_at(r.payload, r.payload_len) \
            if r.payload_len else b""
        if server.admission is not None:
            # admission rides the legacy chain (identical decision tree
            # on all planes); the fused entry still saved the method
            # resolve — _process re-reads its own mdcache
            self._process(token, full, payload, attachment, r.log_id,
                          r.peer_dev, r.recv_ns, collector,
                          (pri_wire, tenant, ddl))
            return
        self.fused_dispatched += 1
        stages = self._stages_on()
        if stages:
            recv_ns = r.recv_ns
            if recv_ns:
                self._record_stage("queue", recv_ns, _time.monotonic_ns(),
                                   None, token)
        if not server.on_request_in():
            if attachment is not None and \
                    type(attachment) is NativeAttachment:
                attachment._dispose_native()
            self._respond_one(token, errors.ELIMIT,
                              "server max_concurrency reached", collector)
            return
        if status is not None and not status.on_requested():
            server.on_request_out()
            if attachment is not None and \
                    type(attachment) is NativeAttachment:
                attachment._dispose_native()
            self._respond_one(token, errors.ELIMIT,
                              f"{full} concurrency limit", collector)
            return
        cntl = self._pool.acquire()  # fablint: custody-moved(request-lifecycle) the shim rides the request; _maybe_recycle releases it back to the pool when the response (or failure path) completes
        d = cntl.__dict__
        log_id = r.log_id
        if log_id:
            d["log_id"] = log_id
        d["server"] = server
        peer_dev = r.peer_dev
        ep = self._peer_eps.get(peer_dev)
        d["remote_side"] = ep if ep is not None \
            else self._peer_endpoint(peer_dev)
        has_meta = False
        if pri_wire:
            d["priority"] = pri_wire - 1
            has_meta = True
        if tb:
            d["tenant"] = tenant
            has_meta = True
        if ddl:
            d["deadline_left_ms"] = ddl
            has_meta = True
        if attachment is not None:
            d["request_attachment"] = attachment
        start_ns = _time.monotonic_ns()
        try:
            request = request_cls()
            request.ParseFromString(payload)
        except Exception as e:
            cntl._maybe_recycle()
            item = (token, errors.EREQUEST,
                    f"fail to parse request: {e}".encode(), b"", b"", (),
                    (status, errors.EREQUEST, 0, server), 0, 0)
            if collector is None or not collector.add(item):
                self._respond_item(item)
            return
        opened = None
        if stages:
            self._record_stage("parse", start_ns, _time.monotonic_ns(),
                               None, token)
            # the handler stage runs from start_ns, parse included, as
            # the method's latency does
            opened = self._enter_handler(start_ns, token)
        response = response_cls()
        fd = _FusedDone(self, token, cntl, response, status, start_ns,
                        collector, stages, opened)
        d["_server_done"] = fd       # cntl.send_response() support
        try:
            # the context scope installs only when it would matter: the
            # request carries admission meta, or an OUTER inline context
            # must be masked for this handler's own outbound calls
            # (nested in-process dispatch) — the no-meta echo shape pays
            # zero frames here.  Inlined _reqctx.scope (same
            # save/install/restore discipline, minus the class frames).
            prev_ctx = getattr(_reqctx_tls, "ctx", None)
            if has_meta or prev_ctx is not None:
                _reqctx_tls.ctx = _reqctx.InboundContext(
                    d.get("priority"), d.get("tenant", ""), ddl) \
                    if has_meta else None
                try:
                    fn(cntl, request, response, fd)
                finally:
                    _reqctx_tls.ctx = prev_ctx
            else:
                fn(cntl, request, response, fd)
        except Exception as e:
            log.error("ici method %s raised: %s", full, e, exc_info=True)
            if not fd.called:
                cntl.set_failed(errors.EINTERNAL,
                                f"{type(e).__name__}: {e}")
                fd()
        finally:
            if opened is not None:
                opened.leave()

    def _process(self, token, full, payload, attachment, log_id, peer_dev,
                 recv_ns, collector, adm_meta=None) -> None:
        self.legacy_dispatched += 1
        server = self._server
        record_stage = self._record_stage
        stages = self._stages_on()
        if stages and recv_ns:
            record_stage("queue", recv_ns, _time.monotonic_ns(), None, token)
        if server._draining:
            # lame-duck: the native front door stays open through the
            # grace window so in-flight calls finish, but new ones bounce
            # with retryable ELOGOFF (mirrors tpu_std.process_request)
            self._release_attachment_custody(attachment)
            self._respond_one(token, errors.ELOGOFF,
                              "server is draining (lame duck)", collector)
            return
        hit = self._mdcache.get(full)
        if hit is None:
            md = server.find_method(full)
            if md is None:
                self._release_attachment_custody(attachment)
                self._respond_one(token, errors.ENOMETHOD,
                                  f"no method {full}", collector)
                return
            hit = self._mdcache[full] = (md, server.method_status(full))
        md, status = hit
        adm = server.admission
        if adm is not None:
            # admission-control path (rpc/admission.py): the same
            # shed-before-queue / WFQ / deadline decision as the wire
            # and loopback planes, in front of the same gates
            pri_wire, tenant, deadline_left = adm_meta or (0, "", 0)
            from ..rpc import admission as admission_mod

            def _admitted(queued_us: int,
                          _stages=stages, _rs=record_stage) -> None:
                if _stages and queued_us:
                    now = _time.monotonic_ns()
                    _rs("queue", now - queued_us * 1000, now, None, token)
                self._execute(token, full, payload, attachment, log_id,
                              peer_dev, collector, md, status, adm_meta)

            def _shed(code: int, text: str, retry_after: int) -> None:
                self._release_attachment_custody(attachment)
                self._respond_one(token, code, text, collector,
                                  retry_after=retry_after)

            adm.submit(
                priority=(pri_wire - 1) if pri_wire else None,
                tenant=tenant,
                deadline_left_ms=deadline_left or None,
                recv_us=(recv_ns // 1000) if recv_ns else 0,
                try_enter=admission_mod.server_method_gate(server, status),
                run=_admitted, shed=_shed)
            return
        if not server.on_request_in():
            self._release_attachment_custody(attachment)
            self._respond_one(token, errors.ELIMIT,
                              "server max_concurrency reached", collector)
            return
        if status is not None and not status.on_requested():
            server.on_request_out()
            self._release_attachment_custody(attachment)
            self._respond_one(token, errors.ELIMIT,
                              f"{full} concurrency limit", collector)
            return
        self._execute(token, full, payload, attachment, log_id, peer_dev,
                      collector, md, status, adm_meta)

    @staticmethod
    def _release_attachment_custody(attachment) -> None:
        """Drop a request attachment on a reject path.  Legacy walk:
        its device arrays left the registry at build time (Python owns
        them through the IOBuf) — letting the IOBuf go is the release.
        Native custody: the view still parks its seg list in the att
        table — dispose is the exactly-one exit (idempotent; a
        materialized or surrendered view holds no handle)."""
        if isinstance(attachment, NativeAttachment):
            attachment._dispose_native()
        return

    def _execute(self, token, full, payload, attachment, log_id,
                 peer_dev, collector, md, status, adm_meta=None) -> None:
        """Gates held: parse → invoke → batched write-back."""
        server_controller_pool = _controller_pool()
        server = self._server
        record_stage = self._record_stage
        stages = self._stages_on()
        cntl = server_controller_pool.acquire()  # fablint: custody-moved(request-lifecycle) the shim rides the request; _maybe_recycle releases it back to the pool when the response (or failure path) completes
        if log_id:
            cntl.log_id = log_id
        cntl.server = server
        cntl.remote_side = self._peer_endpoint(peer_dev)
        if adm_meta is not None:
            pri_wire, tenant, deadline_left = adm_meta
            if pri_wire:
                cntl.priority = pri_wire - 1
            if tenant:
                cntl.tenant = tenant
            if deadline_left:
                cntl.deadline_left_ms = deadline_left
        if attachment is not None:
            cntl.request_attachment = attachment
        start_ns = _time.monotonic_ns()
        try:
            request = md.request_cls()
            request.ParseFromString(payload)
        except Exception as e:
            cntl._maybe_recycle()

            def parse_post(err=errors.EREQUEST):
                if status is not None:
                    status.on_responded(err, 0)
                server.on_request_out()

            self._respond_one(token, errors.EREQUEST,
                              f"fail to parse request: {e}", collector,
                              post=parse_post)
            return
        opened = None
        if stages:
            record_stage("parse", start_ns, _time.monotonic_ns(), None,
                         token)
            # the handler stage runs from start_ns, parse included, as
            # the method's latency does
            opened = self._enter_handler(start_ns, token)
        response = md.response_cls()
        done_called = [False]

        def done() -> None:
            if done_called[0]:
                return
            done_called[0] = True
            t_done = _time.monotonic_ns()
            latency_us = (t_done - start_ns) // 1000
            if stages:
                record_stage("handler", start_ns, t_done, None, token,
                             opened)
            cntl._release_session_data()
            err = cntl.error_code_

            def post() -> None:
                # drain-gate accounting runs AFTER the response crossed
                # back to native: inflight_requests() must never read
                # zero while an EXECUTED request's response still sits
                # in the collector — a lame-duck stop passing the gate
                # there would purge the tokens and turn completed
                # non-idempotent calls into retryable ELOGOFF
                # (duplicate execution), the exact straggler shape the
                # graceful-drain work ordered queued responses ahead of
                # connection failure to prevent
                if status is not None:
                    status.on_responded(err, latency_us)
                server.on_request_out()

            if err:
                # a handler-set shed hint (e.g. the serving pool's
                # saturation shed) rides the respond item like the
                # admission sheds — plane parity with tpu_std/loopback
                self._respond_one(token, err, cntl.error_text_, collector,
                                  post=post,
                                  retry_after=cntl.retry_after_ms or 0)
                return
            resp_att = cntl._peek_response_attachment()
            pass_h = 0
            if resp_att is not None:
                if isinstance(resp_att, NativeAttachment):
                    # echo pass-through: the UNMATERIALIZED request view
                    # assigned as the response — or a ResponseAttachment
                    # that ADOPTED one via append — hands the parked
                    # handle straight back to native; zero Python walks
                    # on the whole response side.  (A materialized view
                    # holds no handle and falls through to the split.)
                    pass_h = resp_att._surrender_native()
                if pass_h:
                    att_host, segs = b"", ()
                elif resp_att.backing_block_num():
                    att_host, segs = split_attachment(resp_att)
                else:
                    att_host, segs = b"", ()
            else:
                att_host, segs = b"", ()
            item = (token, 0, b"", response.SerializeToString(),
                    att_host, segs, post, 0, pass_h)
            if stages:
                record_stage("encode", t_done, _time.monotonic_ns(), None,
                             token)
            if collector is None or not collector.add(item):
                self._respond_item(item)

        cntl._server_done = done
        try:
            md.invoke(cntl, request, response, done)
        except Exception as e:
            log.error("ici method %s raised: %s", full, e, exc_info=True)
            if not done_called[0]:
                cntl.set_failed(errors.EINTERNAL,
                                f"{type(e).__name__}: {e}")
                done()
                cntl._release_session_data()
                cntl._maybe_recycle()
        finally:
            if opened is not None:
                opened.leave()

    def _peer_endpoint(self, peer_dev: int):
        """Per-request endpoint objects are identical for a given peer —
        cache them (a default-mesh lock + EndPoint construction per
        request measured ~1 us on the handler tier).  EndPoints are pure
        (scheme, device-id) values, so the cache survives mesh swaps."""
        ep = self._peer_eps.get(peer_dev)
        if ep is None:
            from .mesh import IciMesh
            ep = self._peer_eps[peer_dev] = \
                IciMesh.default().endpoint(peer_dev)
        return ep

    # ---- batched write-back ------------------------------------------

    def _respond_one(self, token, err, text, collector=None,
                     post=None, retry_after: int = 0) -> None:
        item = (token, err,
                text.encode() if isinstance(text, str) else (text or b""),
                b"", b"", (), post, retry_after, 0)
        if collector is None or not collector.add(item):
            self._respond_item(item)

    def _respond_item(self, item) -> None:
        """Single-response write-back through a per-thread reused
        (IciRespC * 1) array — the batch-of-one fast lane (native copies
        everything during the call, so reuse is safe; every field is
        rewritten here including the NULL ones).  The item's ``post``
        hook (drain-gate accounting) runs AFTER the crossing."""
        tls = self._tls.__dict__
        arr = tls.get("resp1")
        if arr is None:
            arr = tls["resp1"] = (IciRespC * 1)()
        token, err, err_text, payload, att_host, segs, post, \
            retry_after, att_handle = item
        e = arr[0]
        e.token = token
        e.err = err
        e.err_text = err_text or None
        e.retry_after_ms = retry_after
        e.att_handle = att_handle
        if payload:
            e.data = ctypes.cast(payload, _U8P)
            e.len = len(payload)
        else:
            e.data = None
            e.len = 0
        if att_host:
            e.att_host = ctypes.cast(att_host, _U8P)
            e.att_host_len = len(att_host)
        else:
            e.att_host = None
            e.att_host_len = 0
        if segs:
            seg_arr = fill_seg_array(segs)
            e.segs = seg_arr
            e.nsegs = len(segs)
        else:
            seg_arr = None
            e.segs = None
            e.nsegs = 0
        if self._stages_on():
            t0 = _time.monotonic_ns()
            self._lib.brpc_tpu_ici_respond_batch(arr, 1)
            self._record_stage("write", t0, _time.monotonic_ns(), None,
                               token)
        else:
            self._lib.brpc_tpu_ici_respond_batch(arr, 1)
        del seg_arr, payload, att_host, err_text   # alive across the call
        if post is not None:
            if type(post) is tuple:
                # fused accounting (no per-RPC closure): see _FusedDone
                status, perr, lat, server = post
                if status is not None:
                    status.on_responded(perr, lat)
                server.on_request_out()
            else:
                post()

    def _respond_flush(self, items) -> None:
        """One ``brpc_tpu_ici_respond_batch`` crossing for every packed
        response in ``items`` (each: token, err, err_text, payload,
        att_host, segs, post).  Seg-key custody transfers to native,
        which owns release on EVERY drop path — no per-item return code
        needed.  Each item's ``post`` hook (drain-gate accounting) runs
        AFTER the crossing — see _process.done's ordering note."""
        n = len(items)
        arr = (IciRespC * n)()
        keep = []                      # buffers alive across the call
        for i, (token, err, err_text, payload, att_host, segs, _post,
                retry_after, att_handle) in enumerate(items):
            e = arr[i]
            e.token = token
            e.err = err
            e.retry_after_ms = retry_after
            e.att_handle = att_handle
            if err_text:
                e.err_text = err_text
                keep.append(err_text)
            if payload:
                e.data = ctypes.cast(payload, _U8P)
                e.len = len(payload)
                keep.append(payload)
            if att_host:
                e.att_host = ctypes.cast(att_host, _U8P)
                e.att_host_len = len(att_host)
                keep.append(att_host)
            if segs:
                seg_arr = fill_seg_array(segs)
                e.segs = seg_arr
                e.nsegs = len(segs)
                keep.append(seg_arr)
        if self._stages_on():
            t0 = _time.monotonic_ns()
            self._lib.brpc_tpu_ici_respond_batch(arr, n)
            # under batched delivery the write stage is the SHARED flush
            # crossing: every response in the batch records the same
            # crossing latency (what the request actually waited)
            t1 = _time.monotonic_ns()
            for it in items:
                self._record_stage("write", t0, t1, None, it[0])
        else:
            self._lib.brpc_tpu_ici_respond_batch(arr, n)
        del keep
        for it in items:
            post = it[6]
            if post is not None:
                if type(post) is tuple:
                    status, perr, lat, server = post
                    if status is not None:
                        status.on_responded(perr, lat)
                    server.on_request_out()
                else:
                    post()


class _FusedDone:
    """The fused completion: the legacy chain's done() + post() +
    wrapped_done() collapsed into one callable object — response
    encode, attachment custody exit (pass-through / split), the batched
    write-back, and the pool recycle, with the drain-gate accounting
    (status.on_responded + server.on_request_out) packed as a TUPLE
    into the respond item so it still runs AFTER the response crossed
    back to native (see _process.done's ordering note) without a
    per-RPC closure.  Idempotent like the legacy done."""

    __slots__ = ("binding", "token", "cntl", "response", "status",
                 "start_ns", "collector", "stages", "opened", "called")

    def __init__(self, binding, token, cntl, response, status, start_ns,
                 collector, stages, opened=None):
        self.binding = binding
        self.token = token
        self.cntl = cntl
        self.response = response
        self.status = status
        self.start_ns = start_ns
        self.collector = collector
        self.stages = stages
        self.opened = opened          # the handler stage's layer span
        self.called = False

    def __call__(self) -> None:
        if self.called:
            return
        self.called = True
        b = self.binding
        cntl = self.cntl
        t_done = _time.monotonic_ns()
        latency_us = (t_done - self.start_ns) // 1000
        stages = self.stages
        if stages:
            b._record_stage("handler", self.start_ns, t_done, None,
                            self.token, self.opened)
        d = cntl.__dict__
        if d.get("_session_data") is not None:
            cntl._release_session_data()
        err = cntl.error_code_
        status = self.status
        server = b._server
        if err:
            text = cntl.error_text_
            item = (self.token, err,
                    text.encode() if isinstance(text, str)
                    else (text or b""), b"", b"", (),
                    (status, err, latency_us, server),
                    cntl.retry_after_ms or 0, 0)
        else:
            resp_att = d.get("response_attachment")
            pass_h = 0
            att_host = b""
            segs = ()
            if resp_att is not None:
                if isinstance(resp_att, NativeAttachment) \
                        and not resp_att._mat:
                    # echo pass-through (also the adopted append shape,
                    # ISSUE 13 satellite): hand the parked handle
                    # straight back — zero Python walks.  Inlined
                    # _surrender_native.
                    pass_h = resp_att._h
                    resp_att._h = 0
                if not pass_h and resp_att.backing_block_num():
                    att_host, segs = split_attachment(resp_att)
            item = (self.token, 0, b"", self.response.SerializeToString(),
                    att_host, segs, (status, 0, latency_us, server),
                    0, pass_h)
            if stages:
                b._record_stage("encode", t_done, _time.monotonic_ns(),
                                None, self.token)
        coll = self.collector
        if coll is None or not coll.add(item):
            b._respond_item(item)
        # attachment custody exits, inlined (the pool-release hooks
        # would re-discover them through getattr): a request view whose
        # handle never exited (handler ignored it) disposes HERE; a
        # surrendered/adopted/materialized one holds no handle and the
        # pop makes the pool's own duck-typed sweep a no-op
        ra = d.pop("request_attachment", None)
        if ra is not None and isinstance(ra, NativeAttachment):
            h = ra._h
            if h:
                ra._h = 0
                fns = _att_fns
                if fns is not None:
                    fns[1](h)
        ra = d.pop("response_attachment", None)
        if ra is not None and isinstance(ra, NativeAttachment):
            h = ra._h
            if h:
                ra._h = 0
                fns = _att_fns
                if fns is not None:
                    fns[1](h)
        # pool recycle (the wrapped_done tail): safe before the
        # collector flushes — the item owns its own buffers and the
        # accounting tuple carries no controller reference
        pool = d.get("_recycle_pool")
        if pool is not None:
            pool.release(cntl)


# ---------------------------------------------------------------------
# channel binding
# ---------------------------------------------------------------------

class ChannelBinding:
    """Client half: one native connection (with its credit window) to the
    in-process native listener at ``remote_dev``."""

    # class-attribute alias: Channel.call_method compares the fused
    # result against the sentinel without an import frame per call
    FUSED_FALLTHROUGH = FUSED_FALLTHROUGH

    def __init__(self, remote_dev: int, local_dev: Optional[int] = None,
                 window_bytes: int = 0):
        lib = native.load()
        if lib is None or not ensure_hooks():
            raise RuntimeError("native core unavailable")
        from .mesh import IciMesh
        mesh = IciMesh.default()
        if local_dev is None:
            local_dev = (remote_dev + 1) % mesh.size
        self._lib = lib
        self.local_dev = local_dev
        self.remote_dev = remote_dev
        self.window_bytes = window_bytes if window_bytes > 0 else (4 << 20)
        self.remote_side = mesh.endpoint(remote_dev)
        self._names: Dict[str, bytes] = {}      # method encode cache
        self._tenants: Dict[str, bytes] = {}    # tenant encode cache
        self._tls = threading.local()           # reused IciCallOut
        # native att custody: call4 parks device-only response
        # attachments under a handle and releases error-path segs
        # natively — the client sheds its take-walks both ways
        self._call4 = lib.brpc_tpu_ici_call4    # bound once: attr-chain
        self._free = lib.brpc_tpu_buf_free      # lookups are per-call
        # fused client path (ISSUE 13), snapshot at connect:
        # Channel.call_method routes sync calls through call_fused — the
        # preamble/screen/issue/response chain as one flat code object.  Hot module handles resolve on first call
        # (the lazy import dance exists only for load-time cycles).
        self._fused = bool(_flags.get_flag("ici_fused_dispatch"))
        self._callf = _fused_call_binding() if self._fused else None
        self._hot = None
        from ..rpc import span as _span_mod
        self._rpcz_flag = _span_mod._rpcz_flag
        self._start_span = _span_mod.maybe_start_client_span
        self._end_span = _span_mod.end_client_span
        h = lib.brpc_tpu_ici_connect(local_dev, remote_dev, window_bytes)
        if h == 0:
            raise ConnectionRefusedError(
                f"no native listener at ici://{remote_dev}")
        self._handle = h

    def close(self) -> None:
        if self._handle:
            self._lib.brpc_tpu_ici_close(self._handle)
            self._handle = 0

    def __del__(self):                   # noqa: D105 — native conn must not
        try:                             # outlive its Python owner
            self.close()
        except Exception:
            pass

    def window_left(self) -> int:
        return self._lib.brpc_tpu_ici_window_left(self._handle)

    def call(self, full_name: str, cntl, request: Any,
             response_cls: Optional[type] = None):
        """Unary call over the native datapath.  Fills cntl; returns the
        parsed response (or raw payload bytes when response_cls is None)."""
        _fi, scheduler, _t = _hot_modules()
        # fault injection covers the fast plane too, with the SAME
        # semantics as the Python plane's Socket.write boundary: DROP =
        # bytes vanish, the call waits out its deadline; ERROR = the
        # connection is severed (every later call on this binding fails
        # until the channel re-routes/reconnects).
        injector = _fi.active()
        if injector is not None:
            action = injector.decide(self)
            if action == _fi.DROP:
                tms = cntl.timeout_ms
                # no deadline = a genuine hang; bound it so a
                # misconfigured test fails instead of wedging forever
                _time.sleep((tms / 1000.0) if tms and tms > 0 else 60.0)
                cntl.set_failed(errors.ERPCTIMEDOUT
                                if tms and tms > 0 else errors.EFAILEDSOCKET,
                                "rpc timeout (injected drop)")
                return None
            if action == _fi.ERROR:
                cntl.set_failed(errors.EFAILEDSOCKET, "injected fault")
                self.close()             # severed, like Socket.set_failed
                return None
        t0 = _time.monotonic_ns()
        try:
            req = request.SerializeToString()
        except AttributeError:
            req = bytes(request) if request is not None else b""
        req_att = cntl._peek_request_attachment()
        if req_att is not None and req_att.backing_block_num():
            att_host, segs = split_attachment(req_att)
            dev_bytes = sum(s[1] for s in segs if s[3])
        else:
            att_host, segs, dev_bytes = b"", (), 0
        # bytes objects pass by pointer (cast, no copy): the native side
        # never writes through request pointers and copies before returning
        u8p = _U8P
        reqb = ctypes.cast(req, u8p) if req else None
        attb = ctypes.cast(att_host, u8p) if att_host else None
        seg_arr = fill_seg_array(segs) if segs else None
        # one out-block instead of seven byref temporaries: the 17-arg
        # ctypes conversion measured ~3-4 us/call (VERDICT r4 weak #3).
        # Reused per thread — native zeroes every field on entry, so a
        # fresh allocation per call buys nothing
        tls = self._tls.__dict__
        out = tls.get("out")
        if out is None:
            out = tls["out"] = IciCallOut()
            tls["out_ref"] = ctypes.byref(out)
        out_ref = tls["out_ref"]
        name_b = self._names.get(full_name)
        if name_b is None:
            name_b = self._names[full_name] = full_name.encode()
        # timeout_ms <= 0 means NO deadline (controller.py:169 semantics);
        # the native side treats timeout_us <= 0 the same way
        tms = cntl.timeout_ms
        timeout_us = int(tms * 1000) if tms is not None and tms > 0 else 0
        # admission meta rides the native frame: wire-encoded priority
        # (0 = unset), tenant, and the remaining deadline budget (the
        # full per-try budget at this hop's send time)
        pri_wire = cntl.priority + 1 if cntl.priority is not None else 0
        tenant = cntl.tenant
        if tenant:
            tenant_b = self._tenants.get(tenant)
            if tenant_b is None:
                tenant_b = self._tenants[tenant] = tenant.encode()
        else:
            tenant_b = None
        # the FFI call can park on a C condvar (Python-tier handler): a
        # tasklet-pool worker must note itself blocked so the scheduler
        # compensates — otherwise handler tasklets starve behind us and
        # the call deadlocks until timeout (review finding r4)
        blocked = scheduler.in_worker()
        if blocked:
            scheduler.note_worker_blocked()
        # layer span brpc.call.wait: the one call into the native core
        ls = _lspan.layer_begin("brpc.call.wait") \
            if _lspan.layer_on() else None
        try:
            rc = self._call4(
                self._handle, name_b, reqb, len(req), attb,
                len(att_host), seg_arr, len(segs), timeout_us, pri_wire,
                tenant_b, int(tms) if tms is not None and tms > 0 else 0,
                out_ref)
        finally:
            if ls is not None:
                ls.end()
            if blocked:
                scheduler.note_worker_unblocked()
        try:
            cntl.remote_side = self.remote_side
            nsegs = out.nsegs
            if rc != 0:
                # response segs a failed handler shipped were released
                # native-side by call4 (the exactly-one-exit custody
                # invariant) — no walk here
                text = ctypes.string_at(out.err_text).decode() \
                    if out.err_text else errors.berror(int(rc))
                cntl.set_failed(int(rc), text)
                if out.retry_after_ms:
                    # admission shed hint (retryable ELIMIT backoff)
                    cntl.retry_after_ms = int(out.retry_after_ms)
                return None
            payload = ctypes.string_at(out.resp, out.resp_len) \
                if out.resp_len else b""
            if nsegs or out.att_len:
                ah = out.att_handle
                if ah:
                    # native custody: the response seg list stays
                    # parked — wrap it lazily (seg0 rides inline for
                    # the 1-seg shape; the >1 metadata copy is read
                    # NOW, before the finally block frees it)
                    if nsegs == 1:
                        total = out.seg0_nbytes
                        meta = ((out.seg0_key, total, out.seg0_dev),)
                    else:
                        segs_p = out.segs
                        lst = []
                        total = 0
                        for i in range(nsegs):
                            s = segs_p[i]
                            lst.append((s.key, s.nbytes, s.dev))
                            total += s.nbytes
                        meta = tuple(lst)
                    rbuf = NativeAttachment(ah, total, meta)
                else:
                    r_att_host = ctypes.string_at(out.att, out.att_len) \
                        if out.att_len else b""
                    rbuf = build_attachment_from_c(r_att_host, out.segs,
                                                   nsegs)
                prev = cntl._peek_response_attachment()
                if prev is None:
                    cntl.response_attachment = rbuf
                else:
                    prev.append(rbuf)
            # transport accounting (the Python plane's counters — one
            # fabric-wide truth regardless of datapath)
            with _t._ici_stats_lock:
                _t._ici_bytes_moved += len(req) + len(att_host) + dev_bytes
                _t._ici_device_bytes_moved += dev_bytes
            cntl.error_code_ = 0
            if response_cls is None:
                return payload
            response = response_cls()
            response.ParseFromString(payload)
            cntl.response = response
            return response
        finally:
            cntl.latency_us = (_time.monotonic_ns() - t0) // 1000
            # free AND NULL every out pointer: the struct is reused (per
            # thread, and re-entered by nested calls from inline
            # handlers) — a stale pointer surviving into a call whose
            # response leaves that field untouched would double-free
            free = self._free
            if out.resp:
                free(out.resp)
                out.resp = None
            if out.att:
                free(out.att)
                out.att = None
            if out.segs:
                free(out.segs)
                out.segs = None
            if out.err_text:
                free(out.err_text)
                out.err_text = None

    def call_fused(self, full_name: str, cntl, request: Any,
                   response_cls, chan):
        """The fused sync client path (ISSUE 13): Channel.call_method's
        context/default preamble, the per-call screens, and the whole
        ``call`` body as ONE flat code object, with the dominant
        1-device-block attachment shape inlined (no split/fill frames)
        and the shed-retry / fallback helpers entered ONLY when their
        error actually occurred.  Must mirror ``call_method`` +
        ``call`` semantics exactly — the ``ici_fused_dispatch=False``
        leg A/Bs them.  Returns FUSED_FALLTHROUGH when the call must
        re-route to the Python plane (frame too large, hedging
        configured, dead-conn re-route)."""
        opts = chan.options
        # ---- cascading inbound context + channel defaults ------------
        ctx = getattr(_reqctx_tls, "ctx", None)
        if ctx is not None:
            if cntl.priority is None and ctx.priority is not None:
                cntl.priority = ctx.priority
            if not cntl.tenant and ctx.tenant:
                cntl.tenant = ctx.tenant
            residual = ctx.residual_deadline_ms()
            if residual is not None:
                if residual <= 0:
                    cntl.set_failed(
                        errors.ERPCTIMEDOUT,
                        "inherited deadline budget spent before call")
                    if cntl.span is not None:
                        self._end_span(cntl)
                    return None
                base = cntl.timeout_ms if cntl.timeout_ms is not None \
                    else opts.timeout_ms
                if base is None or base <= 0 or base > residual:
                    cntl.timeout_ms = max(int(residual), 1)
        if cntl.priority is None and opts.priority is not None:
            cntl.priority = opts.priority
        if not cntl.tenant and opts.tenant:
            cntl.tenant = opts.tenant
        # ---- per-call screens (mirrors _fast_call_fits) --------------
        if opts.backup_request_ms > 0:
            return FUSED_FALLTHROUGH
        req_att = cntl.__dict__.get("request_attachment")
        if req_att is None:
            att_len = 0
        elif type(req_att) is IOBuf:
            att_len = req_att._size
        else:
            att_len = len(req_att)     # lazy views answer w/o inflating
        try:
            req_sz = request.ByteSize()
        except Exception:
            req_sz = 0
        if att_len + req_sz + 65536 > self.window_bytes:
            return FUSED_FALLTHROUGH
        if cntl.timeout_ms is None:
            cntl.timeout_ms = opts.timeout_ms
        if cntl.span is None and self._rpcz_flag.value:
            self._start_span(cntl, full_name)
        hot = self._hot
        if hot is None:
            hot = self._hot = _hot_modules()
        _fi, scheduler, _t = hot
        if _fi._active is not None:
            # fault injection armed: the legacy body implements the
            # drop/sever semantics — not a hot shape
            result = self.call(full_name, cntl, request, response_cls)
        else:
            t0 = _time.monotonic_ns()
            try:
                req = request.SerializeToString()
            except AttributeError:
                req = bytes(request) if request is not None else b""
            tls = self._tls.__dict__
            att_host = b""
            seg_arr = None
            nseg = 0
            dev_bytes = 0
            if req_att is not None and att_len:
                fast = None
                if type(req_att) is IOBuf:
                    refs = req_att._refs
                    if len(refs) == 1:
                        ref = refs[0]
                        blk = ref.block
                        if (blk.kind == DEVICE and not ref.offset
                                and ref.length == blk.size):
                            fast = (blk.data, ref.length)
                if fast is not None:
                    # the dominant shape — one whole device block:
                    # registry put + reused 1-seg array, zero
                    # split/fill frames; the residence cache hit is
                    # inlined (a steady workload re-posts the same
                    # arrays)
                    arr, nbytes = fast
                    seg_arr = tls.get("seg1")
                    if seg_arr is None:
                        seg_arr = tls["seg1"] = (IciSegC * 1)()
                    e = seg_arr[0]
                    e.key = _registry.put(arr)  # fablint: custody-moved(wire-segment) the key rides the IciSeg to the native sender, which takes/releases it after the DMA posts
                    e.nbytes = nbytes
                    IM = _IciMesh
                    hit = _devidx_cache.get(id(arr)) \
                        if IM is not None else None
                    if hit is not None and hit[0] == IM.generation:
                        e.dev = hit[1]
                    else:
                        e.dev = _device_index(arr)
                    e.is_dev = 1
                    nseg = 1
                    dev_bytes = nbytes
                else:
                    att_host, segs = split_attachment(req_att)
                    if segs:
                        seg_arr = fill_seg_array(segs)
                        nseg = len(segs)
                        dev_bytes = sum(s[1] for s in segs if s[3])
            out = tls.get("out")
            if out is None:
                out = tls["out"] = IciCallOut()
                tls["out_ref"] = ctypes.byref(out)
            out_ref = tls["out_ref"]
            name_b = self._names.get(full_name)
            if name_b is None:
                name_b = self._names[full_name] = full_name.encode()
            tms = cntl.timeout_ms
            timeout_us = int(tms * 1000) if tms is not None and tms > 0 \
                else 0
            pri_wire = cntl.priority + 1 if cntl.priority is not None \
                else 0
            tenant = cntl.tenant
            if tenant:
                tenant_b = self._tenants.get(tenant)
                if tenant_b is None:
                    tenant_b = self._tenants[tenant] = tenant.encode()
            else:
                tenant_b = None
            # inlined scheduler.in_worker (one thread-local read)
            blocked = getattr(scheduler._tls, "group", None) is not None
            if blocked:
                scheduler.note_worker_blocked()
            ls = _lspan.layer_begin("brpc.call.wait") \
                if _lspan.layer_on() else None
            try:
                rc = self._callf(
                    self._handle, name_b, req or None, len(req),
                    att_host or None, len(att_host), seg_arr, nseg,
                    timeout_us, pri_wire, tenant_b,
                    int(tms) if tms is not None and tms > 0 else 0,
                    out_ref)
            finally:
                if ls is not None:
                    ls.end()
                if blocked:
                    scheduler.note_worker_unblocked()
            result = None
            # read each out pointer ONCE into locals: the finally frees
            # from these instead of re-reading the struct
            resp_p = out.resp
            att_p = out.att
            segs_p0 = out.segs
            err_p = out.err_text
            try:
                cntl.remote_side = self.remote_side
                nsegs = out.nsegs
                if rc != 0:
                    text = _string_at(err_p, -1).decode() \
                        if err_p else errors.berror(int(rc))
                    cntl.set_failed(int(rc), text)
                    if out.retry_after_ms:
                        cntl.retry_after_ms = int(out.retry_after_ms)
                else:
                    payload = _string_at(resp_p, out.resp_len) \
                        if out.resp_len else b""
                    if nsegs or out.att_len:
                        ah = out.att_handle
                        if ah:
                            if nsegs == 1:
                                total = out.seg0_nbytes
                                meta = ((out.seg0_key, total,
                                         out.seg0_dev),)
                            else:
                                segs_p = out.segs
                                lst = []
                                total = 0
                                for i in range(nsegs):
                                    s = segs_p[i]
                                    lst.append((s.key, s.nbytes, s.dev))
                                    total += s.nbytes
                                meta = tuple(lst)
                            rbuf = NativeAttachment(ah, total, meta)
                        else:
                            r_att_host = _string_at(
                                att_p, out.att_len) if out.att_len \
                                else b""
                            rbuf = build_attachment_from_c(
                                r_att_host, out.segs, nsegs)
                        prev = cntl.__dict__.get("response_attachment")
                        if prev is None:
                            cntl.response_attachment = rbuf
                        else:
                            prev.append(rbuf)
                    with _t._ici_stats_lock:
                        _t._ici_bytes_moved += \
                            len(req) + len(att_host) + dev_bytes
                        _t._ici_device_bytes_moved += dev_bytes
                    cntl.error_code_ = 0
                    if response_cls is None:
                        result = payload
                    else:
                        response = response_cls()
                        response.ParseFromString(payload)
                        cntl.response = response
                        result = response
            finally:
                cntl.latency_us = (_time.monotonic_ns() - t0) // 1000
                free = self._free
                if resp_p:
                    free(resp_p)
                    out.resp = None
                if att_p:
                    free(att_p)
                    out.att = None
                if segs_p0:
                    free(segs_p0)
                    out.segs = None
                if err_p:
                    free(err_p)
                    out.err_text = None
        # ---- legacy tail, entered only on the error that needs it ----
        ec = cntl.error_code_
        if ec:
            if ec == errors.ELIMIT and cntl.retry_after_ms > 0:
                result = chan._native_shed_retry(
                    self, full_name, cntl, request, response_cls, result)
                ec = cntl.error_code_
            if ec == errors.EFAILEDSOCKET or (
                    ec == errors.EOVERCROWDED
                    and cntl.error_text_.startswith("frame larger")):
                if chan._native_ici_fallback(cntl):
                    return FUSED_FALLTHROUGH
        if cntl.span is not None:
            self._end_span(cntl)
        return result


def native_ici_echo_p50_us(iters: int = 3000, payload: int = 128,
                           device_array=None) -> float:
    """Native-loop ici echo p50 (µs): the C++ client loop over the full
    native ici datapath (window → frame codec → queue hop → dispatch →
    correlation wake).  With ``device_array``, the frame carries that
    array as a device ref (resident = the pure-HBM round trip).  -1 when
    unavailable."""
    lib = native.load()
    if lib is None or not ensure_hooks():
        return -1.0
    key, nbytes, dev = 0, 0, 0
    if device_array is not None:
        # compute the descriptor BEFORE registering: _device_index can
        # raise (stale mesh), and a raise after put would leak the key
        # past the try/finally below (fablint custody true positive)
        nbytes = device_array.nbytes
        dev = _device_index(device_array)
        key = _registry.put(device_array)    # borrowed for the loop
    try:
        ns = lib.brpc_tpu_ici_echo_p50_ns(iters, payload, key, nbytes, dev)
        return ns / 1000.0 if ns > 0 else -1.0
    finally:
        if key:
            _registry.release(key)
