"""Device data plane: payloads cross the mesh through compiled XLA programs.

This is the analogue of the reference's RDMA datapath proper
(src/brpc/rdma/rdma_endpoint.cpp:771 ``ibv_post_send`` posting registered
IOBuf blocks straight on the NIC, :926 freeing send buffers on CQ
completion): instead of staging device payloads through host memory
(``jax.device_put`` in-process, the native bulk TCP plane cross-process),
a DEVICE-block payload is moved chip-to-chip by a **compiled XLA
point-to-point transfer program** — shard_map + ``jax.lax.ppermute`` over
a 2-device submesh (XLA-scheduled; on TPU this lowers to a
collective-permute over the ICI links).  No NIC — and no host — in the
datapath.

QP semantics (rdma_endpoint.h:37-108):

  * ``post_send(block, src, dst, start=, nbytes=)`` posts a work request
    and returns a :class:`DeviceTransfer` (the WR handle).  Nothing moves
    yet — like a posted SGE (address + length inside a registered block),
    the payload is the piece ``[start, start + nbytes)`` of the block and
    the WHOLE block is pinned by the plane until completion.
  * a 16-byte descriptor ``(uuid, nbytes)`` (+ dtype/shape on the fabric
    wire) rides the transport's existing control/delivery channel;
  * the receiver ``post_recv(uuid)``s the matching recv — the rendezvous:
    both sides join the SAME compiled program (in-process: one runtime
    enters it once; multi-controller: each process enters with its local
    shard, the SPMD contract).
  * completion is a :class:`bthread.device_waiter.DeviceCompletion` (the
    CQ entry), signaled from the per-device completion poller — waiters
    yield their M:N worker instead of blocking it, and source pins
    release exactly at completion (the :926 discipline).

The program's ABI is FLAT, and the cut is the program's: its operand is
the global ``u8[2 * B]`` sharded over the two chips (the source's shard IS
the ``B``-byte block, no slice and no reshape on the host; the
destination's a cached dummy of ``B`` zeros) plus the start as a
replicated ``int32`` operand — an operand, never a static, so one program
serves every offset.  Per chip it runs
``ppermute(dynamic_slice(block, start, n))``; the output is the global
``u8[2 * n]`` whose destination shard IS the delivered flat piece
(``t.out``).  One program dispatch per transfer is all the host pays; a
whole array is the case ``start == 0, B == n`` of the same path (XLA folds
the slice away), which is what the fabric and the native tier's relocation
upcall post.  On the TPU a 1-D <-> ``(1, n)`` reshape is a relayout, not a
bitcast, which is why no ``(1, n)`` row appears anywhere.
``stats()["sliced_in_program"]`` counts the transfers whose piece was a
sub-range of its block and was cut by the program, beside ``transfers``.

Program cache: one compiled executable per (block bytes, piece bytes, src,
dst, mesh generation), exactly like the collectives cache — steady
workloads repost the same shapes and pay compilation once (cache
hits/misses are counters).

Failure semantics: a refused/failed post raises :class:`DevicePlaneError`
BEFORE any descriptor exists, so the caller degrades to its previous
path — ``device_put`` in-process, the PR-2 bulk/inline fallback machinery
on the fabric — within the same frame (counted in
``ici_device_plane_fallbacks``).  A program the compiler REFUSES is a
defect, not a degradation: :class:`DevicePlaneBuildError`, logged at error
with the compiler's message and counted apart in
``ici_device_plane_build_failures``.  An IN-PROCESS posted send whose recv
never arrives is reaped after ``ici_device_plane_match_timeout_s`` and
fails only that transfer.  Cross-process (fabric) transfers are owned by
their socket's per-direction executors instead: a transfer still queued
when the socket dies is failed by the executor (``fail_transfer`` —
completion fires, pins release), while one already INSIDE a collective
is uninterruptible from the host and relies on the backend's distributed
error propagation — the same contract every multi-controller XLA program
lives under.  The chaos harness forces the degrade paths
deterministically (``FabricFaultPlan.device_plane_fail_posts``).
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from .. import bvar
from ..butil import flags as _flags
from ..butil import debug_sync as _dbg
from ..butil import logging as log
from ..butil import custody_ledger as _ledger
from ..butil import layer_span as _layer
from ..bthread.device_waiter import (DeviceCompletion,
                                     DeviceEventDispatcher)
from .mesh import IciMesh

_flags.define_flag("ici_device_plane", True,
                   "move DEVICE payloads through compiled XLA transfer "
                   "programs (the no-host datapath) where eligible")
_flags.define_flag("ici_device_plane_threshold", 64 * 1024,
                   "min DEVICE payload bytes routed through the device "
                   "plane (smaller payloads keep the lower-fixed-cost "
                   "host paths)", _flags.positive_integer)
# On a host-memory mesh (the 8-virtual-device CPU platform) a compiled
# transfer program measured ~1.4 GB/s at 4 MB vs ~5.5 GB/s for a plain
# device_put memcpy — the program pays XLA dispatch plus its output's
# materialization for what is physically one host memcpy.  On TPU the
# program IS the ICI datapath and device_put cannot cross processes at
# all, so the plane engages there by default; host meshes must opt in
# (tests and the dryrun do — the code path is identical).
_flags.define_flag("ici_device_plane_host_mesh", False,
                   "engage the device plane on non-TPU (host-memory) "
                   "meshes too; slower than device_put there, real code "
                   "path for CI")
_flags.define_flag("ici_device_plane_match_timeout_s", 30.0,
                   "seconds a posted send waits for its matching recv "
                   "before failing (peer died post-descriptor)")
# Cross-process execution backend for the sequenced (xproc) plane.
# "auto": enter the compiled multi-controller collective on backends
# that have one (TPU pods), and carry the payload on the native bulk
# plane under the SAME epoch-ordered sequencer everywhere else (this
# container's CPU jaxlib raises "Multiprocess computations aren't
# implemented on the CPU backend" — the sequencer, descriptors, pins,
# and completions are identical either way, only the byte mover
# differs).  "on"/"off" force one leg, for tests and TPU bring-up.
_flags.define_flag("ici_device_plane_xproc_compiled", "auto",
                   "xproc transfer execution: 'auto' (compiled "
                   "collectives on TPU, bulk-carried elsewhere), 'on', "
                   "or 'off'")

_g_bytes_sent = bvar.Adder("ici_device_plane_bytes_sent")
_g_bytes_recv = bvar.Adder("ici_device_plane_bytes_recv")
_g_transfers = bvar.Adder("ici_device_plane_transfers")
_g_sliced = bvar.Adder("ici_device_plane_sliced_in_program")
_g_fallbacks = bvar.Adder("ici_device_plane_fallbacks")
_g_cache_hits = bvar.Adder("ici_device_plane_program_cache_hits")
_g_cache_misses = bvar.Adder("ici_device_plane_program_cache_misses")
_g_match_timeouts = bvar.Adder("ici_device_plane_match_timeouts")
_g_build_failures = bvar.Adder("ici_device_plane_build_failures")


class DevicePlaneError(ConnectionError):
    """A post was refused or failed before any descriptor went out; the
    caller must route the payload over its fallback path."""


class DevicePlaneBuildError(DevicePlaneError):
    """The compiler refused the transfer program.  Unlike a runtime
    refusal (chaos, plane health) this is a defect of the program, not of
    the moment: logged at error with the compiler's message and counted
    in ``build_failures`` where it happens, so the frame's degrade to
    device_put is never the only trace of it."""


# transfer states (WR lifecycle)
POSTED = "posted"          # send posted, awaiting the matching recv
MATCHED = "matched"        # rendezvous done, compiled program dispatched
COMPLETE = "complete"      # payload resident at dst; source released
FAILED = "failed"


class DeviceTransfer:
    """One posted work request: uuid-correlated, completion-signaled.

    The payload is the piece ``[start, start + nbytes)`` of the pinned
    source block (``block_bytes`` long; a whole array is the piece at 0
    that is as long as its block).  ``out`` is the dst-resident flat
    uint8 piece once MATCHED (an XLA future — physically resident at
    COMPLETE, which is when the source pin releases).
    ``wait``/``poll``/``add_done_callback`` are the CQ interface (see
    DeviceCompletion)."""

    __slots__ = ("uuid", "src_dev", "dst_dev", "nbytes", "start",
                 "block_bytes", "state", "error",
                 "out", "completion", "posted_ns", "matched_ns",
                 "complete_ns", "_src_arr", "_releases", "_lock",
                 "trace_id", "parent_span_id", "span")

    def __init__(self, uuid: int, src_dev: int, dst_dev: int, nbytes: int,
                 src_arr=None, trace_id: int = 0, parent_span_id: int = 0,
                 start: int = 0):
        self.uuid = uuid
        self.src_dev = src_dev
        self.dst_dev = dst_dev
        self.nbytes = nbytes
        self.start = start
        # a receiver-only half holds no block: its program is the whole
        # array's, which is what the peer entered
        self.block_bytes = (int(src_arr.shape[0]) if src_arr is not None
                            else nbytes)
        self.state = POSTED
        self.error = ""
        self.out = None
        self.completion = DeviceCompletion()
        self.posted_ns = time.monotonic_ns()
        self.matched_ns = 0
        self.complete_ns = 0
        self._src_arr = src_arr        # the pin (rdma_endpoint.cpp:926)
        self._releases: List[Callable[[], None]] = []
        self._lock = _dbg.make_lock("DeviceTransfer._lock")
        # trace context: the RPC span this transfer belongs to, captured
        # at post time (sender) or carried in the kind-4 descriptor
        # (receiver), so the transfer's lifecycle lands in the SAME
        # trace on both processes.  With a context and sampling on, the
        # transfer owns its own SpanDB entry (a "transfer" span parented
        # under the RPC span); without one, annotations degrade to the
        # bthread-local current span, the pre-pod behavior.
        self.trace_id = trace_id
        self.parent_span_id = parent_span_id
        self.span = None
        if trace_id:
            from ..rpc import span as _span
            if _span.rpcz_enabled():
                self.span = _span.start_transfer_span(
                    f"device_plane ici://{src_dev}->{dst_dev} "
                    f"{'send' if src_arr is not None else 'recv'} "
                    f"{nbytes}B", trace_id, parent_span_id)

    # -- source pin ------------------------------------------------------
    def add_source_release(self, cb: Optional[Callable[[], None]]) -> None:
        """Called exactly once when the source block may be reused/donated
        (completion OR failure — either way the transfer holds no more
        references)."""
        if cb is None:
            return
        with self._lock:
            if self.state not in (COMPLETE, FAILED):
                self._releases.append(cb)
                return
        cb()

    def source_array(self):
        return self._src_arr

    def _release_source(self) -> None:
        with self._lock:
            cbs, self._releases = self._releases, []
            self._src_arr = None
        for cb in cbs:
            try:
                cb()
            except Exception:
                pass

    # -- CQ interface ----------------------------------------------------
    def poll(self) -> bool:
        return self.completion.poll()

    def wait(self, timeout: Optional[float] = None) -> int:
        return self.completion.wait(timeout)

    def add_done_callback(self, cb: Callable[[int], None]) -> None:
        self.completion.add_done_callback(cb)

    def describe(self) -> dict:
        return {
            "uuid": f"{self.uuid:#x}",
            "route": f"ici://{self.src_dev} -> ici://{self.dst_dev}",
            "nbytes": self.nbytes,
            "state": self.state,
            "error": self.error,
            "posted_to_matched_us": ((self.matched_ns - self.posted_ns)
                                     // 1000 if self.matched_ns else -1),
            "matched_to_complete_us": ((self.complete_ns - self.matched_ns)
                                       // 1000 if self.complete_ns else -1),
        }


def mesh_index_of(arr, mesh: Optional[IciMesh] = None) -> int:
    """Logical mesh id of a (single-device) array's residence; -1 when
    off-mesh or host-resident."""
    mesh = mesh or IciMesh.default()
    try:
        idx = mesh.device_index(arr.device)
        if idx >= 0:
            return idx
    except Exception:
        pass
    try:
        for d in arr.devices():
            i = mesh.device_index(d)
            if i >= 0:
                return i
    except Exception:
        pass
    return -1


def _platform() -> str:
    import jax
    return jax.devices()[0].platform


def platform_allows() -> bool:
    """The plane engages on TPU by default; host-memory meshes opt in
    (see the ici_device_plane_host_mesh flag rationale)."""
    try:
        return (_platform() == "tpu"
                or bool(_flags.get_flag("ici_device_plane_host_mesh")))
    except Exception:
        return False


def eligible(nbytes: int) -> bool:
    """Route this payload device-plane?  Flag + threshold + platform."""
    return (bool(_flags.get_flag("ici_device_plane"))
            and nbytes >= _flags.get_flag("ici_device_plane_threshold")
            and platform_allows())


def xproc_compiled_ok() -> bool:
    """Does the cross-process plane enter COMPILED multi-controller
    collectives, or carry bytes on the bulk plane under the same
    sequencer?  See the ici_device_plane_xproc_compiled flag."""
    mode = _flags.get_flag("ici_device_plane_xproc_compiled")
    if mode == "on":
        return True
    if mode == "off":
        return False
    try:
        return _platform() == "tpu"
    except Exception:
        return False


class DevicePlane:
    """Per-process device plane: program cache + posted-WR table."""

    _instance: Optional["DevicePlane"] = None
    _ilock = threading.Lock()

    # fablint guarded-state contract: cache/WR-table structure AND the
    # running stats counters — post_send/post_recv/execute_remote run
    # on arbitrary caller + executor + poller threads, so unguarded
    # `+= 1` counter updates were lost under contention (fablint
    # finding; the per-direction executors alone make two writers)
    _GUARDED_BY = {
        "_programs": "_lock",
        "_zeros": "_lock",
        "_starts": "_lock",
        "_pending": "_lock",
        "_active": "_lock",
        "_next_uuid": "_lock",
        "transfers": "_lock",
        "sliced_in_program": "_lock",
        "bytes_sent": "_lock",
        "bytes_recv": "_lock",
        "fallbacks": "_lock",
        "build_failures": "_lock",
        "cache_hits": "_lock",
        "cache_misses": "_lock",
        "match_timeouts": "_lock",
    }

    # fablint custody contract (ISSUE 20): every tracked transfer (its
    # source HBM pin rides the _active entry) must untrack — completion,
    # failure, and the lame-duck fail_pending sweep are the exits.  The
    # post_* sites carry custody-moved markers because the release fires
    # asynchronously from the CQ callback, not on the posting path.
    _CUSTODY = {"_track": ("_untrack",)}

    # cache bounds: steady workloads repost a handful of (size, route)
    # shapes, but arbitrary attachment sizes would otherwise compile and
    # pin one executable + one device-resident dummy block (as long as
    # the SOURCE block: 64 MiB on the destination of a 64 MiB attachment's
    # pieces) PER DISTINCT byte count, forever — LRU-bound both
    MAX_PROGRAMS = 64
    MAX_ZEROS = 64
    MAX_STARTS = 512

    def __init__(self, mesh: Optional[IciMesh] = None):
        self._mesh = mesh
        self._lock = _dbg.make_lock("DevicePlane._lock")
        self._programs: "collections.OrderedDict" = collections.OrderedDict()
        self._zeros: "collections.OrderedDict" = collections.OrderedDict()
        self._starts: Dict[tuple, Any] = {}
        self._pending: Dict[int, DeviceTransfer] = {}   # posted sends
        self._active: set = set()      # posted-but-incomplete (drain gate)
        self._next_uuid = 1
        self._recent: collections.deque = collections.deque(maxlen=64)
        # local running totals (the bvar Adders are process-global and
        # shared with other planes a test may construct)
        self.transfers = 0
        self.sliced_in_program = 0
        self.bytes_sent = 0
        self.bytes_recv = 0
        self.fallbacks = 0
        self.build_failures = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self.match_timeouts = 0

    @classmethod
    def instance(cls) -> "DevicePlane":
        with cls._ilock:
            if cls._instance is None:
                cls._instance = DevicePlane()
            return cls._instance

    def mesh(self) -> IciMesh:
        return self._mesh or IciMesh.default()

    # ---- program cache -------------------------------------------------
    def _program(self, block_bytes: int, nbytes: int, src_dev: int,
                 dst_dev: int):
        """Compile-or-fetch the (src → dst) transfer program that cuts
        ``nbytes`` out of a ``block_bytes`` block.
        Returns (fn, input_sharding, mesh2, src_device, dst_device)."""
        gen = IciMesh.generation
        key = (block_bytes, nbytes, src_dev, dst_dev, gen)
        with self._lock:
            hit = self._programs.get(key)
            if hit is not None:
                self._programs.move_to_end(key)
                self.cache_hits += 1
        if hit is not None:
            _g_cache_hits << 1
            return hit
        built = self._build(block_bytes, nbytes, src_dev, dst_dev)
        with self._lock:
            # a racing builder may have won; keep the first (identical)
            entry = self._programs.setdefault(key, built)
            self._programs.move_to_end(key)
            while len(self._programs) > self.MAX_PROGRAMS:
                self._programs.popitem(last=False)
            self.cache_misses += 1
        _g_cache_misses << 1
        return entry

    def _build(self, block_bytes: int, nbytes: int, src_dev: int,
               dst_dev: int):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from jax import shard_map
        mesh = self.mesh()
        src, dst = mesh.device(src_dev), mesh.device(dst_dev)
        mesh2 = Mesh(np.array([src, dst]), ("p2p",))
        sharding = NamedSharding(mesh2, P("p2p"))

        def per_device(x_local, start):       # the flat block, () int32
            piece = jax.lax.dynamic_slice(x_local, (start,), (nbytes,))
            return jax.lax.ppermute(piece, "p2p", [(0, 1)])

        # compiled HERE, not at first call: the compiler's verdict on the
        # program belongs to the build (post_send), before any descriptor
        fn = jax.jit(shard_map(per_device, mesh=mesh2,
                               in_specs=(P("p2p"), P()),
                               out_specs=P("p2p"), check_vma=False)).lower(
            jax.ShapeDtypeStruct((2 * block_bytes,), jnp.uint8,
                                 sharding=sharding),
            jax.ShapeDtypeStruct((), jnp.int32,
                                 sharding=NamedSharding(mesh2, P()))
        ).compile()
        return (fn, sharding, mesh2, src, dst)

    def _zeros_block(self, dst_dev: int, block_bytes: int):
        """The dst-side input shard (ppermute delivers INTO the program,
        so dst contributes a dummy block as long as the source's).  Cached
        per (dst, size): steady workloads pay this once, not per
        transfer."""
        import jax.numpy as jnp
        gen = IciMesh.generation
        key = (dst_dev, block_bytes, gen)
        with self._lock:
            z = self._zeros.get(key)
            if z is not None:
                self._zeros.move_to_end(key)
        if z is None:
            z = jnp.zeros((block_bytes,), jnp.uint8,
                          device=self.mesh().device(dst_dev))
            with self._lock:
                z = self._zeros.setdefault(key, z)
                self._zeros.move_to_end(key)
                while len(self._zeros) > self.MAX_ZEROS:
                    self._zeros.popitem(last=False)
        return z

    def _start_operand(self, t: "DeviceTransfer", mesh2):
        """The piece's start as the program's replicated int32 operand.
        Kept per (start, route): a frame layout reposts the same offsets,
        and a host scalar handed to the program is a host-to-device copy
        per chip per transfer (0.15 ms of a 0.67 ms dispatch on a CPU
        mesh).  Eight bytes an entry, so a full table is simply dropped."""
        import jax
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        key = (t.start, t.src_dev, t.dst_dev, IciMesh.generation)
        with self._lock:
            op = self._starts.get(key)
        if op is None:
            # assembled from this process's copies: a device_put onto a
            # sharding that spans processes would be a collective itself
            op = jax.make_array_from_single_device_arrays(
                (), NamedSharding(mesh2, P()),
                [jax.device_put(np.int32(t.start), d)
                 for d in mesh2.devices.flat if _is_local(d)])
            with self._lock:
                if len(self._starts) >= self.MAX_STARTS:
                    self._starts.clear()
                self._starts[key] = op
        return op

    # ---- QP interface --------------------------------------------------
    def next_uuid(self) -> int:
        with self._lock:
            u = self._next_uuid
            self._next_uuid += 1
            return u

    def post_send(self, arr, src_dev: int, dst_dev: int, socket=None,
                  uuid: Optional[int] = None, remote: bool = False,
                  start: int = 0,
                  nbytes: Optional[int] = None) -> DeviceTransfer:
        """Post one send WR.  ``arr``: flat uint8 jax array resident on
        mesh device ``src_dev`` — the whole block; the payload is its
        piece ``[start, start + nbytes)`` (all of it by default), which
        the transfer program cuts on the chip.  Raises DevicePlaneError
        (before any descriptor exists) when refused — chaos injection, or
        a plane that cannot serve the route — so the caller can fall back
        in the same frame."""
        if nbytes is None:
            nbytes = int(arr.shape[0]) - start
        # layer span brpc.plane.post: the program's fetch or build, the
        # descriptor and its tracking (under the writer's brpc.ici.piece)
        post = _layer.layer_begin("brpc.plane.post", n=nbytes) \
            if _layer.layer_on() else None
        try:
            return self._post_send(arr, start, nbytes, src_dev, dst_dev,
                                   socket, uuid, remote)
        finally:
            if post is not None:
                post.end()

    def _post_send(self, arr, start: int, nbytes: int, src_dev: int,
                   dst_dev: int, socket, uuid: Optional[int],
                   remote: bool) -> DeviceTransfer:
        from ..rpc import fault_injection as _fi
        plan = _fi.fabric_active()
        if plan is not None and plan.on_device_post(socket):
            with self._lock:
                self.fallbacks += 1
            _g_fallbacks << 1
            raise DevicePlaneError("injected device-plane post refusal")
        if src_dev == dst_dev:
            raise DevicePlaneError("device plane is point-to-point; "
                                   "same-device payloads are ref passes")
        block_bytes = int(arr.shape[0])
        if not 0 <= start <= block_bytes - nbytes:
            # dynamic_slice would clamp an out-of-range start in silence
            raise ValueError(f"piece [{start}, {start + nbytes}) is not "
                             f"inside its {block_bytes}B block")
        if remote and nbytes != block_bytes:
            # the one post whose program cannot cut on the chip: a peer
            # process enters the program its kind-4 descriptor names —
            # the piece alone.  Cut here, the piece crosses as a whole
            # array, which sliced_in_program does not count
            arr = arr[start:start + nbytes]
            start, block_bytes = 0, nbytes
        # compile (or fetch) FIRST: a compilation error must surface before
        # the descriptor is committed to any wire, and the seconds a cold
        # compile takes are not the peer's to answer for — the match
        # timeout runs from the post, which starts once the program exists
        try:
            self._program(block_bytes, nbytes, src_dev, dst_dev)
        except Exception as e:
            with self._lock:
                self.build_failures += 1
            _g_build_failures << 1
            log.error("device plane ici://%d->%d: %dB transfer program "
                      "was refused by the compiler: %s: %s",
                      src_dev, dst_dev, nbytes, type(e).__name__, e)
            raise DevicePlaneBuildError(
                f"transfer program build failed: {e}") from e
        # trace context at post time: the server span being served, or
        # the ACTIVE client span (channel write path) — the context the
        # kind-4 descriptor carries to the receiver
        from ..rpc import span as _span
        tid, psid = _span.current_trace_context()
        t = DeviceTransfer(uuid if uuid is not None else self.next_uuid(),
                           src_dev, dst_dev, nbytes, src_arr=arr,
                           trace_id=tid, parent_span_id=psid, start=start)
        if not remote:
            with self._lock:
                self._pending[t.uuid] = t
        self._track(t)  # fablint: custody-moved(completion-path) the CQ done()/_fail callback untracks when the transfer completes or dies; fail_pending sweeps the orphans
        self._recent.append(t)
        self._annotate(t, "posted")
        self._sweep_stale()
        return t

    def post_recv(self, uuid: int) -> DeviceTransfer:
        """In-process rendezvous: match the posted send and join the
        compiled program.  Raises KeyError when no matching send is
        pending (already reaped by the match timeout, or never posted).
        On a program execution failure the transfer degrades internally
        to a plain device_put of the still-pinned source — the payload is
        in this process either way, so delivery must not fail."""
        with self._lock:
            t = self._pending.pop(uuid, None)
        if t is None:
            raise KeyError(f"device plane: no posted send {uuid:#x}")
        try:
            out = self._run(t)
        except Exception as e:
            # in-process degrade: device_put the pinned source (counted);
            # the compiled path failed but the bytes must still arrive
            import jax
            log.error("device plane %s: compiled transfer failed (%s) — "
                      "device_put fallback", t.describe()["route"], e)
            with self._lock:
                self.fallbacks += 1
            _g_fallbacks << 1
            arr = t.source_array()
            if t.nbytes != t.block_bytes:
                # cut here after all: a whole array from now on, as the
                # posts that _post_send cuts on the host
                arr = arr[t.start:t.start + t.nbytes]
                t.start, t.block_bytes = 0, t.nbytes
            out = jax.device_put(arr, self.mesh().device(t.dst_dev))
        self._matched(t, out)
        return t

    # ---- fabric (multi-controller) halves ------------------------------
    def post_recv_remote(self, uuid: int, nbytes: int, src_dev: int,
                         dst_dev: int, socket=None, trace_id: int = 0,
                         parent_span_id: int = 0) -> DeviceTransfer:
        """Receiver half of a cross-process transfer: the descriptor
        arrived on the control channel; register the recv WR.  The
        collective itself runs on the fabric socket's executor (control
        order = execution order on both sides, the SPMD ordering
        contract).  ``trace_id``/``parent_span_id`` come off the kind-4
        descriptor, so the receiver's half of the transfer joins the
        sender's trace."""
        t = DeviceTransfer(uuid, src_dev, dst_dev, nbytes,
                           trace_id=trace_id,
                           parent_span_id=parent_span_id)
        self._track(t)  # fablint: custody-moved(completion-path) finish_remote/execute_remote completion or failure untracks; fail_pending sweeps the orphans
        self._recent.append(t)
        self._annotate(t, "recv enqueued")
        return t

    def execute_remote(self, t: DeviceTransfer) -> None:
        """Enter the compiled program with THIS process's shard (the
        payload when we own src, a dummy block when we own dst).  Called
        on the fabric executor thread; blocks until the peer joins.
        Failure fails the transfer (completion signaled with an error)
        and re-raises so the socket degrades its plane."""
        try:
            out = self._run(t, local_only=True)
        except Exception as e:
            self._fail(t, f"remote execution failed: {e}")
            raise
        self._matched(t, out)

    # ---- execution -----------------------------------------------------
    def _run(self, t: DeviceTransfer, local_only: bool = False):
        """Assemble the global flat ``(2 * block_bytes,)`` operand — the
        source's shard IS the pinned block, the destination's a cached
        dummy (the peer process's shard is left out under ``local_only``)
        — and run the cached program on it and the piece's start.  Returns
        the dst-resident flat piece, the destination's shard of the
        output as it is (None when dst is not addressable from this
        process)."""
        import jax
        # layer span brpc.plane.run: the dummy block, the global operand's
        # assembly, the program's dispatch and the pick of dst's shard
        run = _layer.layer_begin("brpc.plane.run", n=t.nbytes, cpu=True) \
            if _layer.layer_on() else None
        try:
            fn, sharding, mesh2, src, dst = self._program(
                t.block_bytes, t.nbytes, t.src_dev, t.dst_dev)
            shards = []
            for dev_id, device, shard in ((t.src_dev, src, t.source_array()),
                                          (t.dst_dev, dst, None)):
                if shard is None:
                    if local_only and not _is_local(device):
                        continue           # the peer process's shard
                    shard = self._zeros_block(dev_id, t.block_bytes)
                shards.append(shard)
            ga = jax.make_array_from_single_device_arrays(
                (2 * t.block_bytes,), sharding, shards)
            # the start is an operand, never a static: one program serves
            # every offset of its (block, piece) sizes
            out_global = fn(ga, self._start_operand(t, mesh2))
            for s in out_global.addressable_shards:
                if s.device == dst:
                    return s.data
            return None
        finally:
            if run is not None:
                run.end()

    def _matched(self, t: DeviceTransfer, out) -> None:
        t.state = MATCHED
        t.matched_ns = time.monotonic_ns()
        t.out = out
        self._annotate(t, "matched")
        # bytes_sent is a SENDER-side counter: a pure receiver (fabric
        # recv half, no source pinned) must not inflate it — in-process
        # transfers are both roles and count both directions
        sender = t.source_array() is not None
        # still a piece of a longer block here: the program cut it
        sliced = t.nbytes != t.block_bytes
        with self._lock:
            self.transfers += 1
            if sliced:
                self.sliced_in_program += 1
            if sender:
                self.bytes_sent += t.nbytes
        _g_transfers << 1
        if sliced:
            _g_sliced << 1
        if sender:
            _g_bytes_sent << t.nbytes
        # layer span brpc.plane.complete: from here to done(), on whichever
        # thread signals it: the transfer itself and its wait for the poller
        mark = _layer.layer_mark(t.nbytes) if _layer.layer_on() else None

        def done() -> None:
            if mark is not None:
                _layer.layer_waited("brpc.plane.complete", mark)
            t.state = COMPLETE
            t.complete_ns = time.monotonic_ns()
            if out is not None:
                with self._lock:
                    self.bytes_recv += t.nbytes
                _g_bytes_recv << t.nbytes
            t._release_source()
            self._untrack(t)
            # pin hold-time: posted→complete is exactly how long the
            # source HBM block stayed pinned (the :926 discipline)
            self._annotate(
                t, "complete pin_held_us="
                   f"{(t.complete_ns - t.posted_ns) // 1000}")
            self._close_span(t, 0)
            t.completion.signal(0)

        if out is not None:
            # the device stream is the CQ: completion fires when the
            # transfer's output is physically resident at dst, on the
            # poller thread (DeviceCompletion.signal: must not block)
            DeviceEventDispatcher.instance().on_ready([out], done)
        else:
            done()           # sender-only half: participation is complete

    def _fail(self, t: DeviceTransfer, reason: str) -> None:
        t.state = FAILED
        t.error = reason
        t._release_source()
        self._untrack(t)
        self._annotate(t, f"failed: {reason}")
        self._close_span(t, 1)
        t.completion.signal(1)

    def fail_transfer(self, t: DeviceTransfer, reason: str) -> None:
        """Fail a transfer that can never execute (its socket died while
        it sat in an executor queue): completion fires with an error and
        the source pin releases."""
        self._fail(t, reason)

    def finish_remote(self, t: DeviceTransfer, out) -> None:
        """Complete a cross-process transfer whose bytes were moved by
        the transport itself (the bulk-carried xproc leg): same CQ
        semantics as the compiled path — completion signals when ``out``
        is resident at dst (sender half passes None), and the source pin
        releases exactly then."""
        self._matched(t, out)

    # ---- drain barrier (lame-duck server stop) -------------------------
    def _track(self, t: DeviceTransfer) -> None:
        _ledger.acquire("dev.transfer", (id(self), t.uuid))
        with self._lock:
            self._active.add(t)

    def _untrack(self, t: DeviceTransfer) -> None:
        # non-strict: discard is idempotent (a fail_pending sweep can
        # race the CQ callback), so a second untrack must stay a no-op
        _ledger.release("dev.transfer", (id(self), t.uuid))
        with self._lock:
            self._active.discard(t)

    def active_transfers(self) -> int:
        """Posted-but-incomplete transfers — the server drain gate waits
        for this to reach zero inside the grace window (completion fires,
        pins release — never a leaked HBM pin)."""
        with self._lock:
            return len(self._active)

    def fail_pending(self, reason: str,
                     posted_before_ns: Optional[int] = None) -> None:
        """Fail posted sends whose rendezvous never came (lame-duck
        grace expired): completions fire with an error and the source
        pins release NOW instead of at the 30s match-timeout sweep.
        ``posted_before_ns`` scopes the reap to sends already posted at
        that instant — the plane is process-global, and a transfer some
        OTHER server/channel posted mid-drain (healthy traffic matches
        in microseconds) must not be collateral."""
        stale = []
        with self._lock:
            for uuid, t in list(self._pending.items()):
                if posted_before_ns is None \
                        or t.posted_ns < posted_before_ns:
                    stale.append(self._pending.pop(uuid))
        for t in stale:
            self._fail(t, reason)

    def _sweep_stale(self) -> None:
        """Reap posted sends whose recv never matched (peer died between
        descriptor and rendezvous): fail ONLY those transfers, releasing
        their source pins."""
        timeout = _flags.get_flag("ici_device_plane_match_timeout_s")
        cutoff = time.monotonic_ns() - int(timeout * 1e9)
        stale = []
        with self._lock:
            for uuid, t in list(self._pending.items()):
                if t.posted_ns < cutoff:
                    stale.append(self._pending.pop(uuid))
        for t in stale:
            with self._lock:
                self.match_timeouts += 1
            _g_match_timeouts << 1
            self._fail(t, "no matching recv within "
                          f"{timeout}s (match timeout)")

    # ---- observability -------------------------------------------------
    def _annotate(self, t: DeviceTransfer, what: str) -> None:
        from ..rpc import span as _span
        text = (f"device_plane {what} uuid={t.uuid:#x} "
                f"ici://{t.src_dev}->{t.dst_dev} {t.nbytes}B")
        if t.span is not None:
            # the transfer owns a span in the RPC's trace: its lifecycle
            # lands there on BOTH processes (the receiver's context rode
            # the descriptor) instead of on whatever span happens to be
            # bthread-local on one side
            t.span.annotate(text)
        else:
            _span.annotate_current(text)

    def annotate_transfer(self, t: DeviceTransfer, what: str) -> None:
        """Public hook for transfer-lifecycle events raised OUTSIDE the
        plane (the CollectiveSequencer's assignment/queue-wait/admit)."""
        self._annotate(t, what)

    @staticmethod
    def _close_span(t: DeviceTransfer, error_code: int) -> None:
        from ..rpc import span as _span
        span, t.span = t.span, None
        if span is not None:
            _span.end_span(span, error_code)

    def pending_sends(self) -> int:
        with self._lock:
            return len(self._pending)

    def recent_transfers(self) -> List[dict]:
        return [t.describe() for t in list(self._recent)]

    def stats(self) -> Dict[str, int]:
        with self._lock:
            out = {
                "transfers": self.transfers,
                "sliced_in_program": self.sliced_in_program,
                "bytes_sent": self.bytes_sent,
                "bytes_recv": self.bytes_recv,
                "fallbacks": self.fallbacks,
                "build_failures": self.build_failures,
                "program_cache_hits": self.cache_hits,
                "program_cache_misses": self.cache_misses,
                "match_timeouts": self.match_timeouts,
            }
        out["pending_sends"] = self.pending_sends()
        return out

    # ---- one-call convenience (in-process transports) ------------------
    def transfer_local(self, arr, src_dev: int, dst_dev: int, socket=None):
        """post_send + immediate rendezvous: the in-process fast path
        used by the native plane's relocation upcall.  Returns the
        dst-resident array (an XLA future; the transfer's completion
        releases the source pin).  Raises DevicePlaneError on refusal."""
        t = self.post_send(arr, src_dev, dst_dev, socket=socket)
        return self.post_recv(t.uuid)


def _is_local(device) -> bool:
    try:
        import jax
        return device.process_index == jax.process_index()
    except Exception:
        return True


def plane() -> DevicePlane:
    return DevicePlane.instance()
