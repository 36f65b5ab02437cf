"""Pod-scope compiled collective fan-out: a Parallel/Partition call as ONE
SPMD program — scatter, N device-local handler bodies, gather/psum.

PAPER.md's north-star sentence names "combo channels (Parallel/Partition/
Selective) that lower to mesh collectives" as a defining capability;
``collective_lowering.py`` built the same-process toy (its own method
table, its own call surface).  This module is the RPC-integrated plane:
the SAME ``ParallelChannel.call_method`` that fans out N socket RPCs
instead compiles the whole fan-out+merge into one cached XLA program when
every sub-channel targets a pod member that registered a **device-side
handler** for the method (``Server.register_collective``), and degrades
IN-CALL to the per-member RPC loop — zero client-visible failures — when
any screen fails or any member dies mid-fan-out.

The two execution legs (the ``ici_device_plane_xproc_compiled`` split,
device_plane.py):

  * **local** — every participating device is addressable from the
    calling process (the in-process pod: N servers on ``ici://k``, the
    virtual-mesh CI shape, or a whole-pod single controller).  The
    CallMapper's scatter IS sharded operand placement (``device_put``
    with the submesh sharding, skipped when the caller pre-placed), and
    the program is handler bodies + the merge collective over a submesh
    of exactly the fan-out's target devices.
  * **xproc** — some participants live in other pod processes (a real
    multi-controller pod).  Every participant must enter the SAME
    program in the SAME order (the SPMD deadlock constraint, SURVEY.md
    §7), so the client announces ``(method, shapes, seq)`` over each
    member's fabric control channel (``_F_COLL_CALL``) and members enter
    through a per-process runner in announce order — the client is the
    order master for its fan-out group, and the control channel's FIFO
    makes every member observe the same order.  The operand cannot be
    *placed* onto a remote device, so the xproc program broadcasts from
    the client row instead: every non-client participant contributes a
    zeros row (the ``_zeros_block`` discipline) and ``psum`` over the axis
    reconstructs the request everywhere — scatter by collective, not by
    placement.  Backends without multi-controller programs (this
    container's CPU jaxlib) refuse at the screen (``xproc_compiled_ok``)
    and the call rides the per-member RPC loop: the route table records
    WHY, and the dryrun's collective phase prints the same reason as its
    off-mesh SKIP.

Degradation and revival ride the PR-10 route-table discipline
(``ici/route.py``): one failed execution (member killed mid-fan-out —
the FabricFaultPlan knobs — a compile error, a refused announce) marks
the collective route down with a reason, the call completes on the RPC
loop, and the route re-probes only after the pod epoch moved past the
epoch it died under (a member re-advertising — revival — bumps it).

Execution is SERIALIZED in sequencer order: two overlapping fan-outs
that both enter collective programs over overlapping submeshes would
otherwise interleave their per-device dispatches, and the CPU backend's
rendezvous (and a TPU pod's collective scheduler) deadlocks exactly
there — measured on this host: unsynced back-to-back dispatches of ONE
all_gather program wedge the participant rendezvous.  One program in
flight at a time is the SPMD ordering contract made executable.

KNOWN LIMIT (xproc, recorded in ROADMAP): the sequencer totally orders
ONE process's entries, and the announce protocol totally orders ONE
client's groups per member — but two clients concurrently fanning out
over members that include EACH OTHER have no agreed inter-group order:
each can hold its local slot inside its own program while the peer's
committed member entry waits behind that slot.  Deploy xproc fan-out
with disjoint client/member roles (the serving-pod shape) or a single
fan-out client per overlapping member set until a pod-wide entry
arbiter lands.
"""
from __future__ import annotations

import collections
import itertools
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

from .. import bvar
from ..butil import debug_sync as _dbg
from ..butil import flags as _flags
from ..butil import layer_span as _span
from ..butil import logging as log
from ..butil.iobuf import IOBuf
from . import parallel_channel as _pc
from .collective_lowering import (MERGE_SUM, MERGE_GATHER, MERGE_CONCAT,
                                  MERGE_NONE, MAP_REPLICATE, MAP_SHARD)

_flags.define_flag("ici_fanout_collective", True,
                   "lower eligible Parallel/Partition fan-outs to ONE "
                   "compiled collective program (off: always the "
                   "per-member RPC loop)")
_flags.define_flag("ici_fanout_cache_max", 64,
                   "max cached compiled fan-out programs (LRU)",
                   _flags.positive_integer)
_flags.define_flag("ici_fanout_xproc_timeout_s", 10.0,
                   "seconds the client waits for every remote member to "
                   "accept a collective fan-out announce before "
                   "degrading to per-member RPCs")
_flags.define_flag("ici_fanout_reprobe_s", 5.0,
                   "seconds before a route downed by a TRANSIENT reason "
                   "(exec_failed / announce_refused) re-probes without "
                   "an epoch move; membership reasons stay epoch-gated")

# screen/degrade reasons (route counter labels)
R_XPROC = "xproc_uncompiled"      # remote member, no multi-controller leg
R_MEMBER = "member_down"          # target device not serving the method
R_EXEC = "exec_failed"            # program execution raised mid-fan-out
R_KILLED = "member_killed"        # fault-plan kill fired mid-fan-out
R_ANNOUNCE = "announce_refused"   # a remote member refused/failed entry
R_TARGET = "target_not_ici"      # a sub-channel is not a fixed ici:// peer
R_MAPPER = "mapper"               # CallMapper not lowerable
R_MERGE = "merge_mismatch"        # client merge mode != registered mode
R_SHAPE = "shape"                 # sharded operand rows != fan-out width
R_UNREGISTERED = "unregistered"   # no device handler for the method
R_NO_CARRIER = "no_local_carrier"  # xproc with zero client-owned rows

# transient degrade reasons re-probe on a timer; membership reasons
# (a killed/withdrawn member) wait for the epoch to move
_TRANSIENT_REASONS = (R_EXEC, R_ANNOUNCE)


class CollectiveMethodDef:
    """One registered device-side method body: the SPMD handler plus the
    merge/mapping contract the client's mapper/merger must match."""

    __slots__ = ("name", "handler", "merge", "mapping", "takes_index")

    def __init__(self, name: str, handler: Callable, merge: str,
                 mapping: str, takes_index: bool):
        self.name = name
        self.handler = handler
        self.merge = merge
        self.mapping = mapping
        self.takes_index = takes_index


class CollectiveRegistry:
    """Process-global method table + per-device serving marks.

    ``register`` is the capability half of ``Server.register_collective``
    (one handler per method — the SAME program body runs on every shard,
    the SPMD contract); ``serve``/``withdraw`` track which ``ici://k``
    devices currently have a serving server, the per-member liveness the
    screen consults.  Every transition bumps the local epoch (and
    re-publishes the pod record when a pod is joined) so a degraded
    route observes revival as an epoch move."""

    _GUARDED_BY = {
        "_methods": "_lock",
        "_serving": "_lock",
        "_epoch": "_lock",
    }

    def __init__(self) -> None:
        self._lock = _dbg.make_lock("CollectiveRegistry._lock")
        self._methods: Dict[str, CollectiveMethodDef] = {}
        self._serving: Dict[int, int] = {}      # device -> serve count
        self._epoch = 0

    def register(self, name: str, handler: Callable,
                 merge: str = MERGE_GATHER, mapping: str = MAP_SHARD,
                 takes_index: bool = False) -> None:
        md = CollectiveMethodDef(name, handler, merge, mapping, takes_index)
        with self._lock:
            self._methods[name] = md
            self._epoch += 1
        self._publish_pod()

    def method(self, name: str) -> Optional[CollectiveMethodDef]:
        with self._lock:
            return self._methods.get(name)

    def method_names(self) -> List[str]:
        with self._lock:
            return sorted(self._methods)

    def serve(self, device_id: int) -> None:
        """A server on ``ici://device_id`` (re)started in this process —
        its devices may participate in compiled fan-outs.  Counted, not
        boolean: two servers on one device (restart overlap) must not
        withdraw early."""
        with self._lock:
            self._serving[device_id] = self._serving.get(device_id, 0) + 1
            self._epoch += 1

    def withdraw(self, device_id: int) -> None:
        with self._lock:
            n = self._serving.get(device_id, 0)
            if n <= 1:
                self._serving.pop(device_id, None)
            else:
                self._serving[device_id] = n - 1
            self._epoch += 1

    def serving(self, device_id: int) -> bool:
        with self._lock:
            return self._serving.get(device_id, 0) > 0

    def serving_all(self, device_ids) -> bool:
        """One lock acquisition for a whole fan-out's liveness check
        (the screen sits on the per-call hot path)."""
        with self._lock:
            s = self._serving
            return all(s.get(d, 0) > 0 for d in device_ids)

    def local_epoch(self) -> int:
        with self._lock:
            return self._epoch

    def _publish_pod(self) -> None:
        """Advertise the registered method names in this process's pod
        member record (the capability handshake peers screen against)."""
        from ..ici.pod import Pod
        pod = Pod.current()
        if pod is not None:
            pod.publish_collective(self.method_names())


_registry = CollectiveRegistry()


def registry() -> CollectiveRegistry:
    return _registry


def register_device_handler(name: str, handler: Callable,
                            merge: str = MERGE_GATHER,
                            mapping: str = MAP_SHARD,
                            takes_index: bool = False) -> None:
    """Module-level registration (tests, handler libraries); servers use
    ``Server.register_collective`` which also marks their device."""
    _registry.register(name, handler, merge, mapping, takes_index)


# ---------------------------------------------------------------------------
# Fan-out sequencer: the total order every compiled fan-out enters under.
# ---------------------------------------------------------------------------

class FanoutSequencer:
    """Dense total order over this process's compiled fan-out entries.

    The client side of a fan-out group is the order master: seq is
    assigned at submit and executions are ADMITTED strictly in seq order
    (one at a time — see the module docstring's rendezvous-wedge note).
    The xproc announce carries the seq so every member's entry runner
    observes the same order the client committed to."""

    _GUARDED_BY = {
        "_next_assign": "_cv",
        "_next_exec": "_cv",
        "_aborted": "_cv",
    }

    def __init__(self) -> None:
        self._cv = threading.Condition(
            _dbg.make_lock("FanoutSequencer._lock"))
        self._next_assign = 0
        self._next_exec = 0
        self._aborted: set = set()
        # entered (method, seq) pairs, for /ici and the tests' order
        # equality asserts
        self.executed = collections.deque(maxlen=1024)

    def submit(self) -> int:
        with self._cv:
            seq = self._next_assign
            self._next_assign += 1
            return seq

    # fablint: lock-held(_cv)
    def _advance_aborted_locked(self) -> None:
        while self._next_exec in self._aborted:
            self._aborted.discard(self._next_exec)
            self.executed.append(("aborted", self._next_exec))
            self._next_exec += 1
            self._cv.notify_all()

    def run(self, seq: int, label: str, fn: Callable[[], Any],
            deadline: Optional[float] = None) -> Any:
        """Execute ``fn`` at its slot in the total order (blocks until
        every earlier slot retired).  The slot ALWAYS retires — a raising
        entry must not wedge every later fan-out, and a caller that
        gives up waiting (``deadline``, time.monotonic terms) ABORTS its
        slot so successors advance over it (SlotTimeout raised; the
        caller falls back to the per-member loop, which enforces
        per-sub timeouts properly)."""
        import time as _time
        with self._cv:
            while True:
                self._advance_aborted_locked()
                if self._next_exec == seq:
                    break
                if deadline is not None \
                        and _time.monotonic() >= deadline:
                    self._aborted.add(seq)
                    self._cv.notify_all()
                    raise SlotTimeout(
                        f"fan-out slot {seq} not reached before the "
                        f"call deadline")
                self._cv.wait(0.2)
        try:
            return fn()
        finally:
            with self._cv:
                self._next_exec = seq + 1
                self.executed.append((label, seq))
                self._advance_aborted_locked()
                self._cv.notify_all()

    def describe(self) -> dict:
        with self._cv:
            return {"assigned": self._next_assign,
                    "executed": self._next_exec}


# ---------------------------------------------------------------------------
# Client-side fallback protocol pieces (the per-member RPC loop's halves
# of the same semantics: scatter by per-sub attachments, merge by index).
# ---------------------------------------------------------------------------

def _is_device(x) -> bool:
    """A jax array (anything that says which devices hold it) or an
    ``IOBuf``, whose DEVICE refs are passed on as they are."""
    return isinstance(x, IOBuf) or (hasattr(x, "devices")
                                    and hasattr(x, "dtype"))


def _carrier(dt):
    """The unsigned integer type of a float ``dt``'s width (``dt`` itself
    where it is no float, or too wide to have one).  A device program that
    only MOVES floats carries them as this type and bitcasts last: the
    TPU's ``concatenate`` and ``stack`` of float32 quiet every NaN and flush
    every denormal (chip run, PR 35: 8,178 of 8,178 such values of 2**20
    random bit patterns changed; views and bitcasts changed none), and an
    RPC's bytes arrive as they were sent.  bfloat16 is a float here too,
    though numpy's ``kind`` calls it ``V``: moved as itself even the CPU's
    ``stack`` quiets its NaNs."""
    import jax.numpy as jnp
    if jnp.issubdtype(dt, jnp.floating) and dt.itemsize <= 4:
        return jnp.dtype(f"uint{8 * dt.itemsize}")
    return dt


_flat_program = None


def _flat_bytes(x):
    """A device array's bytes as ONE flat uint8 device array, in the order
    ``np.asarray(x).tobytes()`` gives them: ``x`` itself where it is flat
    uint8 already (no program), else one jitted ``brpc_fanout_flat``."""
    global _flat_program
    if x.ndim == 1 and x.dtype.kind == "u" and x.dtype.itemsize == 1:
        return x
    if _flat_program is None:
        import jax
        import jax.numpy as jnp

        def brpc_fanout_flat(a):
            with jax.named_scope("brpc_fanout_flat"):
                carrier = _carrier(a.dtype)
                if carrier != a.dtype:  # before the ravel, which may move
                    a = jax.lax.bitcast_convert_type(a, carrier)
                return jnp.ravel(a).view(jnp.uint8)

        _flat_program = jax.jit(brpc_fanout_flat)
    return _flat_program(x)


def _whole_block(arr) -> IOBuf:
    out = IOBuf()
    out.append_device_array(_flat_bytes(arr))
    return out


def _device_attachment(parent_cntl, op, index: Optional[int]) \
        -> Optional[IOBuf]:
    """The DEVICE refs sub-call ``index`` carries (the whole operand where
    ``index`` is None), or None for an operand that lives on the host.

    A row that is an ``IOBuf`` already is passed as it is.  A jax array's
    rows are PARTIAL refs into one flat block of the parent — at most one
    device program a fan-out (``_flat_bytes``; none for flat uint8), where
    ``op[i]`` would run one a sub-call; the transport cuts its window
    pieces out of that block as it cuts any DEVICE ref.  An array spread
    over several devices has no one block: it keeps the host path, and
    ``host_operand_bytes`` counts it.
    """
    d = parent_cntl.__dict__
    if isinstance(op, (list, tuple)):
        if index is None or not _is_device(op[index]):
            return None
        row = op[index]
        out = row if isinstance(row, IOBuf) else _whole_block(row)
    elif isinstance(op, IOBuf):
        if index is not None:
            return None                 # rows of one buffer: give a list
        out = op
    elif not _is_device(op) or len(op.devices()) > 1:
        return None
    else:
        whole = d.get("_fanout_whole")
        if whole is None:
            whole = d["_fanout_whole"] = _whole_block(op)
        out = IOBuf(whole)              # the refs copied, the block shared
        if index is not None:
            n = len(whole) // op.shape[0]
            out.pop_front(index * n)
            out.pop_back(len(out) - n)
    device_bytes = out.device_bytes()
    if "_fanout_device" not in d:
        # the fan-out is device-resident from here on; its home is where
        # the merger gathers ONE array, should the caller ask for one
        refs = out.device_refs()
        d["_fanout_device"] = next(iter(refs[0].block.data.devices())) \
            if refs else None
    _pc._g["device_operand_bytes"] << device_bytes
    if len(out) != device_bytes:
        _pc._g["host_operand_bytes"] << len(out) - device_bytes
    return out


class ShardingCallMapper:
    """CallMapper whose scatter is row ``i`` of the parent's fan-out
    operand (``cntl.fanout_operand``) as sub-call ``i``'s request
    attachment — the wire-path half of MAP_SHARD.  What rides is decided
    by what the operand IS: a host (numpy) operand's row rides as its
    bytes; a device array's row, or a row that is itself a device array or
    an ``IOBuf`` of DEVICE refs (``fanout_operand`` a list of them), rides
    as DEVICE refs of an ``IOBuf`` — by reference, with no copy and no
    host (``_device_attachment``)."""

    collective_mapping = MAP_SHARD

    def map_fanout(self, index: int, method_full_name: str, request: Any,
                   parent_cntl) -> "SubCall":
        from .parallel_channel import SubCall
        op = parent_cntl.fanout_operand
        refs = _device_attachment(parent_cntl, op, index)
        if refs is not None:
            return SubCall(request, attachment=refs)
        import numpy as np
        row = np.asarray(op[index]).tobytes()
        _pc._g["host_operand_bytes"] << len(row)
        return SubCall(request, attachment=row)

    def map(self, index: int, method_full_name: str, request: Any):
        from .parallel_channel import SubCall
        return SubCall(request)


class ReplicateFanoutMapper:
    """MAP_REPLICATE with the operand riding every sub-call's request
    attachment: a host operand as its bytes (serialized once per fan-out,
    not per sub), a device array or an ``IOBuf`` of DEVICE refs as refs to
    ONE block (made flat once per fan-out where it is not flat uint8)."""

    collective_mapping = MAP_REPLICATE

    def map_fanout(self, index: int, method_full_name: str, request: Any,
                   parent_cntl) -> "SubCall":
        from .parallel_channel import SubCall
        refs = _device_attachment(parent_cntl, parent_cntl.fanout_operand,
                                  None)
        if refs is not None:
            return SubCall(request, attachment=refs)
        import numpy as np
        blob = parent_cntl.__dict__.get("_fanout_replica_bytes")
        if blob is None:
            blob = np.asarray(parent_cntl.fanout_operand).tobytes()
            parent_cntl.__dict__["_fanout_replica_bytes"] = blob
        _pc._g["host_operand_bytes"] << len(blob)
        return SubCall(request, attachment=blob)

    def map(self, index: int, method_full_name: str, request: Any):
        from .parallel_channel import SubCall
        return SubCall(request)


# the reduce's own totals (``fanout_reduce_stats()``, ``/vars``
# ``rpc_fanout_reduce_<key>``), process-wide and kept past a channel's close:
# ``programs`` dispatches of ``brpc_fanout_gather``; ``input_bytes`` /
# ``input_blocks`` the sub-replies' DEVICE refs that went into them;
# ``output_bytes`` the ONE array each gave; ``host_merges`` merges made with
# numpy (``_merge_host``: a host operand, or a member that answered from the
# host); ``lazy_reads`` programs that ran because ``fanout_result`` was read
# (gather and concat), not at the finalize (sum)
_REDUCE_KEYS = ("programs", "input_bytes", "output_bytes", "input_blocks",
                "host_merges", "lazy_reads")
_gr = {k: bvar.Adder(f"rpc_fanout_reduce_{k}") for k in _REDUCE_KEYS}


def fanout_reduce_stats() -> Dict[str, int]:
    """What the merges of this process's operand fan-outs have done."""
    return {k: v.get_value() for k, v in _gr.items()}


# how the reduce's typed view went (``fanout_view_stats()``, ``/vars``
# ``rpc_fanout_view_<key>``), in blocks of the sub-replies: ``in_layout_blocks``
# had their words built in the byte tiling they arrived in
# (``byte_words.words``); ``relayout_blocks`` took ``.view``, a relayout on
# the TPU (a part with a block that is no whole tile, an 8-byte dtype).  A
# 1-byte dtype is viewed by neither and counted in neither.
_VIEW_KEYS = ("in_layout_blocks", "relayout_blocks")
_gv = {k: bvar.Adder(f"rpc_fanout_view_{k}") for k in _VIEW_KEYS}


def fanout_view_stats() -> Dict[str, int]:
    """How the merges' typed views of this process's sub-replies went."""
    return {k: v.get_value() for k, v in _gv.items()}


_gather_program = None


def _part_words(bs, carrier):
    """One part's blocks (flat ``uint8``) as ONE array of ``carrier``: each
    block's words built in its own tiling where every block of the part is
    ``byte_words.in_layout``, else the blocks joined and ``.view``-ed."""
    import jax.numpy as jnp
    from . import byte_words
    if byte_words.in_layout((b.shape[0] for b in bs), carrier.itemsize):
        ws = [byte_words.words(b, carrier) for b in bs]
        return ws[0] if len(ws) == 1 else jnp.concatenate(ws)
    a = bs[0] if len(bs) == 1 else jnp.concatenate(bs)
    return a.view(carrier)


def _gather_jit():
    """The one jitted ``brpc_fanout_gather``: each part's blocks as ONE array
    of ``dtype`` / ``shard_shape`` (a float as the integer of its width until
    the end, ``_carrier``; ``_part_words``), then stacked, concatenated or
    summed.  Built on first use (this module imports no jax)."""
    global _gather_program
    if _gather_program is None:
        import functools
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnums=(1, 2, 3))
        def brpc_fanout_gather(blocks, merge, dtype, shard_shape):
            with jax.named_scope("brpc_fanout_gather"):
                dt = jnp.dtype(dtype)
                carrier = _carrier(dt)

                def typed(a):
                    return a if carrier == dt \
                        else jax.lax.bitcast_convert_type(a, dt)

                arrs = []
                for bs in blocks:
                    a = _part_words(bs, carrier)
                    if shard_shape is not None:
                        a = a.reshape(shard_shape)
                    arrs.append(a)
                if merge == MERGE_SUM:
                    return functools.reduce(jnp.add, map(typed, arrs))
                # gathered as integers: a float's bits arrive as sent
                if merge == MERGE_CONCAT:
                    return typed(jnp.concatenate(arrs, axis=0))
                return typed(jnp.stack(arrs))

        _gather_program = brpc_fanout_gather
    return _gather_program


def _gather(parts, merge: str, dtype: str, shard_shape, home, mark=None):
    """ONE device array out of the sub-replies' DEVICE refs, in the order
    given, on ``home``: one ``brpc_fanout_gather`` program, dispatched and
    not waited for (a sum runs here under the parent's lock).  A partial ref
    is cut by the transport's compiled slicer (``transport._cut``), never by
    jnp's eager ``__getitem__``.  Layer span ``brpc.fanout.reduce``: the walk
    over the refs and the dispatch (n: the refs' bytes, m: their count); its
    cause is the operation's ``brpc.fanout`` (``mark``) wherever it runs."""
    import jax
    import jax.numpy as jnp
    from ..ici.transport import _cut
    from .byte_words import in_layout
    refs = [part.device_refs() for part in parts]
    nbytes = sum(r.length for rs in refs for r in rs)
    nblocks = sum(map(len, refs))
    ls = _span.layer_begin("brpc.fanout.reduce", n=nbytes, mark=mark,
                           m=nblocks, cpu=True) \
        if _span.layer_on() else None
    try:
        blocks = []
        for rs in refs:
            bs = []
            for r in rs:
                a = r.block.data
                a = _cut(a if a.ndim == 1 else a.reshape(-1), r)
                if home is not None and home not in a.devices():
                    a = jax.device_put(a, home)
                bs.append(a)
            blocks.append(tuple(bs))
        out = _gather_jit()(tuple(blocks), merge, dtype,
                            tuple(shard_shape) if shard_shape is not None
                            else None)
    finally:
        if ls is not None:
            ls.end()
    _gr["programs"] << 1
    _gr["input_bytes"] << nbytes
    _gr["input_blocks"] << nblocks
    _gr["output_bytes"] << out.nbytes
    itemsize = _carrier(jnp.dtype(dtype)).itemsize
    if itemsize > 1:
        for bs in blocks:
            _gv["in_layout_blocks" if in_layout(
                (b.shape[0] for b in bs), itemsize)
                else "relayout_blocks"] << len(bs)
    return out


class CollectiveMerger:
    """ResponseMerger whose merge is the typed collective the compiled
    program runs — reproduced on the RPC loop: sub-response attachments
    are kept by sub-channel INDEX (never arrival), parsed as
    ``dtype``/``shard_shape`` arrays and stacked (gather), summed (sum)
    or concatenated (concat) into ``cntl.fanout_result``.  The same
    instance may serve every sub-channel (per-call state lives on the
    parent controller, not the merger).

    A host operand's fan-out merges on the host (a numpy
    ``fanout_result``).  A DEVICE operand's sub-replies are kept as their
    DEVICE refs and gathered without the host: ``cntl.fanout_attachment``
    is the index-ordered refs as one ``IOBuf`` (no copy, no program) — for
    gather and concat that IS the result, and ``cntl.fanout_result``
    becomes ONE device array on the operand's device only when the caller
    reads it (one ``brpc_fanout_gather`` program); a sum is a device add,
    made at once.  A sub-reply that came back as host bytes is merged as
    the host path merges (and counted in ``host_operand_bytes``)."""

    def __init__(self, merge: str = MERGE_GATHER, dtype: str = "uint8",
                 shard_shape: Optional[Tuple[int, ...]] = None):
        self.collective_merge = merge
        self.dtype = dtype
        self.shard_shape = shard_shape

    def merge_sub(self, parent_cntl, index: int, sub_cntl,
                  response: Any) -> int:
        d = parent_cntl.__dict__
        parts = d.setdefault("_fanout_parts", {})
        att = sub_cntl._peek_response_attachment()
        if "_fanout_device" in d:
            parts[index] = IOBuf(att) if att is not None else IOBuf()
        else:
            parts[index] = att.to_bytes() if att is not None else b""
            _pc._g["host_operand_bytes"] << len(parts[index])
        return 0                         # MERGED

    def finalize_fanout(self, parent_cntl) -> None:
        d = parent_cntl.__dict__
        parts = d.get("_fanout_parts")
        if not parts:
            return
        ordered = [parts[i] for i in sorted(parts)]
        if "_fanout_device" not in d:
            parent_cntl.fanout_result = self._merge_host(ordered)
            return
        gathered = IOBuf()
        for p in ordered:
            gathered.append(p)
        parent_cntl.fanout_attachment = gathered
        if gathered.device_bytes() != len(gathered):
            # a member answered from the host: nothing to gather on a chip
            _pc._g["host_operand_bytes"] << len(gathered)
            parent_cntl.fanout_result = self._merge_host(
                [p.to_bytes() for p in ordered])
            return
        home = d["_fanout_device"]
        # the operation's brpc.fanout, where a session is on (parallel_
        # channel.py leaves it): the cause of the reduce's span, whenever
        # and on whichever thread the one array is made
        mark = d.pop("_fanout_mark", None)

        def one_array():
            return _gather(ordered, self.collective_merge, self.dtype,
                           self.shard_shape, home, mark)

        def one_array_on_read():
            _gr["lazy_reads"] << 1
            return one_array()

        if self.collective_merge == MERGE_SUM:
            parent_cntl.fanout_result = one_array()
        else:
            d["_fanout_result_lazy"] = one_array_on_read

    def _merge_host(self, blobs):
        import numpy as np
        _gr["host_merges"] << 1
        arrs = []
        for blob in blobs:
            a = np.frombuffer(blob, dtype=self.dtype)
            if self.shard_shape is not None:
                a = a.reshape(self.shard_shape)
            arrs.append(a)
        if self.collective_merge == MERGE_SUM:
            out = arrs[0].copy()
            for a in arrs[1:]:
                out = out + a
        elif self.collective_merge == MERGE_CONCAT:
            out = np.concatenate(arrs, axis=0)
        else:                            # gather (and the none fallback)
            out = np.stack(arrs)
        return out


# ---------------------------------------------------------------------------
# The plane.
# ---------------------------------------------------------------------------

class _Lowering:
    """One screened, executable fan-out: everything execute() needs.
    ``operand_shape``/``operand_dtype`` carry the wire-announced shape on
    the member side, where no operand object exists."""
    __slots__ = ("method", "md", "devices", "operand", "mapping", "leg",
                 "remote_owners", "operand_shape", "operand_dtype")

    def __init__(self, method, md, devices, operand, mapping, leg,
                 remote_owners, operand_shape=(), operand_dtype="uint8"):
        self.method = method
        self.md = md
        self.devices = devices
        self.operand = operand
        self.mapping = mapping
        self.leg = leg                   # "local" | "xproc"
        self.remote_owners = remote_owners   # pid -> announce device
        self.operand_shape = operand_shape
        self.operand_dtype = operand_dtype


class CollectiveFanoutPlane:
    """Per-process compiled fan-out plane: screen, compile cache, the
    degradation/revival state machine, and the two execution legs."""

    _instance: Optional["CollectiveFanoutPlane"] = None
    _ilock = threading.Lock()

    # fablint guarded-state contract.  The compile cache is published
    # under _lock with per-key ONCE-GUARD builds OUTSIDE it (an XLA
    # compile can take seconds; holding the cache lock across it starves
    # every other fan-out's lookup — the Collectives._cached bug this PR
    # also fixes at its origin).  Health STATE lives in the shared
    # PlaneHealth engine (ici/plane_health.py, epoch-gated policy) on
    # its own lock: a screen must never wait on a compile to learn the
    # route is down.
    _GUARDED_BY = {
        "_programs": "_lock",
        "_building": "_lock",
    }

    def __init__(self) -> None:
        from ..ici import plane_health as _ph
        self._lock = _dbg.make_lock("CollectiveFanoutPlane._lock")
        self._programs: "collections.OrderedDict" = collections.OrderedDict()
        self._building: Dict[Tuple, threading.Event] = {}
        # the plane's health record: epoch-gated revival (a member
        # re-advertising moves the clock) with the transient-reason
        # reprobe timer; the legacy rpc_fabric_route_collective_*
        # family keeps flowing via the events hook so the unified
        # rpc_fabric_plane_collective_* counters ADD to it, not replace
        self._health = _ph.register_plane(
            "collective",
            _dbg.make_lock("CollectiveFanoutPlane._health"),
            epoch_fn=self._epoch,
            transient_reasons=_TRANSIENT_REASONS,
            reprobe_s=lambda: _flags.get_flag("ici_fanout_reprobe_s"),
            events=self._record_legacy,
            on_down=self._log_down,
            on_revive=self._log_revive)
        self.sequencer = FanoutSequencer()

    @classmethod
    def instance(cls) -> "CollectiveFanoutPlane":
        # lock-free fast path: every ParallelChannel call (compiled or
        # not) passes through here; the attribute read is GIL-atomic
        # and the instance, once published, never changes
        inst = cls._instance
        if inst is not None:
            return inst
        with cls._ilock:
            if cls._instance is None:
                cls._instance = CollectiveFanoutPlane()
            return cls._instance

    # ---- health / epoch ------------------------------------------------
    def _epoch(self) -> int:
        """The revival clock: registry transitions (serve/withdraw/
        register) plus the pod epoch when one is joined — a member
        re-advertising after a kill moves BOTH."""
        from ..ici.pod import Pod
        e = _registry.local_epoch()
        pod = Pod.current()
        if pod is not None:
            e += pod.epoch()
        return e

    def _record_legacy(self, event: str, reason: str) -> None:
        from ..ici import route as _route
        _route.record_collective(event, reason)

    def _log_down(self, reason: str) -> None:
        log.warning("collective fan-out route DOWN (%s); per-member RPC "
                    "fallback until the pod epoch moves%s", reason,
                    " or the reprobe window elapses"
                    if reason in _TRANSIENT_REASONS else "")

    def _log_revive(self, reason: str, via: str) -> None:
        from ..ici import plane_health as _ph
        log.info("collective fan-out route REVIVED (%s past %s)",
                 "reprobe window" if via == _ph.VIA_TIMER
                 else "epoch moved", reason)

    def mark_down(self, reason: str) -> None:
        self._health.mark_down(reason)

    def route_usable(self) -> bool:
        """Healthy, or down-but-revivable — the engine's epoch-gated
        policy: the epoch moved (a member re-advertised), or — for
        TRANSIENT reasons only (a program raised, an announce was
        refused) — the reprobe window elapsed.  Without the timer, one
        bad execution would degrade every method on this process
        forever under stable membership; membership reasons stay
        epoch-gated (a dead member does not resurrect by waiting)."""
        return self._health.usable()

    def health(self) -> dict:
        from ..ici import plane_health as _ph
        snap = self._health.snapshot()
        return {"down": snap["state"] != _ph.UP,
                "reason": snap["reason"],
                "down_epoch": snap["down_epoch"]}

    # ---- screen --------------------------------------------------------
    def screen(self, subs, method_full_name: str, cntl, pchan=None) \
            -> Tuple[Optional[_Lowering], str]:
        """(lowering, "") when the fan-out compiles, (None, reason)
        otherwise.  Cheap-first: the operand peek is one dict lookup, so
        plain (non-collective) ParallelChannel traffic pays ~nothing.
        The static half of the resolution (sub → device, mapper/merger
        contract) caches on the issuing channel when every sub is an
        endpoint-fixed channel — LB-backed subs (PartitionChannel) can
        re-resolve between calls, so they take the full walk."""
        operand = cntl.__dict__.get("fanout_operand")
        if operand is None:
            return None, "no_operand"
        if not _flags.get_flag("ici_fanout_collective"):
            return None, "disabled"
        md = _registry.method(method_full_name)
        if md is None:
            return None, R_UNREGISTERED
        cache = pchan.__dict__.setdefault("_cf_screen", {}) \
            if pchan is not None else None
        cached = cache.get(method_full_name) if cache is not None \
            else None
        # validity = the SAME EndPoint objects, by identity (strong refs
        # held in the cache entry, so ids cannot be reused): a sub
        # re-init()ed to a different device replaces its endpoint and
        # must invalidate — a stale device set would scatter the
        # compiled program to the OLD member
        eps = tuple(getattr(c, "_endpoint", None) for c, _m, _g in subs)
        if cached is not None and cached[0] is not None \
                and len(cached[0]) == len(eps) \
                and all(a is b for a, b in zip(cached[0], eps)):
            devices, mapping, merge_mode = cached[1], cached[2], cached[3]
        else:
            devices_l: List[int] = []
            mapping = None
            merge_mode = None
            cacheable = pchan is not None
            for chan, mapper, merger in subs:
                if getattr(chan, "_endpoint", None) is None:
                    cacheable = False     # LB-backed: membership can move
                dev = _sub_device(chan)
                if dev is None:
                    return None, R_TARGET
                devices_l.append(dev)
                m = getattr(mapper, "collective_mapping", None)
                if m is None or getattr(mapper, "map_fanout",
                                        None) is None:
                    # the compiled route requires a mapper that can ALSO
                    # carry the operand on the RPC loop (map_fanout) —
                    # a degrade mid-call must reproduce the same bytes,
                    # not issue attachment-less sub-calls
                    return None, R_MAPPER
                if mapping is not None and m != mapping:
                    return None, R_MAPPER
                mapping = m
                mm = getattr(merger, "collective_merge", None)
                if mm is None:           # not collective-capable: refuse
                    return None, R_MERGE  # (order-independent: sub 0's
                    # plain merger must refuse exactly like sub 3's)
                if merge_mode is not None and mm != merge_mode:
                    return None, R_MERGE
                merge_mode = mm
            if len(set(devices_l)) != len(devices_l):
                return None, R_TARGET
            devices = tuple(devices_l)
            if cacheable and cache is not None:
                # per-method entries: a channel multiplexing several
                # collective methods must not thrash a 1-entry cache
                cache[method_full_name] = (eps, devices, mapping,
                                           merge_mode)
        if merge_mode != md.merge:
            return None, R_MERGE
        if mapping != md.mapping:
            return None, R_MAPPER
        # array-likes only, for EVERY mapping: a shapeless operand must
        # refuse HERE (this call rides the RPC loop) — raising later in
        # _prepare would mark the whole route down for one bad input
        if not hasattr(operand, "shape") or not hasattr(operand, "dtype"):
            return None, R_SHAPE
        if mapping == MAP_SHARD:
            try:
                rows = operand.shape[0]
            except Exception:
                return None, R_SHAPE
            if rows != len(devices):
                return None, R_SHAPE
        # member liveness + locality (one registry lock; locality memoed
        # per mesh generation — device ownership never moves within one)
        local = _local_devices()
        remote: List[int] = []
        for dev in devices:
            if dev in local:
                continue
            remote.append(dev)
        if not _registry.serving_all(d for d in devices if d in local):
            return None, R_MEMBER
        remote_owners: Dict[int, int] = {}
        if remote:
            from ..ici.mesh import IciMesh
            mesh = IciMesh.default()
            for dev in remote:
                if dev >= mesh.size:
                    return None, R_TARGET
                owner = _pod_owner(dev, method_full_name)
                if owner is None:
                    return None, R_MEMBER
                remote_owners.setdefault(owner, dev)
            from ..ici import device_plane as _dp
            if not _dp.xproc_compiled_ok():
                return None, R_XPROC
            if not any(d in local for d in devices):
                # the xproc program carries the operand on a LOCAL
                # participant row (psum-broadcast); a pure-client
                # process owning none of the rows would psum zeros —
                # a silently zeroed request, never a lowering
                return None, R_NO_CARRIER
        leg = "xproc" if remote_owners else "local"
        if not self.route_usable():
            return None, "route_down"
        return _Lowering(method_full_name, md, devices, operand,
                         mapping, leg, remote_owners), ""

    # ---- compile cache (once-guarded; build OUTSIDE the lock — the
    # shared butil/once_cache.py idiom, LRU-bounded here) ----------------
    def _program(self, key: Tuple, builder: Callable[[], Callable]):
        from ..butil.once_cache import build_once
        cap = _flags.get_flag("ici_fanout_cache_max")
        return build_once(self._lock, self._programs, self._building, key, builder, cap=cap)  # noqa: E501  # fablint: ignore[guarded-state] the guarded containers pass BY REFERENCE into the once-guard helper, which takes _lock itself

    def cache_stats(self) -> dict:
        with self._lock:
            return {"programs": len(self._programs),
                    "building": len(self._building)}

    # ---- execution -----------------------------------------------------
    def execute(self, low: _Lowering, cntl) -> Any:
        """Run one screened fan-out at its slot in the total order.
        Raises on ANY failure — the caller marks the route down and
        completes the call on the per-member RPC loop (in-call, zero
        client-visible failures).  Everything after submit runs INSIDE
        the slot (run()'s finally retires it): an abandoned slot —
        fault-plan kill, refused announce — must still retire, or every
        later fan-out waits on it forever."""
        import time as _time
        seq = self.sequencer.submit()
        deadline = None
        if cntl.timeout_ms is not None and cntl.timeout_ms > 0:
            # bound the SLOT WAIT by the call deadline: an earlier
            # fan-out's multi-second compile must not hold a
            # 100ms-deadline call hostage (the program itself, once
            # entered, is uncancelable — the multi-controller contract)
            deadline = _time.monotonic() + cntl.timeout_ms / 1000.0

        def entry():
            from ..rpc import fault_injection as _fi
            plan = _fi.fabric_active()
            if plan is not None:
                refusal = plan.on_collective_execute(low.devices)
                if refusal is not None:
                    raise CollectiveExecError(R_KILLED, refusal)
            if low.leg == "xproc":
                self._announce_xproc(low, seq)
            return self._enter(low, cntl)

        return self.sequencer.run(seq, low.method, entry,
                                  deadline=deadline)

    def _enter(self, low: _Lowering, cntl) -> Any:
        import jax
        try:
            if low.leg == "xproc":
                fn, placed = self._prepare_xproc(low)
            else:
                fn, placed = self._prepare_local(low)
            out = fn(placed)
            jax.block_until_ready(out)
        except CollectiveExecError:
            raise
        except Exception as e:
            raise CollectiveExecError(R_EXEC, f"{type(e).__name__}: {e}")
        cntl.fanout_result = out
        return out

    # -- local leg: scatter by sharded operand placement -----------------
    def _prepare_local(self, low: _Lowering):
        import jax
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from jax import shard_map
        from ..ici.mesh import IciMesh
        mesh = IciMesh.default()
        md = low.md
        operand = low.operand
        shape = tuple(operand.shape)
        dtype = str(operand.dtype) if hasattr(operand, "dtype") else "?"
        key = ("local", low.method, low.devices, low.mapping, md.merge,
               md.takes_index, shape, dtype, IciMesh.generation)

        def build():
            submesh = Mesh(np.array([mesh.device(d) for d in low.devices]),
                           ("fan",))
            in_spec = P("fan") if low.mapping == MAP_SHARD else P()

            def program(x):
                arg = x[0] if low.mapping == MAP_SHARD else x
                if md.takes_index:
                    r = md.handler(jax.lax.axis_index("fan"), arg)
                else:
                    r = md.handler(arg)
                if md.merge == MERGE_SUM:
                    return jax.lax.psum(r, "fan")
                if md.merge == MERGE_GATHER:
                    return jax.lax.all_gather(r, "fan")
                if md.merge == MERGE_CONCAT:
                    return jax.lax.all_gather(r, "fan", tiled=True)
                return r[None]           # MERGE_NONE: stays sharded

            out_spec = P("fan") if md.merge == MERGE_NONE else P()
            fn = jax.jit(shard_map(program, mesh=submesh,
                                   in_specs=in_spec, out_specs=out_spec,
                                   check_vma=False))
            in_sharding = NamedSharding(submesh, in_spec)
            return (fn, in_sharding)

        fn, in_sharding = self._program(key, build)
        placed = low.operand
        if getattr(placed, "sharding", None) != in_sharding:
            import jax as _jax
            placed = _jax.device_put(placed, in_sharding)
        return fn, placed

    # -- xproc leg: scatter by collective broadcast from the client row --
    def _prepare_xproc(self, low: _Lowering):
        """Multi-controller entry: the operand cannot be placed onto
        remote devices, so row 0 (the first LOCAL participant) carries
        the whole stacked request and ``psum`` reconstructs it on every
        participant (remote rows enter as zeros).  Members run this same
        prepare with ``operand=None`` — their every row is zeros."""
        import jax
        import numpy as np
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from jax import shard_map
        from ..ici.mesh import IciMesh
        mesh = IciMesh.default()
        md = low.md
        operand = low.operand
        n = len(low.devices)
        if operand is not None:
            full = np.asarray(operand)
            if low.mapping == MAP_REPLICATE:
                full = np.broadcast_to(full, (n,) + full.shape)
        else:                            # member side: shapes ride the wire
            full = None
        shape = low.operand_shape if full is None else tuple(full.shape)
        dtype = low.operand_dtype if full is None else str(full.dtype)
        key = ("xproc", low.method, low.devices, low.mapping, md.merge,
               md.takes_index, shape, dtype, IciMesh.generation)

        def build():
            submesh = Mesh(np.array([mesh.device(d) for d in low.devices]),
                           ("fan",))

            def program(x):              # x: (1, n, ...) local row
                fullreq = jax.lax.psum(x[0], "fan")      # broadcast
                idx = jax.lax.axis_index("fan")
                mine = fullreq[idx]
                if md.takes_index:
                    r = md.handler(idx, mine)
                else:
                    r = md.handler(mine)
                if md.merge == MERGE_SUM:
                    return jax.lax.psum(r, "fan")
                if md.merge == MERGE_GATHER:
                    return jax.lax.all_gather(r, "fan")
                if md.merge == MERGE_CONCAT:
                    return jax.lax.all_gather(r, "fan", tiled=True)
                return r[None]

            out_spec = P("fan") if md.merge == MERGE_NONE else P()
            fn = jax.jit(shard_map(program, mesh=submesh,
                                   in_specs=P("fan"), out_specs=out_spec,
                                   check_vma=False))
            return (fn, submesh)

        fn, submesh = self._program(key, build)
        # global (n, n, ...) input: local rows only (multi-controller
        # contract); the first local participant's row carries the data
        from jax.sharding import NamedSharding, PartitionSpec as P
        sharding = NamedSharding(submesh, P("fan"))
        rows = []
        carried = False
        for dev in low.devices:
            device = mesh.device(dev)
            if not _device_obj_local(device):
                continue
            if full is not None and not carried:
                row = jax.device_put(jnp.asarray(full)[None], device)
                carried = True
            else:
                row = jax.device_put(
                    jnp.zeros((1,) + tuple(shape), _np_dtype(dtype)),
                    device)
            rows.append(row)
        ga = jax.make_array_from_single_device_arrays(
            (len(low.devices),) + tuple(shape), sharding, rows)
        return fn, ga

    # -- xproc announce ---------------------------------------------------
    def _announce_xproc(self, low: _Lowering, seq: int) -> None:
        """Tell every remote member process to enter this program at
        ``seq``; wait for every accept, then COMMIT (two-phase — see
        on_remote_announce).  Any refusal/timeout raises — the caller
        degrades in-call.  All accept waits share ONE deadline from the
        first announce, and member parks last TWICE the timeout, so a
        GO that follows a full accept phase still lands inside every
        member's park window."""
        import json as _json
        import time as _time
        from ..ici import fabric as _fab
        operand = low.operand
        # the announced shape is the PROGRAM's row shape — for
        # MAP_REPLICATE that is the broadcast-STACKED (n, ...) shape
        # _prepare_xproc compiles against, not the caller's operand
        # shape, or client and members enter shape-divergent programs
        shape = tuple(getattr(operand, "shape", ()))
        if low.mapping == MAP_REPLICATE:
            shape = (len(low.devices),) + shape
        # group id: a process-wide counter + the client pid key members
        # park under — NEVER id()-derived (address reuse across degraded
        # fan-outs, or a truncation collision across clients, would let
        # one fan-out steal another's parked entry)
        uuid = next(_announce_counter)
        cpid = _own_pid()
        body = _json.dumps({
            "method": low.method, "seq": seq,
            "devices": list(low.devices), "mapping": low.mapping,
            "merge": low.md.merge,
            "shape": list(shape),
            "dtype": str(getattr(operand, "dtype", "uint8")),
            "uuid": uuid, "cpid": cpid,
        }).encode()
        from ..rpc import fault_injection as _fi
        timeout = _flags.get_flag("ici_fanout_xproc_timeout_s")
        deadline = _time.monotonic() + timeout
        waiters = []
        try:
            for pid, dev in sorted(low.remote_owners.items()):
                sock = _member_sock(dev)
                if sock is None:
                    raise CollectiveExecError(
                        R_ANNOUNCE, f"no fabric route to member pid {pid}")
                send = getattr(sock, "_ctrl_send", None)
                if send is None:
                    raise CollectiveExecError(
                        R_ANNOUNCE,
                        f"member pid {pid} has no control channel")
                w = _AnnounceWaiter()
                _announce_waiters_put(uuid, pid, w)
                plan = _fi.fabric_active()
                if plan is not None and plan.on_collective_announce():
                    # injected black-hole: the member never sees the
                    # announce — the waiter times out below (R_ANNOUNCE)
                    waiters.append((pid, w))
                    continue
                try:
                    send(_fab._F_COLL_CALL, body)
                except OSError as e:
                    raise CollectiveExecError(
                        R_ANNOUNCE, f"announce to pid {pid} failed: {e}")
                waiters.append((pid, w))
            for pid, w in waiters:
                if not w.event.wait(
                        max(deadline - _time.monotonic(), 0.001)):
                    raise CollectiveExecError(
                        R_ANNOUNCE, f"member pid {pid} never acknowledged "
                                    f"the fan-out announce")
                if not w.ok:
                    raise CollectiveExecError(
                        R_ANNOUNCE,
                        f"member pid {pid} refused entry: {w.reason}")
        finally:
            # a timeout/refusal abandons the fan-out: un-register every
            # still-pending waiter or the table grows one entry per
            # degraded announce forever (a late reply then no-ops)
            with _announce_lock:
                for pid in low.remote_owners:
                    _announce_waiters.pop((uuid, pid), None)
        # every member accepted: COMMIT — members park their entry until
        # this GO (two-phase, so a refusal/timeout above leaves accepted
        # members parked-then-expired instead of entering a program the
        # degraded client never joins, which would wedge their serial
        # entry runner forever)
        go = _json.dumps({"uuid": uuid, "cpid": cpid}).encode()
        for pid, dev in sorted(low.remote_owners.items()):
            sock = _member_sock(dev)
            try:
                sock._ctrl_send(_fab._F_COLL_GO, go)
            except (OSError, AttributeError) as e:
                # partial-commit window: members already told to go will
                # enter and rely on the backend's distributed error
                # propagation when we bail here (the multi-controller
                # contract); narrower than entering on accept, not zero
                raise CollectiveExecError(
                    R_ANNOUNCE, f"commit to pid {pid} failed: {e}")


class CollectiveExecError(RuntimeError):
    """An execution-stage failure: carries the route-counter reason."""

    def __init__(self, reason: str, text: str):
        super().__init__(text)
        self.reason = reason


class SlotTimeout(RuntimeError):
    """The call's deadline expired before its sequencer slot came up —
    per-call contention, NOT a route failure: the caller falls back to
    the per-member loop without degrading the route."""


# ---------------------------------------------------------------------------
# xproc member side: announce handling + ordered entry runner.
# ---------------------------------------------------------------------------

class _AnnounceWaiter:
    __slots__ = ("event", "ok", "reason")

    def __init__(self):
        self.event = threading.Event()
        self.ok = False
        self.reason = ""


_announce_lock = _dbg.make_lock("collective_fanout._announce_lock")
_GUARDED_BY_GLOBALS = {"_announce_waiters": "_announce_lock",
                       "_announce_socks": "_announce_lock",
                       "_entry_queue": "_entry_lock",
                       "_pending_entries": "_entry_lock",
                       "_entry_thread": "_entry_lock"}
_announce_waiters: Dict[Tuple[int, int], _AnnounceWaiter] = {}

_entry_lock = _dbg.make_lock("collective_fanout._entry_lock")
_entry_queue: "collections.deque" = collections.deque()
# (client pid, uuid) -> (sock, low, expiry) — parked accepted entries
_pending_entries: Dict[Tuple[int, int], Tuple] = {}
_entry_wake = threading.Event()
_entry_thread: Optional[threading.Thread] = None
# announce group ids: a counter, never id()-derived (GIL-atomic next())
_announce_counter = itertools.count(1)


def _announce_waiters_put(uuid: int, pid: int, w: _AnnounceWaiter) -> None:
    with _announce_lock:
        _announce_waiters[(uuid, pid)] = w


def on_remote_reply(sock, msg: dict, ok: bool) -> None:
    """Client side: a member's accept/refuse for one announce."""
    key = (int(msg.get("uuid", 0)), int(msg.get("pid", -1)))
    with _announce_lock:
        w = _announce_waiters.pop(key, None)
    if w is not None:
        w.ok = ok
        w.reason = msg.get("reason", "")
        w.event.set()


def on_remote_announce(sock, msg: dict) -> None:
    """Member side, phase 1: a client proposed a fan-out — validate and
    reply accept/refuse, PARKING the entry until the client's commit
    (``_F_COLL_GO``).  Two-phase because a client whose announce to
    ANOTHER member fails degrades to RPCs: a member that entered the
    program on accept alone would wait on a rendezvous the client never
    joins, wedging its serial entry runner forever.  Parked entries
    expire after the announce timeout."""
    import json as _json
    import time as _time
    from ..ici import fabric as _fab
    from ..ici import device_plane as _dp
    from ..ici import route as _route
    method = msg.get("method", "")
    reply = {"uuid": msg.get("uuid", 0), "pid": _own_pid()}
    md = _registry.method(method)
    refuse = reason = ""
    if md is None:
        refuse, reason = "method has no device handler here", R_UNREGISTERED
    elif not _dp.xproc_compiled_ok():
        refuse, reason = ("no multi-controller backend on this member",
                          R_XPROC)
    elif msg.get("merge") != md.merge or msg.get("mapping") != md.mapping:
        # contract divergence (rolling upgrade: the two sides registered
        # different merge/mapping) must REFUSE — entering a program
        # built from the LOCAL registration while the client compiled
        # the announced one is a shape-divergent rendezvous
        refuse, reason = (
            f"collective contract mismatch: member has "
            f"{md.merge}/{md.mapping}, announce says "
            f"{msg.get('merge')}/{msg.get('mapping')}", R_MERGE)
    if refuse:
        reply["reason"] = refuse
        _route.record_collective("announce_refused", reason)
        try:
            sock._ctrl_send(_fab._F_COLL_ERR, _json.dumps(reply).encode())
        except OSError:
            pass
        return
    low = _Lowering(method, md, tuple(msg.get("devices", ())), None,
                    msg.get("mapping", MAP_SHARD), "xproc", {},
                    operand_shape=tuple(msg.get("shape", ())),
                    operand_dtype=msg.get("dtype", "uint8"))
    # park for TWICE the announce timeout: the client's accept phase may
    # consume up to one full timeout before its GO goes out
    expiry = _time.monotonic() + 2 * _flags.get_flag(
        "ici_fanout_xproc_timeout_s")
    key = (int(msg.get("cpid", -1)), int(msg.get("uuid", 0)))
    with _entry_lock:
        _sweep_pending_locked(_time.monotonic())
        _pending_entries[key] = (sock, low, expiry)
    try:
        sock._ctrl_send(_fab._F_COLL_OK, _json.dumps(reply).encode())
    except OSError:
        with _entry_lock:
            _pending_entries.pop(key, None)


def on_remote_go(sock, msg: dict) -> None:
    """Member side, phase 2: the client committed — queue the parked
    entry on the ordered runner (runner order = GO arrival order, the
    client's commit order on this control channel's FIFO)."""
    import time as _time
    from ..ici import route as _route
    global _entry_thread
    key = (int(msg.get("cpid", -1)), int(msg.get("uuid", 0)))
    with _entry_lock:
        _sweep_pending_locked(_time.monotonic())
        parked = _pending_entries.pop(key, None)
        if parked is None:
            return                       # expired or never announced
        _entry_queue.append((parked[0], parked[1]))
        if _entry_thread is None or not _entry_thread.is_alive():
            # fablint: thread-quiesced(daemon runner; drains the queue and parks — no state outlives the queue entries it consumes)
            _entry_thread = threading.Thread(
                target=_entry_loop, name="collective_fanout_entry",
                daemon=True)
            _entry_thread.start()
    _route.record_collective("member_entries")
    _entry_wake.set()


# fablint: lock-held(_entry_lock)
def _sweep_pending_locked(now: float) -> None:
    """Drop parked entries whose commit never came (client degraded
    after this member's accept).  Caller holds _entry_lock."""
    stale = [u for u, (_s, _l, exp) in _pending_entries.items()
             if exp < now]
    for u in stale:
        _pending_entries.pop(u, None)
    if stale:
        from ..ici import route as _route
        _route.record_collective("member_entry_expired", n=len(stale))


def _entry_loop() -> None:
    plane = CollectiveFanoutPlane.instance()
    while True:
        _entry_wake.wait(1.0)
        with _entry_lock:
            if not _entry_queue:
                _entry_wake.clear()
                continue
            sock, low = _entry_queue.popleft()
        # member entries take a slot in THIS process's sequencer too: a
        # process that is both fan-out client and member must never have
        # two collective programs in flight (the rendezvous wedge)
        seq = plane.sequencer.submit()

        def enter(low=low):
            fn, ga = plane._prepare_xproc(low)
            import jax
            jax.block_until_ready(fn(ga))

        try:
            plane.sequencer.run(seq, f"member:{low.method}", enter)
        except Exception as e:
            from ..ici import route as _route
            _route.record_collective("member_entry_failed", R_EXEC)
            log.warning("collective fan-out member entry failed: %s", e)


def _own_pid() -> int:
    try:
        import jax
        return jax.process_index()
    except Exception:
        return 0


_announce_socks: Dict[int, Any] = {}


def _member_sock(dev: int):
    """A live fabric control channel to the member serving ``dev``:
    prefer the sub-channels' own sockets (the per-member RPC traffic
    already dialed them), else dial one and cache it (invalidated on
    failure — the next fan-out re-dials after revival)."""
    from ..ici.fabric import FabricSocket, connect_any
    from ..ici.mesh import IciMesh
    from ..rpc.socket import list_sockets
    for s in list_sockets():
        if isinstance(s, FabricSocket) and s.remote_dev == dev \
                and not s.failed and not s._peer_gone():
            return s
    with _announce_lock:
        stale = _announce_socks.get(dev)
    if stale is not None and not stale.failed and not stale._peer_gone():
        return stale
    try:
        s = connect_any(IciMesh.default().endpoint(dev))
    except Exception:
        return None
    if not isinstance(s, FabricSocket):
        return None
    with _announce_lock:
        prev = _announce_socks.get(dev)
        _announce_socks[dev] = s
    if prev is not None and prev is not s:
        # the replaced (dead) socket must not linger until GC: its fds
        # and reader thread release on explicit failure
        try:
            from ..rpc import errors as _err
            prev.set_failed(_err.ECLOSE, "announce socket replaced")
        except Exception:
            pass
    return s


# ---------------------------------------------------------------------------
# Screen helpers.
# ---------------------------------------------------------------------------

def _sub_device(chan) -> Optional[int]:
    """The fixed ``ici://k`` device a sub-channel targets, or None.  A
    PartitionChannel sub (LB over one partition) resolves when exactly
    one server backs the partition."""
    from ..butil.endpoint import SCHEME_ICI
    ep = getattr(chan, "_endpoint", None)
    if ep is not None:
        if getattr(ep, "scheme", None) != SCHEME_ICI \
                or len(getattr(ep, "coords", ())) != 1:
            return None
        return ep.device_id
    lb = getattr(chan, "_lb", None)
    if lb is None:
        return None
    try:
        entries = lb.servers()
    except Exception:
        return None
    if len(entries) != 1:
        return None
    ep = entries[0].endpoint
    if getattr(ep, "scheme", None) != SCHEME_ICI \
            or len(getattr(ep, "coords", ())) != 1:
        return None
    return ep.device_id


_local_devs_lock = _dbg.make_lock("collective_fanout._local_devs_lock")
# generation -> frozenset(local device ids).  READS are lock-free on the
# screen hot path (dict.get is GIL-atomic; values are immutable and a
# racing reader that misses mid-swap just recomputes) — the route.py
# counter-dict discipline; the lock only serializes the swap.
_local_devs_memo: Dict[int, frozenset] = {}


def _local_devices() -> frozenset:
    """Mesh device ids owned by THIS process, memoized per mesh
    generation (ownership never moves within one) — the screen's
    locality check without a per-device jax attribute walk."""
    from ..ici.mesh import IciMesh
    gen = IciMesh.generation
    out = _local_devs_memo.get(gen)
    if out is not None:
        return out
    mesh = IciMesh.default()
    me = _own_pid()
    local = frozenset(
        i for i, d in enumerate(mesh.devices)
        if getattr(d, "process_index", 0) == me)
    with _local_devs_lock:
        _local_devs_memo.clear()     # old generations never come back
        _local_devs_memo[gen] = local
    return local


def _device_obj_local(device) -> bool:
    try:
        import jax
        return device.process_index == jax.process_index()
    except Exception:
        return True


def _pod_owner(dev: int, method: str) -> Optional[int]:
    """The pid of the pod member serving ``ici://dev`` with a registered
    device handler for ``method`` (the capability handshake), or None."""
    from ..ici.pod import Pod
    pod = Pod.current()
    if pod is None:
        return None
    from ..ici.pod import UP
    for m in pod.members().values():
        if m.state == UP and dev in m.serving and dev not in m.draining \
                and method in m.coll:
            return m.pid
    return None


def _np_dtype(name: str):
    import numpy as np
    return np.dtype(name)


# ---------------------------------------------------------------------------
# The ParallelChannel hook.
# ---------------------------------------------------------------------------

def _try_execute(plane, low, cntl) -> bool:
    """Run one screened fan-out; True on success (route stamped, result
    in ``cntl.fanout_result``).  On ANY failure: counters/health updated,
    the call's REMAINING deadline budget decremented by the time the
    attempt burned (the PR-9 residual discipline — the RPC fallback must
    not restart with a fresh full budget), and False returned so the
    caller completes on the per-member loop."""
    import time
    from ..ici import route as _route
    t0 = time.monotonic_ns()
    try:
        plane.execute(low, cntl)
    except SlotTimeout as e:
        # contention, not a route failure: THIS call falls back (the
        # RPC loop enforces per-sub timeouts), the route stays up
        _route.record_collective("slot_timeout")
        log.warning("collective fan-out slot timeout (%s); this call "
                    "rides per-member RPCs", e)
    except CollectiveExecError as e:
        plane.mark_down(e.reason)
        log.warning("collective fan-out degraded in-call (%s: %s); "
                    "completing on per-member RPCs", e.reason, e)
    except Exception as e:               # defense: never fail the call
        plane.mark_down(R_EXEC)
        log.error("collective fan-out unexpected failure (%s); "
                  "completing on per-member RPCs", e, exc_info=True)
    else:
        _route.record_collective("selected")
        cntl.fanout_route = "collective"
        cntl.latency_us = (time.monotonic_ns() - t0) // 1000
        return True
    cntl.fanout_route = "rpc"
    if cntl.timeout_ms is not None and cntl.timeout_ms > 0:
        spent_ms = (time.monotonic_ns() - t0) // 1_000_000
        cntl.timeout_ms = max(int(cntl.timeout_ms - spent_ms), 1)
    return False


def maybe_call(pchan, method_full_name: str, cntl, request,
               response, done) -> bool:
    """Try the compiled route for one fan-out.  True → the call is
    handled on the collective plane (result in ``cntl.fanout_result``,
    route stamped; async callers' ``done`` fires from a tasklet — the
    execution itself runs on that tasklet too, preserving the
    non-blocking call_method contract).  False → the caller runs the
    per-member RPC loop; any mid-fan-out failure already marked the
    route down and counted the reason, so the degrade is invisible to
    the caller."""
    if cntl.__dict__.get("_fanout_no_compiled"):
        return False                     # async fallback re-entry guard
    plane = CollectiveFanoutPlane.instance()
    low, reason = plane.screen(pchan._subs, method_full_name, cntl,
                               pchan=pchan)
    from ..ici import route as _route
    if low is None:
        if reason not in ("no_operand", "disabled", "route_down"):
            _route.record_collective("ineligible", reason)
        if cntl.__dict__.get("fanout_operand") is not None:
            cntl.fanout_route = "rpc"
        return False
    if done is not None:
        # async contract: call_method must not block through slot wait /
        # compile / program run — the whole attempt rides a tasklet, and
        # a failed attempt re-issues through the normal path with the
        # compiled route suppressed for this call (residual budget
        # already decremented)
        from ..bthread import scheduler

        def _bg():
            if _try_execute(plane, low, cntl):
                _pc._g["route_collective"] << 1
                cntl.response = response
                done(cntl)
            else:
                cntl.__dict__["_fanout_no_compiled"] = True
                pchan.call_method(method_full_name, cntl, request,
                                  response, done=done)

        scheduler.start_background(_bg, name="collective_fanout_call")
        return True
    if not _try_execute(plane, low, cntl):
        return False
    _pc._g["route_collective"] << 1
    cntl.response = response
    return True


def shard_operand(devices, operand, mapping: str = MAP_SHARD):
    """Pre-place a fan-out operand with the exact sharding the compiled
    local program expects (one row per target device for MAP_SHARD,
    replicated otherwise) — the steady-state caller shape: a pipeline
    holding mesh-resident data hands the plane already-scattered rows
    and the per-call placement copy disappears."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from ..ici.mesh import IciMesh
    mesh = IciMesh.default()
    submesh = Mesh(np.array([mesh.device(d) for d in devices]), ("fan",))
    spec = P("fan") if mapping == MAP_SHARD else P()
    return jax.device_put(operand, NamedSharding(submesh, spec))


def describe() -> dict:
    """The /ici builtin's collective-fan-out block."""
    plane = CollectiveFanoutPlane.instance()
    return {
        "health": plane.health(),
        "sequencer": plane.sequencer.describe(),
        "cache": plane.cache_stats(),
        "registered_methods": _registry.method_names(),
    }
