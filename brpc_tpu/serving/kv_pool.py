"""Paged KV-block pool: fixed-size device blocks, free-list custody,
per-session block tables, admission-aware eviction, timer-driven expiry,
copy-on-write prefix sharing, host-tier spill/restore.

The serving subsystem's memory manager (ROADMAP item 3; the shape every
production LLM server converged on — vLLM's PagedAttention block tables
over a fixed block pool).  One pool per decode worker:

  * **Blocks, not sessions, are the allocation unit.**  The backing
    store is a fixed ``(num_blocks, block_tokens × bytes_per_token)``
    uint8 arena plus a parallel ``(num_blocks, block_tokens)`` int64
    per-token reduction arena (the "attention read" surface the batched
    decode step gathers from — one fancy-index gather per step through
    the block tables, never a per-session copy).  A session holds an
    ordered block list; fragmentation is impossible by construction.
  * **Copy-on-write prefix sharing** (ISSUE 16): at commit time FULL
    blocks are content-hashed (a chained CRC over the block run, so the
    key encodes position-in-prefix) against a pool-wide prefix index —
    when N sessions' token rows share a block-aligned prefix they map
    the SAME physical blocks under a per-block REFCOUNT (the block-level
    analog of the counted session pin: a shared block outlives any one
    owner and frees only when the last refcount drops).  Every index
    hit is BYTE-VERIFIED before sharing, so a hash collision degrades
    to no-sharing, never to cross-session bytes.  Divergence past the
    common prefix keeps private tail blocks, and an in-place
    ``write_rows`` on a shared block performs a CoW SPLIT to a private
    copy first.  ``serving_kv_prefix_share=False`` restores the PR-15
    private-blocks world byte-for-byte for same-run A/B.
  * **Admission-aware eviction** (the PR-9 integration): under memory
    pressure the pool evicts parked sessions in PRIORITY-BAND order —
    sheddable/batch bands (higher band number) before interactive ones,
    lighter admission tenant weights before heavier ones inside a band,
    LRU inside a (band, weight) class — and a loading session may NEVER
    evict a session from a band more protected than its own.  Tenant
    weights come from the same ``AdmissionOptions.tenant_weight``
    table the WFQ admission queue uses (``KvPoolOptions.from_admission``),
    so "who absorbs the pressure" is ONE policy across queueing and
    memory.  Victim selection simulates the refcount decrements, so a
    victim whose blocks other sessions still share contributes only the
    blocks that would actually free.
  * **Timer-driven expiry**, not traffic-driven (the ISSUE-14 bugfix):
    the old example swept stale sessions only inside ``LoadKv``, so an
    idle decode worker parked expired KV forever.  Here the sweep is a
    TimerThread callback scheduled whenever sessions exist — a parked
    session on an otherwise-idle worker is reclaimed on time with zero
    new traffic.  The timer is scheduled lazily (first load) and
    self-cancels when the pool drains, so an idle pool costs nothing.
  * **Pins** fence eviction: the decode scheduler pins every session in
    its step roster; pinned sessions are never evicted or expired (their
    block tables are live in the current batched program).

Custody: a session's bytes enter the pool exactly once and leave by
exactly one of release / evict / expire / close — where "leave" for a
SHARED block means its refcount decrement, the physical free happening
only at zero.  Two entry surfaces:

  * ``load`` — the caller already holds the whole session as one
    contiguous token-major array (the PR-14 materialized path, kept for
    A/B and for sources that cannot scatter).  Since ISSUE 16 it is a
    thin delegation to ``load_into`` with a row-copy fill, so both
    surfaces ride ONE reserve/fill/commit shape (locking parity is
    structural, not duplicated);
  * ``load_into`` (ISSUE 15) — the block table is RESERVED first, then
    the caller's ``fill`` writes token rows DIRECTLY into the arena
    blocks, so a loader never materializes the session as one
    intermediate array.  The serving loader feeds this from the wire:
    shm ring claims and parked native att segments scatter straight
    into the reserved blocks (``serving/kv_source.py``), one copy pass
    total.  Since ISSUE 16 the fill runs OUTSIDE the pool lock by
    default (``serving_kv_concurrent_fill``): reserve under the lock,
    scatter unlocked, COMMIT WITH A RE-CHECK — so concurrent LoadKv
    fills no longer serialize on one decode host.

ISSUE 19 adds the HOST TIER (ROADMAP 2b): with ``host_blocks > 0`` the
victim picker's "evict" becomes "demote" — a pressure victim's blocks
are copied into a host arena (a refcounted shared block spills ONCE)
and the session becomes retrievable instead of dead.  Any later touch
(get / pin / snapshot / write_rows / the scheduler's roster add)
RESTORES it through the same reserve / fill-outside-the-lock / commit
shape ``load_into`` rides, with a chained-CRC byte verification so a
corrupted host block degrades to a typed re-prefill shed, never to
serving wrong bytes.  The spill path registers as a plane-health row
("spill", timer-latch policy) so a failing host arena degrades
in-policy — demotes stop, eviction falls back to the PR-16 behavior —
and revives through the standard reprobe/ramp counters.
"""
from __future__ import annotations

import time
import zlib
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from .. import bvar
from ..butil import custody_ledger as _ledger
from ..butil import debug_sync as _dbg
from ..butil import flags as _flags

_flags.define_flag(
    "serving_kv_prefix_share", True,
    "content-hash FULL KV blocks at load commit so sessions sharing a "
    "block-aligned prefix map the same physical blocks under a "
    "refcount (byte-verified on every hit; divergence or write_rows "
    "triggers a CoW split to a private copy).  False restores the "
    "PR-15 private-blocks-per-session behavior byte-for-byte for "
    "same-run A/B")

_flags.define_flag(
    "serving_kv_concurrent_fill", True,
    "run load_into's fill OUTSIDE the pool lock: reserve under the "
    "lock, scatter unlocked, commit with a re-check — concurrent "
    "LoadKv fills proceed in parallel instead of serializing.  False "
    "restores the PR-15 hold-through-the-fill discipline byte-for-byte "
    "for same-run A/B")

_flags.define_flag(
    "serving_kv_spill", True,
    "demote pressure victims to the host arena tier instead of "
    "evicting them (pools built with host_blocks > 0).  False restores "
    "the PR-16 evict-on-pressure behavior byte-for-byte for same-run "
    "A/B")

_flags.define_flag(
    "serving_kv_spill_reprobe_s", 0.25,
    "spill plane-health timer latch: how long after a demote/restore "
    "IO failure before the first use re-probes the host tier "
    "optimistically")


class SessionBusy(RuntimeError):
    """``load`` hit a session id that is PINNED in the step roster: a
    re-prefill while the first decode still runs.  Freeing a rostered
    session's blocks would hand them to the new bytes mid-program (the
    running gather would read the replacement's KV), so the reload is
    refused — the RPC layer maps this to a retryable shed.  The same
    refusal fires at COMMIT time when a concurrent loader won the race
    for the session id and its entry got pinned before our re-check."""

    def __init__(self, session: str):
        super().__init__(
            f"session {session!r} is pinned in the decode roster; "
            f"re-prefill must wait for (or cancel) the running decode")
        self.session = session


class PoolSaturated(RuntimeError):
    """``load`` could not free enough blocks: every candidate session is
    pinned or lives in a band more protected than the requester's.  The
    RPC layer maps this to retryable ``ELIMIT`` + a ``retry_after_ms``
    hint — the shed, not a failure."""

    def __init__(self, needed: int, free: int):
        super().__init__(
            f"kv pool saturated: need {needed} blocks, {free} free and "
            f"no evictable session in an equal-or-less-protected band")
        self.needed = needed
        self.free = free


@dataclass
class KvPoolOptions:
    """Pool geometry + the eviction/expiry policy."""
    bytes_per_token: int
    num_blocks: int = 256
    block_tokens: int = 16
    bands: int = 4                   # priority bands, 0 = most protected
    default_priority: int = 2        # sessions arriving without one
    # host-tier arena size in blocks (ISSUE 19): 0 disables spill —
    # pressure evicts exactly as before
    host_blocks: int = 0
    ttl_s: float = 120.0             # idle-session expiry
    sweep_interval_s: float = 0.0    # 0 = auto: ttl_s / 4, floored
    use_timers: bool = True          # False: tests drive expire_idle()
    tenant_weights: Dict[str, int] = field(default_factory=dict)
    default_tenant_weight: int = 1

    @classmethod
    def from_admission(cls, adm, **kw) -> "KvPoolOptions":
        """Derive the eviction policy from a PR-9 ``AdmissionOptions``
        so queue fairness and memory pressure share one tenant table."""
        kw.setdefault("bands", adm.bands)
        kw.setdefault("default_priority", adm.default_priority)
        kw.setdefault("tenant_weights", dict(adm.tenant_weights))
        kw.setdefault("default_tenant_weight", adm.default_tenant_weight)
        return cls(**kw)

    def effective_sweep_s(self) -> float:
        if self.sweep_interval_s > 0:
            return self.sweep_interval_s
        return max(self.ttl_s / 4.0, 0.05)


class _KvSession:
    """One session's block table (access under the pool lock; the
    numeric fields are immutable after load, so the scheduler may READ
    blocks/seq_len/acc/last_token from its roster snapshot lock-free —
    ``write_rows`` preserves this by publishing a NEW blocks array on a
    CoW split, never mutating the one a roster snapshot may hold).

    ``pinned`` is a COUNT (ISSUE 15), not a flag: the step roster holds
    one pin per roster entry and a zero-copy ``snapshot(view=True)``
    reader holds another — either alone fences eviction/expiry, and
    releasing one must not unfence the other.  ``release_pending``
    marks a ``release`` that arrived while pinned: the free is DEFERRED
    to the last unpin instead of yanking blocks out from under a
    reader (or being silently dropped).  ISSUE 16 extends the same
    counted-holder idea one level down: a PHYSICAL block shared across
    sessions carries a pool-side refcount (``PagedKvPool._refs``) that
    outlives any one owner — a session's free decrements, the block
    only rejoins the free list at zero."""

    __slots__ = ("session", "tenant", "priority", "seq_len", "last_token",
                 "acc", "blocks", "last_used", "pinned",
                 "release_pending", "contiguous")

    def __init__(self, session: str, tenant: str, priority: int,
                 seq_len: int, last_token: int, acc: int,
                 blocks: np.ndarray, now: float):
        self.session = session
        self.tenant = tenant
        self.priority = priority
        self.seq_len = seq_len
        self.last_token = last_token
        self.acc = acc
        self.blocks = blocks             # np.int64 (n_blocks,)
        self.last_used = now
        self.pinned = 0
        self.release_pending = False
        # blocks are immutable after commit, so the one-ascending-
        # extent test is computed ONCE here — snapshot(view=True)'s
        # per-read eligibility is a field read, not an array compare
        # (prefix-share dedupe and CoW splits recompute it when they
        # publish a substituted array)
        self.contiguous = bool((np.diff(blocks) == 1).all())


class _SpilledSession:
    """One session parked in the host tier (access under the pool
    lock).  ``hblocks`` indexes the host arena; ``crcs`` holds the
    CHAINED crc32 per block position, computed from the DEVICE bytes at
    demote time — the restore path recomputes the chain from the host
    copy and any divergence aborts the restore into a typed re-prefill
    shed, never into serving corrupted bytes.  ``acc`` survives the
    round trip so a restored session's decode recurrence is bit-exact
    without re-deriving the reduction arena from scratch."""

    __slots__ = ("session", "tenant", "priority", "seq_len",
                 "last_token", "acc", "hblocks", "crcs", "last_used")

    def __init__(self, session: str, tenant: str, priority: int,
                 seq_len: int, last_token: int, acc: int,
                 hblocks: np.ndarray, crcs: List[int], now: float):
        self.session = session
        self.tenant = tenant
        self.priority = priority
        self.seq_len = seq_len
        self.last_token = last_token
        self.acc = acc
        self.hblocks = hblocks           # np.int64 (n_blocks,)
        self.crcs = crcs                 # chained crc32 per position
        self.last_used = now


class PagedKvPool:
    """The paged KV arena.  Thread-safe; one per decode worker."""

    # cardinality cap for per-tenant eviction counters — the tenant
    # string is untrusted wire input (the admission controller's rule)
    MAX_TRACKED_TENANTS = 64

    _GUARDED_BY = {
        "_free": "_lock",
        "_tables": "_lock",
        "_refs": "_lock",
        "_prefix_index": "_lock",
        "_block_hash": "_lock",
        "_recent_evicted": "_lock",
        "_host_free": "_lock",
        "_spilled": "_lock",
        "_host_refs": "_lock",
        "_spill_map": "_lock",
        "_restoring": "_lock",
        "_spill_fault": "_lock",
        "_restore_us": "_lock",
        "_sweep_timer": "_lock",
        "_closed": "_lock",
        "_counters": "_counters_lock",
        "_tenant_labels": "_counters_lock",
    }

    # fablint custody contract (ISSUE 20).  A pin is owed an unpin; a
    # reservation is owed exactly one of commit / abort / return (the
    # restore path resolves through _finish_restore_locked); the block
    # refcounts free through _free_session_locked (or an inline
    # guarded decrement), the host-tier refcounts through
    # _host_unref_locked.  The methods named here are the protocol
    # implementation and are exempt from the acquire-release rule;
    # everything else that acquires must release on every exit path.
    _CUSTODY = {
        "pin": ("unpin",),
        "pinned": ("unpin",),
        "_reserve_locked": ("_commit_locked", "_abort_fill_locked",
                            "_return_blocks_locked",
                            "_finish_restore_locked"),
        "_refs": ("_free_session_locked", "_return_blocks_locked"),
        "_host_refs": ("_host_unref_locked", "_finish_restore_locked"),
    }

    def __init__(self, options: KvPoolOptions,
                 now: Optional[Callable[[], float]] = None):
        o = options
        self.options = o
        self._now = now or time.monotonic
        self._lock = _dbg.make_lock("PagedKvPool._lock")
        self._counters_lock = _dbg.make_lock("PagedKvPool._counters_lock")
        # the arenas are DELIBERATELY unguarded: a reserved block is off
        # the free list and in no table, so its rows have exactly one
        # writer (the in-flight fill) and no reader — the disjoint-row
        # discipline that makes the outside-the-lock fill safe
        self._store = np.zeros(
            (o.num_blocks, o.block_tokens * o.bytes_per_token), np.uint8)
        self._pos_sums = np.zeros((o.num_blocks, o.block_tokens), np.int64)
        # row-sum accumulator dtype: int32 sums measured 2.7x faster
        # than int64 on the uint8 arena (numpy SIMD), and a row of
        # bytes_per_token 255s fits int32 up to ~8.4 MB/token — fall
        # back to int64 beyond (the arena itself stays int64 either way)
        self._sum_dtype = (np.int32
                           if o.bytes_per_token * 255 < 2**31 - 1
                           else np.int64)
        # the batched decode step's gather surface: a VIEW over the
        # reduction arena (C-contiguous reshape shares memory), fixed
        # shape for the whole pool lifetime — jit-friendly
        self.pos_sums_flat = self._pos_sums.reshape(-1)
        self._free: List[int] = list(range(o.num_blocks - 1, -1, -1))
        self._tables: Dict[str, _KvSession] = {}
        # per-PHYSICAL-block refcount for every block owned by >= 1
        # session table (1 = private, >= 2 = prefix-shared); reserved
        # blocks mid-fill are in neither _free nor _refs, so
        # len(_free) + len(_refs) + in-flight == num_blocks always
        self._refs: Dict[int, int] = {}
        # chained-CRC prefix hash -> physical block, plus the reverse
        # map for unregistration at free time.  The index is a LOOKUP
        # ACCELERATOR only: every hit is byte-verified before sharing
        self._prefix_index: Dict[int, int] = {}
        self._block_hash: Dict[int, int] = {}
        # recently-evicted ids → reason, so a late Decode gets a typed
        # "re-prefill" shed instead of an unknown-session error
        self._recent_evicted: Dict[str, str] = {}
        # ---- host tier (ISSUE 19) — all empty when host_blocks == 0.
        # The host arena itself is unguarded for the same disjoint-row
        # reason as the device arenas: a host block is written exactly
        # once (at demote, under the lock) and read by at most one
        # restore, which holds its own host refcount for the copy.
        self._host_store = np.zeros(
            (o.host_blocks, o.block_tokens * o.bytes_per_token),
            np.uint8)
        self._host_free: List[int] = list(
            range(o.host_blocks - 1, -1, -1))
        self._spilled: Dict[str, _SpilledSession] = {}
        # per-HOST-block refcount: spilled sessions sharing a prefix
        # share ONE host copy (a shared block spills once); an in-flight
        # restore holds an extra count so a concurrent drop of the
        # record can never free host bytes mid-copy
        self._host_refs: Dict[int, int] = {}
        # live device block -> its host copy: the demote-time dedupe
        # accelerator.  An entry is valid exactly while the device
        # block's bytes are unchanged — invalidated on physical free,
        # on an in-place private write, and when the host copy frees
        self._spill_map: Dict[int, int] = {}
        self._restoring: set = set()
        self._spill_fault: Optional[str] = None   # test injection
        self._restore_us: deque = deque(maxlen=512)
        self._spill_health = None
        if o.host_blocks > 0:
            from ..ici.plane_health import register_plane
            self._spill_health = register_plane(
                "spill",
                retry_s=lambda: float(_flags.get_flag(
                    "serving_kv_spill_reprobe_s")))
        self._sweep_timer = None
        self._closed = False
        self.loads = bvar.Adder("serving_kv_pool_loads")
        self.bytes_in = bvar.Adder("serving_kv_pool_bytes_in")
        self.evictions = bvar.Adder("serving_kv_pool_evictions")
        self.expirations = bvar.Adder("serving_kv_pool_expired")
        # load_into fills that raised: the reservation aborted clean
        self.fill_aborts = bvar.Adder("serving_kv_pool_fill_aborts")
        # ISSUE 16 truth: blocks shared at commit, CoW splits, commit
        # re-checks that found a raced incumbent, and the fill-route
        # counters the concurrency tests assert per call
        self.prefix_hits = bvar.Adder("serving_kv_pool_prefix_hits")
        self.cow_splits = bvar.Adder("serving_kv_pool_cow_splits")
        self.commit_races = bvar.Adder("serving_kv_pool_commit_races")
        self.locked_fills = bvar.Adder("serving_kv_pool_locked_fills")
        self.unlocked_fills = bvar.Adder("serving_kv_pool_unlocked_fills")
        # ISSUE 19 tier truth: demote/restore round trips, restores
        # that failed byte verification (degraded to re-prefill), and
        # spilled sessions dropped under HOST-tier pressure
        self.demotions = bvar.Adder("serving_kv_pool_demotions")
        self.restores = bvar.Adder("serving_kv_pool_restores")
        self.restore_corrupt = bvar.Adder(
            "serving_kv_pool_restore_corrupt")
        self.host_evictions = bvar.Adder(
            "serving_kv_pool_host_evictions")
        self._counters: Dict[tuple, bvar.Adder] = {}
        self._tenant_labels: set = set()

    # ---- policy helpers -----------------------------------------------
    def _weight(self, tenant: str) -> int:
        from ..rpc.admission import tenant_weight_of
        return tenant_weight_of(self.options.tenant_weights,
                                self.options.default_tenant_weight,
                                tenant)

    def _clip_priority(self, priority: Optional[int]) -> int:
        pri = self.options.default_priority if priority is None \
            else priority
        return min(max(pri, 0), self.options.bands - 1)

    def _count(self, what: str, tenant: str) -> None:
        with self._counters_lock:
            if tenant and tenant not in self.options.tenant_weights \
                    and tenant not in self._tenant_labels:
                if len(self._tenant_labels) >= self.MAX_TRACKED_TENANTS:
                    tenant = "~other"
                else:
                    self._tenant_labels.add(tenant)
            key = (what, tenant)
            a = self._counters.get(key)
            if a is None:
                safe = bvar.to_underscored_name(tenant or "shared")
                a = self._counters[key] = bvar.Adder(
                    f"serving_kv_{what}_{safe}")
        a << 1

    # ---- load / release -----------------------------------------------
    def blocks_for(self, seq_len: int) -> int:
        bt = self.options.block_tokens
        return (seq_len + bt - 1) // bt

    def load(self, session: str, token_rows: np.ndarray, *,
             last_token: int, tenant: str = "",
             priority: Optional[int] = None) -> _KvSession:
        """Page a session's KV in.  ``token_rows`` is token-major uint8,
        shape ``(seq_len, bytes_per_token)`` — the caller transposes the
        model's layer-major layout once here, so every block row is one
        token's bytes and paging never splits a token.  Raises
        :class:`PoolSaturated` when eviction cannot make room.

        Since ISSUE 16 this is a delegation to :meth:`load_into` with a
        row-copy fill: both entry surfaces ride the SAME
        reserve/fill/commit shape (and the same flags), so locking
        discipline, abort semantics, prefix sharing, and the concurrent
        fill can never drift between them."""
        o = self.options
        rows = np.ascontiguousarray(token_rows, dtype=np.uint8)
        if rows.ndim != 2 or rows.shape[1] != o.bytes_per_token:
            raise ValueError(
                f"token_rows must be (seq_len, {o.bytes_per_token}), "
                f"got {rows.shape}")
        seq_len = rows.shape[0]
        if seq_len <= 0:
            # a 0-token session would build an empty block table the
            # batched step cannot index — reject at the boundary
            raise ValueError("token_rows must hold at least one token")

        def fill(views: List[np.ndarray]) -> None:
            off = 0
            for v in views:
                n = v.shape[0]
                v[:] = rows[off:off + n]
                off += n

        return self.load_into(session, seq_len, fill,
                              last_token=last_token, tenant=tenant,
                              priority=priority)

    def load_into(self, session: str, seq_len: int,
                  fill: Callable[[List[np.ndarray]], None], *,
                  last_token: int, tenant: str = "",
                  priority: Optional[int] = None) -> _KvSession:
        """Reserve the block table FIRST, then fill blocks IN PLACE —
        the zero-intermediate-copy loader surface (ISSUE 15).

        ``fill(views)`` receives an ordered list of writable
        ``(n_rows, bytes_per_token)`` uint8 views — one per CONTIGUOUS
        EXTENT of reserved blocks, together covering exactly
        ``seq_len`` token rows (a fresh or steady pool allocates one
        extent, so the common fill is ONE strided pass; a fragmented
        pool hands out more, smaller views).  It must write every row
        (a partial write would publish a table over stale arena bytes).

        With ``serving_kv_concurrent_fill`` ON (the default) the fill
        runs OUTSIDE the pool lock — the ISSUE-16 concurrency lever:
        reserved blocks are off the free list and in no table, so no
        eviction, expiry, or concurrent loader can touch their arena
        rows, and two LoadKv fills scatter in parallel.  The commit
        then RE-CHECKS under the lock: a pool closed mid-fill raises
        (``close()`` already reclaimed every block); a concurrent
        loader that committed the same session id mid-fill is replaced
        last-commit-wins when unpinned, or aborts THIS fill with
        :class:`SessionBusy` when the incumbent got pinned (counted in
        ``commit_races`` either way).  OFF restores the PR-15
        hold-through-the-fill discipline byte-for-byte — in that shape
        ``fill`` must not call back into this pool.

        If ``fill`` raises, the reservation ABORTS clean: blocks
        return to the free list, no session entry is created — a
        same-session RELOAD keeps its previous KV valid whenever the
        free list alone covered the reservation (see
        ``_reserve_locked``) — and the exception propagates (the RPC
        layer's eviction-mid-load / bad-source path).  After a
        successful fill the pool derives the reduction arena
        (``pos_sums``/``acc``) from the written bytes, zeroes the
        partial tail so no prior tenant's bytes survive adoption,
        dedupes full blocks against the prefix index
        (``serving_kv_prefix_share``), and commits the table —
        byte-for-byte the state ``load`` builds from a pre-materialized
        array."""
        o = self.options
        if seq_len <= 0:
            raise ValueError("seq_len must be >= 1")
        pri = self._clip_priority(priority)
        need = self.blocks_for(seq_len)
        now = self._now()
        bpt = o.bytes_per_token
        if _flags.get_flag("serving_kv_concurrent_fill"):
            with self._lock:
                blocks, deferred_old = self._reserve_locked(session, need,
                                                            pri)
            _ledger.acquire("kv.reserve", (id(self), id(blocks)))
            # the fill below touches only the unguarded arenas through
            # rows nothing else references (reserved blocks are
            # invisible to every other pool operation).  EVERYTHING
            # between the reserve and the commit sits inside the try:
            # the extent-view build and the session construction can
            # raise under allocator pressure just like the fill, and
            # an abort must reach the reservation from every one of
            # those edges (ISSUE 20 — the custody pass proves this)
            try:
                extents, views = self._extent_views(blocks, seq_len)
                fill(views)
                acc = self._derive_sums(extents, views, seq_len)
                s = _KvSession(session, tenant, pri, seq_len, last_token,
                               acc, blocks, now)
            except BaseException:
                # abort clean: the reservation never became a session
                with self._lock:
                    self._abort_fill_locked(blocks)
                _ledger.release("kv.reserve", (id(self), id(blocks)))
                self.fill_aborts << 1
                raise
            try:
                with self._lock:
                    self._commit_locked(s, deferred_old)
            finally:
                # a SessionBusy / closed-pool commit refusal already
                # returned the blocks internally: custody ends either way
                _ledger.release("kv.reserve", (id(self), id(blocks)))
            self.unlocked_fills << 1
        else:
            with self._lock:
                blocks, deferred_old = self._reserve_locked(session, need,
                                                            pri)
                _ledger.acquire("kv.reserve", (id(self), id(blocks)))
                try:
                    extents, views = self._extent_views(blocks, seq_len)
                    fill(views)
                    acc = self._derive_sums(extents, views, seq_len)
                    s = _KvSession(session, tenant, pri, seq_len,
                                   last_token, acc, blocks, now)
                except BaseException:
                    # abort clean: the reservation never became a
                    # session (close() cannot race — we hold the lock)
                    self._return_blocks_locked(blocks)
                    _ledger.release("kv.reserve", (id(self), id(blocks)))
                    self.fill_aborts << 1
                    raise
                try:
                    self._commit_locked(s, deferred_old)
                finally:
                    _ledger.release("kv.reserve",
                                    (id(self), id(blocks)))
            self.locked_fills << 1
        self.loads << 1
        self.bytes_in << seq_len * bpt
        return s

    def _extent_views(self, blocks: np.ndarray, seq_len: int):
        """Coalesce a reservation into contiguous extents and build the
        writable fill views: per-extent numpy ops amortize over whole
        runs of blocks instead of paying call overhead per 16-token
        block.  Touches only the unguarded arena (reserved rows have
        exactly one writer), so it runs with or without the pool lock."""
        o = self.options
        bt, bpt = o.block_tokens, o.bytes_per_token
        need = len(blocks)
        extents = []              # (first_block, n_blocks, n_rows)
        left = seq_len
        b0 = int(blocks[0])
        k = 1
        for i in range(1, need):
            b = int(blocks[i])
            if b == b0 + k:
                k += 1
                continue
            rows = min(left, k * bt)
            extents.append((b0, k, rows))
            left -= rows
            b0, k = b, 1
        extents.append((b0, k, min(left, k * bt)))
        views = [self._store[e0:e0 + ek].reshape(-1, bpt)[:rows]
                 for e0, ek, rows in extents]
        return extents, views

    def _derive_sums(self, extents, views, seq_len: int) -> int:
        """Derive the reduction arena from the filled bytes and zero the
        partial tail so no prior tenant's bytes survive adoption.
        Returns the session accumulator.  Unguarded-arena-only, same
        rationale as :meth:`_extent_views`."""
        o = self.options
        bt, bpt = o.block_tokens, o.bytes_per_token
        acc = 0
        for (e0, ek, rows), v in zip(extents, views):
            sums = v.sum(axis=1, dtype=self._sum_dtype)
            ps = self._pos_sums[e0:e0 + ek].reshape(-1)
            ps[:rows] = sums
            acc += int(sums.sum(dtype=np.int64))
            if rows < ek * bt:
                # zero the tail so no prior tenant's bytes survive
                # in the partially-filled final block
                ps[rows:] = 0
                self._store[e0:e0 + ek].reshape(-1)[rows * bpt:] = 0
        return acc

    # fablint: lock-held(_lock)
    def _reserve_locked(self, session: str, need: int, pri: int):
        """Allocate ``need`` blocks for ``session`` (evicting under
        pressure per the band/weight/LRU policy): the shared first half
        of ``load`` and ``load_into``.  Returns ``(blocks,
        deferred_old)`` — blocks are OFF the free list and in no table
        (invisible to eviction, expiry, and every concurrent loader);
        the caller fills them and commits (or returns them on a fill
        failure).  A same-session reload keeps the OLD entry alive as
        ``deferred_old`` whenever the free list alone covers the
        reservation, so an aborted fill leaves the previous KV valid
        (``_commit_locked`` frees it); only a reservation that NEEDS
        the old blocks for capacity reclaims them up front — the one
        case an abort genuinely cannot restore."""
        o = self.options
        if need > o.num_blocks:
            raise PoolSaturated(need, o.num_blocks)
        if self._closed:
            raise RuntimeError("kv pool is closed")
        old = self._tables.get(session)
        deferred_old = None
        if old is not None:
            if old.pinned:
                # NEVER free a rostered session's blocks out from
                # under the running batched step
                raise SessionBusy(session)
            if need <= len(self._free):
                deferred_old = old
            else:
                # a re-prefill bigger than the free space reclaims its
                # own previous table first
                self._free_session_locked(old, "reloaded")
        if need > len(self._free):
            spill = self._spill_usable_locked()
            victims = self._pick_victims_locked(
                need - len(self._free), pri, spill=spill)
            if victims is None:
                raise PoolSaturated(need, len(self._free))
            for v in victims:
                # eviction becomes DEMOTION when the host tier is
                # usable; a per-victim demote failure (host arena
                # full / injected IO fault) falls back to the PR-16
                # evict, so the picker's free-bytes simulation stays
                # exact either way — _free_session_locked runs under
                # both outcomes, only the reason differs
                if spill and self._demote_session_locked(v):
                    continue
                self._free_session_locked(v, "pressure")
        blocks = np.empty(need, np.int64)
        for k in range(need):
            blocks[k] = self._free.pop()
        return blocks, deferred_old

    # fablint: lock-held(_lock)
    def _abort_fill_locked(self, blocks) -> None:
        """Return an aborted outside-the-lock reservation — UNLESS the
        pool closed mid-fill, whose free-list rebuild already reclaimed
        every block (returning ours again would double-count them)."""
        if not self._closed:
            self._return_blocks_locked(blocks)

    # fablint: lock-held(_lock)
    def _commit_locked(self, s: _KvSession, deferred_old) -> None:
        """Publish a filled reservation: the COMMIT-TIME RE-CHECK of
        the outside-the-lock fill (a no-op re-check when the caller
        held the lock through the fill).  Order matters: the raced/
        pinned check FIRST (an abort must return the ORIGINAL blocks,
        never deduped substitutes another session owns), then prefix
        dedupe + refcounts, and only then the incumbent's free — so a
        same-content reload SHARES its predecessor's blocks for the
        one lock hold both are alive, and the decrement leaves them
        owned by the new entry alone."""
        if self._closed:
            # close() raced the fill: its free-list rebuild already
            # reclaimed every block — publishing (or returning) now
            # would resurrect custody close() ended
            raise RuntimeError("kv pool is closed")
        cur = self._tables.get(s.session)
        if cur is not None:
            if cur is not deferred_old:
                # a concurrent loader committed this session id mid-fill
                self.commit_races << 1
            if cur.pinned:
                # the incumbent — a raced commit OR our own
                # deferred_old that a roster/view pinned during the
                # outside-the-lock fill window — is being READ right
                # now: OUR fill aborts, its blocks stay intact (the
                # reserve-time pinned check cannot see a pin that
                # arrives mid-fill, so the re-check must)
                self._return_blocks_locked(s.blocks)
                raise SessionBusy(s.session)
            # last-commit-wins: retire the raced incumbent (after
            # dedupe below would be too late — but sharing against it
            # is still possible because the free only happens further
            # down, after refcounts pin the shared blocks)
        if _flags.get_flag("serving_kv_prefix_share"):
            self._dedupe_blocks_locked(s)
        for b in s.blocks:
            b = int(b)
            self._refs[b] = self._refs.get(b, 0) + 1
        # fresh bytes supersede any parked host copy of this id — a
        # re-prefill must never leave a stale spilled record behind
        # for a later restore to resurrect
        self._drop_spilled_locked(s.session)
        if cur is not None:
            # deferred_old or the raced unpinned incumbent: either way
            # the fill succeeded, NOW retire the replaced table (still
            # under the same lock hold, so no reader ever saw a gap)
            self._free_session_locked(cur, "reloaded")
        self._tables[s.session] = s
        self._recent_evicted.pop(s.session, None)
        self._schedule_sweep_locked()

    # fablint: lock-held(_lock)
    def _dedupe_blocks_locked(self, s: _KvSession) -> None:
        """Map ``s``'s FULL blocks onto existing physical blocks where
        a byte-identical block-aligned prefix already lives in the pool
        (ISSUE 16).  The key is a CHAINED crc32 over the block run, so
        equal keys mean equal position-in-prefix candidates; every hit
        is BYTE-VERIFIED before substitution, so a collision degrades
        to a miss, never to sharing wrong bytes.  Sharing stops at the
        first miss (prefixes only — a mid-sequence match cannot share
        because the chain key diverged), but hashing continues so this
        session's full blocks register as donors for longer prefixes.
        Partial tail blocks never share and never register."""
        o = self.options
        blocks = s.blocks
        full = s.seq_len // o.block_tokens
        h = 0
        sharing = True
        new_blocks = None
        returned = []
        for k in range(full):
            blk = int(blocks[k])
            data = self._store[blk]
            h = zlib.crc32(data, h)
            if sharing:
                eb = self._prefix_index.get(h)
                if (eb is not None and eb != blk and eb in self._refs
                        and np.array_equal(self._store[eb], data)):  # fablint: ignore[blocking-under-lock] dedupe byte-verify: one block-sized compare under _lock is the accepted PR-16 collision fence; moving it outside would race the donor's free (ROADMAP 5 residue)
                    # verified content match: map this position onto
                    # the existing physical block, hand ours back
                    if new_blocks is None:
                        new_blocks = blocks.copy()
                    new_blocks[k] = eb
                    returned.append(blk)
                    self.prefix_hits << 1
                    continue
                sharing = False
            if h not in self._prefix_index:
                self._prefix_index[h] = blk
                self._block_hash[blk] = h
        if new_blocks is not None:
            s.blocks = new_blocks
            s.contiguous = bool((np.diff(new_blocks) == 1).all())
            self._return_blocks_locked(returned)

    # fablint: lock-held(_lock)
    def _unregister_block_locked(self, blk: int) -> None:
        """Drop a freed (or about-to-be-overwritten) block from the
        prefix index so no future load shares stale content."""
        h = self._block_hash.pop(blk, None)
        if h is not None and self._prefix_index.get(h) == blk:
            del self._prefix_index[h]

    # fablint: lock-held(_lock)
    def _pick_victims_locked(self, blocks_needed: int,
                             requester_pri: int, exclude=None,
                             spill: bool = False):
        """Eviction order under pressure: most-sheddable band first,
        lighter tenants before heavier inside a band, LRU inside a
        class; never a band more protected than the requester's.  A
        victim only contributes the blocks that would ACTUALLY free —
        the refcount decrements are simulated cumulatively across the
        victim list, so two sessions sharing a prefix free its blocks
        only when BOTH are on the list.  ``exclude`` fences one session
        out of the candidate set (``write_rows`` evicting on behalf of
        the session it is mutating must never pick that session).

        ``spill=True`` (ISSUE 19): victims will be DEMOTED, not killed,
        so the ordering PREFERS taking a whole shared-owner set over an
        unshared live session of the same protection class — the set's
        blocks spill ONCE for all its owners, and taking it whole is
        the only way its shared blocks free at all (PR 16's picker
        saturated there).  Candidates are grouped into shared-block
        connected components; a group sorts by its MOST PROTECTED
        member's band (taking any member means taking the set, so the
        set is as protected as its most protected owner), shared sets
        before singletons within a band, then lightest member weight,
        then oldest member LRU.  The cumulative free-bytes simulation
        is IDENTICAL to the ungrouped path — grouping only reorders."""
        cands = [s for s in self._tables.values()
                 if not s.pinned and s.priority >= requester_pri
                 and s is not exclude]
        if spill and len(cands) > 1:
            parent = list(range(len(cands)))

            def find(x: int) -> int:
                while parent[x] != x:
                    parent[x] = parent[parent[x]]
                    x = parent[x]
                return x

            block_owner: Dict[int, int] = {}
            for i, s in enumerate(cands):
                for b in s.blocks:
                    b = int(b)
                    if self._refs.get(b, 1) > 1:
                        j = block_owner.get(b)
                        if j is None:
                            block_owner[b] = i
                        else:
                            ra, rb = find(i), find(j)
                            if ra != rb:
                                parent[rb] = ra
            comps: Dict[int, List[_KvSession]] = {}
            for i, s in enumerate(cands):
                comps.setdefault(find(i), []).append(s)
            groups = list(comps.values())
            groups.sort(key=lambda g: (
                -min(s.priority for s in g),
                0 if len(g) > 1 else 1,
                min(self._weight(s.tenant) for s in g),
                min(s.last_used for s in g)))
            for g in groups:
                g.sort(key=lambda s: (-s.priority,
                                      self._weight(s.tenant),
                                      s.last_used))
            cands = [s for g in groups for s in g]
        else:
            cands.sort(key=lambda s: (-s.priority,
                                      self._weight(s.tenant),
                                      s.last_used))
        victims, have = [], 0
        sim: Dict[int, int] = {}
        for s in cands:
            if have >= blocks_needed:
                break
            victims.append(s)
            for b in s.blocks:
                b = int(b)
                taken = sim.get(b, 0)
                sim[b] = taken + 1
                if self._refs.get(b, 1) - taken == 1:
                    have += 1
        return victims if have >= blocks_needed else None

    # fablint: lock-held(_lock)
    def _return_blocks_locked(self, blocks) -> None:
        """Put blocks back KEEPING the free list sorted descending —
        the invariant that makes ``pop()`` hand out ASCENDING runs, so
        ``load_into`` reservations coalesce into few contiguous extents
        (one strided fill pass each) instead of 1-block shards.  Timsort
        on the mostly-sorted list is microseconds at pool sizes."""
        self._free.extend(int(b) for b in blocks)
        self._free.sort(reverse=True)

    # fablint: lock-held(_lock)
    def _free_session_locked(self, s: _KvSession, reason: str) -> None:
        """Retire a session's table: DECREMENT each block's refcount,
        physically freeing (and unregistering from the prefix index)
        only the blocks that hit zero — a prefix another session still
        shares survives its co-owner's eviction/release/expiry."""
        self._tables.pop(s.session, None)
        dead = []
        for b in s.blocks:
            b = int(b)
            r = self._refs.get(b, 1) - 1
            if r <= 0:
                self._refs.pop(b, None)
                self._unregister_block_locked(b)
                # a physically-freed block's bytes are about to be
                # rewritten by the next reservation: its host-copy
                # mapping is stale the moment it leaves custody
                self._spill_map.pop(b, None)
                dead.append(b)
            else:
                self._refs[b] = r
        if dead:
            self._return_blocks_locked(dead)
        if reason in ("pressure", "expired"):
            self._recent_evicted[s.session] = reason
            while len(self._recent_evicted) > 256:
                self._recent_evicted.pop(
                    next(iter(self._recent_evicted)))
        if reason == "expired":
            self.expirations << 1
        elif reason == "pressure":
            self.evictions << 1
        elif reason == "spilled":
            # demotion, not death: the session is retrievable from the
            # host tier, so it gets neither a _recent_evicted entry nor
            # an eviction count
            self.demotions << 1
        if reason == "released":
            self._count("released", s.tenant)
        elif reason == "spilled":
            self._count("spilled", s.tenant)
        else:
            self._count(f"evicted_{reason}", s.tenant)

    # ---- host tier: spill / restore (ISSUE 19) -------------------------
    # fablint: lock-held(_lock)
    def _spill_usable_locked(self) -> bool:
        """Demotion is on exactly when the pool HAS a host arena, the
        A/B flag says so, and the spill plane-health row is usable —
        a latched IO failure turns pressure back into PR-16 eviction
        until the timer latch lapses and the plane revives."""
        return (self.options.host_blocks > 0
                and bool(_flags.get_flag("serving_kv_spill"))
                and self._spill_health.usable())

    # fablint: lock-held(_lock)
    def _demote_session_locked(self, s: _KvSession) -> bool:
        """Copy ``s``'s blocks into the host arena and retire its
        device table ("spilled" — retrievable, not dead).  A device
        block that already has a live host copy (a co-owner spilled
        first, or shares the block with an already-spilled session)
        reuses it with a refcount bump — a SHARED BLOCK SPILLS ONCE.
        Returns False without side effects on the session when the
        host tier cannot take it (arena full even after reclaiming
        older spilled sessions, or the injected IO fault) — the caller
        falls back to eviction."""
        if self._spill_fault == "demote":
            # injected demote-IO failure: latch the plane down so
            # pressure stops routing victims at a failing host arena
            self._spill_health.mark_down("demote_io")
            return False
        need_new = 0
        for b in s.blocks:
            b = int(b)
            if b not in self._spill_map:
                need_new += 1
        if need_new > len(self._host_free) and \
                not self._host_reclaim_locked(
                    need_new - len(self._host_free), s.priority):
            return False
        hblocks = np.empty(len(s.blocks), np.int64)
        crcs: List[int] = []
        chain = 0
        new_host: List[int] = []
        for k, b in enumerate(s.blocks):
            b = int(b)
            data = self._store[b]
            chain = zlib.crc32(data, chain)
            crcs.append(chain)
            hb = self._spill_map.get(b)
            if hb is None:
                hb = self._host_free.pop()
                self._host_store[hb] = data
                self._spill_map[b] = hb
                new_host.append(hb)
            # fablint: custody-moved(spill-record) the ref lives in the _SpilledSession entry below; _drop_spilled_locked / _host_unref_locked balance it
            self._host_refs[hb] = self._host_refs.get(hb, 0) + 1
            hblocks[k] = hb
        now = self._now()
        self._spilled[s.session] = _SpilledSession(
            s.session, s.tenant, s.priority, s.seq_len, s.last_token,
            s.acc, hblocks, crcs, now)
        self._free_session_locked(s, "spilled")
        return True

    # fablint: lock-held(_lock)
    def _host_reclaim_locked(self, shortage: int,
                             requester_pri: int) -> bool:
        """Make room in the HOST arena by dropping the most sheddable
        spilled sessions — same band/weight/LRU order and the same
        cumulative refcount simulation as the device picker, fenced to
        bands no more protected than the demoting session's.  Sessions
        mid-restore are skipped (their host bytes are being read).
        Dropped sessions die for real: typed "pressure" shed."""
        cands = [sp for sess, sp in self._spilled.items()
                 if sess not in self._restoring
                 and sp.priority >= requester_pri]
        cands.sort(key=lambda sp: (-sp.priority,
                                   self._weight(sp.tenant),
                                   sp.last_used))
        victims, have = [], 0
        sim: Dict[int, int] = {}
        for sp in cands:
            if have >= shortage:
                break
            victims.append(sp)
            for h in sp.hblocks:
                h = int(h)
                taken = sim.get(h, 0)
                sim[h] = taken + 1
                if self._host_refs.get(h, 1) - taken == 1:
                    have += 1
        if have < shortage:
            return False
        for sp in victims:
            self._drop_spilled_locked(sp.session)
            self._recent_evicted[sp.session] = "pressure"
            while len(self._recent_evicted) > 256:
                self._recent_evicted.pop(
                    next(iter(self._recent_evicted)))
            self.host_evictions << 1
            self._count("evicted_pressure", sp.tenant)
        return True

    # fablint: lock-held(_lock)
    def _drop_spilled_locked(self, session: str) -> None:
        """Retire one spilled record: decrement its host refcounts,
        freeing (and unmapping) only the host blocks that hit zero."""
        sp = self._spilled.pop(session, None)
        if sp is not None:
            self._host_unref_locked(sp.hblocks)

    # fablint: lock-held(_lock)
    def _host_unref_locked(self, hblocks) -> None:
        dead = []
        for h in hblocks:
            h = int(h)
            r = self._host_refs.get(h, 1) - 1
            if r <= 0:
                self._host_refs.pop(h, None)
                dead.append(h)
            else:
                self._host_refs[h] = r
        if dead:
            dead_set = set(dead)
            # a freed host block's device->host mapping is stale: a
            # later demote must never alias a recycled host slot
            for b in [b for b, h in self._spill_map.items()
                      if h in dead_set]:
                del self._spill_map[b]
            self._host_free.extend(dead)
            self._host_free.sort(reverse=True)

    def _maybe_restore(self, session: str) -> None:
        """Fault a spilled session back in if (and only if) it is
        host-resident — the cheap pre-check every lookup surface
        calls before taking its own locked path."""
        with self._lock:
            if session in self._tables or session not in self._spilled:
                return
        self._restore(session)

    def _restore(self, session: str) -> Optional[_KvSession]:
        """Bring a spilled session back to the device tier, riding the
        SAME reserve / fill-outside-the-lock / commit shape as
        ``load_into``: device blocks reserved under the lock (evicting
        or demoting others under the session's own priority), the
        host→device copy and reduction-arena rebuild run OUTSIDE it
        (the restore holds its own host refcounts so a concurrent drop
        of the record cannot free the bytes mid-copy), and the commit
        re-checks under a relock.  The chained CRC recorded at demote
        is recomputed from the HOST bytes during the copy: any
        mismatch aborts the restore and the session degrades to a
        typed "corrupt" re-prefill shed — wrong bytes are never
        published.  Returns None when the restore could not happen
        (device saturation, lost race, IO fault) — the caller sheds."""
        o = self.options
        bt, bpt = o.block_tokens, o.bytes_per_token
        t0 = time.perf_counter_ns()
        while True:
            with self._lock:
                s = self._tables.get(session)
                if s is not None:
                    return s
                sp = self._spilled.get(session)
                if sp is None:
                    return None
                if session not in self._restoring:
                    self._restoring.add(session)
                    try:
                        blocks, _ = self._reserve_locked(
                            session, len(sp.hblocks), sp.priority)
                    except PoolSaturated:
                        # no device room even after pressure: the
                        # session STAYS spilled (retryable shed), the
                        # host copy intact
                        self._restoring.discard(session)
                        return None
                    for h in sp.hblocks:
                        self._host_refs[int(h)] += 1
                    _ledger.acquire("kv.reserve",
                                    (id(self), id(blocks)))
                    fault = self._spill_fault
                    break
            # another thread is restoring this session: wait it out
            time.sleep(0.0005)
        # ---- outside the lock: reserved rows have exactly one writer,
        # and our extra host refs pin the source bytes.  The copy sits
        # inside a try: an allocator failure mid-copy must still drop
        # the host refs and return the reservation (ISSUE 20), and
        # EVERY outcome resolves through the one declared custody exit,
        # _finish_restore_locked
        ok = True
        try:
            io_fail = fault == "restore"
            if not io_fail:
                chain = 0
                for k in range(len(blocks)):
                    data = self._host_store[int(sp.hblocks[k])]
                    chain = zlib.crc32(data, chain)
                    if chain != sp.crcs[k]:
                        ok = False
                        break
                    b = int(blocks[k])
                    self._store[b] = data
                    self._pos_sums[b] = self._store[b].reshape(
                        bt, bpt).sum(axis=1, dtype=np.int64)
            now = self._now()
        except BaseException:
            with self._lock:
                self._finish_restore_locked(session, sp, blocks, t0,
                                            ok=False, io_fail=False,
                                            now=None, failed=True)
            raise
        with self._lock:
            return self._finish_restore_locked(session, sp, blocks, t0,
                                               ok=ok, io_fail=io_fail,
                                               now=now)

    # fablint: lock-held(_lock)
    def _finish_restore_locked(self, session: str, sp, blocks, t0, *,
                               ok: bool, io_fail: bool,
                               now: Optional[float],
                               failed: bool = False):
        """The restore's single custody-resolution point, declared as
        the release of BOTH the device reservation and the restore's
        host refs: exactly one of commit / return-blocks / close-race
        custody-end happens here, under one lock hold."""
        _ledger.release("kv.reserve", (id(self), id(blocks)))
        self._restoring.discard(session)
        if self._closed:
            # close() rebuilt the free list and cleared the host
            # tier — nothing left to return or unref
            return None
        self._host_unref_locked(sp.hblocks)
        if failed:
            # the outside-the-lock copy RAISED (allocator pressure /
            # test hook): host record intact, reservation returns, the
            # exception propagates to the caller
            self._return_blocks_locked(blocks)
            return None
        if io_fail:
            # transport failed, host bytes presumed intact: keep
            # the record, latch the plane, shed
            self._return_blocks_locked(blocks)
            self._spill_health.mark_down("restore_io")
            return None
        if not ok:
            # byte verification failed: the host copy is corrupt —
            # drop it and degrade to a typed re-prefill, NOT a
            # plane event (corruption is not plane death)
            self._return_blocks_locked(blocks)
            if self._spilled.get(session) is sp:
                self._drop_spilled_locked(session)
            self._recent_evicted[session] = "corrupt"
            while len(self._recent_evicted) > 256:
                self._recent_evicted.pop(
                    next(iter(self._recent_evicted)))
            self.restore_corrupt << 1
            return None
        cur = self._tables.get(session)
        if cur is not None:
            # a re-prefill committed fresh bytes mid-restore: the
            # fresh load wins, our copy aborts
            self._return_blocks_locked(blocks)
            return cur
        if self._spilled.get(session) is not sp:
            # the record was released/expired/reclaimed mid-copy
            self._return_blocks_locked(blocks)
            return None
        s = _KvSession(session, sp.tenant, sp.priority, sp.seq_len,
                       sp.last_token, sp.acc, blocks, now)
        # same commit as a load: prefix dedupe means the FIRST
        # restored co-owner re-registers the shared blocks and
        # every later restore maps onto them — one physical copy
        # restores N sessions
        self._commit_locked(s, None)
        self._drop_spilled_locked(session)
        self.restores << 1
        self._restore_us.append(
            (time.perf_counter_ns() - t0) // 1000)
        return s

    def spill(self, session: str) -> bool:
        """Demote one session to the host tier NOW — the autoscaler's
        drain surface (scale-down demotes its live sessions instead of
        killing them).  A pinned session refuses with
        :class:`SessionBusy` (it is being read); False when the
        session is unknown or the host tier cannot take it."""
        with self._lock:
            s = self._tables.get(session)
            if s is None:
                return False
            if s.pinned:
                raise SessionBusy(session)
            if not self._spill_usable_locked():
                return False
            return self._demote_session_locked(s)

    def spilled_sessions(self) -> List[str]:
        with self._lock:
            return list(self._spilled)

    def inject_spill_fault(self, mode: Optional[str]) -> None:
        """Chaos hook: ``"demote"`` fails every demote attempt,
        ``"restore"`` fails every restore copy (both latch the spill
        plane down), ``None`` heals."""
        if mode not in (None, "demote", "restore"):
            raise ValueError(f"unknown spill fault {mode!r}")
        with self._lock:
            self._spill_fault = mode

    def release(self, session: str) -> bool:
        """Session finished: return its blocks (the decode-complete
        path).  Idempotent.  A PINNED session is not freed NOW — a pin
        means a roster entry or a zero-copy snapshot view is still
        reading these blocks, and freeing them would hand the bytes to
        the next loader mid-read — but the release is ACCEPTED and
        deferred to the last unpin (a race between a completion's
        release and a concurrent reader's pin window must not leak the
        blocks forever).  Every in-tree completion path unpins before
        releasing, so the deferral only fires on genuine races."""
        with self._lock:
            s = self._tables.get(session)
            if s is None:
                sp = self._spilled.get(session)
                if sp is not None:
                    # released while parked in the host tier: drop the
                    # record directly, no restore round trip.  An
                    # in-flight restore survives the drop (it holds
                    # its own host refs for the copy) and its commit
                    # re-check observes the record identity changed,
                    # aborting into "released" instead of publishing
                    self._drop_spilled_locked(session)
                    self._count("released", sp.tenant)
                    return True
                return False
            if s.pinned:
                s.release_pending = True
                return True
            self._free_session_locked(s, "released")
            return True

    # ---- mutation / CoW -------------------------------------------------
    def write_rows(self, session: str, start_token: int,
                   rows: np.ndarray) -> int:
        """Overwrite token rows of a LIVE session in place — the CoW
        mutation surface (ISSUE 16).  A target block whose refcount is
        > 1 is SPLIT first: a private copy is allocated (evicting under
        the session's own priority if the free list is empty), the
        shared original keeps its other owners untouched, and the
        session publishes a NEW blocks array (roster snapshots holding
        the old array keep reading the old — still valid — physical
        blocks).  A private block that is REGISTERED as a prefix donor
        is unregistered before the overwrite so no later load shares
        its stale hash.  Returns the number of CoW splits performed.
        Callers must not write under their own outstanding
        ``snapshot(view=True)`` read — the same discipline the roster
        pin documents."""
        o = self.options
        bt, bpt = o.block_tokens, o.bytes_per_token
        rows = np.ascontiguousarray(rows, dtype=np.uint8)
        if rows.ndim != 2 or rows.shape[1] != bpt:
            raise ValueError(
                f"rows must be (n, {bpt}), got {rows.shape}")
        n = rows.shape[0]
        if n <= 0:
            raise ValueError("rows must hold at least one token")
        now = self._now()
        self._maybe_restore(session)
        with self._lock:
            s = self._tables.get(session)
            if s is None or s.release_pending:
                raise KeyError(session)
            if start_token < 0 or start_token + n > s.seq_len:
                raise ValueError(
                    f"write [{start_token}, {start_token + n}) outside "
                    f"session of {s.seq_len} tokens")
            first_b = start_token // bt
            last_b = (start_token + n - 1) // bt
            new_blocks = None
            splits = 0
            for k in range(first_b, last_b + 1):
                blk = int(s.blocks[k] if new_blocks is None
                          else new_blocks[k])
                if self._refs.get(blk, 1) > 1 and not self._free:
                    # a split needs a free block: evict — NEVER the
                    # session being written (unpinned + a stale
                    # last_used would otherwise make it the likely
                    # LRU pick, and freeing it mid-write mutates a
                    # zombie over blocks back on the free list)
                    victims = self._pick_victims_locked(
                        1, s.priority, exclude=s)
                    if victims is None:
                        raise PoolSaturated(1, 0)
                    for v in victims:
                        self._free_session_locked(v, "pressure")
                if self._refs.get(blk, 1) > 1:
                    # CoW split: other sessions own these bytes too.
                    # RE-CHECKED after any eviction — taking the last
                    # co-owner drops the refcount to 1 and the block
                    # is already private; splitting then would strand
                    # it at refcount 0, off both the free list and
                    # every table
                    nb = self._free.pop()
                    self._store[nb] = self._store[blk]
                    self._pos_sums[nb] = self._pos_sums[blk]
                    self._refs[blk] -= 1
                    self._refs[nb] = 1
                    if new_blocks is None:
                        new_blocks = s.blocks.copy()
                    new_blocks[k] = nb
                    splits += 1
                    self.cow_splits << 1
                else:
                    # private — but a registered donor's content is
                    # about to change: drop it from the index, and
                    # drop any host copy mapped to the OLD bytes so a
                    # later demote re-copies instead of aliasing stale
                    # content
                    self._unregister_block_locked(blk)
                    self._spill_map.pop(blk, None)
            if new_blocks is not None:
                s.blocks = new_blocks
                s.contiguous = bool((np.diff(new_blocks) == 1).all())
            acc_delta = 0
            for k in range(first_b, last_b + 1):
                blk = int(s.blocks[k])
                t0 = max(start_token, k * bt)
                t1 = min(start_token + n, (k + 1) * bt)
                src = rows[t0 - start_token:t1 - start_token]
                sl0 = t0 - k * bt
                self._store[blk].reshape(bt, bpt)[
                    sl0:sl0 + (t1 - t0)] = src
                new_sums = src.sum(axis=1, dtype=self._sum_dtype)
                old = self._pos_sums[blk, sl0:sl0 + (t1 - t0)]
                acc_delta += (int(new_sums.sum(dtype=np.int64))
                              - int(old.sum(dtype=np.int64)))
                self._pos_sums[blk, sl0:sl0 + (t1 - t0)] = new_sums
            s.acc += acc_delta
            s.last_used = now
            return splits

    # ---- lookup / scheduler surface -----------------------------------
    def get(self, session: str) -> Optional[_KvSession]:
        with self._lock:
            s = self._tables.get(session)
            if s is not None or session not in self._spilled:
                return s
        # host-resident: fault it back in (the scheduler's roster add
        # and every read surface restore transparently)
        return self._restore(session)

    def evicted_reason(self, session: str) -> Optional[str]:
        """Why a recently-missing session is gone ("pressure" /
        "expired" / "corrupt"), so the RPC layer sheds with a typed
        re-prefill hint instead of an unknown-session error.  A
        session still PARKED in the host tier answers "spilled": its
        restore just failed transiently (device saturation / spill
        plane down) and a retry may succeed without a re-prefill."""
        with self._lock:
            if session in self._spilled:
                return "spilled"
            return self._recent_evicted.get(session)

    def touch(self, session: str) -> None:
        now = self._now()
        with self._lock:
            s = self._tables.get(session)
            if s is not None:
                s.last_used = now
            else:
                sp = self._spilled.get(session)
                if sp is not None:
                    # keep-alive reaches the host tier too — touch is
                    # deliberately NOT a restore trigger
                    sp.last_used = now

    def pin(self, session: str) -> bool:
        """Fence a session against eviction/expiry (step-roster entry
        or snapshot view; counted — pins nest).  False when the session
        is gone — including LOGICALLY gone: a deferred release
        (``release_pending``) means the pool already reported this
        session released, so no NEW reader may pin it while the last
        old reader drains.  A host-resident session is RESTORED first:
        a pin is a read-intent, and reads happen on the device tier."""
        self._maybe_restore(session)
        with self._lock:
            s = self._tables.get(session)
            if s is None or s.release_pending:
                return False
            s.pinned += 1
            _ledger.acquire("kv.pin", (id(self), session))
            return True

    def unpin(self, session: str) -> None:
        now = self._now()
        unbalanced = False
        with self._lock:
            s = self._tables.get(session)
            if s is not None:
                if s.pinned:
                    s.pinned -= 1
                    _ledger.release("kv.pin", (id(self), session),
                                    strict=True)
                else:
                    # an unpin nobody holds: swallowing it silently
                    # would let the NEXT unpin steal a live holder's
                    # fence (eviction under a reader's view) — scream
                    unbalanced = True
                s.last_used = now
                if not s.pinned and s.release_pending:
                    # a release arrived during the pin window: the last
                    # reader out frees the blocks
                    self._free_session_locked(s, "released")
        if unbalanced:
            from ..butil import logging as log
            log.error("kv pool: unbalanced unpin of session %r "
                      "(no pin held) — caller bug", session)

    def materialize(self, session: str) -> Optional[np.ndarray]:
        """COPY a session's token rows back out, ``(seq_len,
        bytes_per_token)`` — the byte-exactness tests' surface.  The
        read-only SYNC path should use ``snapshot(view=True)`` instead
        (the ISSUE-15 bugfix: a contiguous-extent session reads as a
        zero-copy pinned view, no reshape copy) — that surface returns
        an explicit ``is_view`` flag so the caller knows whether an
        unpin is owed; this one stays copy-only exactly so no caller
        can lose that flag."""
        snap = self.snapshot(session)
        return snap[0] if snap is not None else None

    def snapshot(self, session: str, *, view: bool = False):
        """``(rows, seq_len, last_token)`` under ONE lock acquisition —
        the sync decode path's atomic read (a separate get() +
        materialize() pair could straddle an eviction and pair the old
        entry's metadata with the new entry's bytes).

        ``view=True`` returns ``(rows, seq_len, last_token, is_view)``:
        when the session's blocks are one contiguous ascending extent,
        ``rows`` is a READ-ONLY view straight into the arena (no copy)
        and the session is PINNED — the caller MUST ``unpin(session)``
        when done reading, BEFORE any release.  The read-only flag is
        what keeps a view over PREFIX-SHARED blocks safe: no reader can
        scribble on bytes other sessions gather through.  Non-contiguous
        sessions (or pools under a straddle risk the caller can't
        fence) keep the copy, ``is_view=False``, no pin owed — the copy
        is what makes a concurrent eviction safe there, so it stays."""
        o = self.options
        self._maybe_restore(session)
        with self._lock:
            s = self._tables.get(session)
            if s is None or s.release_pending:
                # a deferred release means "already released" to every
                # NEW reader — only the pinned old readers drain it
                return None
            blocks = s.blocks
            if view and s.contiguous:
                b0 = int(blocks[0])
                rows = self._store[b0:b0 + len(blocks)].reshape(
                    -1, o.bytes_per_token)[:s.seq_len]
                rows.flags.writeable = False   # read-only: arena intact
                # fablint: custody-moved(caller) the view pin is owed back through the caller's unpin before any release — the documented view=True contract
                s.pinned += 1
                _ledger.acquire("kv.pin", (id(self), session))
                return rows, s.seq_len, s.last_token, True
            rows = self._store[blocks].reshape(
                -1, o.bytes_per_token)[:s.seq_len].copy()
            if view:
                return rows, s.seq_len, s.last_token, False
            return rows, s.seq_len, s.last_token

    # ---- expiry ---------------------------------------------------------
    # fablint: lock-held(_lock)
    def _schedule_sweep_locked(self) -> None:
        if (not self.options.use_timers or self._closed
                or self._sweep_timer is not None or not self._tables):
            return
        from ..bthread.timer_thread import TimerThread
        self._sweep_timer = TimerThread.instance().schedule_after(
            self._sweep, self.options.effective_sweep_s())

    def _sweep(self) -> None:
        """TimerThread callback: reclaim idle sessions past TTL — the
        traffic-independent expiry the ISSUE-14 bugfix demands."""
        with self._lock:
            self._sweep_timer = None
        self.expire_idle()
        with self._lock:
            self._schedule_sweep_locked()

    def expire_idle(self, now: Optional[float] = None) -> int:
        """Reclaim every unpinned session idle past ``ttl_s``.  Returns
        the count (also the manual surface for ``use_timers=False``
        tests)."""
        now = self._now() if now is None else now
        ttl = self.options.ttl_s
        n = 0
        with self._lock:
            for s in list(self._tables.values()):
                if not s.pinned and now - s.last_used > ttl:
                    self._free_session_locked(s, "expired")
                    n += 1
            for sess, sp in list(self._spilled.items()):
                # spilled sessions age out on the same TTL — an idle
                # host tier must not park bytes forever either
                if sess not in self._restoring \
                        and now - sp.last_used > ttl:
                    self._drop_spilled_locked(sess)
                    self._recent_evicted[sess] = "expired"
                    while len(self._recent_evicted) > 256:
                        self._recent_evicted.pop(
                            next(iter(self._recent_evicted)))
                    self.expirations << 1
                    self._count("evicted_expired", sp.tenant)
                    n += 1
        return n

    # ---- lifecycle / observability --------------------------------------
    def sessions(self) -> int:
        with self._lock:
            return len(self._tables)

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            timer = self._sweep_timer
            self._sweep_timer = None
            self._tables.clear()
            self._refs.clear()
            self._prefix_index.clear()
            self._block_hash.clear()
            self._free = list(range(self.options.num_blocks - 1, -1, -1))
            self._spilled.clear()
            self._host_refs.clear()
            self._spill_map.clear()
            self._restoring.clear()
            self._host_free = list(
                range(self.options.host_blocks - 1, -1, -1))
        # custody ends with the pool: the free-list rebuild reclaimed
        # every block, outstanding pins die with the tables
        _ledger.drop_prefix("kv.pin", id(self))
        _ledger.drop_prefix("kv.reserve", id(self))
        if timer is not None:
            from ..bthread.timer_thread import TimerThread
            TimerThread.instance().unschedule(timer)

    def describe(self) -> dict:
        """The /status serving block's pool half."""
        o = self.options
        with self._lock:
            free = len(self._free)
            sessions = len(self._tables)
            pinned = sum(1 for s in self._tables.values() if s.pinned)
            per_tenant: Dict[str, int] = {}
            logical = 0
            for s in self._tables.values():
                key = s.tenant or "shared"
                per_tenant[key] = per_tenant.get(key, 0) + len(s.blocks)
                logical += len(s.blocks)
            shared = sum(1 for r in self._refs.values() if r > 1)
            physical = len(self._refs)
            host_free = len(self._host_free)
            spilled_sessions = len(self._spilled)
            spilled_blocks = len(self._host_refs)
            restore_us = sorted(self._restore_us)
            plane = (self._spill_health.snapshot()
                     if self._spill_health is not None else None)
        with self._counters_lock:
            by_class = {f"{what}[{tenant or 'shared'}]": a.get_value()
                        for (what, tenant), a in self._counters.items()}
        used = o.num_blocks - free
        return {
            "blocks_total": o.num_blocks,
            "blocks_free": free,
            "blocks_used": used,
            "block_tokens": o.block_tokens,
            "utilization": round(used / o.num_blocks, 3),
            "sessions": sessions,
            "pinned": pinned,
            "blocks_by_tenant": per_tenant,
            "loads": self.loads.get_value(),
            "bytes_in": self.bytes_in.get_value(),
            "evictions": self.evictions.get_value(),
            "expired": self.expirations.get_value(),
            "fill_aborts": self.fill_aborts.get_value(),
            "by_tenant": by_class,
            "ttl_s": o.ttl_s,
            # ISSUE 16: prefix-sharing / concurrent-fill truth —
            # logical blocks are session-table entries, physical are
            # distinct live blocks; the ratio is the capacity win
            "prefix": {
                "enabled": bool(_flags.get_flag(
                    "serving_kv_prefix_share")),
                "concurrent_fill": bool(_flags.get_flag(
                    "serving_kv_concurrent_fill")),
                "shared_blocks": shared,
                "prefix_hits": self.prefix_hits.get_value(),
                "cow_splits": self.cow_splits.get_value(),
                "commit_races": self.commit_races.get_value(),
                "locked_fills": self.locked_fills.get_value(),
                "unlocked_fills": self.unlocked_fills.get_value(),
                "logical_blocks": logical,
                "physical_blocks": physical,
                "sharing_ratio": (round(logical / physical, 3)
                                  if physical else 1.0),
            },
            # ISSUE 19: tiered-memory truth — resident vs host-parked
            # sessions, demote/restore round trips, restore latency,
            # and the spill plane-health row.  "migration" is the
            # PROCESS-WIDE pool-to-pool transfer ledger (the counters
            # live in serving/migration.py)
            "tiers": self._describe_tiers(
                sessions, host_free, spilled_sessions, spilled_blocks,
                restore_us, plane),
        }

    def _describe_tiers(self, resident: int, host_free: int,
                        spilled_sessions: int, spilled_blocks: int,
                        restore_us: List[int], plane) -> dict:
        o = self.options
        out = {
            "enabled": (o.host_blocks > 0
                        and bool(_flags.get_flag("serving_kv_spill"))),
            "host_blocks_total": o.host_blocks,
            "host_blocks_free": host_free,
            "resident_sessions": resident,
            "spilled_sessions": spilled_sessions,
            "spilled_blocks": spilled_blocks,
            "demotions": self.demotions.get_value(),
            "restores": self.restores.get_value(),
            "restore_corrupt": self.restore_corrupt.get_value(),
            "host_evictions": self.host_evictions.get_value(),
            "restore_p50_us": (restore_us[len(restore_us) // 2]
                               if restore_us else 0),
        }
        if plane is not None:
            out["plane"] = plane
        try:
            from . import migration as _migration
            out["migration"] = {**_migration.migration_stats(),
                                "scope": "process"}
        except Exception:   # pragma: no cover - import cycles only
            pass
        return out
