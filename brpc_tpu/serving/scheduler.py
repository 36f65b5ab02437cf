"""Continuous-batching decode scheduler: ONE batched step per tick over
the active session set, sessions admitted and retired BETWEEN steps.

The serving subsystem's execution loop (ROADMAP item 3).  The old
example decoded one-session-per-RPC — every token paid a full RPC and a
full cache walk, and concurrent sessions serialized behind each other.
Here decode is a step loop:

  * **per-step admit/evict** — before every step the scheduler admits
    pending sessions into the roster (strict priority-band order, the
    PR-9 bands) up to ``max_batch``, retires sessions that produced
    their requested tokens, fails queued sessions whose deadline budget
    died waiting, and — when an INTERACTIVE session is pending and the
    roster is full of batch-band work — PREEMPTS the most sheddable
    active session (its progress is preserved; it resumes from its next
    token when a slot frees, bit-exact);
  * **one batched program per step** — the whole roster advances one
    token with one vectorized gather through the paged pool's block
    tables into the per-token reduction arena (``pos_sums_flat``) plus
    a handful of elementwise ops: numpy by default (the 1-core host's
    fastest dispatch), or ONE jit-compiled XLA program per
    (batch, table-width) bucket under ``serving_compiled_step`` — the
    shape a TPU pod runs, parity-pinned against the numpy step;
  * **pins** — every rostered session is pinned in the pool for exactly
    the steps it spends in the roster, so the eviction policy can never
    pull a block table out from under the running program.

Completion callbacks (``emit``/``fail``) run ON the step thread: on
every call plane completion is a response enqueue, never a blocking
write, and the deterministic ordering is what the bit-exactness tests
pin.  The loop thread starts lazily on first submit and parks on its
condvar when idle; ``stop()`` fails everything queued and joins it.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

import numpy as np

from .. import bvar
from ..butil import flags as _flags
from ..rpc import errors
from .kv_pool import PagedKvPool

_flags.define_flag(
    "serving_compiled_step", False,
    "run the continuous-batching decode step as ONE jit-compiled XLA "
    "program per (batch, table-width) bucket instead of the numpy "
    "vector step (parity-pinned; numpy dispatches faster on 1-core "
    "CPU hosts, the compiled program is the TPU-pod shape)")


@dataclass
class BatchSchedulerOptions:
    vocab: int                       # the decode recurrence's modulus
    max_batch: int = 64
    bands: int = 4
    default_priority: int = 2
    # bands <= this are "interactive": they may preempt batch-band
    # sessions out of a full roster (progress preserved)
    interactive_priority_max: int = 1
    preempt: bool = True
    # False: no step thread — tests drive step_once() deterministically
    auto_start: bool = True


class StepRequest:
    """One decode request: produce ``steps`` tokens for ``session``.

    Mutable progress (``prev``/``stepi``/``tokens``) lives here so a
    preempted session resumes exactly where it stopped.  ``emit(tokens)``
    / ``fail(code, text, retry_after_ms)`` fire exactly once, on the
    step thread."""

    __slots__ = ("session", "steps", "priority", "tenant", "deadline_us",
                 "emit", "fail", "enq_us", "prev", "stepi", "tokens",
                 "kv", "_done")

    def __init__(self, session: str, steps: int,
                 emit: Callable[[List[int]], None],
                 fail: Callable[[int, str, int], None],
                 priority: Optional[int] = None, tenant: str = "",
                 deadline_us: Optional[int] = None):
        self.session = session
        self.steps = steps
        self.priority = priority
        self.tenant = tenant
        self.deadline_us = deadline_us
        self.emit = emit
        self.fail = fail
        self.enq_us = 0
        self.prev = 0                # resumes carry the live recurrence
        self.stepi = 0
        self.tokens: List[int] = []
        self.kv = None               # _KvSession while rostered
        self._done = False


class ContinuousBatchScheduler:
    """Admit → step → retire, forever.  One per decode worker."""

    _GUARDED_BY = {
        "_pending": "_cv",
        "_active": "_cv",
        "_owned": "_cv",
        "_stopping": "_cv",
        "_thread": "_cv",
    }

    def __init__(self, pool: PagedKvPool,
                 options: BatchSchedulerOptions,
                 now_us: Optional[Callable[[], int]] = None):
        self.pool = pool
        self.options = options
        self._now_us = now_us or (lambda: time.monotonic_ns() // 1000)
        self._cv = threading.Condition()
        self._pending: List[deque] = [deque()
                                      for _ in range(options.bands)]
        self._active: List[StepRequest] = []     # roster, admit order
        # sessions currently owned by the scheduler (pending OR
        # rostered).  A duplicate submit — a retry storm re-issuing a
        # Decode whose first copy is still running — is REFUSED here:
        # two roster entries on one session would let the first
        # completion release the pool blocks the second still gathers
        # through (another tenant's bytes after block reuse)
        self._owned: set = set()
        self._stopping = False
        self._thread: Optional[threading.Thread] = None
        # roster numeric arrays (step-thread-owned; rebuilt when the
        # roster changes membership)
        self._dirty = True
        self._tbl = self._seq = self._acc = None
        self._prev = self._stepi = self._rows = None
        self._jit_cache: Dict[int, Callable] = {}
        # counters / gauges
        self.steps = bvar.Adder("serving_steps")
        self.tokens_out = bvar.Adder("serving_tokens")
        self.admitted = bvar.Adder("serving_admitted")
        self.retired = bvar.Adder("serving_retired")
        self.preempted = bvar.Adder("serving_preempted")
        self.expired = bvar.Adder("serving_deadline_expired")
        self.rejected = bvar.Adder("serving_rejected")
        self.occupancy = bvar.IntRecorder("serving_batch_occupancy")
        self._rate_lock = threading.Lock()
        self._rate_ema = 0.0         # steps/s EMA
        self._last_step_us = 0

    # ---- submission -----------------------------------------------------
    def submit(self, req: StepRequest) -> None:
        """Queue one decode request.  Admission happens at the next step
        boundary; refusal paths fire ``req.fail`` (on this thread when
        the scheduler is stopping, on the step thread otherwise)."""
        pri = self.options.default_priority if req.priority is None \
            else req.priority
        pri = min(max(pri, 0), self.options.bands - 1)
        req.priority = pri
        req.enq_us = self._now_us()
        duplicate = False
        with self._cv:
            if self._stopping:
                stopped = True
            elif req.session in self._owned:
                stopped = False
                duplicate = True
            else:
                stopped = False
                self._owned.add(req.session)
                self._pending[pri].append(req)
                if self.options.auto_start and self._thread is None:
                    # fablint: thread-quiesced(stop() sets _stopping and notifies; the loop fails leftovers and exits, stop() joins)
                    t = threading.Thread(target=self._run,
                                         name="serving_step_loop",
                                         daemon=True)
                    self._thread = t
                    t.start()
                self._cv.notify()
        if stopped:
            self.rejected << 1
            self._safe_fail(req, errors.ELOGOFF,
                            "decode scheduler stopping", 0)
        elif duplicate:
            self.rejected << 1
            self._safe_fail(req, errors.EREQUEST,
                            f"session {req.session!r} is already "
                            "decoding (duplicate submit refused)", 0)

    # ---- the loop -------------------------------------------------------
    def _run(self) -> None:
        while True:
            with self._cv:
                while (not self._stopping
                       and not self._active
                       and not any(self._pending)):
                    self._cv.wait()
                if self._stopping:
                    victims = self._drain_locked()
                    break
            try:
                self.step_once()
            except Exception as e:
                # one bad roster must not wedge the worker forever:
                # fail the CURRENT roster (the failing entry is in it)
                # and keep the loop alive for the pending queue
                from ..butil import logging as log
                log.error("serving: batched step failed", exc_info=True)
                with self._cv:
                    crashed = self._active
                    self._active = []
                    for req in crashed:
                        self._owned.discard(req.session)
                    self._dirty = True
                for req in crashed:
                    self.pool.unpin(req.session)
                    self._safe_fail(
                        req, errors.EINTERNAL,
                        f"batched decode step failed: "
                        f"{type(e).__name__}: {e}", 0)
        for req, (code, text) in victims:
            self._safe_fail(req, code, text, 0)

    # fablint: lock-held(_cv)
    def _drain_locked(self):
        victims = []
        for band in self._pending:
            while band:
                victims.append((band.popleft(),
                                (errors.ELOGOFF,
                                 "decode scheduler stopping")))
        for req in self._active:
            self.pool.unpin(req.session)
            victims.append((req, (errors.ELOGOFF,
                                  "decode scheduler stopping")))
        self._active = []
        self._owned.clear()
        self._dirty = True
        return victims

    def step_once(self) -> int:
        """Admit/evict at the boundary, then run ONE batched step over
        the roster.  Returns the roster size stepped (0 = idle).  The
        test surface for ``auto_start=False`` schedulers; the loop
        thread calls exactly this."""
        admit_events = []
        with self._cv:
            admit_events = self._admit_locked()
            for req, _code, _text, _hint in admit_events:
                self._owned.discard(req.session)
            roster = list(self._active)
        # refusal callbacks fire outside the lock, in decision order
        for req, code, text, hint in admit_events:
            self._safe_fail(req, code, text, hint)
        if not roster:
            return 0
        self._step_roster(roster)
        # retire finished sessions at the step boundary
        finished = [r for r in roster if len(r.tokens) >= r.steps]
        if finished:
            with self._cv:
                for req in finished:
                    if req in self._active:
                        self._active.remove(req)
                    self._owned.discard(req.session)
                self._dirty = True
            for req in finished:
                self.pool.unpin(req.session)
                self.retired << 1
                req._done = True
                self._safe_emit(req)
        self.steps << 1
        self.occupancy << len(roster)
        now = self._now_us()
        with self._rate_lock:
            if self._last_step_us:
                dt = max(now - self._last_step_us, 1)
                inst = 1e6 / dt
                self._rate_ema = (inst if self._rate_ema == 0.0
                                  else 0.98 * self._rate_ema
                                  + 0.02 * inst)
            self._last_step_us = now
        return len(roster)

    # fablint: lock-held(_cv)
    def _admit_locked(self):
        """Fill the roster from the band queues (strict priority order),
        expire dead deadlines, preempt batch work for interactive
        arrivals.  Returns [(req, code, text, retry_after)] refusals to
        fire outside the lock."""
        o = self.options
        refusals = []
        now = self._now_us()
        for band in self._pending:
            kept = None
            while band:
                req = band.popleft()
                if req.deadline_us is not None and now >= req.deadline_us:
                    self.expired << 1
                    refusals.append((req, errors.ERPCTIMEDOUT,
                                     "decode deadline expired in batch "
                                     "queue", 0))
                    continue
                if len(self._active) >= o.max_batch:
                    kept = req
                    break
                code_text = self._roster_add(req)
                if code_text is not None:
                    refusals.append((req, *code_text))
            if kept is not None:
                band.appendleft(kept)
                break
        # preemption: an interactive arrival blocked by a full roster
        # bumps the most sheddable batch session (progress preserved)
        if o.preempt:
            while (len(self._active) >= o.max_batch
                   and self._interactive_waiting_locked()):
                victim = self._pick_preempt_locked()
                if victim is None:
                    break
                self._active.remove(victim)
                self._dirty = True
                self.pool.unpin(victim.session)
                victim.kv = None
                self._pending[victim.priority].appendleft(victim)
                self.preempted << 1
                nxt = self._pop_interactive_locked(now, refusals)
                if nxt is None:
                    break
                code_text = self._roster_add(nxt)
                if code_text is not None:
                    refusals.append((nxt, *code_text))
        return refusals

    # fablint: lock-held(_cv)
    def _roster_add(self, req: StepRequest):
        """Pin + roster one admitted request; returns (code, text,
        hint) on refusal, None on success."""
        kv = self.pool.get(req.session)
        # fablint: custody-moved(decode-roster) the pin rides req into _active; every roster exit (completion, shed, deadline expiry, drain) unpins before dropping the request
        if kv is None or not self.pool.pin(req.session):
            reason = self.pool.evicted_reason(req.session)
            self.rejected << 1
            if reason is not None:
                return (errors.ELIMIT,
                        f"kv {reason}-evicted: re-prefill the session",
                        1)
            return (errors.EREQUEST,
                    f"unknown session {req.session!r}", 0)
        req.kv = kv
        if not req.tokens and req.stepi == 0:
            req.prev = kv.last_token          # fresh admit
        self._active.append(req)
        self._dirty = True
        self.admitted << 1
        return None

    # fablint: lock-held(_cv)
    def _interactive_waiting_locked(self) -> bool:
        mx = self.options.interactive_priority_max
        return any(self._pending[b] for b in range(mx + 1))

    # fablint: lock-held(_cv)
    def _pop_interactive_locked(self, now, refusals):
        mx = self.options.interactive_priority_max
        for b in range(mx + 1):
            while self._pending[b]:
                req = self._pending[b].popleft()
                if req.deadline_us is not None \
                        and now >= req.deadline_us:
                    self.expired << 1
                    refusals.append((req, errors.ERPCTIMEDOUT,
                                     "decode deadline expired in batch "
                                     "queue", 0))
                    continue
                return req
        return None

    # fablint: lock-held(_cv)
    def _pick_preempt_locked(self):
        mx = self.options.interactive_priority_max
        best = None
        for req in self._active:
            if req.priority <= mx:
                continue
            if best is None or (req.priority, req.enq_us) > \
                    (best.priority, best.enq_us):
                best = req
        return best

    # ---- the batched step ----------------------------------------------
    def _step_roster(self, roster: List[StepRequest]) -> None:
        bt = self.pool.options.block_tokens
        if self._dirty or self._tbl is None \
                or self._tbl.shape[0] != len(roster):
            self._build_arrays(roster)
            self._dirty = False
        if _flags.get_flag("serving_compiled_step"):
            prev = self._step_compiled(bt)
        else:
            prev = self._step_numpy(bt)
        self._prev = prev
        self._stepi += 1
        toks = prev.tolist()
        for k, req in enumerate(roster):
            req.tokens.append(toks[k])
            req.prev = toks[k]
            req.stepi += 1
        self.tokens_out << len(roster)

    def _build_arrays(self, roster: List[StepRequest]) -> None:
        # r.kv.blocks may be PREFIX-SHARED (ISSUE 16): two rostered
        # sessions with a common prefix gather through the SAME physical
        # block ids — correct by construction (the gather only reads),
        # and the roster pin on each session keeps every shared block's
        # refcount holder alive for the step's lifetime
        maxb = max(len(r.kv.blocks) for r in roster)
        tbl = np.zeros((len(roster), maxb), np.int64)
        for k, r in enumerate(roster):
            tbl[k, :len(r.kv.blocks)] = r.kv.blocks
        self._tbl = tbl
        self._seq = np.array([r.kv.seq_len for r in roster], np.int64)
        self._acc = np.array([r.kv.acc for r in roster], np.int64)
        self._prev = np.array([r.prev for r in roster], np.int64)
        self._stepi = np.array([r.stepi for r in roster], np.int64)
        self._rows = np.arange(len(roster))

    def _step_numpy(self, bt: int) -> np.ndarray:
        """The per-step decode recurrence over the whole roster — one
        gather through the block tables into the pool's reduction arena
        plus elementwise ops (matches the toy model's reference decode
        token for token)."""
        pos = (self._prev + self._stepi) % self._seq
        blk = self._tbl[self._rows, pos // bt]
        read = self.pool.pos_sums_flat[blk * bt + pos % bt]
        return (self._acc + read * (self._stepi + 1)
                + self._prev * 31) % self.options.vocab

    def _compiled_step_fn(self, bt: int):
        """The step as a jitted function of (pos_flat, tbl, seq, acc,
        prev, stepi) — shape-polymorphic until called, so one function
        serves every (batch-bucket, table-width-bucket)."""
        fn = self._jit_cache.get(bt)
        if fn is None:
            import jax
            import jax.numpy as jnp
            vocab = self.options.vocab

            def _step(pos_flat, tbl, seq, acc, prev, stepi):
                pos = (prev + stepi) % seq
                blk = jnp.take_along_axis(
                    tbl, (pos // bt)[:, None], axis=1)[:, 0]
                read = pos_flat[blk * bt + pos % bt]
                return (acc + read * (stepi + 1) + prev * 31) % vocab

            fn = self._jit_cache[bt] = jax.jit(_step)
        return fn

    def _step_compiled(self, bt: int) -> np.ndarray:
        """The same step as ONE jit-compiled XLA program; inputs are
        padded to (batch-bucket, table-width-bucket) so roster churn
        compiles a handful of programs, not one per shape."""
        b = len(self._rows)
        bpad = 1 << max(b - 1, 0).bit_length()
        wpad = 1 << max(self._tbl.shape[1] - 1, 0).bit_length()
        fn = self._compiled_step_fn(bt)

        def pad(a, n, fill=0):
            out = np.full((n,) + a.shape[1:], fill, a.dtype)
            out[:a.shape[0]] = a
            return out

        tblp = pad(self._tbl, bpad)
        if tblp.shape[1] < wpad:
            tblp = np.pad(tblp, ((0, 0), (0, wpad - tblp.shape[1])))
        out = fn(self.pool.pos_sums_flat, tblp,
                 pad(self._seq, bpad, 1), pad(self._acc, bpad),
                 pad(self._prev, bpad), pad(self._stepi, bpad))
        return np.asarray(out)[:b].astype(np.int64)

    # ---- completion plumbing -------------------------------------------
    def _safe_emit(self, req: StepRequest) -> None:
        try:
            req.emit(req.tokens)
        except Exception:
            from ..butil import logging as log
            log.error("serving: emit for session %s failed",
                      req.session, exc_info=True)

    def _safe_fail(self, req: StepRequest, code: int, text: str,
                   retry_after_ms: int) -> None:
        try:
            req.fail(code, text, retry_after_ms)
        except Exception:
            from ..butil import logging as log
            log.error("serving: fail for session %s failed",
                      req.session, exc_info=True)

    # ---- lifecycle / observability --------------------------------------
    def stop(self) -> None:
        """Fail everything queued/active and join the step thread."""
        with self._cv:
            self._stopping = True
            t = self._thread
            self._thread = None
            self._cv.notify_all()
        if t is not None and t is not threading.current_thread():
            t.join(5.0)
        else:
            # no loop thread (manual mode): drain here
            with self._cv:
                victims = self._drain_locked()
            for req, (code, text) in victims:
                self._safe_fail(req, code, text, 0)

    def owns(self, session: str) -> bool:
        """True while this scheduler holds the session (pending or
        rostered) — the migration fence: a session mid-decode must not
        cut over under its running batched step (ISSUE 19)."""
        with self._cv:
            return session in self._owned

    def queued(self) -> int:
        with self._cv:
            return sum(len(b) for b in self._pending)

    def active(self) -> int:
        with self._cv:
            return len(self._active)

    def step_rate(self) -> float:
        with self._rate_lock:
            return self._rate_ema

    def describe(self) -> dict:
        """The /status serving block's scheduler half."""
        with self._cv:
            active = len(self._active)
            pending = [len(b) for b in self._pending]
        return {
            "active": active,
            "pending_by_band": pending,
            "max_batch": self.options.max_batch,
            "steps": self.steps.get_value(),
            "step_rate_hz": round(self.step_rate(), 1),
            "tokens": self.tokens_out.get_value(),
            "batch_occupancy_avg": round(self.occupancy.average(), 2),
            "admitted": self.admitted.get_value(),
            "retired": self.retired.get_value(),
            "preempted": self.preempted.get_value(),
            "deadline_expired": self.expired.get_value(),
            "rejected": self.rejected.get_value(),
            "compiled_step": bool(
                _flags.get_flag("serving_compiled_step")),
        }
