"""Device-completion waits: the scheduler⇄XLA bridge.

This is the new primitive SURVEY.md §2.3 calls for: the reference's
``bthread_fd_wait`` (src/bthread/fd.cpp) runs one EpollThread that maps fd
readiness → butex wakes so bthreads block on IO without pinning workers,
and never runs user code.  The TPU analogue maps *device-stream completion*
→ butex wakes: tasklets enqueue XLA work (a jitted transport step, a
collective, a D2H copy), then either block on or register a callback for its
completion.

Design point that makes this correct without an epoll equivalent: XLA
completes work on a device's stream in enqueue (FIFO) order, so ONE poller
thread per device, blocking on the *oldest* outstanding array of that
device, observes every completion in order — the exact multiplexing
EpollThread provides for fds, with the stream standing in for the epoll set.
Nobody but a poller thread waits for a device.

Two entry points with two contracts; nothing looks at who called:

* ``DeviceEventDispatcher.on_ready(arrays, cb)`` — the program's own
  completions (a delivery gate, a send pin, a ring credit, a butex wake).
  ``cb`` runs on the device's poller thread, in submit order, like a CQ
  callback on the CQ thread: it must not block, because every later
  completion of that device waits behind it.
* ``device_on_ready(arrays, cb)`` — user code (a handler's completion).  It
  never runs on a poller thread.  The entry is parked; a scheduler worker
  that has run out of tasklets takes the oldest parked entry and runs
  ``cb``, which may block (a device-to-host read, a lock); callbacks of
  different entries run concurrently, on at most half of the workers, and
  in any order.  While workers are running no thread is woken for a
  completion: on a host where every hand-over of the interpreter lock
  costs tens of microseconds, that is what the completion path can save
  (PERF.md §6, PR 27).  An entry that is not computed at its turn goes to
  its device's poller, which waits for it in that device's order and gives
  it back, so a slow device holds no worker.  And the workers are the fast
  way, not the only one: an entry that none has taken within ``_OVERDUE_S``
  (they all run tasklets, or wait — perhaps for this callback) is served by
  the backstop thread, one after another as the poller did before PR 27.

Either way an entry whose arrays all answer ``is_ready()`` at its turn is
not blocked on: ``block_until_ready`` gives the interpreter lock away and
has to get it back, for nothing.  It is also what raises a failed program,
and a failed program may report ready: then the callback's own access to
the array raises it, and a callback that raises is logged and counted in
``failures()`` like a wait that did.  A callback that does not read its
arrays (a delivery gate) passes them on, and whoever reads them raises.
(Blocking on the spared arrays later, 32 in one call, was measured: keeping
them that long cost the cell 4 % — PERF.md §6, PR 27.)
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

from . import scheduler
from .butex import Butex
from ..butil import layer_span as _span

# a parked completion older than this is the backstop thread's: well above
# what a busy server makes one wait for a worker (milliseconds), well below
# what a caller takes for a hang
_OVERDUE_S = 0.02


def _all_ready(arrays: Any) -> bool:
    import jax
    return all(hasattr(x, "is_ready") and x.is_ready()
               for x in jax.tree_util.tree_leaves(arrays))


def _block(arrays: Any, who: str) -> bool:
    """Wait for the arrays; False when the device program failed."""
    import jax
    try:
        jax.block_until_ready(arrays)
        return True
    except Exception as e:
        # the callback still fires (a waiter must not hang), but a
        # device program that failed is never silent: logged and
        # counted, and the waiter's own access raises it
        from ..butil import logging as log
        log.error("device completion (%s): block_until_ready failed: "
                  "%s: %s", who, type(e).__name__, e)
        return False


def _turn(mark: Any, wait_for: Any, who: str) -> bool:
    """An entry's turn, on whichever thread has it; False when the device
    program failed.  Layer spans (butil/layer_span.py), caused by the
    submitter's span: brpc.poller.queue from the submit to here (n =
    entries ahead of it then) and brpc.poller.block around the wait for
    ``wait_for``, or around nothing where it is None: the wait was
    spared."""
    ls = None
    if mark is not None:
        _span.layer_waited("brpc.poller.queue", mark)
        ls = _span.layer_begin("brpc.poller.block", mark=mark)
    ok = wait_for is None or _block(wait_for, who)
    if ls is not None:
        ls.end()
    return ok


def _call(on_ready: Callable[[], None], mark: Any) -> bool:
    """Run the callback; False when it raised.  Layer span
    brpc.poller.callback, caused by the submitter's span, so that what the
    callback starts (a response's encode and write) keeps that cause."""
    ls = _span.layer_begin("brpc.poller.callback", mark=mark, cpu=True) \
        if mark is not None else None
    ok = True
    try:
        on_ready()
    except Exception:
        from ..butil import logging as log
        log.error("device completion callback raised", exc_info=True)
        ok = False
    if ls is not None:
        ls.end()
    return ok


def _sleep_until_put(cv: threading.Condition, queue: Deque,
                     asleep: List[bool]) -> None:
    """A serving thread with an empty queue.  It says that it sleeps
    BEFORE it looks at the queue for the last time, and ``_wake_if_asleep``
    looks after the put: either this sees the entry or that sees it asleep."""
    with cv:
        asleep[0] = True
        if not queue:
            cv.wait()
        asleep[0] = False


def _wake_if_asleep(cv: threading.Condition, asleep: List[bool]) -> None:
    if asleep[0]:
        with cv:
            cv.notify()


class _DevicePoller:
    """One device's poller thread: the program's own completions, inline
    and in submit order."""

    def __init__(self, device_key: str):
        self.key = device_key
        # entries: (arrays, on_ready, the submitter's layer mark or None)
        self.queue: Deque[Tuple[Any, Callable[[], None], Any]] = \
            collections.deque()
        self.cv = threading.Condition()
        self.asleep = [False]
        self.completed_count = 0        # the poller thread's own
        self.failed_count = 0
        # fablint: thread-quiesced(process-lifetime CQ poller parked on its condvar; owns no native state at exit)
        self.thread = threading.Thread(
            target=self._run, name=f"device_poller_{device_key}", daemon=True)
        self.thread.start()

    def submit(self, arrays: Any, on_ready: Callable[[], None],
               traced: bool = True) -> None:
        mark = _span.layer_mark(len(self.queue)) \
            if traced and _span.layer_on() else None
        self.queue.append((arrays, on_ready, mark))
        _wake_if_asleep(self.cv, self.asleep)

    def _run(self) -> None:
        queue = self.queue
        while True:
            try:
                # deque.popleft is atomic: the condition variable is taken
                # only to sleep, never between two entries that are there
                arrays, on_ready, mark = queue.popleft()
            except IndexError:
                _sleep_until_put(self.cv, queue, self.asleep)
                continue
            if not _turn(mark, None if _all_ready(arrays) else arrays,
                         self.key):
                self.failed_count += 1
            self.completed_count += 1
            if not _call(on_ready, mark):
                self.failed_count += 1


class _ParkedCompletions:
    """``device_on_ready``'s side: completions of user code, parked until a
    scheduler worker takes them (``serve_one`` is an idle source of the
    scheduler: the work of a worker that found no tasklet) or, overdue,
    the backstop thread does."""

    # fablint guarded-state contract
    _GUARDED_BY = {"serving": "lock", "completed_count": "lock",
                   "failed_count": "lock"}

    def __init__(self, dispatcher: "DeviceEventDispatcher"):
        self.dispatcher = dispatcher
        # entries: (arrays, on_ready, mark, parked at, a poller has waited)
        self.queue: Deque[Tuple[Any, Callable[[], None], Any, float,
                                bool]] = collections.deque()
        self.lock = threading.Lock()
        self.serving = 0
        self.completed_count = 0
        self.failed_count = 0
        self.control = scheduler.TaskControl.instance()
        # handlers first: they are what keeps the device fed and what a
        # new request waits for, so completions may hold half of the
        # workers and no more.  Half is fitted, not derived: of 1 to 4 of
        # four workers it is the most that kept ``local_compute_1m``'s
        # p95 inside its bound (PERF.md §6, PR 27)
        self.limit = max(1, self.control.concurrency // 2)
        self.control.add_idle_source(self.serve_one)
        self.cv = threading.Condition()
        self.asleep = [False]
        # fablint: thread-quiesced(process-lifetime backstop parked on its condvar; owns no native state at exit)
        threading.Thread(target=self._backstop, daemon=True,
                         name="device_completion_backstop").start()

    def park(self, arrays: Any, on_ready: Callable[[], None]) -> None:
        mark = _span.layer_mark(len(self.queue)) \
            if _span.layer_on() else None
        self._put((arrays, on_ready, mark, time.monotonic(), False),
                  self.queue.append)

    def _put(self, entry: tuple, put: Callable[[tuple], None]) -> None:
        put(entry)
        # a worker that is running comes by when it runs out of tasklets,
        # this one included: nobody is woken then.  Only where no worker
        # would come by soon (the caller is none, or all others sleep
        # while this one's tasklet goes on) one is woken for it.  What
        # this rule misses waits for the backstop.
        if not scheduler.in_worker() or self.control.others_parked():
            self.control.wake_one()
        _wake_if_asleep(self.cv, self.asleep)

    def serve_one(self) -> bool:
        with self.lock:
            if self.serving >= self.limit:
                return False
            try:
                entry = self.queue.popleft()
            except IndexError:          # none, or the backstop has it
                return False
            self.serving += 1
        try:
            self._serve(entry)
        finally:
            with self.lock:
                self.serving -= 1
        return True

    def _serve(self, entry: tuple) -> None:
        arrays, on_ready, mark, at, waited = entry
        if not waited and not _all_ready(arrays):
            # the device's poller waits for it, in that device's order,
            # and gives it back at the head of the queue
            back = (arrays, on_ready, mark, at, True)
            self.dispatcher._poller_for(arrays).submit(
                arrays, lambda: self._put(back, self.queue.appendleft),
                traced=False)
            return
        _turn(mark, None, "parked")
        with self.lock:
            self.completed_count += 1
        if not _call(on_ready, mark):
            with self.lock:
                self.failed_count += 1

    def _backstop(self) -> None:
        queue = self.queue
        while True:
            try:
                due = queue[0][3] + _OVERDUE_S - time.monotonic()
            except IndexError:
                _sleep_until_put(self.cv, queue, self.asleep)
                continue
            if due > 0:
                time.sleep(due)
                continue
            try:
                # the oldest there is now, should a worker have taken the
                # one that was looked at
                entry = queue.popleft()
            except IndexError:
                continue
            self._serve(entry)


class DeviceEventDispatcher:
    """Per-device completion pollers (the EventDispatcher of the device
    plane) and the parked completions of user code."""

    _instance: Optional["DeviceEventDispatcher"] = None
    _lock = threading.Lock()

    def __init__(self):
        self._pollers: Dict[str, _DevicePoller] = {}
        self._parked: Optional[_ParkedCompletions] = None
        self._plock = threading.Lock()

    @classmethod
    def instance(cls) -> "DeviceEventDispatcher":
        with cls._lock:
            if cls._instance is None:
                cls._instance = DeviceEventDispatcher()
            return cls._instance

    def _poller_for(self, arrays: Any) -> _DevicePoller:
        key = self._device_key(arrays)
        with self._plock:
            p = self._pollers.get(key)
            if p is None:
                p = _DevicePoller(key)
                self._pollers[key] = p
            return p

    @staticmethod
    def _device_key(arrays: Any) -> str:
        import jax
        leaves = jax.tree_util.tree_leaves(arrays)
        for leaf in leaves:
            devs = getattr(leaf, "devices", None)
            if devs is not None:
                try:
                    return ",".join(sorted(str(d) for d in leaf.devices()))
                except Exception:
                    pass
        return "host"

    def on_ready(self, arrays: Any, callback: Callable[[], None]) -> None:
        """Invoke callback once every array in the pytree is computed, ON
        THE DEVICE'S POLLER THREAD and in submit order.  For the program's
        own completions: like a CQ callback on the CQ thread it must not
        block — every later completion of that device waits behind it.
        User code goes through ``device_on_ready``."""
        self._poller_for(arrays).submit(arrays, callback)

    def on_ready_parked(self, arrays: Any,
                        callback: Callable[[], None]) -> None:
        """``device_on_ready``'s body: callback runs off the poller
        threads and may block."""
        parked = self._parked
        if parked is None:
            with self._plock:
                if self._parked is None:
                    self._parked = _ParkedCompletions(self)
                parked = self._parked
        parked.park(arrays, callback)

    def wait(self, arrays: Any, timeout: Optional[float] = None) -> int:
        """Block the calling tasklet until the arrays are ready (the
        bthread_fd_wait analogue).  Returns 0 or ETIMEDOUT."""
        done = Butex(0)
        self.on_ready(arrays, lambda: done.wake_all_and_set(1))
        return done.wait(0, timeout)

    def stats(self) -> Dict[str, int]:
        """Completions served, by poller (a device's key) and, under
        ``"user"``, those of user code."""
        with self._plock:
            out = {k: p.completed_count for k, p in self._pollers.items()}
            if self._parked is not None:
                out["user"] = self._parked.completed_count
            return out

    def failures(self) -> int:
        """Waits for the device that raised, and callbacks that did (which
        is how a failed program shows whose arrays reported ready)."""
        with self._plock:
            return sum(p.failed_count for p in self._pollers.values()) \
                + (self._parked.failed_count if self._parked else 0)

    def handoffs(self) -> int:
        """Completions of user code, served off the poller threads: the
        ``device_on_ready`` kind."""
        with self._plock:
            return self._parked.completed_count if self._parked else 0


def device_wait(arrays: Any, timeout: Optional[float] = None) -> int:
    return DeviceEventDispatcher.instance().wait(arrays, timeout)


def device_on_ready(arrays: Any, callback: Callable[[], None]) -> None:
    """Run ``callback`` once, after every array in the pytree is computed —
    also when the device program failed (the failure is logged and counted,
    and the callback's own access to the array raises it).

    The callback is user code: it runs on a scheduler worker (or, overdue,
    on the backstop thread), never on a poller thread, so it may block.
    Callbacks of different calls may run concurrently and in any order."""
    DeviceEventDispatcher.instance().on_ready_parked(arrays, callback)


class DeviceCompletion:
    """One-shot completion record — the CQ-entry of the device plane.

    An RDMA work request completes exactly once, with a status; waiters
    either block (``wait``, butex-parked so an M:N worker yields instead
    of spinning) or register callbacks (``add_done_callback``, the
    CQ-polling analogue).  Used by ici/device_plane.py transfers; generic
    enough for any post/poll device-side operation."""

    __slots__ = ("_butex", "_lock", "_cbs", "_done", "error")

    def __init__(self):
        self._butex = Butex(0)
        self._lock = threading.Lock()
        self._cbs: list = []
        self._done = False
        self.error = 0

    def signal(self, error: int = 0) -> bool:
        """Complete with ``error`` (0 = success).  Exactly-once: a second
        signal is a no-op returning False.  Callbacks run on the signaling
        thread (the device poller), like CQ callbacks run on the CQ
        thread — they must not block."""
        with self._lock:
            if self._done:
                return False
            self._done = True
            self.error = error
            cbs, self._cbs = self._cbs, []
        self._butex.wake_all_and_set(1)
        for cb in cbs:
            try:
                cb(error)
            except Exception:
                from ..butil import logging as log
                log.error("device completion callback raised", exc_info=True)
        return True

    def poll(self) -> bool:
        with self._lock:
            return self._done

    def add_done_callback(self, cb: Callable[[int], None]) -> None:
        """cb(error) once complete; fires immediately (on the caller's
        thread) when already done."""
        with self._lock:
            if not self._done:
                self._cbs.append(cb)
                return
            err = self.error
        cb(err)

    def wait(self, timeout: Optional[float] = None) -> int:
        """Block until complete.  Returns the completion's error code, or
        ETIMEDOUT (110) when the timeout expires first."""
        while True:
            with self._lock:
                if self._done:
                    return self.error
            if self._butex.wait(0, timeout) == 110:   # ETIMEDOUT
                with self._lock:
                    return self.error if self._done else 110
