"""M:N tasklet scheduler: TaskControl + per-worker TaskGroups with stealing.

Reference: src/bthread/task_control.{h,cpp} + task_group.{h,cpp}.  The
reference multiplexes bthreads over N pthread workers with per-worker
work-stealing deques, a remote queue for submissions from non-workers, and
ParkingLot futexes for idle-worker signaling; ``start_urgent`` runs the new
bthread immediately for cache locality (task_group.cpp:361) while
``start_background`` queues it (task_group.cpp:420).

TPU-native translation: tasklets are Python callables carried by a worker
pool.  CPython cannot switch stacks, so "urgent" maps to LIFO dispatch on the
submitting worker's own deque (next thing it or a thief runs) and blocking
primitives park the carrying worker, with *compensation*: whenever every
worker is blocked inside a butex and runnable work exists, the pool grows one
worker (bounded), preserving the reference's core liveness property that a
blocked request never wedges unrelated requests (docs/en/io.md tail-latency
doctrine).  The hard-latency datapath belongs to the C++ core (native/),
which implements real fibers; this scheduler is the orchestration layer
driving it and the JAX control plane.
"""
from __future__ import annotations

import collections
import threading
from typing import Any, Callable, Deque, Dict, List, Optional

from ..butil.resource_pool import ResourcePool
from ..butil import debug_sync as _dbg
from ..butil import flags as _flags
from .butex import Butex

_flags.define_flag("bthread_concurrency", 4,
                   "number of scheduler worker threads",
                   _flags.positive_integer)
_flags.define_flag("bthread_max_concurrency", 64,
                   "cap on compensated workers", _flags.positive_integer)


class Tasklet:
    __slots__ = ("fn", "args", "kwargs", "result", "exception", "done_butex",
                 "tid", "name", "local_storage")

    def __init__(self, fn: Callable, args: tuple, kwargs: dict,
                 name: Optional[str] = None):
        self.fn = fn
        self.args = args
        self.kwargs = kwargs
        self.result: Any = None
        self.exception: Optional[BaseException] = None
        self.done_butex = Butex(0)
        self.tid = 0
        self.name = name
        self.local_storage: Dict[str, Any] = {}   # bthread-local (key.cpp)


_tls = threading.local()


class TaskGroup:
    """Per-worker run queue (work_stealing_queue.h + remote_task_queue.h)."""

    # fablint guarded-state contract: the deque is popped by its owner
    # and stolen from by every other worker
    _GUARDED_BY = {"deque": "lock"}

    def __init__(self, control: "TaskControl", index: int):
        self.control = control
        self.index = index
        self.deque: Deque[Tasklet] = collections.deque()
        self.lock = _dbg.make_lock("TaskGroup.lock")
        self.steal_count = 0

    def push_urgent(self, t: Tasklet) -> None:
        with self.lock:
            self.deque.appendleft(t)

    def push_background(self, t: Tasklet) -> None:
        with self.lock:
            self.deque.append(t)

    def pop_local(self) -> Optional[Tasklet]:
        with self.lock:
            return self.deque.popleft() if self.deque else None

    def steal(self) -> Optional[Tasklet]:
        """Victims are stolen from the tail (FIFO side), reference
        WorkStealingQueue::steal."""
        with self.lock:
            return self.deque.pop() if self.deque else None


class TaskControl:
    _instance: Optional["TaskControl"] = None
    _instance_lock = threading.Lock()

    # fablint guarded-state contract: the ParkingLot condvar doubles as
    # the pending-signal lock (reference ParkingLot semantics)
    _GUARDED_BY = {
        "_blocked_workers": "_blocked_lock",
        "tasklet_count": "_count_lock",
        "_pending_signal": "_parking",
        "_parked": "_parking",
    }

    def __init__(self, concurrency: Optional[int] = None):
        self.concurrency = concurrency or _flags.get_flag("bthread_concurrency")
        self.groups: List[TaskGroup] = []
        self.pool: ResourcePool = ResourcePool()
        self._parking = threading.Condition()     # ParkingLot
        self._pending_signal = 0
        self._parked = 0                          # workers asleep in it
        self._idle_sources: List[Callable[[], bool]] = []
        self._workers: List[threading.Thread] = []
        self._blocked_workers = 0
        self._blocked_lock = _dbg.make_lock("TaskControl._blocked_lock")
        self._stop = False
        self._next_victim = 0
        self.tasklet_count = 0
        self._count_lock = _dbg.make_lock("TaskControl._count_lock")
        for i in range(self.concurrency):
            self._add_worker(i)

    @classmethod
    def instance(cls) -> "TaskControl":
        with cls._instance_lock:
            if cls._instance is None:
                cls._instance = TaskControl()
            return cls._instance

    # -- workers -------------------------------------------------------
    def _add_worker(self, index: int) -> None:
        g = TaskGroup(self, index)
        self.groups.append(g)
        # fablint: thread-quiesced(process-lifetime M:N worker pool; parks on the ParkingLot condvar with a 0.5s timeout)
        t = threading.Thread(target=self._worker_main, args=(g,),
                             name=f"bthread_worker_{index}", daemon=True)
        self._workers.append(t)
        t.start()

    def _worker_main(self, group: TaskGroup) -> None:
        _tls.group = group
        while not self._stop:
            task = group.pop_local() or self._steal_task(group)
            if task is None:
                if self._serve_idle():
                    continue
                with self._parking:
                    if self._pending_signal > 0:
                        self._pending_signal -= 1
                        continue
                    self._parked += 1
                    try:
                        self._parking.wait(timeout=0.5)
                    finally:
                        self._parked -= 1
                continue
            self._run_task(task)

    # -- idle sources: work below every tasklet -------------------------
    def add_idle_source(self, source: Callable[[], bool]) -> None:
        """``source()`` is called by a worker that found no tasklet to run,
        before it parks; it does one piece of work and returns True, or
        returns False when it has none (the device waiter's parked
        completions).  It may block.  Whoever gives a source work wakes a
        worker for it (``wake_one``) where none would come by."""
        self._idle_sources.append(source)

    def _serve_idle(self) -> bool:
        for source in self._idle_sources:
            try:
                if source():
                    return True
            except Exception:
                from ..butil import logging as log
                log.error("scheduler idle source raised", exc_info=True)
        return False

    def wake_one(self) -> None:
        """One parked worker looks for work again; with none parked, the
        next that would park does."""
        with self._parking:
            self._pending_signal += 1
            self._parking.notify()

    def others_parked(self) -> bool:
        """Called on a worker: does every other worker sleep?"""
        with self._parking:
            return self._parked >= len(self._workers) - 1

    def _steal_task(self, thief: TaskGroup) -> Optional[Tasklet]:
        n = len(self.groups)
        start = self._next_victim
        self._next_victim = (start + 1) % max(n, 1)
        for i in range(n):
            victim = self.groups[(start + i) % n]
            if victim is thief:
                continue
            t = victim.steal()
            if t is not None:
                thief.steal_count += 1
                return t
        return None

    def _run_task(self, task: Tasklet) -> None:
        _tls.current = task
        try:
            task.result = task.fn(*task.args, **task.kwargs)
        except BaseException as e:  # noqa: BLE001 — reported via join
            task.exception = e
        finally:
            _tls.current = None
            task.done_butex.wake_all_and_set(1)
            self.pool.return_resource(task.tid)
            with self._count_lock:
                self.tasklet_count -= 1

    # -- submission (signal_task / steal_task of the reference) --------
    def submit(self, task: Tasklet, urgent: bool) -> int:
        task.tid = self.pool.get_resource(task)
        with self._count_lock:
            self.tasklet_count += 1
        group: Optional[TaskGroup] = getattr(_tls, "group", None)
        if group is not None:
            (group.push_urgent if urgent else group.push_background)(task)
        else:
            # remote submission: round-robin a group's FIFO side
            victim = self.groups[task.tid % len(self.groups)]
            victim.push_background(task)
        self.wake_one()
        self._maybe_compensate()
        return task.tid

    # -- blocked-worker compensation ----------------------------------
    def note_blocked(self) -> None:
        with self._blocked_lock:
            self._blocked_workers += 1
        self._maybe_compensate()

    def note_unblocked(self) -> None:
        with self._blocked_lock:
            self._blocked_workers -= 1

    def _maybe_compensate(self) -> None:
        with self._blocked_lock:
            blocked = self._blocked_workers
        runnable = any(g.deque for g in self.groups)
        if (runnable and blocked >= len(self._workers)
                and len(self._workers) < _flags.get_flag("bthread_max_concurrency")):
            self._add_worker(len(self.groups))

    # -- introspection -------------------------------------------------
    def worker_count(self) -> int:
        return len(self._workers)

    def address(self, tid: int) -> Optional[Tasklet]:
        return self.pool.address(tid)


# ---- module-level API (the bthread_* C functions) ---------------------

def start_urgent(fn: Callable, *args, name: Optional[str] = None, **kwargs) -> int:
    """bthread_start_urgent: scheduled LIFO so it runs next."""
    return TaskControl.instance().submit(Tasklet(fn, args, kwargs, name), True)


def start_background(fn: Callable, *args, name: Optional[str] = None, **kwargs) -> int:
    """bthread_start_background: scheduled FIFO."""
    return TaskControl.instance().submit(Tasklet(fn, args, kwargs, name), False)


def join(tid: int, timeout: Optional[float] = None):
    """bthread_join: wait for completion, return the tasklet's result.
    Raises the tasklet's exception if it failed."""
    ctl = TaskControl.instance()
    task = ctl.address(tid)
    if task is None:
        return None       # already finished & reclaimed
    rc = task.done_butex.wait(0, timeout)
    if rc == 110:  # ETIMEDOUT
        raise TimeoutError(f"join({tid}) timed out")
    if task.exception is not None:
        raise task.exception
    return task.result


def self_id() -> int:
    cur = getattr(_tls, "current", None)
    return cur.tid if cur is not None else 0


def current_tasklet() -> Optional[Tasklet]:
    return getattr(_tls, "current", None)


def in_worker() -> bool:
    return getattr(_tls, "group", None) is not None


def note_worker_blocked() -> None:
    if in_worker():
        TaskControl.instance().note_blocked()


def note_worker_unblocked() -> None:
    if in_worker():
        TaskControl.instance().note_unblocked()


def yield_tasklet() -> None:
    """bthread_yield: give other runnables a chance (a hint here)."""
    import time
    time.sleep(0)


# ---- bthread-local storage (reference key.cpp) ------------------------

def local_set(key: str, value: Any) -> None:
    cur = current_tasklet()
    store = cur.local_storage if cur is not None else _thread_fallback_store()
    store[key] = value


def local_get(key: str, default: Any = None) -> Any:
    cur = current_tasklet()
    store = cur.local_storage if cur is not None else _thread_fallback_store()
    return store.get(key, default)


def _thread_fallback_store() -> Dict[str, Any]:
    s = getattr(_tls, "fallback_store", None)
    if s is None:
        s = {}
        _tls.fallback_store = s
    return s
