"""Device-completion waits: the scheduler⇄XLA bridge.

This is the new primitive SURVEY.md §2.3 calls for: the reference's
``bthread_fd_wait`` (src/bthread/fd.cpp) runs one EpollThread that maps fd
readiness → butex wakes so bthreads block on IO without pinning workers.
The TPU analogue maps *device-stream completion* → butex wakes: tasklets
enqueue XLA work (a jitted transport step, a collective, a D2H copy), then
either block on or register a callback for its completion.

Design point that makes this correct without an epoll equivalent: XLA
completes work on a device's stream in enqueue (FIFO) order, so ONE poller
thread per device, blocking on the *oldest* outstanding array of that
device, observes every completion in order — the exact multiplexing
EpollThread provides for fds, with the stream standing in for the epoll set.
"""
from __future__ import annotations

import collections
import threading
from typing import Any, Callable, Deque, Dict, Optional, Tuple

from .butex import Butex
from ..butil import layer_span as _span


class _DevicePoller:
    def __init__(self, device_key: str):
        self.key = device_key
        # entries: (arrays, on_ready, the submitter's layer mark or None)
        self.queue: Deque[Tuple[Any, Callable[[], None], Any]] = \
            collections.deque()
        self.cv = threading.Condition()
        # fablint: thread-quiesced(process-lifetime CQ poller parked on its condvar; owns no native state at exit)
        self.thread = threading.Thread(
            target=self._run, name=f"device_poller_{device_key}", daemon=True)
        self.completed_count = 0
        self.failed_count = 0
        self.thread.start()

    def submit(self, arrays: Any, on_ready: Callable[[], None]) -> None:
        with self.cv:
            # layer span brpc.poller.queue (butil/layer_span.py): the wait
            # behind older entries, from here to the popleft; n = its depth
            mark = _span.layer_mark(len(self.queue)) \
                if _span.layer_on() else None
            self.queue.append((arrays, on_ready, mark))
            self.cv.notify()

    def _run(self) -> None:
        import jax
        while True:
            with self.cv:
                while not self.queue:
                    self.cv.wait()
                arrays, on_ready, mark = self.queue.popleft()
            ls = None
            if mark is not None:
                _span.layer_waited("brpc.poller.queue", mark)
                ls = _span.layer_begin("brpc.poller.block", mark=mark)
            try:
                jax.block_until_ready(arrays)
            except Exception as e:
                # the callback still fires (a waiter must not hang), but
                # a device program that failed is never silent: logged
                # and counted, and the waiter's own access raises it
                self.failed_count += 1
                from ..butil import logging as log
                log.error("device poller %s: block_until_ready failed: "
                          "%s: %s", self.key, type(e).__name__, e)
            self.completed_count += 1
            if ls is not None:
                ls.end()
            if mark is not None:
                ls = _span.layer_begin("brpc.poller.callback", mark=mark)
            try:
                on_ready()
            except Exception:
                from ..butil import logging as log
                log.error("device completion callback raised", exc_info=True)
            if ls is not None:
                ls.end()


class DeviceEventDispatcher:
    """Per-device completion pollers (the EventDispatcher of the device
    plane)."""

    _instance: Optional["DeviceEventDispatcher"] = None
    _lock = threading.Lock()

    def __init__(self):
        self._pollers: Dict[str, _DevicePoller] = {}
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
        """Invoke callback once every array in the pytree is computed."""
        self._poller_for(arrays).submit(arrays, callback)

    def wait(self, arrays: Any, timeout: Optional[float] = None) -> int:
        """Block the calling tasklet until the arrays are ready (the
        bthread_fd_wait analogue).  Returns 0 or ETIMEDOUT."""
        done = Butex(0)
        self.on_ready(arrays, lambda: done.wake_all_and_set(1))
        return done.wait(0, timeout)

    def stats(self) -> Dict[str, int]:
        with self._plock:
            return {k: p.completed_count for k, p in self._pollers.items()}

    def failures(self) -> int:
        """Completions whose device work raised (all pollers)."""
        with self._plock:
            return sum(p.failed_count for p in self._pollers.values())


def device_wait(arrays: Any, timeout: Optional[float] = None) -> int:
    return DeviceEventDispatcher.instance().wait(arrays, timeout)


def device_on_ready(arrays: Any, callback: Callable[[], None]) -> None:
    DeviceEventDispatcher.instance().on_ready(arrays, callback)


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
