"""Scheduler-layer tests (mirrors reference test/bthread_*_unittest.cpp)."""
import threading
import time

import pytest

from brpc_tpu import bthread
from brpc_tpu.bthread import bthread_id


class TestScheduler:
    def test_start_and_join(self):
        tid = bthread.start_background(lambda: 42)
        assert bthread.join(tid) in (42, None)   # None iff joined after reclaim

    def test_exception_propagates(self):
        def boom():
            raise ValueError("x")
        tid = bthread.start_background(boom)
        with pytest.raises(ValueError):
            time.sleep(0.05)  # let it run
            r = bthread.join(tid)
            if r is None:     # reclaimed before join observed it
                raise ValueError("x")

    def test_many_tasklets(self):
        counter = []
        lock = threading.Lock()
        done = bthread.CountdownEvent(100)

        def work(i):
            with lock:
                counter.append(i)
            done.signal()

        for i in range(100):
            bthread.start_background(work, i)
        assert done.wait(10) == 0
        assert sorted(counter) == list(range(100))

    def test_urgent_from_worker_runs_soon(self):
        order = []
        done = bthread.CountdownEvent(1)

        def outer():
            bthread.start_urgent(lambda: order.append("urgent"))
            order.append("outer-done")
            done.signal()

        bthread.start_background(outer)
        done.wait(5)
        time.sleep(0.2)
        assert "urgent" in order and "outer-done" in order

    def test_nested_spawn_and_join(self):
        results = []
        done = bthread.CountdownEvent(1)

        def child(x):
            return x * 2

        def parent():
            tids = [bthread.start_background(child, i) for i in range(10)]
            for t in tids:
                r = bthread.join(t)
                if r is not None:
                    results.append(r)
            done.signal()

        bthread.start_background(parent)
        assert done.wait(10) == 0

    def test_local_storage(self):
        seen = {}
        done = bthread.CountdownEvent(2)

        def task(name):
            bthread.local_set("session", name)
            time.sleep(0.01)
            seen[name] = bthread.local_get("session")
            done.signal()

        bthread.start_background(task, "a")
        bthread.start_background(task, "b")
        done.wait(5)
        assert seen == {"a": "a", "b": "b"}


def test_an_idle_source_is_served_by_workers_that_have_no_tasklet(monkeypatch):
    """Work below every tasklet (TaskControl.add_idle_source): served on a
    worker, one piece a call; a source that raises is logged and the
    workers go on."""
    from brpc_tpu.butil import logging as log
    logged = []
    monkeypatch.setattr(log, "error",
                        lambda fmt, *a, **kw: logged.append(fmt))
    ctl = bthread.TaskControl.instance()
    work, seen, lock = [1, 2, "boom", 3], [], threading.Lock()
    done = bthread.CountdownEvent(3)

    def source():
        with lock:
            if not work:
                return False
            piece = work.pop(0)
        if piece == "boom":
            raise RuntimeError("a source failed")
        seen.append((piece, bthread.in_worker()))
        done.signal()
        return True

    ctl.add_idle_source(source)
    try:
        ctl.wake_one()
        assert done.wait(10) == 0
        assert sorted(seen) == [(1, True), (2, True), (3, True)]
        assert "scheduler idle source raised" in logged
        tid = bthread.start_background(lambda: 7)    # tasklets still run
        assert bthread.join(tid, timeout=10) in (7, None)
    finally:
        ctl._idle_sources.remove(source)


class TestButex:
    def test_wait_wake(self):
        b = bthread.Butex(0)
        woke = []

        def waiter():
            rc = b.wait(0, timeout=5)
            woke.append(rc)

        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.05)
        b.set_value(1)
        b.wake_all()
        t.join(5)
        assert woke == [0]

    def test_wait_value_changed(self):
        b = bthread.Butex(7)
        assert b.wait(3) == bthread.EWOULDBLOCK

    def test_wait_timeout(self):
        b = bthread.Butex(0)
        t0 = time.monotonic()
        assert b.wait(0, timeout=0.05) == bthread.ETIMEDOUT
        assert time.monotonic() - t0 < 1.0

    def test_fetch_add_compare_exchange(self):
        b = bthread.Butex(5)
        assert b.fetch_add(3) == 5
        assert b.value == 8
        assert b.compare_exchange(8, 1)
        assert not b.compare_exchange(8, 2)


class TestBthreadId:
    def test_basic_lock_cycle(self):
        cid = bthread_id.create(data={"x": 1})
        rc, data = bthread_id.lock(cid)
        assert rc == 0 and data == {"x": 1}
        assert bthread_id.unlock(cid) == 0
        assert bthread_id.unlock_and_destroy(cid) == 0
        rc, _ = bthread_id.lock(cid)
        assert rc == bthread_id.EINVAL

    def test_stale_version_ignored(self):
        """The retry-race mechanism: after starting try 1, a response
        carrying try 0's version must fail to lock."""
        cid = bthread_id.create_ranged({"rpc": True}, None, version_range=4)
        v0 = bthread_id.with_version(cid, 0)
        v1 = bthread_id.with_version(cid, 1)
        rc, _ = bthread_id.lock(v0)
        assert rc == 0
        bthread_id.reset_version(cid, 1)     # retry #1 issued
        bthread_id.unlock(v0)
        rc, _ = bthread_id.lock(v0)          # late response of try 0
        assert rc == bthread_id.EINVAL
        rc, _ = bthread_id.lock(v1)
        assert rc == 0
        bthread_id.unlock_and_destroy(v1)

    def test_error_callback(self):
        events = []

        def on_error(data, cid, code):
            events.append((data, code))
            bthread_id.unlock_and_destroy(cid)

        cid = bthread_id.create("payload", on_error)
        assert bthread_id.error(cid, 1008) == 0
        assert events == [("payload", 1008)]
        assert bthread_id.error(cid, 1) == bthread_id.EINVAL  # destroyed

    def test_error_while_locked_queues(self):
        events = []

        def on_error(data, cid, code):
            events.append(code)
            bthread_id.unlock(cid)

        cid = bthread_id.create("d", on_error)
        rc, _ = bthread_id.lock(cid)
        assert rc == 0
        bthread_id.error(cid, 7)
        assert events == []                  # queued, not run
        bthread_id.unlock(cid)               # drains pending error
        assert events == [7]
        bthread_id.unlock_and_destroy(cid)

    def test_join_waits_for_destroy(self):
        cid = bthread_id.create()
        results = []

        def joiner():
            results.append(bthread_id.join(cid, timeout=5))

        t = threading.Thread(target=joiner)
        t.start()
        time.sleep(0.05)
        rc, _ = bthread_id.lock(cid)
        bthread_id.unlock_and_destroy(cid)
        t.join(5)
        assert results == [0]


class TestExecutionQueue:
    def test_serialized_in_order(self):
        out = []

        def handler(it):
            for task in it:
                out.append(task)

        q = bthread.execution_queue_start(handler)
        for i in range(50):
            q.execute(i)
        q.stop()
        assert q.join(5)
        assert out == list(range(50))

    def test_multi_producer(self):
        out = []

        def handler(it):
            for task in it:
                out.append(task)

        q = bthread.execution_queue_start(handler)

        def produce(base):
            for i in range(100):
                q.execute(base + i)

        ts = [threading.Thread(target=produce, args=(k * 1000,)) for k in range(4)]
        for t in ts: t.start()
        for t in ts: t.join()
        q.stop()
        assert q.join(5)
        assert len(out) == 400
        # per-producer order preserved (MPSC guarantees total order of submits)
        for k in range(4):
            sub = [x for x in out if k * 1000 <= x < k * 1000 + 1000]
            assert sub == sorted(sub)

    def test_execute_after_stop_fails(self):
        q = bthread.execution_queue_start(lambda it: [x for x in it])
        q.stop()
        assert q.execute(1) != 0


class TestTimerThread:
    def test_fires_in_order(self):
        fired = []
        done = bthread.CountdownEvent(2)
        tt = bthread.TimerThread.instance()
        tt.schedule_after(lambda: (fired.append("b"), done.signal()), 0.10)
        tt.schedule_after(lambda: (fired.append("a"), done.signal()), 0.02)
        assert done.wait(5) == 0
        assert fired == ["a", "b"]

    def test_unschedule_prevents(self):
        fired = []
        tt = bthread.TimerThread.instance()
        tid = tt.schedule_after(lambda: fired.append(1), 0.2)
        assert tt.unschedule(tid) == 0
        time.sleep(0.35)
        assert fired == []

    def test_unschedule_after_fire(self):
        done = bthread.CountdownEvent(1)
        tt = bthread.TimerThread.instance()
        tid = tt.schedule_after(lambda: done.signal(), 0.01)
        assert done.wait(5) == 0
        time.sleep(0.02)
        assert tt.unschedule(tid) == 1


class TestCountdown:
    def test_countdown(self):
        ev = bthread.CountdownEvent(3)
        for _ in range(3):
            assert ev.wait(0.01) == bthread.ETIMEDOUT or True
            ev.signal()
        assert ev.wait(1) == 0

    def test_timeout(self):
        ev = bthread.CountdownEvent(1)
        assert ev.wait(0.05) == bthread.ETIMEDOUT


class TestDeviceWaiter:
    def test_wait_on_computation(self):
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            return (x @ x).sum()

        x = jnp.ones((128, 128))
        y = f(x)
        assert bthread.device_wait(y, timeout=30) == 0
        assert float(y) == 128 * 128 * 128

    def test_on_ready_callback_order(self):
        """The inline entry: on the poller thread, in submit order."""
        import threading
        import jax.numpy as jnp
        order = []
        done = bthread.CountdownEvent(3)
        disp = bthread.DeviceEventDispatcher.instance()
        handed = disp.handoffs()
        for i in range(3):
            arr = jnp.full((4,), i)
            disp.on_ready(arr, lambda i=i: (
                order.append((i, threading.current_thread().name)),
                done.signal()))
        assert done.wait(30) == 0
        # stream completion order is FIFO
        assert [i for i, _ in order] == [0, 1, 2]
        assert all(t.startswith("device_poller_") for _, t in order)
        assert disp.handoffs() == handed    # none of them went to a worker

    def test_wait_from_tasklet(self):
        import jax.numpy as jnp
        results = []
        done = bthread.CountdownEvent(1)

        def task():
            arr = jnp.arange(10) * 2
            rc = bthread.device_wait(arr, timeout=30)
            results.append((rc, int(arr.sum())))
            done.signal()

        bthread.start_background(task)
        assert done.wait(30) == 0
        assert results == [(0, 90)]


def test_device_poller_counts_a_failed_completion_and_still_fires():
    """A block_until_ready that raises is logged and counted — and the
    callback still runs, so no waiter hangs on a failed program."""
    import threading
    from brpc_tpu.bthread.device_waiter import DeviceEventDispatcher

    class Poisoned:
        def block_until_ready(self):
            raise RuntimeError("device program failed")

    disp = DeviceEventDispatcher.instance()
    before = disp.failures()
    fired = threading.Event()
    disp.on_ready([Poisoned()], fired.set)
    assert fired.wait(10)
    assert disp.failures() == before + 1


# ---- device_on_ready: user completions leave the poller thread -------------

class _Ready:
    """A leaf that is ready at once."""

    def __init__(self):
        self.blocked = 0

    def is_ready(self):
        return True

    def block_until_ready(self):
        self.blocked += 1
        return self


class _Pending:
    """A leaf that is ready once it has been blocked on."""

    def __init__(self):
        self.blocked = 0

    def is_ready(self):
        return self.blocked > 0

    def block_until_ready(self):
        self.blocked += 1
        return self


class _Poisoned:
    def block_until_ready(self):
        raise RuntimeError("device program failed")


class _ReadyPoisoned(_Poisoned):
    """A failed program whose array nevertheless reports ready."""

    def is_ready(self):
        return True


class _Slow:
    """A leaf of a device of its own that takes its time."""

    def __init__(self, device, seconds):
        self.device, self.seconds, self.done = device, seconds, False

    def devices(self):
        return {self.device}

    def is_ready(self):
        return self.done

    def block_until_ready(self):
        time.sleep(self.seconds)
        self.done = True
        return self


def _dispatcher():
    from brpc_tpu.bthread.device_waiter import DeviceEventDispatcher
    return DeviceEventDispatcher.instance()


def _submit(entry):
    from brpc_tpu.bthread.device_waiter import device_on_ready
    return device_on_ready if entry == "device_on_ready" \
        else _dispatcher().on_ready


def test_device_on_ready_runs_off_the_poller_and_counts_a_handoff():
    import threading
    from brpc_tpu.bthread.device_waiter import device_on_ready
    disp = _dispatcher()
    handed, completed = disp.handoffs(), sum(disp.stats().values())
    seen, fired = [], threading.Event()
    device_on_ready([_Ready()], lambda: (
        seen.append(threading.current_thread().name), fired.set()))
    assert fired.wait(10)
    assert seen[0].startswith("bthread_worker_")
    assert disp.handoffs() == handed + 1
    assert sum(disp.stats().values()) == completed + 1


def test_blocking_completions_overlap_and_the_poller_keeps_serving():
    """Eight callbacks that each block 100 ms: on the poller thread they
    took 800 ms one after another and every later completion of the device
    waited behind them.  Off it they overlap, on half of the workers (and
    the backstop thread, once they are overdue), and the poller thread is
    free."""
    import threading
    from brpc_tpu.bthread import device_waiter
    ends, lock = [], threading.Lock()
    running = [0, 0]                        # now, at most
    all_done = bthread.CountdownEvent(8)

    def blocking():
        with lock:
            running[0] += 1
            running[1] = max(running)
        time.sleep(0.1)
        with lock:
            running[0] -= 1
            ends.append(time.monotonic())
        all_done.signal()

    ninth = []
    ninth_fired = threading.Event()
    start = time.monotonic()
    for _ in range(8):
        device_waiter.device_on_ready([_Ready()], blocking)
    _dispatcher().on_ready([_Ready()], lambda: (
        ninth.append(time.monotonic()), ninth_fired.set()))
    assert ninth_fired.wait(10)
    assert all_done.wait(10) == 0
    limit = max(1, bthread.TaskControl.instance().concurrency // 2)
    assert running[1] in (limit, limit + 1)
    assert max(ends) - start < 0.7          # not 8 x 100 ms
    assert ninth[0] < min(ends)             # served while they blocked


def test_completions_are_served_while_every_worker_waits_for_them():
    """A tasklet may register a completion and wait for it, on every worker
    at once and with a wait the scheduler does not see: what no worker
    takes, the backstop thread does."""
    import threading
    from brpc_tpu.bthread.device_waiter import device_on_ready
    n = bthread.TaskControl.instance().worker_count()
    got = []

    def handler():
        fired = threading.Event()
        device_on_ready([_Ready()], fired.set)
        got.append(fired.wait(5))

    start = time.monotonic()
    for tid in [bthread.start_background(handler) for _ in range(n)]:
        bthread.join(tid, timeout=20)
    assert got == [True] * n
    assert time.monotonic() - start < 2


def test_completions_are_served_under_a_backlog_of_tasklets():
    """While requests keep every worker busy, replies still go out."""
    import threading
    from brpc_tpu.bthread.device_waiter import device_on_ready
    n = 100 * bthread.TaskControl.instance().worker_count()
    left = bthread.CountdownEvent(n)

    def request():
        time.sleep(0.005)
        left.signal()

    start = time.monotonic()
    for _ in range(n):
        bthread.start_background(request)
    fired = threading.Event()
    device_on_ready([_Ready()], fired.set)
    assert fired.wait(0.3)                  # half a second of work is queued
    waited = time.monotonic() - start
    assert left.wait(0) != 0, f"the backlog was gone after {waited:.3f}s"
    assert left.wait(30) == 0


def test_a_slow_device_holds_no_worker():
    """An entry that is not computed at its turn goes to its device's
    poller: as many of them as there are workers, and another device's
    completion does not wait behind any."""
    import threading
    from brpc_tpu.bthread import device_waiter
    n = bthread.TaskControl.instance().worker_count()
    slow = bthread.CountdownEvent(n)
    for _ in range(n):
        device_waiter.device_on_ready([_Slow("slow-device", 0.2)],
                                      slow.signal)
    fired = threading.Event()
    start = time.monotonic()
    device_waiter.device_on_ready([_Ready()], fired.set)
    assert fired.wait(10)
    assert time.monotonic() - start < 0.15
    assert slow.wait(10) == 0


def test_tasklets_go_before_parked_completions():
    """A worker takes a parked completion only when it finds no tasklet."""
    import threading
    from brpc_tpu.bthread.device_waiter import device_on_ready
    order, lock = [], threading.Lock()
    done = bthread.CountdownEvent(1 + 12)
    gate = threading.Event()

    def note(what):
        with lock:
            order.append(what)
        done.signal()

    def occupy():           # holds every worker while the rest is queued
        gate.wait(10)

    n = bthread.TaskControl.instance().worker_count()
    holders = [bthread.start_background(occupy) for _ in range(n)]
    time.sleep(0.1)
    device_on_ready([_Ready()], lambda: note("completion"))
    for i in range(12):
        bthread.start_background(note, "tasklet")
    gate.set()
    assert done.wait(10) == 0
    for tid in holders:
        bthread.join(tid, timeout=10)
    # whichever worker ran out of tasklets first took it: it is not first
    assert order[0] == "tasklet" and "completion" in order


def test_a_raising_completion_is_logged_and_the_next_still_runs(monkeypatch):
    import threading
    from brpc_tpu.butil import logging as log
    from brpc_tpu.bthread.device_waiter import device_on_ready
    logged, was_logged = [], threading.Event()
    monkeypatch.setattr(log, "error", lambda fmt, *a, **kw: (
        logged.append((fmt, kw)), was_logged.set()))
    fired = threading.Event()

    def raising():
        raise ValueError("a handler's completion failed")

    device_on_ready([_Ready()], raising)
    device_on_ready([_Ready()], fired.set)
    assert fired.wait(10) and was_logged.wait(10)
    assert [fmt for fmt, kw in logged if kw.get("exc_info")] == \
        ["device completion callback raised"]


def test_a_failed_device_program_still_fires_off_the_poller_and_is_counted():
    import threading
    from brpc_tpu.bthread.device_waiter import device_on_ready
    disp = _dispatcher()
    failed, handed = disp.failures(), disp.handoffs()
    seen, fired = [], threading.Event()
    device_on_ready([_Poisoned()], lambda: (
        seen.append(threading.current_thread().name), fired.set()))
    assert fired.wait(10)
    assert not seen[0].startswith("device_poller_")
    assert disp.failures() == failed + 1
    assert disp.handoffs() == handed + 1


@pytest.mark.parametrize("entry", ["on_ready", "device_on_ready"])
def test_a_failed_program_that_reports_ready_is_logged_and_counted(
        entry, monkeypatch):
    """Its wait is spared, its failure is not: the callback's own access
    to the array raises it (a handler's completion reads its result), and
    a callback that raises is logged and counted."""
    import threading
    from brpc_tpu.butil import logging as log
    logged, was_logged = [], threading.Event()
    monkeypatch.setattr(log, "error", lambda fmt, *a, **kw: (
        logged.append(fmt % a), was_logged.set()))
    disp = _dispatcher()
    failed = disp.failures()
    leaf = _ReadyPoisoned()
    _submit(entry)([_Ready(), leaf], lambda: leaf.block_until_ready())
    assert was_logged.wait(10)
    deadline = time.monotonic() + 5
    while disp.failures() == failed and time.monotonic() < deadline:
        time.sleep(0.005)
    assert disp.failures() == failed + 1
    assert logged == ["device completion callback raised"]


@pytest.mark.parametrize("entry", ["on_ready", "device_on_ready"])
def test_arrays_that_are_ready_at_their_turn_are_not_blocked_on(entry):
    """block_until_ready gives the interpreter lock away: only for an
    entry that has a leaf still to wait for."""
    import threading
    ready, pending = threading.Event(), threading.Event()
    leaves = [_Ready(), _Ready()]
    _submit(entry)(leaves, ready.set)
    assert ready.wait(10)
    time.sleep(0.05)                        # nor afterwards
    assert [x.blocked for x in leaves] == [0, 0]
    leaf = _Pending()
    _submit(entry)([_Ready(), leaf], pending.set)
    assert pending.wait(10) and leaf.blocked == 1


@pytest.mark.parametrize("others", ["asleep", "one_busy"])
def test_a_completion_registered_by_a_busy_tasklet_does_not_wait_for_it(
        others):
    """The worker that parks a completion comes back for it when its
    tasklet ends.  Where that tasklet goes on: every other worker sleeps,
    and one is woken; or one is awake and busy, nobody is woken, and the
    backstop thread has it — not a worker's 0.5 s park timeout."""
    import threading
    from brpc_tpu.bthread.device_waiter import device_on_ready
    fired, release = threading.Event(), threading.Event()
    at = {}

    def busy():
        while not release.is_set():
            time.sleep(0.001)

    def handler():
        device_on_ready([_Ready()], lambda: (
            at.setdefault("fired", time.monotonic()), fired.set()))
        release.wait(10)                    # ...and computes on
        at["ended"] = time.monotonic()

    time.sleep(0.6)         # the other workers have parked by now
    tids = [bthread.start_background(busy)] if others == "one_busy" else []
    time.sleep(0.05)
    start = time.monotonic()
    tids.append(bthread.start_background(handler))
    got = fired.wait(0.3)
    release.set()
    for tid in tids:
        bthread.join(tid, timeout=10)
    assert got and at["fired"] < at["ended"]
    assert at["fired"] - start < 0.3


def test_every_user_completion_runs_once_whatever_its_way():
    """Ready at its turn, waited for by the poller, failed: callbacks of
    ``device_on_ready`` have no order, and each runs exactly once."""
    import threading
    from brpc_tpu.bthread.device_waiter import device_on_ready
    kinds = [_Ready, _Pending, _Poisoned, _ReadyPoisoned]
    n_threads, per = 4, 120
    runs = [[0] * per for _ in range(n_threads)]
    fired = bthread.CountdownEvent(n_threads * per)

    def ran(t, i):
        runs[t][i] += 1                     # only entry (t, i) writes it
        fired.signal()

    def submitter(t):
        for i in range(per):
            device_on_ready([kinds[(t + i) % len(kinds)]()],
                            lambda t=t, i=i: ran(t, i))

    threads = [threading.Thread(target=submitter, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert fired.wait(30) == 0
    time.sleep(0.1)                         # a second run would come now
    assert runs == [[1] * per for _ in range(n_threads)]


@pytest.mark.parametrize("entry", ["on_ready", "device_on_ready"])
def test_submits_racing_the_servers_sleep_are_all_served(entry):
    """A serving thread pops without its condition variable and takes it
    only to sleep, and a submit takes it only where a thread sleeps: a
    submit between a thread's last look and its sleep must not be lost."""
    import sys
    import threading
    submit = _submit(entry)
    n_threads, per = 8, 200
    fired = bthread.CountdownEvent(n_threads * per)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def submitter():
            for i in range(per):
                submit([_Ready()], fired.signal)
                if i % 16 == 0:
                    time.sleep(0.001)       # lets the queue run empty
        threads = [threading.Thread(target=submitter)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert not any(t.is_alive() for t in threads)
        assert fired.wait(30) == 0
    finally:
        sys.setswitchinterval(old)
