"""Layer spans: what each layer of the RPC stack was doing, and for how long,
recorded inside the program while a jax profiler session is on.

A record is ``(name, start_ns, end_ns, call_id, span_id, cause_id, thread,
n[, m[, cpu_ns]])`` on ``time.perf_counter_ns()`` (CLOCK_MONOTONIC, as
``monotonic_ns`` and the native tier's ``recv_ns``).  ``cause_id`` is the
enclosing span on the same thread, or, for work that crossed threads (a
poller entry, a delivery gate), the span that was open where it was
submitted.  ``cpu_ns`` is what the span's thread RAN between the span's two
ends (``time.thread_time_ns()``, CLOCK_THREAD_CPUTIME_ID): a thread that
waits for the interpreter lock or for a device sleeps and accrues none, so
the span's length less it is what the thread waited.  Only a lexical span
begun with ``cpu=True`` and ended on the thread that began it has one — the
read is a system call (6 us on the chip machine's host, where the clock
also moves in 10 ms steps: sums and means read it, a median cannot), so only
the sites a reader asks for pay it, and a CPU clock is one thread's —; every
other record says -1.  Records go to
per-thread lists, ``LAYER_SPAN_CAP`` in all and then dropped and counted, and
stay in memory until the next session starts: ``layer_spans()`` reads them
after the window.  Nothing is written out and nothing rides the wire.

The switch is the profiler session, not a flag: a site asks ``layer_on()``
(no clock read, no allocation) and does nothing more while it is false.  A
lexical span also opens a ``jax.profiler.TraceAnnotation`` of its name, so it
lies on the profiler's ``/host:`` plane beside the device's operations.

A leaf: every layer from bthread up records here, so this module imports
nothing of the package, and never jax (host-only processes import it) — it
finds ``jax.profiler`` in ``sys.modules`` once something else has loaded it;
before that no session can be on.  ``rpc/span.py`` re-exports the names.
docs/OBSERVABILITY.md, "Layer spans".
"""
from __future__ import annotations

import itertools
import sys
import threading
import time
from typing import List, NamedTuple, Optional

LAYER_SPAN_CAP = 1 << 18          # records a session may make; then dropped


class LayerSpan(NamedTuple):
    name: str
    start_ns: int                 # time.perf_counter_ns
    end_ns: int
    call_id: int                  # correlation id / native token / 0
    span_id: int
    cause_id: int                 # enclosing or submitting span, 0 = none
    thread: str
    n: int                        # the site's integer (bytes, depth)
    m: int = 0                    # a second one, where a site has two
    cpu_ns: int = -1              # its thread's CPU time inside; -1 = none


class LayerMark(NamedTuple):
    """Where work was handed to another thread: the stamp and the
    submitter's span, carried in the queue entry."""
    ns: int
    span_id: int
    call_id: int
    n: int


class _LayerThread:
    """One thread's records (plain tuples in LayerSpan's order) and its
    innermost open span.  Only the owner appends; readers copy."""
    __slots__ = ("records", "cur_id", "cur_call", "dropped", "thread",
                 "name")

    def __init__(self):
        self.records: List[tuple] = []
        self.cur_id = 0
        self.cur_call = 0
        self.dropped = 0
        self.thread = threading.current_thread()
        self.name = self.thread.name


_tls = threading.local()
_threads: List[_LayerThread] = []
_threads_lock = threading.Lock()
_ids = itertools.count(1)         # next() is atomic under the GIL
_base = 0                         # ids at or below it predate the session
_annotation = None                # jax.profiler.TraceAnnotation, once bound
_session = False                  # a site has seen the session that is on


def _unbound() -> bool:
    global _enabled, _annotation
    note = getattr(sys.modules.get("jax.profiler"), "TraceAnnotation", None)
    if note is None:
        return False
    _annotation = note
    _enabled = note.is_enabled
    return _enabled()


_enabled = _unbound               # TraceAnnotation.is_enabled, once bound


def layer_on() -> bool:
    """True while a profiler session is on.  The first site to see a new
    session empties the store, so that each session has the whole cap and
    holds no record of the one before."""
    global _session
    if _enabled():
        if not _session:
            with _threads_lock:
                if not _session:
                    _reset_locked()
                    _session = True
        return True
    _session = False
    return False


def _thread() -> _LayerThread:
    st = getattr(_tls, "st", None)
    if st is None:
        st = _tls.st = _LayerThread()
        with _threads_lock:
            _threads.append(st)
    return st


def layer_mark(n: int = 0) -> LayerMark:
    """Stamp a hand-off on the submitting thread (a poller entry, a
    delivery gate): the far side records the wait from it
    (``layer_waited``) and names the submitter as the cause of what it
    then does (``layer_begin(mark=...)``)."""
    st = getattr(_tls, "st", None) or _thread()
    # tuple.__new__: half the cost of the NamedTuple's own constructor
    return tuple.__new__(LayerMark, (time.perf_counter_ns(), st.cur_id,
                                     st.cur_call, n))


def layer_adopt_call(call_id: int) -> None:
    """The call this thread is inside got its id (the Python plane makes
    it on the way to the socket): spans begun from here on, until the
    enclosing one is left, inherit it.  A thread with no span open (a
    retry or backup issued from a timer) adopts nothing: nothing there
    would give the id back."""
    st = getattr(_tls, "st", None)
    if st is not None and st.cur_id:
        st.cur_call = call_id


def layer_record(name: str, start_ns: int, end_ns: int,
                 call_id: int = 0) -> None:
    """A span whose two ends are already stamped (a server stage), under
    this thread's innermost span.  In the store only."""
    st = getattr(_tls, "st", None) or _thread()
    sid = next(_ids)
    if sid - _base > LAYER_SPAN_CAP:
        st.dropped += 1
    else:
        st.records.append((name, start_ns, end_ns, call_id or st.cur_call,
                           sid, st.cur_id, st.name, 0))


def layer_waited(name: str, mark: LayerMark) -> None:
    """The wait that began at ``mark`` on the submitting thread ends here,
    on the thread that took the work up.  In the store only."""
    st = getattr(_tls, "st", None) or _thread()
    sid = next(_ids)
    if sid - _base > LAYER_SPAN_CAP:
        st.dropped += 1
    else:
        st.records.append((name, mark.ns, time.perf_counter_ns(),
                           mark.call_id, sid, mark.span_id, st.name, mark.n))


class _OpenLayerSpan:
    """A lexical span: the thread's innermost from ``layer_begin`` to
    ``leave`` (so spans begun and hand-offs marked inside it name it as
    their cause), on the profiler's host plane for the same stretch, and
    in the store from ``finish``.  ``end`` is the two together."""
    __slots__ = ("name", "start_ns", "call_id", "span_id", "cause_id", "n",
                 "m", "_st", "_prev_id", "_prev_call", "_note", "_cpu0")

    def leave(self) -> None:
        st = self._st
        st.cur_id = self._prev_id
        st.cur_call = self._prev_call
        self._note.__exit__(None, None, None)

    def finish(self, end_ns: int = 0, cpu_ns: int = -1) -> None:
        """May run on another thread than the one that began the span (a
        handler's ``done``): the record goes to the finishing thread's
        list, under the opening thread's name, and with no CPU time (only
        ``end`` knows that it is on the opening thread)."""
        st = getattr(_tls, "st", None) or _thread()
        st.records.append((
            self.name, self.start_ns, end_ns or time.perf_counter_ns(),
            self.call_id, self.span_id, self.cause_id, self._st.name,
            self.n, self.m, cpu_ns))

    def end(self) -> None:
        # on the thread that began the span, by contract (``leave`` restores
        # that thread's innermost span); the CPU clock is read inside the
        # wall clock's two reads, so that cpu_ns never passes the length
        cpu_ns = -1 if self._cpu0 < 0 else time.thread_time_ns() - self._cpu0
        end_ns = time.perf_counter_ns()
        self.leave()
        self.finish(end_ns, cpu_ns)


def layer_begin(name: str, call_id: int = 0, n: int = 0,
                mark: Optional[LayerMark] = None,
                m: int = 0, cpu: bool = False) -> Optional[_OpenLayerSpan]:
    """Open a lexical span on this thread; ``None`` once the session has
    used its cap.  Call only under ``layer_on()`` (or with a ``mark`` taken
    under it).  ``mark`` names the submitter as the cause where the work
    crossed threads; ``cpu`` also records the thread's CPU time inside the
    span (two system calls: for the spans whose ``cpu_ns`` is read)."""
    st = getattr(_tls, "st", None) or _thread()
    sid = next(_ids)
    if sid - _base > LAYER_SPAN_CAP:
        st.dropped += 1
        return None
    ls = _OpenLayerSpan()
    ls.name = name
    ls.span_id = sid
    ls.n = n
    ls.m = m
    if mark is not None:
        ls.cause_id = mark.span_id
        ls.call_id = call_id = call_id or mark.call_id
    else:
        ls.cause_id = st.cur_id
        ls.call_id = call_id = call_id or st.cur_call
    ls._st = st
    ls._prev_id = st.cur_id
    ls._prev_call = st.cur_call
    st.cur_id = sid
    st.cur_call = call_id
    # the name alone: the call's id is in the record, and as an argument
    # of the annotation it costs as much again as the annotation itself
    ls._note = note = _annotation(name)
    note.__enter__()
    ls.start_ns = time.perf_counter_ns()
    ls._cpu0 = time.thread_time_ns() if cpu else -1
    return ls


def layer_spans(since_ns: int = 0, until_ns: Optional[int] = None,
                name: Optional[str] = None) -> List[LayerSpan]:
    """Records that lie at least partly inside [since_ns, until_ns], every
    thread's, by start time."""
    with _threads_lock:
        threads = list(_threads)
    out = [LayerSpan(*r) for st in threads for r in list(st.records)
           if r[2] >= since_ns and (until_ns is None or r[1] <= until_ns)
           and (name is None or r[0] == name)]
    out.sort(key=lambda r: (r.start_ns, r.span_id))
    return out


def layer_spans_dropped() -> int:
    with _threads_lock:
        return sum(st.dropped for st in _threads)


def _reset_locked() -> None:
    global _base
    _base = next(_ids)
    _threads[:] = [st for st in _threads if st.thread.is_alive()]
    for st in _threads:
        st.records = []
        st.dropped = 0


def layer_spans_reset() -> None:
    """Forget every record and drop count, and the threads that have
    ended.  A session's first site does it; so does
    ``rpc.profiler.start_device_trace``, for a process so idle that no
    site ran between two sessions."""
    with _threads_lock:
        _reset_locked()
