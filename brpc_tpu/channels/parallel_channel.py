"""ParallelChannel: fan one RPC out to N sub-channels concurrently.

Reference: src/brpc/parallel_channel.{h,cpp} (CallMethod :551, CallMapper::Map
:94-107, ResponseMerger::Merge :127-144).  Semantics kept:

  * CallMapper rewrites the request per sub-channel (replicate by default;
    shard for scatter patterns) and may skip a sub-channel.
  * ResponseMerger folds each arriving sub-response into the caller's
    response (called serially, in arrival order, under the parent's lock).
  * fail_limit: the call fails once that many sub-calls failed
    (ETOOMANYFAILS); success completes when every non-skipped sub-call ends.

When every sub-channel targets the same ICI mesh and payloads are device
arrays, use channels/collective_lowering.py instead — the same fan-out
semantics compile to ONE mesh collective (SURVEY.md §2.6's TPU-native
lowering).

Counters (``fanout_stats()``, ``/vars`` ``rpc_fanout_<key>``) are
process-wide totals that survive a channel's close.  Layer spans
(``butil/layer_span.py``, docs/OBSERVABILITY.md "Layer spans"), recorded
only while a jax profiler session is on: ``brpc.fanout`` (``call_method``
entry → the operation's end), ``brpc.fanout.issue`` (one a sub-call: map and
the sub-call's start, its ``brpc.call`` inside), ``brpc.fanout.wait`` (the
caller parked for the last sub-call) and ``brpc.fanout.merge`` (a sub-reply
folded under the parent's lock, and the finalize); inside the finalize, or
where ``fanout_result`` is read, ``brpc.fanout.reduce`` (collective_fanout.py:
the one array made of the sub-replies' DEVICE refs, with totals of its own,
``fanout_reduce_stats()``).
"""
from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional, Union

from .. import bvar
from ..butil import layer_span as _span
from ..butil.iobuf import IOBuf
from ..rpc import errors
from ..rpc.controller import Controller

# process-wide totals, kept past a channel's close.  ``partial_results``: an
# operand fan-out that ended NOT failed with fewer merged sub-replies than
# sub-channels (all-or-nothing holds it at zero); ``device_operand_bytes``:
# operand bytes handed to sub-calls as DEVICE refs; ``host_operand_bytes``:
# operand or reply bytes that crossed the host inside the loop;
# ``route_rpc`` / ``route_collective``: operand fan-outs by the route that
# carried them to their end (one that degrades in mid-call counts as rpc).
_STAT_KEYS = ("calls", "sub_calls", "sub_calls_failed", "merges",
              "partial_results", "device_operand_bytes",
              "host_operand_bytes", "route_rpc", "route_collective")
_g = {k: bvar.Adder(f"rpc_fanout_{k}") for k in _STAT_KEYS}


def fanout_stats() -> Dict[str, int]:
    """What the fan-outs of this process have done so far."""
    return {k: v.get_value() for k, v in _g.items()}


class SubCall:
    """What CallMapper returns for one sub-channel.  ``attachment``, when
    set, becomes the sub-call's request attachment — the wire half of a
    scattered fan-out operand (collective_fanout.py's ShardingCallMapper):
    ``bytes`` for a host operand, an ``IOBuf`` whose DEVICE refs are passed
    on by reference (no copy, no host) for a device one."""
    __slots__ = ("request", "skip", "attachment")

    def __init__(self, request: Any = None, skip: bool = False,
                 attachment: Union[bytes, IOBuf, None] = None):
        self.request = request
        self.skip = skip
        self.attachment = attachment

    @staticmethod
    def skip_call() -> "SubCall":
        return SubCall(skip=True)


class CallMapper:
    # Lowerability contract (collective_fanout.py): a mapper opts into
    # the compiled route by declaring ``collective_mapping`` ("replicate"
    # or "shard") AND implementing ``map_fanout`` (the RPC-loop half
    # that carries the operand — a degrade mid-call must reproduce the
    # same bytes).  This base class has neither, so it always rides the
    # per-member loop; ReplicateFanoutMapper / ShardingCallMapper are
    # the opt-ins.  A subclass with a custom map() and no declaration
    # likewise refuses — inheritance must never smuggle an unknown
    # map() into a lowering.

    def map(self, channel_index: int, method_full_name: str,
            request: Any) -> SubCall:
        return SubCall(request)             # default: replicate


class ResponseMerger:
    MERGED = 0
    FAIL = 1
    FAIL_ALL = 2

    def merge(self, response: Any, sub_response: Any) -> int:
        """Fold sub_response into response; default: protobuf MergeFrom.
        Mergers may instead implement ``merge_sub(parent_cntl, index,
        sub_cntl, response)`` to see the sub-call's INDEX and controller
        (attachment-carrying fan-outs merge by index, never arrival
        order), plus ``finalize_fanout(parent_cntl)`` run once when the
        whole fan-out succeeded."""
        if response is not None and hasattr(response, "MergeFrom"):
            response.MergeFrom(sub_response)
            return self.MERGED
        return self.MERGED


class ParallelChannel:
    def __init__(self, fail_limit: int = -1):
        self._subs: List = []               # (channel, mapper, merger)
        self.fail_limit = fail_limit

    def add_channel(self, channel, mapper: Optional[CallMapper] = None,
                    merger: Optional[ResponseMerger] = None) -> int:
        self._subs.append((channel, mapper or CallMapper(),
                           merger or ResponseMerger()))
        return 0

    def channel_count(self) -> int:
        return len(self._subs)

    def call_method(self, method_full_name: str, cntl: Controller,
                    request: Any, response: Any = None,
                    done: Optional[Callable[[Controller], None]] = None):
        n = len(self._subs)
        if n == 0:
            cntl.set_failed(errors.EINVAL, "no sub channels")
            if done: done(cntl)
            return None
        if "_fanout_no_compiled" not in cntl.__dict__:
            _g["calls"] << 1            # (a degraded async call comes twice)
        ls = _span.layer_begin("brpc.fanout", n=n) \
            if _span.layer_on() else None
        try:
            return self._fan_out(method_full_name, cntl, request, response,
                                 done, ls)
        finally:
            if ls is not None:
                ls.leave()

    def _fan_out(self, method_full_name, cntl, request, response, done, ls):
        n = len(self._subs)
        # Compiled collective route (collective_fanout.py): when every
        # sub targets a pod member with a registered device handler and
        # the operand/mapper/merger lower, the WHOLE fan-out+merge runs
        # as one cached SPMD program — and any mid-fan-out failure falls
        # through HERE, completing on the per-member loop below with the
        # route already marked down (zero client-visible failures).
        from . import collective_fanout as _cf
        if _cf.maybe_call(self, method_full_name, cntl, request,
                          response, done):
            if ls is not None:          # the program ran (sync), or rides a
                ls.finish()             # tasklet that re-enters here if not
            return response if done is None else None
        fail_limit = self.fail_limit if self.fail_limit > 0 else n
        # finalizer lookup only for operand fan-outs: the common plain
        # protobuf fan-out must not pay a per-call merger scan
        finalizer = None
        if cntl.__dict__.get("fanout_operand") is not None:
            _g["route_rpc"] << 1
            finalizer = next(
                (m for _, _, m in self._subs
                 if hasattr(m, "finalize_fanout")), None)
        state = _ParallelCallState(cntl, response, n, fail_limit, done,
                                   finalizer=finalizer, span=ls)

        cntl._start_us = time.monotonic_ns() // 1000
        for i in range(n):
            issue = _span.layer_begin("brpc.fanout.issue", cpu=True) \
                if ls is not None else None
            try:
                self._issue(i, method_full_name, request, state, issue)
            finally:
                if issue is not None:
                    issue.end()
        if done is None:
            wait = _span.layer_begin("brpc.fanout.wait") \
                if ls is not None else None
            state.wait()
            if wait is not None:
                wait.end()
            return response
        return None

    def _issue(self, i, method_full_name, request, state, issue) -> None:
        """Sub-call ``i``: map, then start it (or, for an inline-eligible
        listener, run it); ``issue`` is its open span, if any."""
        chan, mapper, merger = self._subs[i]
        cntl, response, done = state.cntl, state.response, state.done
        try:
            mf = getattr(mapper, "map_fanout", None)
            if mf is not None \
                    and cntl.__dict__.get("fanout_operand") is not None:
                sub = mf(i, method_full_name, request, cntl)
            else:
                sub = mapper.map(i, method_full_name, request)
        except Exception as e:
            # a raising mapper (operand/sub-count mismatch, a user
            # bug) fails ITS sub-call, never the whole issue loop
            bad = Controller()
            bad.set_failed(errors.EREQUEST,
                           f"CallMapper failed for sub {i}: {e}")
            state.on_sub_done(i, merger, bad)
            return
        if sub.skip:
            state.on_skip()
            return
        _g["sub_calls"] << 1
        sub_cntl = Controller()
        if sub.attachment is not None:
            sub_cntl.request_attachment.append(sub.attachment)
            if issue is not None:
                issue.n = len(sub.attachment)
        sub_cntl.timeout_ms = cntl.timeout_ms
        sub_cntl.max_retry = cntl.max_retry
        sub_cntl.log_id = cntl.log_id
        response_cls = type(response) if response is not None else None
        # Sub-calls to an in-process native listener that dispatches
        # handlers INLINE are issued inline too: the handler would
        # run in this very stack either way, so a tasklet per
        # sub-call adds a scheduling hop (~100 us on a busy host) and
        # zero concurrency (VERDICT r4 weak #4; the reference's
        # fan-out is a plain IssueRPC loop, parallel_channel.cpp:551
        # — its completions overlap because handlers run in OTHER
        # processes, which an inline in-process server's cannot).
        # Servers that park handlers on tasklets keep the concurrent
        # fan-out: there, completions genuinely overlap.
        if done is None and self._inline_eligible(
                chan, sub_cntl, sub.request, method_full_name):
            chan.call_method(method_full_name, sub_cntl, sub.request,
                             response_cls)
            state.on_sub_done(i, merger, sub_cntl)
            return
        chan.call_method(
            method_full_name, sub_cntl, sub.request, response_cls,
            done=lambda sc, idx=i, m=merger: state.on_sub_done(idx, m, sc))

    @staticmethod
    def _inline_eligible(chan, sub_cntl, request, method_full_name) -> bool:
        # the channel mirrors call_method's full routing screen (window
        # fit, hedging, streaming, dispatch mode) so inline issue can
        # never commit to a call that would actually ride the Python
        # plane and serialize the fan-out
        check = getattr(chan, "inline_fast_call_ok", None)
        return check is not None and check(sub_cntl, request,
                                           method_full_name)


class _ParallelCallState:
    def __init__(self, cntl: Controller, response: Any, total: int,
                 fail_limit: int, done, finalizer=None, span=None):
        self.cntl = cntl
        self.response = response
        self.subs = total               # sub-channels, skipped ones too
        self.total = total
        self.merged = 0
        # the parent's open ``brpc.fanout`` span, and a mark inside it: a
        # merge on whichever thread ends a sub-call names it as its cause
        self.span = span
        self.mark = _span.layer_mark() if span is not None else None
        self.fail_limit = fail_limit
        self.done = done
        self.lock = threading.Lock()
        self.finished = 0
        self.failed = 0
        self.skipped = 0
        self.ended = False
        self.event = threading.Event()
        self.sub_errors: List[int] = []
        # one finalize per fan-out (operand fan-outs only): the merger
        # exposing finalize_fanout runs once at success end — the
        # index-ordered merge of attachment-carrying fan-outs
        self.finalizer = finalizer

    def on_skip(self) -> None:
        with self.lock:
            self.total -= 1
            self.skipped += 1
            if self.finished >= self.total:
                self._maybe_end_locked()

    def on_sub_done(self, index: int, merger: ResponseMerger,
                    sub_cntl: Controller) -> None:
        with self.lock:
            if self.ended:
                return
            self.finished += 1
            if sub_cntl.failed():
                self.failed += 1
                _g["sub_calls_failed"] << 1
                self.sub_errors.append(sub_cntl.error_code_)
            else:
                ls = _span.layer_begin("brpc.fanout.merge", n=index,
                                       mark=self.mark) \
                    if self.mark is not None else None
                try:
                    ms = getattr(merger, "merge_sub", None)
                    if ms is not None:
                        rc = ms(self.cntl, index, sub_cntl,
                                self.response)
                    else:
                        rc = merger.merge(self.response, sub_cntl.response)
                except Exception as e:
                    from ..butil import logging as log
                    log.warning("fan-out merge failed for sub %d: %s",
                                index, e)
                    rc = ResponseMerger.FAIL
                finally:
                    if ls is not None:
                        ls.end()
                if rc == ResponseMerger.MERGED:
                    self.merged += 1
                    _g["merges"] << 1
                if rc == ResponseMerger.FAIL:
                    self.failed += 1
                    self.sub_errors.append(errors.ERESPONSE)
                elif rc == ResponseMerger.FAIL_ALL:
                    self.failed = self.fail_limit
            self._maybe_end_locked()

    def _maybe_end_locked(self) -> None:
        if self.ended:
            return
        if self.failed >= self.fail_limit:
            self.cntl.set_failed(
                errors.ETOOMANYFAILS,
                f"{self.failed}/{self.total} sub-calls failed: "
                f"{self.sub_errors[:4]}")
            self._end_locked()
        elif self.finished >= self.total:
            self._end_locked()

    def _end_locked(self) -> None:
        self.ended = True
        # a merger whose skipped sub-calls are empty parts, not missing ones
        # (an expert rank with no token: expert_dispatch.py)
        skips_merge = getattr(self.finalizer, "merges_skips", False)
        if self.finalizer is not None and not self.cntl.failed():
            if self.failed or (self.skipped and not skips_merge):
                # index-merged collective semantics are all-or-nothing:
                # a gather/sum missing a shard — whether its sub FAILED
                # or was mapper-SKIPPED — is WRONG data, not a partial
                # success; it must not yield a silently truncated
                # fanout_result
                self.cntl.set_failed(
                    errors.ERESPONSE,
                    f"fan-out merge incomplete: {self.failed} failed / "
                    f"{self.skipped} skipped sub-call(s) before merge: "
                    f"{self.sub_errors[:4]}")
            else:
                # m=1: the finalize, not a sub-reply's merge
                ls = None
                if self.mark is not None:
                    ls = _span.layer_begin("brpc.fanout.merge",
                                           n=self.merged, mark=self.mark,
                                           m=1)
                    # for the finalizer's brpc.fanout.reduce
                    self.cntl.__dict__["_fanout_mark"] = self.mark
                try:
                    self.finalizer.finalize_fanout(self.cntl)
                except Exception as e:
                    self.cntl.set_failed(
                        errors.ERESPONSE,
                        f"fan-out finalize failed: {e}")
                finally:
                    if ls is not None:
                        ls.end()
            merged = self.merged + (self.skipped if skips_merge else 0)
            if not self.cntl.failed() and merged < self.subs:
                _g["partial_results"] << 1
        if self.span is not None:
            self.span.finish()
        self.cntl.latency_us = time.monotonic_ns() // 1000 - self.cntl._start_us
        self.cntl.response = self.response
        self.event.set()
        if self.done is not None:
            from ..bthread import scheduler
            scheduler.start_background(self.done, self.cntl, name="pchan_done")

    def wait(self) -> None:
        self.event.wait()
