"""rpcz spans: per-RPC timelines sampled through the bvar Collector.

Reference: src/brpc/span.{h,cpp} (Span at span.h:47-150, tls_parent :115,
SpanDB :206-223) + builtin/rpcz_service.cpp.  Client and server spans record
annotated timelines; sampling is speed-limited via CollectorSpeedLimit; kept
spans land in an in-memory ring (the LevelDB store's stand-in) rendered by
the /rpcz builtin service.  Propagation: trace/span/parent ids ride RpcMeta.

Pod-scope additions (docs/OBSERVABILITY.md):

  * every span records a **wall-clock anchor** (``wall_us``) alongside its
    monotonic timeline, so spans from DIFFERENT processes can be placed on
    one axis — refined by the fabric's per-pair clock-offset estimate
    (ici/clock.py, ±RTT/2 bound) when the pod stitcher merges them;
  * ``annotate_current`` consults the bthread-local *server* span AND the
    active *client* span (set around the channel write path), so
    client-side relocation/bulk/device-plane events are no longer lost;
  * deep subsystems that know their trace context (device-plane transfers
    carry trace/span ids on their descriptors) open **transfer spans** —
    first-class SpanDB entries parented under the RPC span that caused
    them, so a ``/rpcz?trace_id=`` query shows sequencer queue-wait,
    collective admit, CQ completion, and pin hold-time in the same tree.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Deque, List, Optional, Tuple

from ..butil.misc import fast_rand
from ..butil import flags as _flags
from .. import bvar
from ..bthread import scheduler

_rpcz_flag = _flags.define_flag("rpcz_enabled", False,
                                "collect per-RPC rpcz spans")
_flags.define_flag("rpcz_keep", 1000, "spans kept in memory",
                   _flags.positive_integer)

_speed_limit = bvar.CollectorSpeedLimit()
_store_lock = threading.Lock()
_store: Deque["Span"] = collections.deque(maxlen=10000)


class Span:
    __slots__ = ("trace_id", "span_id", "parent_span_id", "is_client",
                 "method", "start_us", "wall_us", "end_us", "annotations",
                 "error_code", "remote_side", "request_size",
                 "response_size", "kind")

    def __init__(self, method: str, is_client: bool, trace_id: int = 0,
                 parent_span_id: int = 0, kind: Optional[str] = None):
        self.trace_id = trace_id or fast_rand()
        self.span_id = fast_rand()
        self.parent_span_id = parent_span_id
        self.is_client = is_client
        self.method = method
        self.start_us = time.monotonic_ns() // 1000
        # wall-clock anchor: lets a remote process place this span on its
        # own axis (offset by the fabric clock estimate); annotations stay
        # monotonic offsets from start, so wall_us + offset reconstructs
        # their wall time without per-annotation wall reads
        self.wall_us = time.time_ns() // 1000
        self.end_us = 0
        self.annotations: List[Tuple[int, str]] = []
        self.error_code = 0
        self.remote_side = None
        self.request_size = 0
        self.response_size = 0
        self.kind = kind or ("client" if is_client else "server")

    def annotate(self, text: str) -> None:
        self.annotations.append((time.monotonic_ns() // 1000, text))

    def latency_us(self) -> int:
        return (self.end_us or time.monotonic_ns() // 1000) - self.start_us

    def describe(self) -> dict:
        return {
            "trace_id": f"{self.trace_id:016x}",
            "span_id": f"{self.span_id:016x}",
            "parent": f"{self.parent_span_id:016x}",
            "side": self.kind,
            "method": self.method,
            "start_real_us": self.wall_us,
            "latency_us": self.latency_us(),
            "error_code": self.error_code,
            "remote": str(self.remote_side),
            "annotations": [(t - self.start_us, a) for t, a in self.annotations],
        }


def rpcz_enabled() -> bool:
    # one attribute load, not a registry-dict lookup: this gate sits on
    # every call's client-span check
    return bool(_rpcz_flag.value)


def maybe_start_client_span(cntl, method: str) -> None:
    if not rpcz_enabled() or not _speed_limit.is_sampled():
        return
    # inherit trace from an enclosing server span (bthread-local parenting)
    parent: Optional[Span] = scheduler.local_get("rpcz_span")
    if parent is not None:
        span = Span(method, True, parent.trace_id, parent.span_id)
    else:
        span = Span(method, True)
    cntl.span = span
    cntl.trace_id = span.trace_id
    cntl.span_id = span.span_id
    cntl.parent_span_id = span.parent_span_id


def start_server_span(cntl, method: str, trace_id: int, parent_span_id: int) -> None:
    if not rpcz_enabled() or not _speed_limit.is_sampled():
        return
    span = Span(method, False, trace_id, parent_span_id)
    cntl.span = span
    scheduler.local_set("rpcz_span", span)


def current_span() -> Optional[Span]:
    """The span deep subsystems should annotate.  The ACTIVE client span
    wins when set — it is only published for the duration of a channel
    write, so inside that window it is the INNERMOST context (a client
    call issued from a server handler must stamp its relocation events
    on the client span, not the enclosing server span) — else the
    bthread-local server span.  Consulting the client span at all is the
    fix for client-side RPCs, whose relocation/bulk/device-plane events
    used to be lost because only the server span was read."""
    span: Optional[Span] = scheduler.local_get("rpcz_client_span")
    if span is not None:
        return span
    return scheduler.local_get("rpcz_span")


def current_trace_context() -> Tuple[int, int]:
    """(trace_id, span_id) of the span currently in scope, or (0, 0).
    Captured by the device plane at post time so transfer events can be
    parented into the RPC's trace — on BOTH processes, via the kind-4
    descriptor's trace fields."""
    span = current_span()
    if span is None:
        return 0, 0
    return span.trace_id, span.span_id


def set_client_span_local(span: Optional[Span]) -> None:
    """Publish ``span`` as the bthread-local active client span for the
    duration of the channel's encode/write (cleared with None after)."""
    scheduler.local_set("rpcz_client_span", span)


def annotate_current(text: str) -> None:
    """Annotate the span currently in scope (the ACTIVE client span
    during a channel write — the innermost context — else the
    bthread-local server span; see current_span), if sampling kept one.
    Deep subsystems (the device plane's posted→matched→complete
    lifecycle, bulk claims) use this to stamp their timeline onto
    whatever RPC is in progress without threading a Controller down the
    datapath."""
    if not rpcz_enabled():
        return
    span = current_span()
    if span is not None:
        span.annotate(text)


def start_transfer_span(method: str, trace_id: int,
                        parent_span_id: int) -> Span:
    """A data-plane event span (device-plane transfer, bulk claim):
    stored like any RPC span, parented under the RPC span that caused it,
    so the stitched trace shows the transfer's own timeline."""
    return Span(method, False, trace_id, parent_span_id, kind="transfer")


def end_span(span: Span, error_code: int = 0) -> None:
    """Close and store a span the caller owns (transfer spans)."""
    span.end_us = time.monotonic_ns() // 1000
    span.error_code = error_code
    store_span(span)


def store_span(span: Span) -> None:
    with _store_lock:
        _store.append(span)
        while len(_store) > _flags.get_flag("rpcz_keep"):
            _store.popleft()


def end_client_span(cntl) -> None:
    _finish(cntl)


def end_server_span(cntl) -> None:
    _finish(cntl)
    scheduler.local_set("rpcz_span", None)


def _finish(cntl) -> None:
    span = cntl.span
    if span is None:
        return
    span.end_us = time.monotonic_ns() // 1000
    span.error_code = cntl.error_code_
    span.remote_side = cntl.remote_side
    store_span(span)
    cntl.span = None


def recent_spans(limit: int = 100) -> List[Span]:
    with _store_lock:
        return list(_store)[-limit:]


def find_trace(trace_id: int) -> List[Span]:
    with _store_lock:
        return [s for s in _store if s.trace_id == trace_id]


# ---- layer spans ------------------------------------------------------
#
# The other recorder: every request of a profiler session, by layer, on
# the profiler's clock (rpcz above is sampled, cross-process, for /rpcz).
# It lives in butil/layer_span.py, a leaf, because bthread and ici record
# into it too; readers and the rpc layer's own sites use it by these names.
from ..butil.layer_span import (  # noqa: E402,F401
    LAYER_SPAN_CAP, LayerMark, LayerSpan, layer_adopt_call, layer_begin,
    layer_mark, layer_on, layer_record, layer_spans, layer_spans_dropped,
    layer_spans_reset, layer_waited)
