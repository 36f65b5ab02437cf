"""Profiling: CPU hotspots + lock-contention sampling.

Reference: src/brpc/builtin/hotspots_service.cpp (gperftools ProfilerStart /
pprof rendering) and the contention profiler inside src/bthread/mutex.cpp:
107-313 (lock-wait edges sampled through the bvar Collector).

TPU build equivalents:
  * CPU hotspots: stdlib cProfile driven start/stop, rendered as pprof-ish
    text (callers sorted by cumulative time) — served by /hotspots with
    ?seconds=N.
  * Contention: ``ContentionMutex`` wraps a lock; acquisition waits above a
    microsecond floor are sampled (speed-limited) with the blocking call
    site, aggregated into a contention profile — the exact mechanism of the
    reference's bthread_mutex hook.
  * Device hotspots: jax profiler hooks (trace to a dir) when available —
    the piece CPU-only bRPC has no analogue for.
"""
from __future__ import annotations

import cProfile
import io
import pstats
import threading
import time
import traceback
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from .. import bvar

# ---- CPU hotspots -----------------------------------------------------

_profile_lock = threading.Lock()


def profile_for(seconds: float = 1.0, top: int = 40) -> str:
    """Profile the whole process for ``seconds`` and render hotspots."""
    with _profile_lock:
        pr = cProfile.Profile()
        pr.enable()
        # the sleep IS the sampled window; the lock exists precisely to
        # serialize concurrent profilers over process-global cProfile
        # state, so holding it across the window is the point
        time.sleep(seconds)  # fablint: ignore[blocking-under-lock] the lock serializes the process-global profiler; the sleep is the sampling window itself
        pr.disable()
    out = io.StringIO()
    stats = pstats.Stats(pr, stream=out)
    stats.sort_stats("cumulative").print_stats(top)
    return out.getvalue()


def profile_call(fn, *args, top: int = 40, **kwargs) -> Tuple[object, str]:
    pr = cProfile.Profile()
    pr.enable()
    try:
        result = fn(*args, **kwargs)
    finally:
        pr.disable()
    out = io.StringIO()
    pstats.Stats(pr, stream=out).sort_stats("cumulative").print_stats(top)
    return result, out.getvalue()


# ---- contention profiler ---------------------------------------------

_contention_enabled = False
_contention_limit = bvar.CollectorSpeedLimit(max_samples_per_second=200)
_contention_lock = threading.Lock()
_contention_samples: Dict[str, List[float]] = defaultdict(list)
contention_sample_count = bvar.Adder("lock_contention_samples")

CONTENTION_FLOOR_US = 50        # waits shorter than this are never sampled


def enable_contention_profiler(enabled: bool = True) -> None:
    global _contention_enabled
    _contention_enabled = enabled
    if not enabled:
        with _contention_lock:
            _contention_samples.clear()


def contention_profile() -> List[Tuple[str, int, float]]:
    """(call_site, samples, total_wait_s) sorted by total wait."""
    with _contention_lock:
        rows = [(site, len(waits), sum(waits))
                for site, waits in _contention_samples.items()]
    return sorted(rows, key=lambda r: -r[2])


def _record_contention(wait_s: float) -> None:
    if not _contention_limit.is_sampled():
        return
    # the blocking call site: skip our own frames
    stack = traceback.extract_stack(limit=6)
    site = "?"
    for frame in reversed(stack):
        if "profiler.py" not in frame.filename:
            site = f"{frame.filename}:{frame.lineno} {frame.name}"
            break
    with _contention_lock:
        _contention_samples[site].append(wait_s)
    contention_sample_count << 1


class ContentionMutex:
    """A mutex whose contended acquisitions feed the contention profiler
    (reference bthread_mutex with g_cp sampling, mutex.cpp:107)."""

    def __init__(self):
        self._lock = threading.Lock()

    def acquire(self, timeout: Optional[float] = None) -> bool:
        if self._lock.acquire(blocking=False):
            return True
        t0 = time.monotonic()
        ok = self._lock.acquire(timeout=timeout if timeout is not None else -1)
        wait = time.monotonic() - t0
        if _contention_enabled and wait * 1e6 >= CONTENTION_FLOOR_US:
            _record_contention(wait)
        return ok

    def release(self) -> None:
        self._lock.release()

    def __enter__(self) -> "ContentionMutex":
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self.release()


# ---- device profiling (jax tracer) ------------------------------------

def start_device_trace(log_dir: str) -> bool:
    """A jax profiler session: the device's operations and, with them, the
    RPC stack's layer spans (butil/layer_span.py) on the host plane and in
    ``span.layer_spans()``, which starts empty."""
    try:
        import jax
        from . import span
        span.layer_spans_reset()
        jax.profiler.start_trace(log_dir)
        return True
    except Exception:
        return False


def stop_device_trace() -> bool:
    try:
        import jax
        jax.profiler.stop_trace()
        return True
    except Exception:
        return False
