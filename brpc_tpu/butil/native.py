"""ctypes bindings to the native core (native/libbrpc_tpu_core.so).

The reference runtime is entirely C++; this binding exposes the native
fiber scheduler, butex, versioned pools, MPSC write queue, block pool, and
timer to Python (no pybind11 in the image — plain ctypes).  The Python
runtime uses these opportunistically: ``available()`` gates every use, so
the pure-Python implementations above stay the behavioral reference and CI
fixture.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

# stale-.so detector: ALWAYS the most recently added C symbol, so an old
# build triggers a rebuild instead of silently disabling the native layer
_BRPC_TPU_NEWEST_SYMBOL_ = "brpc_tpu_shm_stripe_stats"

_lib = None
_lib_lock = threading.Lock()
_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")
_SO = os.path.join(_NATIVE_DIR, "libbrpc_tpu_core.so")

_FIBER_FN = ctypes.CFUNCTYPE(None, ctypes.c_void_p)
_SINK_FN = ctypes.CFUNCTYPE(None, ctypes.c_void_p, ctypes.c_size_t,
                            ctypes.c_void_p)
_TIMER_FN = ctypes.CFUNCTYPE(None, ctypes.c_void_p)
# native RPC request hook: (token, method, payload, payload_len, att,
# att_len, log_id) — see native/rpc.cpp py_request_fn
_NREQ_FN = ctypes.CFUNCTYPE(None, ctypes.c_uint64, ctypes.c_char_p,
                            ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint64,
                            ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint64,
                            ctypes.c_uint64)


class IciSegC(ctypes.Structure):
    """Attachment segment descriptor for the native ici plane (the SGE of
    a zero-copy post — native/rpc.cpp IciSegC).  Host segments name a span
    of the att_host byte stream; device segments name a registry key."""
    _fields_ = [("key", ctypes.c_uint64),
                ("nbytes", ctypes.c_uint64),
                ("dev", ctypes.c_int32),
                ("is_dev", ctypes.c_int32)]


class IciCallOut(ctypes.Structure):
    """One out-block for the unary ici call (native/rpc.cpp IciCallOut):
    replaces seven per-call byref temporaries with a single pointer.
    err_text is a raw pointer (c_void_p, NOT c_char_p — the automatic
    bytes conversion would lose the pointer the caller must buf_free)."""
    _fields_ = [("resp", ctypes.POINTER(ctypes.c_uint8)),
                ("resp_len", ctypes.c_uint64),
                ("att", ctypes.POINTER(ctypes.c_uint8)),
                ("att_len", ctypes.c_uint64),
                ("segs", ctypes.POINTER(IciSegC)),
                ("nsegs", ctypes.c_uint64),
                ("err_text", ctypes.c_void_p),
                ("retry_after_ms", ctypes.c_uint64),
                # native att custody (call4 only): the response seg list
                # parked under att_handle; seg0_* mirrors segs[0] inline
                # so the 1-seg shape needs no pointer deref (segs stays
                # NULL then — nothing to free)
                ("att_handle", ctypes.c_uint64),
                ("seg0_key", ctypes.c_uint64),
                ("seg0_nbytes", ctypes.c_uint64),
                ("seg0_dev", ctypes.c_int32),
                ("_pad", ctypes.c_int32)]


# relocation upcall: (key, target_dev) -> new key (0 = failure)
_ICI_RELOCATE_FN = ctypes.CFUNCTYPE(ctypes.c_uint64, ctypes.c_uint64,
                                    ctypes.c_int32)
# release upcall: native custody of a key ends on a drop path
_ICI_RELEASE_FN = ctypes.CFUNCTYPE(None, ctypes.c_uint64)
# async completion: (user, error_code, err_text, resp, resp_len, att,
# att_len) — fires once from the channel's reader thread
_ASYNC_CB = ctypes.CFUNCTYPE(None, ctypes.c_void_p, ctypes.c_uint64,
                             ctypes.c_char_p,
                             ctypes.POINTER(ctypes.c_uint8),
                             ctypes.c_uint64,
                             ctypes.POINTER(ctypes.c_uint8),
                             ctypes.c_uint64)
# ici request hook: (token, method, payload, payload_len, att_host,
# att_host_len, segs, nsegs, log_id, peer_dev)
_ICI_REQ_FN = ctypes.CFUNCTYPE(None, ctypes.c_uint64, ctypes.c_char_p,
                               ctypes.POINTER(ctypes.c_uint8),
                               ctypes.c_uint64,
                               ctypes.POINTER(ctypes.c_uint8),
                               ctypes.c_uint64,
                               ctypes.POINTER(IciSegC), ctypes.c_uint64,
                               ctypes.c_uint64, ctypes.c_int32)


class IciReqC(ctypes.Structure):
    """One packed request of the batched one-struct upcall ABI
    (native/rpc.cpp IciReqC): a single ctypes crossing hands the Python
    handler tier an ARRAY of these.  Pointers are borrowed for the
    duration of the upcall; seg keys are TAKEN by Python during it."""
    _fields_ = [("token", ctypes.c_uint64),
                ("method", ctypes.c_char_p),
                ("payload", ctypes.POINTER(ctypes.c_uint8)),
                ("payload_len", ctypes.c_uint64),
                ("att_host", ctypes.POINTER(ctypes.c_uint8)),
                ("att_host_len", ctypes.c_uint64),
                ("segs", ctypes.POINTER(IciSegC)),
                ("nsegs", ctypes.c_uint64),
                ("log_id", ctypes.c_uint64),
                ("recv_ns", ctypes.c_int64),
                ("peer_dev", ctypes.c_int32),
                ("_pad", ctypes.c_int32),
                # admission meta (appended; wire-encoded priority:
                # 0 = unset, 1..N = band 0..N-1)
                ("tenant", ctypes.c_char_p),
                ("deadline_left_ms", ctypes.c_uint64),
                ("priority", ctypes.c_int32),
                ("_pad2", ctypes.c_int32),
                # native att custody (appended, ISSUE 12): nonzero
                # att_handle parks the device-seg list natively; seg0_*
                # mirrors segs[0] so the dominant 1-seg shape reads
                # plain struct fields, never the segs pointer
                ("att_handle", ctypes.c_uint64),
                ("seg0_key", ctypes.c_uint64),
                ("seg0_nbytes", ctypes.c_uint64),
                ("seg0_dev", ctypes.c_int32),
                ("_pad3", ctypes.c_int32)]


class IciRespC(ctypes.Structure):
    """One packed response for brpc_tpu_ici_respond_batch — the batched
    write-back half (native/rpc.cpp IciRespC).  Seg custody transfers to
    native on the call; native releases it on every drop path."""
    _fields_ = [("token", ctypes.c_uint64),
                ("err", ctypes.c_uint64),
                ("err_text", ctypes.c_char_p),
                ("data", ctypes.POINTER(ctypes.c_uint8)),
                ("len", ctypes.c_uint64),
                ("att_host", ctypes.POINTER(ctypes.c_uint8)),
                ("att_host_len", ctypes.c_uint64),
                ("segs", ctypes.POINTER(IciSegC)),
                ("nsegs", ctypes.c_uint64),
                ("retry_after_ms", ctypes.c_uint64),
                # nonzero: pass a parked att-table entry back as this
                # response's attachment (segs/nsegs ignored) — the echo
                # pass-through never walks segs in Python
                ("att_handle", ctypes.c_uint64)]


# batched ici request upcall: (reqs, n)
_ICI_BATCH_FN = ctypes.CFUNCTYPE(None, ctypes.POINTER(IciReqC),
                                 ctypes.c_uint64)


_unavailable_logged = False


def _unavailable(why: str) -> None:
    """The pure-Python implementations take over — said once, at error:
    a process that believes it runs the native datapath and does not is
    the failure this line exists for."""
    global _unavailable_logged
    if _unavailable_logged:
        return
    _unavailable_logged = True
    from . import logging as log
    log.error("native core unavailable (%s); every native-gated path runs "
              "its pure-Python implementation", why)


def _build() -> bool:
    try:
        subprocess.run(["make", "-C", _NATIVE_DIR, "libbrpc_tpu_core.so"],
                       check=True, capture_output=True, timeout=300)
        return True
    except subprocess.CalledProcessError as e:
        _unavailable("make -C native failed: "
                     + e.stderr.decode(errors="replace")[-2000:])
    except Exception as e:
        _unavailable(f"make -C native: {type(e).__name__}: {e}")
    return False


def load() -> Optional[ctypes.CDLL]:
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        if not os.path.exists(_SO) and not _build():
            return None
        try:
            lib = ctypes.CDLL(_SO)
            if not hasattr(lib, _BRPC_TPU_NEWEST_SYMBOL_):
                # stale .so predating native/rpc.cpp: rebuild, then load
                # through a unique temp copy — dlopen dedups by pathname,
                # so re-opening _SO would return the stale mapping
                if not _build():
                    return None
                import shutil
                import tempfile
                tmp = tempfile.NamedTemporaryFile(
                    suffix=".so", prefix="brpc_tpu_core_", delete=False)
                tmp.close()
                shutil.copy(_SO, tmp.name)
                lib = ctypes.CDLL(tmp.name)
                if not hasattr(lib, _BRPC_TPU_NEWEST_SYMBOL_):
                    _unavailable(f"rebuilt {_SO} still lacks "
                                 f"{_BRPC_TPU_NEWEST_SYMBOL_}")
                    return None
            return _bind(lib)
        except (OSError, AttributeError) as e:
            # broken core library → none; callers fall back to the
            # pure-Python implementations
            _unavailable(f"{type(e).__name__}: {e}")
            return None


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    global _lib
    # signatures
    lib.brpc_tpu_pool_new.restype = ctypes.c_void_p
    lib.brpc_tpu_pool_get.restype = ctypes.c_uint64
    lib.brpc_tpu_pool_get.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    lib.brpc_tpu_pool_address.restype = ctypes.c_void_p
    lib.brpc_tpu_pool_address.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.brpc_tpu_pool_put.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.brpc_tpu_pool_live.restype = ctypes.c_uint64
    lib.brpc_tpu_pool_live.argtypes = [ctypes.c_void_p]
    lib.brpc_tpu_butex_new.restype = ctypes.c_void_p
    lib.brpc_tpu_butex_new.argtypes = [ctypes.c_int32]
    lib.brpc_tpu_butex_wait.restype = ctypes.c_int
    lib.brpc_tpu_butex_wait.argtypes = [ctypes.c_void_p, ctypes.c_int32,
                                        ctypes.c_int64]
    lib.brpc_tpu_butex_set_wake_all.argtypes = [ctypes.c_void_p,
                                                ctypes.c_int32]
    lib.brpc_tpu_butex_value.restype = ctypes.c_int32
    lib.brpc_tpu_butex_value.argtypes = [ctypes.c_void_p]
    lib.brpc_tpu_sched_start.argtypes = [ctypes.c_int]
    lib.brpc_tpu_sched_spawn.restype = ctypes.c_uint64
    lib.brpc_tpu_sched_spawn.argtypes = [_FIBER_FN, ctypes.c_void_p,
                                         ctypes.c_int]
    lib.brpc_tpu_sched_join.restype = ctypes.c_int
    lib.brpc_tpu_sched_join.argtypes = [ctypes.c_uint64, ctypes.c_int64]
    lib.brpc_tpu_sched_selftest.restype = ctypes.c_int64
    lib.brpc_tpu_sched_selftest.argtypes = [ctypes.c_int]
    lib.brpc_tpu_sched_completed.restype = ctypes.c_uint64
    lib.brpc_tpu_sched_spawned.restype = ctypes.c_uint64
    lib.brpc_tpu_mpsc_new.restype = ctypes.c_void_p
    lib.brpc_tpu_mpsc_push.restype = ctypes.c_int
    lib.brpc_tpu_mpsc_push.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                       ctypes.c_uint64]
    lib.brpc_tpu_mpsc_drain.restype = ctypes.c_uint64
    lib.brpc_tpu_mpsc_drain.argtypes = [ctypes.c_void_p, _SINK_FN,
                                        ctypes.c_void_p]
    lib.brpc_tpu_blockpool_new.restype = ctypes.c_void_p
    lib.brpc_tpu_blockpool_new.argtypes = [ctypes.c_uint64, ctypes.c_uint64]
    lib.brpc_tpu_blockpool_alloc.restype = ctypes.c_void_p
    lib.brpc_tpu_blockpool_alloc.argtypes = [ctypes.c_void_p]
    lib.brpc_tpu_blockpool_release.restype = ctypes.c_int
    lib.brpc_tpu_blockpool_release.argtypes = [ctypes.c_void_p,
                                               ctypes.c_void_p]
    lib.brpc_tpu_blockpool_free_count.restype = ctypes.c_uint64
    lib.brpc_tpu_blockpool_free_count.argtypes = [ctypes.c_void_p]
    lib.brpc_tpu_timer_schedule.restype = ctypes.c_uint64
    lib.brpc_tpu_timer_schedule.argtypes = [_TIMER_FN, ctypes.c_void_p,
                                            ctypes.c_int64]
    lib.brpc_tpu_timer_unschedule.restype = ctypes.c_int
    lib.brpc_tpu_timer_unschedule.argtypes = [ctypes.c_uint64]
    lib.brpc_tpu_native_echo_p50_ns.restype = ctypes.c_int64
    lib.brpc_tpu_native_echo_p50_ns.argtypes = [ctypes.c_int,
                                                ctypes.c_int]
    # ---- native RPC datapath (native/rpc.cpp) ----
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.brpc_tpu_nserver_start.restype = ctypes.c_uint64
    lib.brpc_tpu_nserver_start.argtypes = [ctypes.c_int]
    lib.brpc_tpu_nserver_port.restype = ctypes.c_int
    lib.brpc_tpu_nserver_port.argtypes = [ctypes.c_uint64]
    lib.brpc_tpu_nserver_register_echo.restype = ctypes.c_int
    lib.brpc_tpu_nserver_register_echo.argtypes = [ctypes.c_uint64,
                                                   ctypes.c_char_p]
    lib.brpc_tpu_nserver_set_handler.restype = ctypes.c_int
    lib.brpc_tpu_nserver_set_handler.argtypes = [ctypes.c_uint64,
                                                 _NREQ_FN]
    lib.brpc_tpu_nserver_requests.restype = ctypes.c_uint64
    lib.brpc_tpu_nserver_requests.argtypes = [ctypes.c_uint64]
    lib.brpc_tpu_nserver_respond.restype = ctypes.c_int
    lib.brpc_tpu_nserver_respond.argtypes = [
        ctypes.c_uint64, ctypes.c_uint64, ctypes.c_char_p, u8p,
        ctypes.c_uint64, u8p, ctypes.c_uint64]
    lib.brpc_tpu_nserver_stop.argtypes = [ctypes.c_uint64]
    lib.brpc_tpu_nchannel_connect.restype = ctypes.c_uint64
    lib.brpc_tpu_nchannel_connect.argtypes = [ctypes.c_char_p,
                                              ctypes.c_int]
    lib.brpc_tpu_nchannel_call.restype = ctypes.c_uint64
    lib.brpc_tpu_nchannel_call.argtypes = [
        ctypes.c_uint64, ctypes.c_char_p, u8p, ctypes.c_uint64, u8p,
        ctypes.c_uint64, ctypes.c_int64, ctypes.POINTER(u8p),
        ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(u8p),
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_char_p)]
    lib.brpc_tpu_buf_free.argtypes = [ctypes.c_void_p]
    lib.brpc_tpu_nchannel_close.argtypes = [ctypes.c_uint64]
    lib.brpc_tpu_nchannel_call_async.restype = ctypes.c_uint64
    lib.brpc_tpu_nchannel_call_async.argtypes = [
        ctypes.c_uint64, ctypes.c_char_p, u8p, ctypes.c_uint64, u8p,
        ctypes.c_uint64, ctypes.c_int64, _ASYNC_CB, ctypes.c_void_p]
    lib.brpc_tpu_npool_connect.restype = ctypes.c_uint64
    lib.brpc_tpu_npool_connect.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                           ctypes.c_int]
    lib.brpc_tpu_npool_call.restype = ctypes.c_uint64
    lib.brpc_tpu_npool_call.argtypes = [
        ctypes.c_uint64, ctypes.c_char_p, u8p, ctypes.c_uint64, u8p,
        ctypes.c_uint64, ctypes.c_int64, ctypes.POINTER(u8p),
        ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(u8p),
        ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_char_p)]
    lib.brpc_tpu_npool_close.argtypes = [ctypes.c_uint64]
    lib.brpc_tpu_native_rpc_echo_p50_ns.restype = ctypes.c_int64
    lib.brpc_tpu_native_rpc_echo_p50_ns.argtypes = [ctypes.c_int,
                                                    ctypes.c_int]
    lib.brpc_tpu_native_rpc_qps.restype = ctypes.c_double
    lib.brpc_tpu_native_rpc_qps.argtypes = [ctypes.c_int, ctypes.c_int,
                                            ctypes.c_int]
    # ---- native ici:// plane (native/rpc.cpp ici section) ----
    segp = ctypes.POINTER(IciSegC)
    lib.brpc_tpu_ici_set_hooks.argtypes = [_ICI_RELOCATE_FN, _ICI_RELEASE_FN]
    lib.brpc_tpu_ici_listen.restype = ctypes.c_uint64
    lib.brpc_tpu_ici_listen.argtypes = [ctypes.c_int32, _ICI_REQ_FN]
    lib.brpc_tpu_ici_register_echo.restype = ctypes.c_int
    lib.brpc_tpu_ici_register_echo.argtypes = [ctypes.c_uint64,
                                               ctypes.c_char_p]
    lib.brpc_tpu_ici_set_handler.restype = ctypes.c_int
    lib.brpc_tpu_ici_set_handler.argtypes = [ctypes.c_uint64, _ICI_REQ_FN]
    lib.brpc_tpu_ici_requests.restype = ctypes.c_uint64
    lib.brpc_tpu_ici_requests.argtypes = [ctypes.c_uint64]
    lib.brpc_tpu_ici_has_listener.restype = ctypes.c_int
    lib.brpc_tpu_ici_has_listener.argtypes = [ctypes.c_int32]
    lib.brpc_tpu_ici_unlisten.argtypes = [ctypes.c_uint64]
    lib.brpc_tpu_ici_connect.restype = ctypes.c_uint64
    lib.brpc_tpu_ici_connect.argtypes = [ctypes.c_int32, ctypes.c_int32,
                                         ctypes.c_int64]
    lib.brpc_tpu_ici_close.argtypes = [ctypes.c_uint64]
    lib.brpc_tpu_ici_window_left.restype = ctypes.c_int64
    lib.brpc_tpu_ici_window_left.argtypes = [ctypes.c_uint64]
    lib.brpc_tpu_ici_call2.restype = ctypes.c_uint64
    lib.brpc_tpu_ici_call2.argtypes = [
        ctypes.c_uint64, ctypes.c_char_p, u8p, ctypes.c_uint64, u8p,
        ctypes.c_uint64, segp, ctypes.c_uint64, ctypes.c_int64,
        ctypes.POINTER(IciCallOut)]
    # call2 + admission meta (priority wire-encoded, tenant, remaining
    # deadline budget; out.retry_after_ms carries the shed hint back) +
    # native att custody on the response (out.att_handle + seg0 inline;
    # error-path response segs released natively)
    lib.brpc_tpu_ici_call4.restype = ctypes.c_uint64
    lib.brpc_tpu_ici_call4.argtypes = [
        ctypes.c_uint64, ctypes.c_char_p, u8p, ctypes.c_uint64, u8p,
        ctypes.c_uint64, segp, ctypes.c_uint64, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_char_p, ctypes.c_int64,
        ctypes.POINTER(IciCallOut)]
    # native att custody handle ops: each consumes the handle exactly
    # once (take = Python assumed the keys; dispose = release upcalls)
    lib.brpc_tpu_ici_att_take.restype = ctypes.c_int64
    lib.brpc_tpu_ici_att_take.argtypes = [ctypes.c_uint64]
    lib.brpc_tpu_ici_att_dispose.restype = ctypes.c_int
    lib.brpc_tpu_ici_att_dispose.argtypes = [ctypes.c_uint64]
    lib.brpc_tpu_ici_att_peek.restype = ctypes.c_int64
    lib.brpc_tpu_ici_att_peek.argtypes = [ctypes.c_uint64, segp,
                                          ctypes.c_uint64]
    lib.brpc_tpu_ici_att_count.restype = ctypes.c_uint64
    lib.brpc_tpu_ici_att_count.argtypes = []
    lib.brpc_tpu_ici_respond.restype = ctypes.c_int
    lib.brpc_tpu_ici_respond.argtypes = [
        ctypes.c_uint64, ctypes.c_uint64, ctypes.c_char_p, u8p,
        ctypes.c_uint64, u8p, ctypes.c_uint64, segp, ctypes.c_uint64]
    lib.brpc_tpu_ici_listen_batch.restype = ctypes.c_uint64
    lib.brpc_tpu_ici_listen_batch.argtypes = [ctypes.c_int32,
                                              _ICI_BATCH_FN]
    lib.brpc_tpu_ici_set_batch_params.restype = ctypes.c_int
    lib.brpc_tpu_ici_set_batch_params.argtypes = [
        ctypes.c_uint64, ctypes.c_int64, ctypes.c_int64]
    lib.brpc_tpu_ici_batch_stats.restype = ctypes.c_int
    lib.brpc_tpu_ici_batch_stats.argtypes = [
        ctypes.c_uint64, ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64)]
    lib.brpc_tpu_ici_respond_batch.restype = ctypes.c_int
    lib.brpc_tpu_ici_respond_batch.argtypes = [ctypes.POINTER(IciRespC),
                                               ctypes.c_uint64]
    lib.brpc_tpu_ici_echo_p50_ns.restype = ctypes.c_int64
    lib.brpc_tpu_ici_echo_p50_ns.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.c_uint64, ctypes.c_uint64,
        ctypes.c_int32]
    # fabric bulk data plane (native/fabric.cpp): uuid-tagged bulk frames
    # over a dedicated per-socket-pair TCP connection
    lib.brpc_tpu_fab_listen.restype = ctypes.c_uint64
    lib.brpc_tpu_fab_listen.argtypes = [ctypes.c_char_p,
                                        ctypes.POINTER(ctypes.c_int),
                                        ctypes.c_char_p, ctypes.c_int]
    lib.brpc_tpu_fab_connect_uds.restype = ctypes.c_uint64
    lib.brpc_tpu_fab_connect_uds.argtypes = [ctypes.c_char_p,
                                             ctypes.c_char_p]
    lib.brpc_tpu_fab_accept.restype = ctypes.c_uint64
    lib.brpc_tpu_fab_accept.argtypes = [ctypes.c_uint64, ctypes.c_char_p,
                                        ctypes.c_int64]
    lib.brpc_tpu_fab_connect.restype = ctypes.c_uint64
    lib.brpc_tpu_fab_connect.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                         ctypes.c_char_p]
    lib.brpc_tpu_fab_send.restype = ctypes.c_int
    lib.brpc_tpu_fab_send.argtypes = [ctypes.c_uint64, ctypes.c_uint64,
                                      u8p, ctypes.c_uint64]
    lib.brpc_tpu_fab_sendv.restype = ctypes.c_int
    lib.brpc_tpu_fab_sendv.argtypes = [
        ctypes.c_uint64, ctypes.c_uint64, ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_uint64), ctypes.c_int]
    lib.brpc_tpu_fab_recv.restype = ctypes.c_int
    lib.brpc_tpu_fab_recv.argtypes = [
        ctypes.c_uint64, ctypes.c_uint64, ctypes.c_int64,
        ctypes.POINTER(u8p), ctypes.POINTER(ctypes.c_uint64)]
    lib.brpc_tpu_fab_bytes.restype = ctypes.c_uint64
    lib.brpc_tpu_fab_bytes.argtypes = [ctypes.c_uint64, ctypes.c_int]
    lib.brpc_tpu_fab_buf_release.argtypes = [ctypes.c_uint64, u8p,
                                             ctypes.c_uint64]
    lib.brpc_tpu_fab_conn_close.argtypes = [ctypes.c_uint64]
    lib.brpc_tpu_fab_listener_close.argtypes = [ctypes.c_uint64]
    lib.brpc_tpu_fab_alive.restype = ctypes.c_int
    lib.brpc_tpu_fab_alive.argtypes = [ctypes.c_uint64]
    # deterministic chaos hooks (fault injection for the chaos harness)
    lib.brpc_tpu_fab_chaos.restype = ctypes.c_int
    lib.brpc_tpu_fab_chaos.argtypes = [ctypes.c_uint64, ctypes.c_int,
                                       ctypes.c_int64]
    lib.brpc_tpu_fab_quiesce.restype = None
    lib.brpc_tpu_fab_quiesce.argtypes = []
    lib.brpc_tpu_fab_chaos_listener.restype = ctypes.c_int
    lib.brpc_tpu_fab_chaos_listener.argtypes = [ctypes.c_uint64,
                                                ctypes.c_int64]
    # per-pair plane registry (pod observability): conns tagged with the
    # peer pid, aggregated per pair
    lib.brpc_tpu_fab_set_peer.restype = None
    lib.brpc_tpu_fab_set_peer.argtypes = [ctypes.c_uint64, ctypes.c_int32]
    lib.brpc_tpu_fab_pair_stats.restype = ctypes.c_int
    lib.brpc_tpu_fab_pair_stats.argtypes = [
        ctypes.c_int32, ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64)]
    lib.brpc_tpu_fab_peer_list.restype = ctypes.c_int
    lib.brpc_tpu_fab_peer_list.argtypes = [ctypes.POINTER(ctypes.c_int32),
                                           ctypes.c_int]
    # same-host shared-memory ring tier (native/fabric.cpp nshm): one
    # mmap'd /dev/shm segment per fabric socket pair, futex doorbells,
    # zero-copy claims retired on release (consume-to-release credit)
    lib.brpc_tpu_shm_create.restype = ctypes.c_uint64
    lib.brpc_tpu_shm_create.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
    lib.brpc_tpu_shm_attach.restype = ctypes.c_uint64
    lib.brpc_tpu_shm_attach.argtypes = [ctypes.c_char_p]
    lib.brpc_tpu_shm_unlink.restype = ctypes.c_int
    lib.brpc_tpu_shm_unlink.argtypes = [ctypes.c_char_p]
    lib.brpc_tpu_shm_send.restype = ctypes.c_int
    lib.brpc_tpu_shm_send.argtypes = [ctypes.c_uint64, ctypes.c_uint64,
                                      u8p, ctypes.c_uint64, ctypes.c_int64]
    lib.brpc_tpu_shm_sendv.restype = ctypes.c_int
    lib.brpc_tpu_shm_sendv.argtypes = [
        ctypes.c_uint64, ctypes.c_uint64, ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_uint64), ctypes.c_int, ctypes.c_int64]
    lib.brpc_tpu_shm_recv.restype = ctypes.c_int
    lib.brpc_tpu_shm_recv.argtypes = [
        ctypes.c_uint64, ctypes.c_uint64, ctypes.c_int64,
        ctypes.POINTER(u8p), ctypes.POINTER(ctypes.c_uint64)]
    lib.brpc_tpu_shm_release.restype = None
    lib.brpc_tpu_shm_release.argtypes = [ctypes.c_uint64, u8p,
                                         ctypes.c_uint64]
    lib.brpc_tpu_shm_alive.restype = ctypes.c_int
    lib.brpc_tpu_shm_alive.argtypes = [ctypes.c_uint64]
    lib.brpc_tpu_shm_mark_dead.restype = None
    lib.brpc_tpu_shm_mark_dead.argtypes = [ctypes.c_uint64]
    lib.brpc_tpu_shm_close.restype = None
    lib.brpc_tpu_shm_close.argtypes = [ctypes.c_uint64]
    lib.brpc_tpu_shm_chaos.restype = ctypes.c_int
    lib.brpc_tpu_shm_chaos.argtypes = [ctypes.c_uint64, ctypes.c_int,
                                       ctypes.c_int64]
    lib.brpc_tpu_shm_stats.restype = ctypes.c_int
    lib.brpc_tpu_shm_stats.argtypes = [ctypes.c_uint64,
                                       ctypes.POINTER(ctypes.c_uint64),
                                       ctypes.c_int]
    # striped shm (ISSUE 12): N independent ring pairs per segment with
    # explicit per-call stripe selection; a 1-stripe segment is the v1
    # layout byte-for-byte (create2 delegates)
    lib.brpc_tpu_shm_create2.restype = ctypes.c_uint64
    lib.brpc_tpu_shm_create2.argtypes = [ctypes.c_char_p, ctypes.c_uint64,
                                         ctypes.c_uint32]
    lib.brpc_tpu_shm_send2.restype = ctypes.c_int
    lib.brpc_tpu_shm_send2.argtypes = [
        ctypes.c_uint64, ctypes.c_uint32, ctypes.c_uint64, u8p,
        ctypes.c_uint64, ctypes.c_int64]
    lib.brpc_tpu_shm_sendv2.restype = ctypes.c_int
    lib.brpc_tpu_shm_sendv2.argtypes = [
        ctypes.c_uint64, ctypes.c_uint32, ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_uint64),
        ctypes.c_int, ctypes.c_int64]
    lib.brpc_tpu_shm_recv2.restype = ctypes.c_int
    lib.brpc_tpu_shm_recv2.argtypes = [
        ctypes.c_uint64, ctypes.c_uint32, ctypes.c_uint64, ctypes.c_int64,
        ctypes.POINTER(u8p), ctypes.POINTER(ctypes.c_uint64)]
    lib.brpc_tpu_shm_stripes.restype = ctypes.c_uint32
    lib.brpc_tpu_shm_stripes.argtypes = [ctypes.c_uint64]
    lib.brpc_tpu_shm_stripe_stats.restype = ctypes.c_int
    lib.brpc_tpu_shm_stripe_stats.argtypes = [
        ctypes.c_uint64, ctypes.c_uint32,
        ctypes.POINTER(ctypes.c_uint64), ctypes.c_int]
    _lib = lib
    return _lib



def available() -> bool:
    return load() is not None


class NativeScheduler:
    """Fiber scheduler facade.  Python callables never run on fiber stacks
    (CPython's stack-bound checks fault on ucontext stacks); cross-language
    work is submitted as native ops.  ``selftest(n)`` exercises the full
    spawn/steal/join machinery natively."""

    def __init__(self, workers: int = 4):
        self.lib = load()
        if self.lib is None:
            raise RuntimeError("native core unavailable")
        self.lib.brpc_tpu_sched_start(workers)

    def selftest(self, n: int) -> int:
        return self.lib.brpc_tpu_sched_selftest(n)

    def completed(self) -> int:
        return self.lib.brpc_tpu_sched_completed()

    def spawned(self) -> int:
        return self.lib.brpc_tpu_sched_spawned()


def native_echo_p50_us(iters: int = 2000, payload: int = 4096) -> float:
    """Native epoll TCP echo round-trip p50 (µs); -1 if unavailable."""
    lib = load()
    if lib is None:
        return -1.0
    ns = lib.brpc_tpu_native_echo_p50_ns(iters, payload)
    return ns / 1000.0 if ns > 0 else -1.0


def native_rpc_echo_p50_us(iters: int = 3000, payload: int = 4096) -> float:
    """Full native RPC stack echo p50 (µs): channel → TRPC frame → epoll
    server → dispatch → response → correlation wake, all in native/rpc.cpp.
    -1 if unavailable."""
    lib = load()
    if lib is None:
        return -1.0
    ns = lib.brpc_tpu_native_rpc_echo_p50_ns(iters, payload)
    return ns / 1000.0 if ns > 0 else -1.0


def native_rpc_qps(threads: int = 16, duration_ms: int = 1500,
                   payload: int = 128) -> float:
    """Multi-threaded native RPC echo QPS; -1 if unavailable."""
    lib = load()
    if lib is None:
        return -1.0
    return lib.brpc_tpu_native_rpc_qps(threads, duration_ms, payload)
