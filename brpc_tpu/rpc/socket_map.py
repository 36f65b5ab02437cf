"""SocketMap: process-global EndPoint → single-connection cache.

Reference: src/brpc/socket_map.{h,cpp} (SocketMapInsert :82,
SingleConnection :180).  Channels to the same endpoint share one "single"
connection; pooled and short connections hang off it (GetPooledSocket).
Failed sockets are replaced on next use and handed to the health checker.
"""
from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional

from ..butil.endpoint import EndPoint, SCHEME_MEM, SCHEME_TCP, SCHEME_ICI
from .socket import Socket


class _SingleConnection:
    def __init__(self):
        self.socket: Optional[Socket] = None
        self.pooled: List[Socket] = []       # idle pooled connections
        self.lock = threading.Lock()


class SocketMap:
    _instance = None
    _instance_lock = threading.Lock()

    def __init__(self):
        self._map: Dict[tuple, _SingleConnection] = {}
        self._lock = threading.Lock()

    @classmethod
    def instance(cls) -> "SocketMap":
        with cls._instance_lock:
            if cls._instance is None:
                cls._instance = SocketMap()
            return cls._instance

    def _entry(self, ep: EndPoint,
               group: Any = "") -> _SingleConnection:
        # key = (endpoint, channel signature): channels speaking different
        # protocols to one endpoint must not share a connection, because
        # the peer locks each connection to the first detected protocol
        # (reference channel.cpp ComputeChannelSignature folds protocol
        # and auth into the SocketMapKey)
        key = (ep, group)
        with self._lock:
            e = self._map.get(key)
            if e is None:
                e = _SingleConnection()
                self._map[key] = e
            return e

    def get_socket(self, ep: EndPoint, messenger=None,
                   ssl_context=None, group: Any = "",
                   connect_timeout: float = 5.0,
                   ici_local_device: Optional[int] = None) -> Socket:
        """The shared 'single' connection to ep (creates/replaces lazily).
        ``ici_local_device`` (ici:// only) is the caller's residence —
        part of the caller's ``group``, so it is the same for every user
        of one entry."""
        e = self._entry(ep, group)
        with e.lock:
            if e.socket is not None and not e.socket.failed \
                    and not e.socket.logoff:
                return e.socket
            s = self._checked_connect(ep, ssl_context, connect_timeout,
                                      ici_local_device)
            s.messenger = messenger
            e.socket = s
            return s

    def get_pooled_socket(self, ep: EndPoint, messenger=None,
                          group: Any = "", ssl_context=None,
                          connect_timeout: float = 5.0,
                          ici_local_device: Optional[int] = None) -> Socket:
        """An exclusive connection from the pool (reference
        GetPooledSocket); return it with return_pooled_socket."""
        e = self._entry(ep, group)
        with e.lock:
            while e.pooled:
                s = e.pooled.pop()
                if not s.failed and not s.logoff:
                    return s
        s = self._checked_connect(ep, ssl_context, connect_timeout,
                                  ici_local_device)
        s.messenger = messenger
        return s

    @classmethod
    def _checked_connect(cls, ep: EndPoint, ssl_context=None,
                         connect_timeout: float = 5.0,
                         ici_local_device: Optional[int] = None) -> Socket:
        """_connect, but an unreachable endpoint is handed to the health
        checker before the error propagates: the reference starts a
        health check whenever a connect fails, which keeps a DOWN
        endpoint under backoff probing across the whole outage (a failed
        connect creates no socket, so the socket-failure hand-off alone
        would miss retries issued while the peer is gone)."""
        try:
            return cls._connect(ep, ssl_context, connect_timeout,
                                ici_local_device)
        except Exception:
            try:
                from .health_check import start_health_check
                start_health_check(ep)
            except Exception:
                pass
            raise

    def return_pooled_socket(self, ep: EndPoint, s: Socket,
                             group: Any = "") -> None:
        if s.failed or s.logoff:
            return
        # do NOT auto-create the entry: close_endpoint() pops it, and a
        # pooled socket checked out across the close must be failed on
        # return, not resurrect the mapping (review finding)
        with self._lock:
            e = self._map.get((ep, group))
        if e is None:
            from . import errors
            s.set_failed(errors.ECLOSE, "endpoint closed while checked out")
            return
        with e.lock:
            e.pooled.append(s)

    def get_short_socket(self, ep: EndPoint, messenger=None,
                         ssl_context=None,
                         connect_timeout: float = 5.0,
                         ici_local_device: Optional[int] = None) -> Socket:
        s = self._checked_connect(ep, ssl_context, connect_timeout,
                                  ici_local_device)
        s.messenger = messenger
        return s

    @staticmethod
    def _connect(ep: EndPoint, ssl_context=None,
                 connect_timeout: float = 5.0,
                 ici_local_device: Optional[int] = None) -> Socket:
        if ep.scheme == SCHEME_MEM:
            from .mem_transport import mem_connect
            return mem_connect(ep.host)
        if ep.scheme == SCHEME_TCP:
            from .tcp_transport import tcp_connect
            return tcp_connect(ep, timeout=connect_timeout,
                               ssl_context=ssl_context)
        if ep.scheme == SCHEME_ICI:
            # routes in-process targets through the zero-copy IciSocket,
            # remote (other-controller) ones through the fabric
            from ..ici.fabric import connect_any
            return connect_any(ep, ici_local_device)
        raise ValueError(f"unsupported scheme {ep.scheme}")

    def remove(self, ep: EndPoint, group: Any = "") -> None:
        with self._lock:
            self._map.pop((ep, group), None)

    def close_endpoint(self, ep: EndPoint, group: Any = "") -> None:
        """Fail and drop every connection held for (ep, group): client
        teardown (Channel.close).  ECLOSE keeps the endpoint out of
        health-check revival — this is a deliberate local close, not a
        peer failure."""
        with self._lock:
            e = self._map.pop((ep, group), None)
        if e is None:
            return
        with e.lock:
            socks = list(e.pooled)
            if e.socket is not None:
                socks.append(e.socket)
            e.socket = None
            e.pooled = []
        from . import errors
        for s in socks:
            try:
                s.set_failed(errors.ECLOSE, "channel closed")
            except Exception:
                pass

    def stats(self) -> Dict[EndPoint, int]:
        with self._lock:
            out: Dict[EndPoint, int] = {}
            for (ep, _group), e in self._map.items():
                out[ep] = out.get(ep, 0) + \
                    (0 if e.socket is None or e.socket.failed else 1) + \
                    len(e.pooled)
            return out
