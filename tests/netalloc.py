"""Seeded port / UDS-path allocator (no jax dependency).

N-process tests (the chaos harness, the pod suite, the fabric suite)
need coordinator ports and unix-socket paths that (a) are DETERMINISTIC
per test — a failure reproduces with the same addresses — and (b) can't
collide when several pytest processes run the same suite on one host
(parallel CI).  The allocator hashes (tag, pid) into a seeded probe
sequence and bind-verifies each candidate, so two workers land on
disjoint ports by seed and the bind check catches any residual clash.

Lives outside conftest.py so the N-process harnesses in test_pod.py can
be imported by ``__graft_entry__.dryrun_multichip`` from a parent that
lacks the 8-device virtual mesh conftest asserts at import time (the
child processes set up their own jax environments).
"""
import hashlib
import os
import socket as _socket
import tempfile

_PORT_LO, _PORT_HI = 21000, 59000


def alloc_port(tag: str = "") -> int:
    """A free TCP port, seeded by (tag, pid): deterministic per test
    within a run, disjoint across parallel pytest processes."""
    seed = f"{tag}|{os.getpid()}"
    h = int.from_bytes(hashlib.sha1(seed.encode()).digest()[:4], "big")
    span = _PORT_HI - _PORT_LO
    for i in range(256):
        port = _PORT_LO + (h + i * 131) % span
        s = _socket.socket()
        try:
            s.setsockopt(_socket.SOL_SOCKET, _socket.SO_REUSEADDR, 1)
            s.bind(("127.0.0.1", port))
            return port
        except OSError:
            continue
        finally:
            s.close()
    s = _socket.socket()            # exhausted the seeded probes: any port
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def alloc_uds(tag: str = "") -> str:
    """A unix-socket path seeded the same way (unused on disk)."""
    seed = f"{tag}|{os.getpid()}"
    h = hashlib.sha1(seed.encode()).hexdigest()[:12]
    for i in range(64):
        path = os.path.join(tempfile.gettempdir(),
                            f"brpc_tpu_{h}_{i}.sock")
        if not os.path.exists(path):
            return path
    return tempfile.mktemp(prefix=f"brpc_tpu_{h}_", suffix=".sock")
