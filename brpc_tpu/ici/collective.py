"""Mesh collectives: the lowering target for combo channels.

SURVEY.md §2.6: the reference's ParallelChannel broadcast/scatter + merge is
re-expressed here as XLA collectives over the ICI mesh — psum/all_gather/
reduce_scatter/ppermute compiled once per (op, shape, dtype) via shard_map
and cached.  These are *scheduled* device programs, not per-socket writes:
every mesh participant enters the same program (the SPMD ordering constraint
called out in SURVEY.md §7 "hard parts"), which is why combo-channel calls
compile to ONE program instead of N point-to-point sockets.

All functions take/return global ``jax.Array``s sharded over the mesh axis
(leading dimension = mesh size unless noted).
"""
from __future__ import annotations

import threading
from typing import Callable, Dict, Optional, Tuple

from .mesh import IciMesh


class Collectives:
    def __init__(self, mesh: Optional[IciMesh] = None):
        self.mesh = mesh or IciMesh.default()
        self._cache: Dict[Tuple, Callable] = {}
        self._building: Dict[Tuple, threading.Event] = {}
        self._cache_lock = threading.Lock()

    # -- plumbing --------------------------------------------------------
    def _cached(self, key: Tuple, builder: Callable[[], Callable]) -> Callable:
        """Compile-or-fetch with the build OUTSIDE the cache lock: an
        XLA compile can take seconds, and holding ``_cache_lock`` across
        it blocked every OTHER key's lookup for the duration (ISSUE 11
        satellite bugfix; the once-guard idiom lives in
        butil/once_cache.py, shared with the fan-out plane's cache)."""
        from ..butil.once_cache import build_once
        return build_once(self._cache_lock, self._cache, self._building,
                          key, builder)

    def _shard_map(self, fn, in_spec, out_spec):
        import jax
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        return jax.jit(shard_map(
            fn, mesh=self.mesh.mesh, in_specs=in_spec, out_specs=out_spec,
            check_vma=False))

    def shard(self, x):
        """Place a (mesh_size, ...) array with one row per device."""
        import jax
        from jax.sharding import PartitionSpec as P
        return jax.device_put(
            x, jax.sharding.NamedSharding(self.mesh.mesh,
                                          P(self.mesh.axis_name)))

    def replicate(self, x):
        import jax
        from jax.sharding import PartitionSpec as P
        return jax.device_put(
            x, jax.sharding.NamedSharding(self.mesh.mesh, P()))

    # -- collectives -----------------------------------------------------
    def all_reduce(self, x):
        """Sum over the mesh axis; in: (n, ...) sharded, out: (...) summed,
        replicated (ParallelChannel response-merge as a reduction)."""
        import jax
        from jax.sharding import PartitionSpec as P
        ax = self.mesh.axis_name
        key = ("all_reduce", x.shape, str(x.dtype))

        def build():
            def f(xs):                      # xs: (1, ...) local shard
                return jax.lax.psum(xs[0], ax)
            return self._shard_map(f, P(ax), P())
        return self._cached(key, build)(x)

    def all_gather(self, x):
        """in: (n, ...) sharded → out: (n, ...) fully replicated (every
        device sees every response)."""
        import jax
        from jax.sharding import PartitionSpec as P
        ax = self.mesh.axis_name
        key = ("all_gather", x.shape, str(x.dtype))

        def build():
            def f(xs):
                return jax.lax.all_gather(xs[0], ax)
            return self._shard_map(f, P(ax), P())
        return self._cached(key, build)(x)

    def reduce_scatter(self, x):
        """in: (n, n, ...) sharded on dim0 → out: (n, ...) sharded: device d
        gets sum_s x[s, d] (gradient-bucket exchange)."""
        import jax
        from jax.sharding import PartitionSpec as P
        ax = self.mesh.axis_name
        key = ("reduce_scatter", x.shape, str(x.dtype))

        def build():
            def f(xs):                      # xs: (1, n, ...)
                return jax.lax.psum_scatter(
                    xs[0], ax, scatter_dimension=0, tiled=True)[None]
            return self._shard_map(f, P(ax), P(ax))
        return self._cached(key, build)(x)

    def ppermute(self, x, shift: int = 1):
        """Rotate shards around the ring by ``shift`` hops (the chained
        Send/Recv primitive; streaming/sequence pipelines build on this)."""
        import jax
        from jax.sharding import PartitionSpec as P
        ax = self.mesh.axis_name
        n = self.mesh.size
        perm = [(i, (i + shift) % n) for i in range(n)]
        key = ("ppermute", x.shape, str(x.dtype), shift)

        def build():
            def f(xs):
                return jax.lax.ppermute(xs, ax, perm)
            return self._shard_map(f, P(ax), P(ax))
        return self._cached(key, build)(x)

    def broadcast(self, x, root: int = 0):
        """Replicate device ``root``'s row to all devices
        (ParallelChannel request replication)."""
        import jax
        from jax.sharding import PartitionSpec as P
        ax = self.mesh.axis_name
        key = ("broadcast", x.shape, str(x.dtype), root)

        def build():
            def f(xs):                      # (1, ...) local
                g = jax.lax.all_gather(xs[0], ax)   # (n, ...)
                return g[root]
            return self._shard_map(f, P(ax), P())
        return self._cached(key, build)(x)

    def all_to_all(self, x):
        """in: (n, n, ...) sharded dim0 — row s holds what s sends to every
        d → out: (n, n, ...) sharded: row d holds what every s sent to d
        (PartitionChannel resharding)."""
        import jax
        from jax.sharding import PartitionSpec as P
        ax = self.mesh.axis_name
        key = ("all_to_all", x.shape, str(x.dtype))

        def build():
            def f(xs):                      # (1, n, ...) local row
                return jax.lax.all_to_all(xs, ax, split_axis=1,
                                          concat_axis=1, tiled=True)
            return self._shard_map(f, P(ax), P(ax))
        return self._cached(key, build)(x)


_default_collectives: Optional[Collectives] = None
_default_lock = threading.Lock()


def default_collectives() -> Collectives:
    global _default_collectives
    with _default_lock:
        if _default_collectives is None:
            _default_collectives = Collectives()
        return _default_collectives
