"""Combo-channel fan-out lowered to mesh collectives — the TPU-native path.

SURVEY.md §2.6 / BASELINE.json: "ParallelChannel/PartitionChannel fan-out
lowers to scatter/all_gather over the ICI mesh, turning combo-channels into
a collectives API."  This module is that lowering.  Where
``ParallelChannel.call_method`` issues N socket RPCs and merges N responses
on the host, a CollectiveChannel compiles the SAME semantics

    CallMapper(replicate|shard)  →  broadcast | already-sharded operand
    per-server handler           →  the device-local jitted method body
    ResponseMerger(sum|gather|concat) → psum | all_gather

into ONE SPMD program per (method, shapes) — the whole fan-out+merge rides
ICI at line rate with zero host round-trips.  This is also why it must be a
*scheduled* program rather than N queued sockets: every participant enters
the same collective in the same order (the SPMD deadlock constraint of
SURVEY.md §7).

Service methods register device-side handlers:

    ch = CollectiveChannel(mesh)
    ch.register("Shard.MatVec", lambda shard_idx, w, x: w @ x, merge="sum")
    y = ch.call("Shard.MatVec", w_sharded, x_replicated)
"""
from __future__ import annotations

import threading
from typing import Callable, Dict, Optional, Tuple

from ..ici.mesh import IciMesh

MERGE_SUM = "sum"           # ResponseMerger that adds (reduction)
MERGE_GATHER = "gather"     # ResponseMerger that stacks all responses
MERGE_CONCAT = "concat"     # stack along existing axis 0
MERGE_NONE = "none"         # keep responses sharded (each caller-shard keeps its own)

MAP_REPLICATE = "replicate"  # CallMapper: same request to every server
MAP_SHARD = "shard"          # CallMapper: row i of the request to server i


class _Method:
    __slots__ = ("name", "handler", "merge", "mapping", "takes_index")

    def __init__(self, name, handler, merge, mapping, takes_index):
        self.name = name
        self.handler = handler
        self.merge = merge
        self.mapping = mapping
        self.takes_index = takes_index


class CollectiveChannel:
    def __init__(self, mesh: Optional[IciMesh] = None):
        self.mesh = mesh or IciMesh.default()
        self._methods: Dict[str, _Method] = {}
        self._compiled: Dict[Tuple, Callable] = {}
        self._lock = threading.Lock()

    def register(self, name: str, handler: Callable, merge: str = MERGE_GATHER,
                 mapping: str = MAP_SHARD, takes_index: bool = False) -> None:
        """handler(*operands) -> result, operating on device-local shards.
        With takes_index=True the handler receives the device index first
        (the CallMapper's channel_index)."""
        self._methods[name] = _Method(name, handler, merge, mapping,
                                      takes_index)

    def shard(self, x):
        """Lay a (n, ...) operand out one-row-per-device (MAP_SHARD input)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(x, NamedSharding(self.mesh.mesh,
                                               P(self.mesh.axis_name)))

    def replicate(self, x):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(x, NamedSharding(self.mesh.mesh, P()))

    def _operand_is_sharded(self, o) -> bool:
        """Per-operand mapping: an operand laid out with the mesh axis on
        dim 0 is a sharded request (CallMapper::Map produced distinct
        sub-requests); anything else is replicated."""
        try:
            spec = o.sharding.spec
        except AttributeError:
            return False
        return len(spec) > 0 and spec[0] == self.mesh.axis_name

    def call(self, name: str, *operands):
        """One fan-out+merge as a single compiled mesh program."""
        md = self._methods[name]
        shard_flags = tuple(self._operand_is_sharded(o) for o in operands)
        key = (name, shard_flags) + tuple(
            (o.shape, str(o.dtype)) for o in operands)
        with self._lock:
            fn = self._compiled.get(key)
        if fn is None:
            fn = self._compile(md, operands, shard_flags)
            with self._lock:
                self._compiled[key] = fn
        return fn(*operands)

    def _compile(self, md: _Method, operands, shard_flags) -> Callable:
        import jax
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        ax = self.mesh.axis_name

        in_specs = tuple(P(ax) if flag else P() for flag in shard_flags)

        def program(*locals_):
            args = []
            for o, flag in zip(locals_, shard_flags):
                # sharded operands arrive as (1, ...): strip the shard dim
                args.append(o[0] if flag else o)
            if md.takes_index:
                idx = jax.lax.axis_index(ax)
                result = md.handler(idx, *args)
            else:
                result = md.handler(*args)
            if md.merge == MERGE_SUM:
                return jax.lax.psum(result, ax)
            if md.merge == MERGE_GATHER:
                return jax.lax.all_gather(result, ax)
            if md.merge == MERGE_CONCAT:
                return jax.lax.all_gather(result, ax, tiled=True)
            return result[None]         # MERGE_NONE: keep sharded rows

        out_spec = P() if md.merge in (MERGE_SUM, MERGE_GATHER, MERGE_CONCAT) \
            else P(ax)
        return jax.jit(shard_map(program, mesh=self.mesh.mesh,
                                 in_specs=in_specs, out_specs=out_spec,
                                 check_vma=False))
