"""Allreduce bandwidth benchmark — the example/rdma_performance analogue
(BASELINE config 5): data-parallel gradient push-pull over the ICI mesh,
both the XLA-native psum path and the explicit ring pipeline."""
from __future__ import annotations

import time


def main(size_mb: int = 64) -> None:
    import jax
    import jax.numpy as jnp
    from brpc_tpu.ici.mesh import IciMesh
    from brpc_tpu.ici.collective import Collectives
    from brpc_tpu.ici.ring import ring_all_reduce

    mesh = IciMesh.default()
    coll = Collectives(mesh)
    n = mesh.size
    elems = size_mb * 1024 * 1024 // 4
    grads = coll.shard(jnp.ones((n, max(elems // max(n, 1), 1)), jnp.float32))
    nbytes = grads.size * 4

    for name, fn in (("xla psum", coll.all_reduce),
                     ("explicit ring", lambda x: ring_all_reduce(x, mesh))):
        out = fn(grads)
        jax.block_until_ready(out)       # compile + warm
        reps = 5
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(grads)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / reps
        print(f"{name:14s}: {nbytes/1e6:.0f} MB allreduce over {n} devices "
              f"in {dt*1e3:.1f} ms -> {nbytes/dt/1e9:.2f} GB/s")


if __name__ == "__main__":
    from brpc_tpu.butil import compile_cache
    compile_cache.enable()
    main()
