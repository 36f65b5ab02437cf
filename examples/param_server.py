"""Parameter-server gradient push-pull with checkpoint/resume —
the rdma_performance "param-server" mode of BASELINE config 5, plus the
checkpointing SURVEY.md §5.4 calls out as the TPU build's responsibility.

A data-parallel trainer over the ICI mesh: each device computes a gradient
shard, ParallelChannel-merge-as-psum synchronizes them (one compiled
collective per step), and orbax checkpoints the replicated params so
training resumes exactly where it stopped.

Which route the benchmark measures: this example runs the LOWERED route (a
``CollectiveChannel`` over the mesh: one SPMD program, the merge a ``psum``).
The benchmark's deployment ``param_server_local`` (cell ``pushpull_4x16m``,
``benchmarks/configs/param_server_local.json``) measures the other one, the
per-member loop on ONE chip: a ``ParallelChannel`` of four sub-channels, the
range replicated by reference, each worker's float32 contribution an RPC
reply, ``CollectiveMerger(MERGE_SUM)`` summing them in one device program
(``brpc_fanout_gather``) into ``cntl.fanout_result``.  The mesh ``psum`` is
not measured by any cell yet (PERF.md section 7, ``allreduce_1g``).
"""
from __future__ import annotations

import os
import tempfile


def main(steps: int = 6, resume_at: int = 3) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import orbax.checkpoint as ocp

    from brpc_tpu.ici.mesh import IciMesh
    from brpc_tpu import channels

    mesh = IciMesh.default()
    n = mesh.size
    d = 32
    cc = channels.CollectiveChannel(mesh)

    # the "push-pull": every device pushes its gradient shard, pulls the sum
    cc.register("ParamServer.PushPull",
                lambda g_shard: g_shard,
                merge=channels.MERGE_SUM, mapping=channels.MAP_SHARD)

    key = jax.random.PRNGKey(0)
    w = jnp.zeros((d,), jnp.float32)
    target = jnp.linspace(0.0, 1.0, d)

    def local_grads(w, step):
        """Per-device gradient shards (n, d): simple quadratic loss with
        per-device minibatch noise."""
        g = 2 * (w - target)
        noise = jax.random.normal(
            jax.random.fold_in(key, step), (n, d)) * 0.01
        return cc.shard(g[None, :] + noise)

    ckpt_dir = tempfile.mkdtemp(prefix="brpc_tpu_ckpt_")
    ckptr = ocp.PyTreeCheckpointer()

    losses = []
    step = 0
    while step < steps:
        if step == resume_at:
            # simulate a restart: drop everything, restore from checkpoint
            restored = ckptr.restore(os.path.join(ckpt_dir, f"step_{step}"))
            w = jnp.asarray(restored["w"])
            assert int(restored["step"]) == step
            print(f"resumed from checkpoint at step {step}")
        grads = local_grads(w, step)
        g_sum = cc.call("ParamServer.PushPull", grads)   # psum over mesh
        w = w - 0.05 * (g_sum / n)
        loss = float(((w - target) ** 2).sum())
        losses.append(loss)
        step += 1
        if step == resume_at:
            ckptr.save(os.path.join(ckpt_dir, f"step_{step}"),
                       {"w": np.asarray(w), "step": step})
    print(f"losses: {[round(l, 4) for l in losses]}")
    assert losses[-1] < losses[0], "training must make progress"
    print(f"param-server push-pull over {n} devices: "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f} (checkpoint ok)")


if __name__ == "__main__":
    from brpc_tpu.butil import compile_cache
    compile_cache.enable()
    main()
