"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The task's long-context mandate, built directly on the fabric's collective
substrate (SURVEY.md §5.7 maps the reference's sliding-window streaming to
exactly this machinery):

  * ``ring_attention`` — K/V shards rotate around the ring (one ppermute
    per step, the RingStream pattern fused into the kernel's math) while
    every device keeps a numerically-stable running softmax over its local
    Q block (flash-attention style m/l accumulators).  Sequence length
    scales with mesh size; peak memory per chip stays O(seq/n).
  * ``ulysses_attention`` — the all-to-all alternative: reshard from
    sequence-sharded to head-sharded (one all_to_all), run plain attention
    per head group, reshard back.  Better when heads ≥ devices and ICI
    all-to-all bandwidth is plentiful.

Both compile to ONE XLA program via shard_map and are verified against the
dense reference in tests on the 8-device CPU mesh.
"""
from __future__ import annotations

import threading
from typing import Callable, Dict, Optional, Tuple

from .mesh import IciMesh

_cache: Dict[Tuple, Callable] = {}
_lock = threading.Lock()


def _cached(key, builder):
    with _lock:
        fn = _cache.get(key)
        if fn is None:
            fn = builder()
            _cache[key] = fn
        return fn


def ring_attention(q, k, v, mesh: Optional[IciMesh] = None, causal: bool = False):
    """Blockwise ring attention.

    q, k, v: (n, block, heads, dim) — sequence sharded over the mesh axis
    (row i = tokens [i*block, (i+1)*block)).  Returns attention output with
    the same layout.  ``causal=True`` masks by absolute token position.
    """
    mesh = mesh or IciMesh.default()
    key = ("ring_attn", tuple(q.shape), str(q.dtype), causal, mesh.size)
    fn = _cached(key, lambda: _build_ring_attention(
        mesh, tuple(q.shape[1:]), q.dtype, causal))
    return fn(q, k, v)


def _build_ring_attention(mesh: IciMesh, block_shape, dtype, causal: bool):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n = mesh.size
    ax = mesh.axis_name
    perm = [(i, (i + 1) % n) for i in range(n)]
    block, heads, dim = block_shape
    scale = dim ** -0.5

    def local_block(q_blk, k_blk, v_blk, q_pos, k_pos):
        """One (Q-block × K-block) panel with running-softmax stats.
        q_blk: (B, H, D); returns (scores_exp@v, row_max, row_sum)."""
        # (H, B, B) logits
        s = jnp.einsum("qhd,khd->hqk", q_blk, k_blk) * scale
        if causal:
            mask = (q_pos[None, :, None] >= k_pos[None, None, :])
            s = jnp.where(mask, s, -jnp.inf)
        m = jnp.max(s, axis=-1)                        # (H, B)
        # guard fully-masked rows
        m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        l = jnp.sum(p, axis=-1)                        # (H, B)
        o = jnp.einsum("hqk,khd->qhd", p, v_blk)       # (B, H, D)
        return o, m_safe, l, jnp.isfinite(m)

    def body(q_l, k_l, v_l):
        # locals arrive as (1, B, H, D)
        q_blk = q_l[0]
        my_id = lax.axis_index(ax)
        q_pos = my_id * block + jnp.arange(block)

        def step(carry, step_idx):
            k_cur, v_cur, o_acc, m_acc, l_acc = carry
            src_dev = lax.rem(my_id - step_idx + n, n)  # owner of current k/v
            k_pos = src_dev * block + jnp.arange(block)
            o_new, m_new, l_new, any_valid = local_block(
                q_blk, k_cur[0], v_cur[0], q_pos, k_pos)
            # merge running softmax (flash-attention accumulator update)
            m_next = jnp.maximum(m_acc, m_new)
            alpha = jnp.exp(m_acc - m_next)
            beta = jnp.exp(m_new - m_next)
            # rows with no valid entries in this panel contribute nothing
            beta = jnp.where(any_valid, beta, 0.0)
            l_next = l_acc * alpha + l_new * beta
            o_next = (o_acc * alpha.T[:, :, None]
                      + o_new * beta.T[:, :, None])
            # rotate k/v one hop for the next step
            k_rot = lax.ppermute(k_cur, ax, perm)
            v_rot = lax.ppermute(v_cur, ax, perm)
            return (k_rot, v_rot, o_next, m_next, l_next), None

        o0 = jnp.zeros((block, heads, dim), jnp.float32)
        m0 = jnp.full((heads, block), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((heads, block), jnp.float32)
        (k_f, v_f, o_acc, m_acc, l_acc), _ = lax.scan(
            step, (k_l.astype(jnp.float32), v_l.astype(jnp.float32),
                   o0, m0, l0),
            jnp.arange(n))
        out = o_acc / jnp.maximum(l_acc.T[:, :, None], 1e-20)
        return out.astype(dtype)[None]

    return jax.jit(shard_map(
        body, mesh=mesh.mesh, in_specs=(P(ax), P(ax), P(ax)),
        out_specs=P(ax), check_vma=False))


def ulysses_attention(q, k, v, mesh: Optional[IciMesh] = None):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses shape):
    q,k,v (n, block, heads, dim) sequence-sharded, heads divisible by n.
    Reshard to head-sharded full-sequence, attend, reshard back."""
    mesh = mesh or IciMesh.default()
    key = ("ulysses", tuple(q.shape), str(q.dtype), mesh.size)
    fn = _cached(key, lambda: _build_ulysses(mesh, tuple(q.shape[1:]),
                                             q.dtype))
    return fn(q, k, v)


def _build_ulysses(mesh: IciMesh, block_shape, dtype):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n = mesh.size
    ax = mesh.axis_name
    block, heads, dim = block_shape
    assert heads % n == 0, "ulysses needs heads % devices == 0"
    hpg = heads // n
    scale = dim ** -0.5

    def reshard_to_heads(x_l):
        # local (1, B, H, D) → (1, n*B, H/n, D): all_to_all over head groups
        x = x_l[0].reshape(block, n, hpg, dim)          # (B, n, hpg, D)
        x = jnp.moveaxis(x, 1, 0)                        # (n, B, hpg, D)
        g = lax.all_to_all(x, ax, split_axis=0, concat_axis=0, tiled=True)
        return g.reshape(n * block, hpg, dim)            # full seq, my heads

    def reshard_to_seq(y):
        # (n*B, hpg, D) → back to (1, B, H, D)
        y = y.reshape(n, block, hpg, dim)
        y = lax.all_to_all(y, ax, split_axis=0, concat_axis=0, tiled=True)
        # y now: (n, B, hpg, D) where axis0 = head groups
        y = jnp.moveaxis(y, 0, 1)                        # (B, n, hpg, D)
        return y.reshape(block, heads, dim)[None]

    def body(q_l, k_l, v_l):
        qh = reshard_to_heads(q_l).astype(jnp.float32)
        kh = reshard_to_heads(k_l).astype(jnp.float32)
        vh = reshard_to_heads(v_l).astype(jnp.float32)
        s = jnp.einsum("qhd,khd->hqk", qh, kh) * scale
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, vh)
        return reshard_to_seq(o.astype(dtype))

    return jax.jit(shard_map(
        body, mesh=mesh.mesh, in_specs=(P(ax), P(ax), P(ax)),
        out_specs=P(ax), check_vma=False))


def reference_attention(q, k, v, causal: bool = False):
    """Dense single-device reference for testing: q,k,v (S, H, D)."""
    import jax.numpy as jnp
    import jax
    S = q.shape[0]
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v.astype(jnp.float32)).astype(q.dtype)
