"""Pallas ring collective kernels (the Pallas TPU interpreter on the CPU
mesh — the exact control flow the TPU executes, with remote DMA and the
semaphores emulated; tests/test_tpu_compile.py hands the same kernels to the
real TPU compiler)."""
import numpy as np
import pytest

from brpc_tpu import ici
from brpc_tpu.ici import pallas_ring


@pytest.fixture(scope="module")
def mesh():
    """Seven devices, NOT the process's first: the interpreter's callbacks
    run small jax ops on ``jax.devices()[0]``, and when that device is
    itself parked inside one of those callbacks they queue behind it —
    the kernel's results are out, but its trailing barrier callbacks hold
    every mesh device for good and the next test file to touch them hangs
    (seen once the suite ran under xdist).  Off the mesh, device 0 stays
    free to serve them."""
    import jax
    return ici.IciMesh(jax.devices()[1:])


class TestPallasRing:
    def test_all_gather(self, mesh):
        import jax.numpy as jnp
        from brpc_tpu.ici.collective import Collectives
        coll = Collectives(mesh)
        n = mesh.size
        C = 128
        x = coll.shard(jnp.arange(n * C, dtype=jnp.float32).reshape(n, C))
        out = np.asarray(pallas_ring.ring_all_gather(x, mesh))
        assert out.shape == (n, n, C)
        expect = np.arange(n * C, dtype=np.float32).reshape(n, C)
        for d in range(n):
            np.testing.assert_allclose(out[d], expect)

    def test_all_reduce(self, mesh):
        import jax.numpy as jnp
        from brpc_tpu.ici.collective import Collectives
        coll = Collectives(mesh)
        n = mesh.size
        C = 128
        x = coll.shard(jnp.arange(n * C, dtype=jnp.float32).reshape(n, C))
        out = np.asarray(pallas_ring.ring_all_reduce(x, mesh))
        assert out.shape == (n, C)
        expect = np.arange(n * C, dtype=np.float32).reshape(n, C).sum(0)
        for d in range(n):
            np.testing.assert_allclose(out[d], expect)

    def test_all_reduce_matches_psum(self, mesh):
        import jax.numpy as jnp
        from brpc_tpu.ici.collective import Collectives
        coll = Collectives(mesh)
        n = mesh.size
        x = coll.shard(jnp.ones((n, 256), jnp.float32) * 3)
        pallas_out = np.asarray(pallas_ring.ring_all_reduce(x, mesh))
        psum_out = np.asarray(coll.all_reduce(x))
        for d in range(n):
            np.testing.assert_allclose(pallas_out[d], psum_out)

    def test_kernel_cache(self, mesh):
        import jax.numpy as jnp
        from brpc_tpu.ici.collective import Collectives
        coll = Collectives(mesh)
        n = mesh.size
        x = coll.shard(jnp.ones((n, 128), jnp.float32))
        pallas_ring.ring_all_reduce(x, mesh)
        before = len(pallas_ring._cache)
        pallas_ring.ring_all_reduce(x * 2, mesh)
        assert len(pallas_ring._cache) == before
