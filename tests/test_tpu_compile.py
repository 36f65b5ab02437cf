"""Ahead-of-time compiles for a described TPU v5e 2x2 — no chip attached.

The TPU compiler is installed wherever jax's TPU support is, and it
compiles for a topology that is described rather than attached
(/opt/skills/guides/on-chip-measurement §2).  These cases hand it every
device program of the main path at the sizes ``chip_smoke.py`` runs them,
so a kernel the chip's compiler would refuse (a load from an ``ANY`` ref,
a scratch buffer that outgrows VMEM, a slice off the dtype's tiling)
fails tier-1 here and not a chip run.  Nothing executes: a pass says the
program compiles, never that it is right or fast.

The topology is described inside a fixture (never at import, in a
``skipif`` or in ``parametrize``: only one process at a time may load
libtpu, and every xdist worker imports this file), everything built from
it lives in fixtures too, the compiles run in the test's own process, and
all of them stay in THIS file so one worker owns the library.
"""
import numpy as np
import pytest

KB, MB = 1 << 10, 1 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def tpu_mesh(topo):
    from brpc_tpu.ici.mesh import IciMesh
    return IciMesh(devices=topo.devices)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these silent and hermetic."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", saved)
    cc.reset_cache()


@pytest.fixture()
def tpu_default_mesh(tpu_mesh):
    """Code that asks IciMesh.default() builds for the described chips."""
    from brpc_tpu.ici.mesh import IciMesh
    saved = IciMesh._default
    IciMesh.set_default(tpu_mesh)
    yield tpu_mesh
    IciMesh.set_default(saved)


def _row_sharded(tpu_mesh, shape, dtype):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=tpu_mesh.shard_along_axis())


# ---- device plane: the point-to-point transfer program ------------------

# What xchip_bulk_64m compiles (callers on chip 0, a server each on chips
# 1-3): every directed pair with chip 0 gets its own two-chip sub-mesh
# executable (PERF.md §7 row 1b: the sub-mesh per pair is what proved
# fragile).  Since PR 31 a frame's header rides with its first window
# piece, so the cell's device operations are two shapes, whatever the
# header's length: the request's pieces are u8[4194304] cut out of the
# caller's 64 MiB block ("4MB-of-64MB", the start an operand), the reply's
# are the 4 MiB blocks the server received, whole ("reply-whole-block":
# block == piece, XLA folds the slice away).  The odd sizes below are what
# the cell compiled before (u8[4194237], u8[4194279]: PERF_LEDGER.jsonl,
# PR 29, breakdown.device_ops) and what other traffic still meets: a
# header at or over the plane's threshold, a partly credited window, a
# block that is no multiple of the window.  The start is an operand, so
# one program serves every (unaligned) start.
_REQ_HDR, _REPLY_SHIFT = 67, 25
_FROM_0 = [(0, 1), (0, 2), (0, 3)]
_TO_0 = [(1, 0), (2, 0), (3, 0)]


def _transfer_case(name, block, nbytes, src=0, dst=1):
    pair = "" if (src, dst) == (0, 1) else f"-{src}to{dst}"
    return pytest.param(block, nbytes, src, dst, id=name + pair)


@pytest.mark.parametrize(
    "block,nbytes,src,dst",
    [_transfer_case("4KB", 4 * KB, 4 * KB),
     _transfer_case("4MB", 4 * MB, 4 * MB),
     _transfer_case("64MB", 64 * MB, 64 * MB)]
    + [_transfer_case("4MB-of-64MB", 64 * MB, 4 * MB, s, d)
       for s, d in _FROM_0 + _TO_0]
    + [_transfer_case("request-first-piece", 64 * MB, 4 * MB - _REQ_HDR,
                      s, d) for s, d in _FROM_0]
    + [_transfer_case("reply-piece", 4 * MB, 4 * MB - _REPLY_SHIFT, s, d)
       for s, d in _TO_0]
    + [_transfer_case("reply-whole-block", 4 * MB, 4 * MB, s, d)
       for s, d in _TO_0])
def test_device_plane_transfer_program(tpu_mesh, block, nbytes, src, dst):
    """A whole array (block == piece) and a window piece cut out of its
    block on the chip are one program family, built per directed pair."""
    from brpc_tpu.ici.device_plane import DevicePlane
    plane = DevicePlane(mesh=tpu_mesh)
    compiled, _, mesh2, s_dev, d_dev = plane._build(block, nbytes, src, dst)
    assert [d.id for d in mesh2.devices.flat] == [s_dev.id, d_dev.id]
    assert (s_dev, d_dev) == (tpu_mesh.device(src), tpu_mesh.device(dst))
    text = compiled.as_text()
    assert "collective-permute" in text
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes >= block
    assert ma.output_size_in_bytes >= nbytes
    # 16 GB of HBM per chip; the program's own footprint must leave room
    assert (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes) < 4 << 30


def test_device_plane_unbuildable_program_is_loud(tpu_mesh):
    """A program the compiler refuses raises at BUILD time and is counted
    apart from runtime refusals — it never reaches the first call."""
    from brpc_tpu.ici import device_plane as dp
    plane = dp.DevicePlane(mesh=tpu_mesh)

    def refuse(*a, **kw):
        raise ValueError("Loads are only allowed on VMEM and SMEM "
                         "references")
    plane._build = refuse
    with pytest.raises(dp.DevicePlaneBuildError, match="Loads are only"):
        plane.post_send(np.zeros(64 * KB, np.uint8), 0, 1)
    st = plane.stats()
    assert st["build_failures"] == 1 and st["fallbacks"] == 0
    assert plane.active_transfers() == 0


# ---- Pallas ring collectives --------------------------------------------

@pytest.mark.parametrize("chunk", [(8, 128), (1024,), (2048, 2048)],
                         ids=["8x128", "1024", "16MiB"])
@pytest.mark.parametrize("kind", ["all_gather", "all_reduce"])
def test_pallas_ring_kernel(tpu_mesh, kind, chunk):
    import jax.numpy as jnp
    from brpc_tpu.ici import pallas_ring
    build = {"all_gather": pallas_ring._build_all_gather,
             "all_reduce": pallas_ring._build_all_reduce}[kind]
    interpret = pallas_ring.interpret_for(tpu_mesh.devices, kind)
    assert interpret is False          # decided from the MESH: compiled
    fn = build(tpu_mesh, chunk, jnp.float32, interpret)
    compiled = fn.lower(_row_sharded(
        tpu_mesh, (tpu_mesh.size,) + chunk, jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_interpret_follows_the_mesh_not_the_process():
    """On this CPU process a CPU mesh interprets — and says so."""
    import jax
    from brpc_tpu.ici import pallas_ring
    mode = pallas_ring.interpret_for(jax.devices(), "probe")
    assert mode is not False


# ---- XLA collectives behind the combo channels --------------------------

def test_collectives_all_reduce_256mib_per_chip(tpu_mesh):
    import jax
    import jax.numpy as jnp
    from brpc_tpu.ici.collective import Collectives
    coll = Collectives(tpu_mesh)
    x = _row_sharded(tpu_mesh, (tpu_mesh.size, 32768, 2048), jnp.float32)
    compiled = jax.jit(coll.all_reduce).lower(x).compile()
    assert "all-reduce" in compiled.as_text()
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes == 256 * MB


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_ring_attention_seq4096(tpu_mesh, causal):
    import jax.numpy as jnp
    from brpc_tpu.ici.ring_attention import _build_ring_attention
    n = tpu_mesh.size
    block = (4096 // n, 8, 128)                   # seq x heads x dim
    fn = _build_ring_attention(tpu_mesh, block, jnp.bfloat16, causal)
    qkv = _row_sharded(tpu_mesh, (n,) + block, jnp.bfloat16)
    compiled = fn.lower(qkv, qkv, qkv).compile()
    assert "collective-permute" in compiled.as_text()


@pytest.mark.parametrize("merge,mapping", [("gather", "shard"),
                                           ("sum", "replicate")])
def test_collective_fanout_program(tpu_default_mesh, merge, mapping):
    """The ONE program a lowered Parallel/PartitionChannel call enters."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from brpc_tpu.channels import collective_fanout as cf
    mesh = tpu_default_mesh
    merge = {"gather": cf.MERGE_GATHER, "sum": cf.MERGE_SUM}[merge]
    mapping = {"shard": cf.MAP_SHARD, "replicate": cf.MAP_REPLICATE}[mapping]
    devices = tuple(range(mesh.size))
    submesh = Mesh(np.array([mesh.device(d) for d in devices]), ("fan",))
    if mapping == cf.MAP_SHARD:
        shape, spec = (mesh.size, 1 << 20), P("fan")
    else:
        shape, spec = (1 << 20,), P()
    operand = jax.ShapeDtypeStruct(shape, jnp.float32,
                                   sharding=NamedSharding(submesh, spec))
    md = cf.CollectiveMethodDef("Fan.Compile", lambda x: x * 2.0, merge,
                                mapping, False)
    low = cf._Lowering("Fan.Compile", md, devices, operand, mapping,
                       "local", {})
    fn, placed = cf.CollectiveFanoutPlane.instance()._prepare_local(low)
    assert placed is operand           # already "placed": nothing moved
    text = fn.lower(placed).compile().as_text()
    assert ("all-gather" if merge == cf.MERGE_GATHER else "all-reduce") \
        in text


# ---- the serving step ----------------------------------------------------

@pytest.mark.parametrize("batch,width", [(8, 8), (64, 64)])
def test_serving_compiled_step(one_chip, batch, width):
    import jax
    import jax.numpy as jnp
    from brpc_tpu.serving import (BatchSchedulerOptions,
                                  ContinuousBatchScheduler, KvPoolOptions,
                                  PagedKvPool)
    bt = 16
    pool = PagedKvPool(KvPoolOptions(bytes_per_token=1024, num_blocks=64,
                                     block_tokens=bt, use_timers=False))
    sched = ContinuousBatchScheduler(pool, BatchSchedulerOptions(
        vocab=50257, max_batch=batch, auto_start=False))
    try:
        def arg(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
        compiled = sched._compiled_step_fn(bt).lower(
            arg(4096 * bt), arg(batch, width), arg(batch), arg(batch),
            arg(batch), arg(batch)).compile()
        assert compiled.memory_analysis().output_size_in_bytes >= 4 * batch
    finally:
        sched.stop()
        pool.close()


# ---- the streaming cell's two device programs -----------------------------

def test_stream_cell_programs_at_the_cells_size(one_chip):
    """``stream_1m`` keeps to one slice shape and one xor program a run: the
    client's ``block[a:b]`` of a 64 MiB resident block and the handler's
    jitted xor of a 1 MiB chunk (benchmarks/services/StartStream.py), at the
    cell's own sizes."""
    import os
    import sys
    import jax
    import jax.numpy as jnp
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from benchmarks.services.StartStream import transform
    chunk = jax.ShapeDtypeStruct((MB,), jnp.uint8, sharding=one_chip)
    xor = transform.lower(chunk).compile()
    assert xor.memory_analysis().output_size_in_bytes == MB
    block = jax.ShapeDtypeStruct((64 * MB,), jnp.uint8, sharding=one_chip)
    cut = jax.jit(lambda b: b[3 * MB:4 * MB]).lower(block).compile()
    assert cut.memory_analysis().output_size_in_bytes == MB


# ---- the fan-out cell's device programs ------------------------------------

def test_fanout_cell_programs_at_the_cells_size(one_chip):
    """``fanout_4x16m``'s device programs: the transport's cut of a 4 MiB
    window piece out of the block a ref points into (the request's out of
    the caller's 64 MiB block, the reply's out of the handler's 16 MiB
    array), and the handler's ONE program a shard, which joins the four
    pieces the shard crossed in as and xors them into one 16 MiB array
    (benchmarks/services/EchoShard.py).  The gather the cell never asks for
    (``cntl.fanout_result``: ``brpc_fanout_gather`` over sixteen pieces)
    compiles at the cell's size too."""
    import os
    import sys
    import jax
    import jax.numpy as jnp
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from benchmarks.services.EchoShard import transform
    from brpc_tpu.channels import collective_fanout as cf
    from brpc_tpu.ici.transport import piece_slicer
    piece = jax.ShapeDtypeStruct((4 * MB,), jnp.uint8, sharding=one_chip)
    xor = transform.lower((piece,) * 4, ((0, 4 * MB),) * 4).compile()
    assert xor.memory_analysis().output_size_in_bytes == 16 * MB
    start = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    for size in (64 * MB, 16 * MB):     # the request's block, the reply's
        block = jax.ShapeDtypeStruct((size,), jnp.uint8, sharding=one_chip)
        # the transport's own slicer (ici.transport._cut), not a copy
        cut = piece_slicer().lower(block, start, 4 * MB).compile()
        assert cut.memory_analysis().output_size_in_bytes == 4 * MB
    parts = tuple((piece,) * 4 for _ in range(4))
    whole = cf._gather_jit().lower(parts, cf.MERGE_CONCAT, "uint8",
                                   None).compile()
    assert whole.memory_analysis().output_size_in_bytes == 64 * MB
    # a float result is gathered as the integer of its width and bitcast last
    stacked = cf._gather_jit().lower(parts, cf.MERGE_GATHER, "float32",
                                     None).compile()
    assert stacked.memory_analysis().output_size_in_bytes == 64 * MB
