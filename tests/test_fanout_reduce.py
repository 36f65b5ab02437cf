"""The reduce of an operand fan-out on the per-member loop
(channels/collective_fanout.py: ``CollectiveMerger(MERGE_SUM)``,
``_gather``, ``brpc_fanout_gather``): a range replicated by reference to four
workers, float32 contributions summed by ONE device program into one array,
against ``benchmarks/reference/PushPull.py`` on seeded blocks, bit for bit —
for replies that arrive whole (the native tier), as several pieces (the
Python ici plane) and as partial refs into larger blocks — with the span
``brpc.fanout.reduce`` and the six counters of ``fanout_reduce_stats()``.

The workers, the mapper and the merger are the benchmark's own
(``benchmarks/services/PushPull.py``, ``benchmarks/clients/pushpull.py``: the
deployment ``param_server_local``); the cell's rehearsal is
tests/benchmarks/test_pushpull_cell.py's.
"""
import re

import numpy as np
import pytest

import brpc_tpu.policy  # noqa: F401  (registers protocols)
from brpc_tpu import channels, ici, rpc
from brpc_tpu.butil.iobuf import IOBuf
from brpc_tpu.channels import collective_fanout as cf
from brpc_tpu.ici import transport as tr
from brpc_tpu.rpc import errors
from brpc_tpu.rpc import span
from benchmarks.clients import pushpull
from benchmarks.harness.resident import make_set
from benchmarks.reference import PushPull as reference
from benchmarks.reference import payload
from benchmarks.services import PushPull as service
from benchmarks.services.messages import Request, Response

WORKERS = 4
SEED = 2 ** 31 + 39
REDUCE_KEYS = ("programs", "input_bytes", "output_bytes", "input_blocks",
               "host_merges", "lazy_reads")


@pytest.fixture(scope="module")
def mesh():
    import jax
    m = ici.IciMesh(jax.devices())
    before = ici.IciMesh._default
    ici.IciMesh.set_default(m)
    yield m
    ici.IciMesh.set_default(before)


class FailingWorker(rpc.Service):
    """The benchmark's workers, but that worker ``bad`` fails its sub-call."""
    SERVICE_NAME = "FailingPushPull"
    bad = 2

    @rpc.method(Request, Response)
    def PushPull(self, cntl, request, response, done):
        if request.message.endswith(f"#{self.bad}"):
            cntl.set_failed(errors.EINTERNAL, "told to fail")
            done()
            return
        att = cntl.request_attachment
        refs = att.device_refs()
        cntl.response_attachment.append_device_array(service.contribution(
            tuple(r.block.data for r in refs),
            tuple((r.offset, r.length) for r in refs),
            int(request.message.rpartition("#")[2])))
        response.message = request.message
        done()


@pytest.fixture
def deployment(mesh):
    """A test's own: the pooled connections it opens close with it."""
    workers = service.build(None)
    server = rpc.Server()
    server.add_service(workers)
    server.add_service(FailingWorker())
    assert server.start("ici://0") == 0
    channel = rpc.Channel()
    assert channel.init("ici://0", options=rpc.ChannelOptions(
        ici_local_device=0, max_retry=0, timeout_ms=60000,
        connection_type="pooled")) == 0
    yield f"{workers.service_name()}.PushPull", channel
    channel.close()
    server.stop()


def fanout(channel, merger=None):
    pc = channels.ParallelChannel(fail_limit=1)
    mapper = pushpull.WorkerMapper()
    merger = merger or pushpull.KeyedSumMerger(merge=channels.MERGE_SUM,
                                               dtype="float32")
    for _ in range(WORKERS):
        pc.add_channel(channel, mapper=mapper, merger=merger)
    return pc


def push_pull(pc, method, block, key="op"):
    cntl = rpc.Controller()
    cntl.fanout_operand = block
    resp = pc.call_method(method, cntl, Request(message=key), Response())
    return cntl, resp


def seeded(mesh, index, nbytes):
    """Block ``index`` of the seeded set on device 0, and on the host."""
    (block,) = make_set(SEED, 0, index + 1, nbytes, mesh.device(0))[index:]
    return block, payload.block(SEED, 0, index, nbytes)


def same(got, want_bytes) -> bool:
    got = np.asarray(got)
    return got.dtype == np.float32 \
        and got.tobytes() == want_bytes.tobytes()


# ---- the reference itself --------------------------------------------------

def test_the_reference_adds_four_different_exact_contributions():
    host = payload.block(SEED, 0, 3, 4096)
    parts = reference.contributions(host)
    assert len(parts) == WORKERS
    for i, g in enumerate(parts):
        assert g.dtype == np.float32
        assert np.array_equal(g, host[i::4].astype(np.float32) * 4 ** i)
    want, key = reference.expected(host, "k")
    total = want.view(np.float32)
    assert key == "k" and total.max() <= 21675 and total.min() >= 0
    # exact: the float64 sum of the four is the float32 sum
    assert np.array_equal(total.astype(np.float64),
                          sum(g.astype(np.float64) for g in parts))
    # each worker changes the sum: left out, taken twice, taken for another's
    for i in range(WORKERS):
        assert np.any(total - parts[i] != total)
        assert np.any(total + parts[i] != total)
        assert np.any(parts[i] != parts[(i + 1) % WORKERS])


def test_a_sum_in_the_precision_below_is_not_the_reference():
    """``precision``: the nearest precision below float32 cannot hold the
    sums (bfloat16 has 8 bits of mantissa, the sums reach 21,675)."""
    import ml_dtypes
    host = payload.block(SEED, 0, 5, 1 << 16)
    parts = [g.astype(ml_dtypes.bfloat16)
             for g in reference.contributions(host)]
    low = parts[0]
    for g in parts[1:]:
        low = low + g
    want = reference.expected(host, "k")[0].view(np.float32)
    wrong = np.count_nonzero(low.astype(np.float32) != want)
    assert wrong > want.size // 2


# ---- the system against it, end to end -------------------------------------

@pytest.mark.parametrize("nbytes,piece,blocks_a_worker", [
    (1 << 20, None, 1),                 # the native tier: a reply is whole
    (5 << 20, 320 * 1024, 16),          # the Python ici plane: 16 pieces
], ids=["whole", "pieced"])
def test_replicate_and_sum_equals_the_reference_bit_for_bit(
        mesh, deployment, monkeypatch, nbytes, piece, blocks_a_worker):
    method, channel = deployment
    if piece:
        monkeypatch.setattr(tr, "PIECE_BYTES", piece)
    pc = fanout(channel)
    for index in (0, 1):
        block, host = seeded(mesh, index, nbytes)
        before = channels.fanout_stats(), channels.fanout_reduce_stats()
        cntl, resp = push_pull(pc, method, block, key=f"op{index}")
        assert not cntl.failed(), cntl.error_text
        assert cntl.fanout_route == "rpc" and resp.message == f"op{index}"
        # a sum is made at once: ONE array, on the operand's device
        got = cntl.__dict__["fanout_result"]
        assert got.dtype == np.float32 and got.shape == (nbytes // 4,)
        assert set(got.devices()) == {mesh.device(0)}
        want, key = reference.expected(host, f"op{index}")
        assert same(got, want) and key == resp.message
        att = cntl.fanout_attachment
        assert len(att) == att.device_bytes() == WORKERS * nbytes
        assert att.backing_block_num() == WORKERS * blocks_a_worker
        fan = {k: v - before[0][k] for k, v in
               channels.fanout_stats().items()}
        red = {k: v - before[1][k] for k, v in
               channels.fanout_reduce_stats().items()}
        assert fan["host_operand_bytes"] == 0 and fan["partial_results"] == 0
        assert fan["device_operand_bytes"] == WORKERS * nbytes
        assert red == {"programs": 1, "input_bytes": WORKERS * nbytes,
                       "output_bytes": nbytes,
                       "input_blocks": WORKERS * blocks_a_worker,
                       "host_merges": 0, "lazy_reads": 0}


def test_a_failed_worker_fails_the_operation_with_no_result(
        mesh, deployment):
    _, channel = deployment
    pc = fanout(channel)
    block, _ = seeded(mesh, 2, 1 << 16)
    before = channels.fanout_stats(), channels.fanout_reduce_stats()
    cntl, _ = push_pull(pc, "FailingPushPull.PushPull", block)
    assert cntl.failed() and cntl.error_code_ == errors.ETOOMANYFAILS
    assert cntl.fanout_result is None and cntl.fanout_attachment is None
    after = channels.fanout_stats()
    assert after["partial_results"] == before[0]["partial_results"]
    assert after["sub_calls_failed"] == before[0]["sub_calls_failed"] + 1
    assert channels.fanout_reduce_stats() == before[1]   # nothing summed


def test_a_reply_under_anothers_index_is_not_the_key(mesh, deployment):
    """The merged message is the key only if every worker answered under
    its own index."""
    method, channel = deployment

    class Crossed(pushpull.WorkerMapper):
        def map_fanout(self, index, method_full_name, request, parent_cntl):
            return super().map_fanout(index ^ 1 if index < 2 else index,
                                      method_full_name, request, parent_cntl)

    pc = channels.ParallelChannel(fail_limit=1)
    merger = pushpull.KeyedSumMerger(merge=channels.MERGE_SUM,
                                     dtype="float32")
    for _ in range(WORKERS):
        pc.add_channel(channel, mapper=Crossed(), merger=merger)
    block, host = seeded(mesh, 0, 1 << 16)
    cntl, resp = push_pull(pc, method, block)
    assert not cntl.failed(), cntl.error_text
    # whichever replies arrived first, the message is not the key and names
    # a worker that answered under another's index
    assert resp.message != "op" and re.search(
        r"worker [01] answered 'op#[01]'", resp.message), resp.message
    # the sum is the reference's all the same: addition commutes here
    assert same(cntl.fanout_result, reference.expected(host, "op")[0])


# ---- the merger on refs of every shape -------------------------------------

def _parts_with_partial_refs(mesh, host, pieces):
    """Each worker's contribution as ``pieces`` PARTIAL refs: its bytes lie
    inside larger device blocks, with other bytes before and after."""
    import jax
    parts = []
    for i, g in enumerate(reference.contributions(host)):
        raw = g.view(np.uint8)
        step = raw.size // pieces
        part = IOBuf()
        for k in range(pieces):
            lead, tail = 64 * (i + 1), 32 * (k + 1)
            padded = np.concatenate([np.full(lead, 0xEE, np.uint8),
                                     raw[k * step:(k + 1) * step],
                                     np.full(tail, 0xDD, np.uint8)])
            whole = IOBuf()
            whole.append_device_array(jax.device_put(padded, mesh.device(0)))
            whole.pop_front(lead)
            whole.pop_back(tail)
            part.append(whole)
        parts.append(part)
    return parts


@pytest.mark.parametrize("pieces", [1, 4])
def test_partial_refs_are_cut_by_the_compiled_slicer_and_sum_exactly(
        mesh, pieces):
    host = payload.block(SEED, 0, 7, 1 << 16)
    parts = _parts_with_partial_refs(mesh, host, pieces)
    assert all(r.offset and r.length < r.block.data.shape[0]
               for p in parts for r in p.device_refs())
    cuts, red = tr.ici_piece_stats(), channels.fanout_reduce_stats()
    got = cf._gather(parts, channels.MERGE_SUM, "float32", None,
                     mesh.device(0))
    assert same(got, reference.expected(host, "k")[0])
    assert set(got.devices()) == {mesh.device(0)}
    after = tr.ici_piece_stats()
    # no path of the reduce runs jnp's __getitem__
    assert after["compiled_cuts"] - cuts["compiled_cuts"] == WORKERS * pieces
    assert after["eager_cuts"] == cuts["eager_cuts"]
    grew = {k: v - red[k] for k, v in channels.fanout_reduce_stats().items()}
    assert grew == {"programs": 1, "input_bytes": host.size * WORKERS,
                    "output_bytes": host.size,
                    "input_blocks": WORKERS * pieces, "host_merges": 0,
                    "lazy_reads": 0}


def test_a_ref_outside_its_block_raises_and_sums_nothing(mesh):
    import jax
    buf = IOBuf()
    buf.append_device_array(jax.device_put(np.zeros(64, np.uint8),
                                           mesh.device(0)))
    buf.device_refs()[0].length = 128           # past its block's end
    with pytest.raises(ValueError, match="not inside its block"):
        cf._gather([buf], channels.MERGE_SUM, "float32", None,
                   mesh.device(0))


# ---- a gather's lazy read, and the host's merge ----------------------------

def test_a_gather_counts_its_program_when_the_result_is_read(
        mesh, deployment):
    method, channel = deployment
    merger = pushpull.KeyedSumMerger(merge=channels.MERGE_GATHER,
                                     dtype="float32")
    pc = fanout(channel, merger)
    block, host = seeded(mesh, 1, 1 << 16)
    before = channels.fanout_reduce_stats()
    cntl, _ = push_pull(pc, method, block)
    assert not cntl.failed(), cntl.error_text
    assert channels.fanout_reduce_stats() == before      # nothing made yet
    got = cntl.fanout_result
    assert np.array_equal(np.asarray(got),
                          np.stack(reference.contributions(host)))
    grew = {k: v - before[k] for k, v in
            channels.fanout_reduce_stats().items()}
    assert grew == {"programs": 1, "input_bytes": WORKERS * host.size,
                    "output_bytes": WORKERS * host.size,
                    "input_blocks": WORKERS, "host_merges": 0,
                    "lazy_reads": 1}
    assert cntl.fanout_result is got                     # made once


def test_a_host_operand_is_merged_by_numpy_and_counted(mesh, deployment):
    method, channel = deployment
    pc = fanout(channel)
    host = payload.block(SEED, 0, 4, 1 << 12)
    before = channels.fanout_reduce_stats()
    cntl = rpc.Controller()
    cntl.fanout_operand = host
    pc.call_method(method, cntl, Request(message="op"), Response())
    grew = {k: v - before[k] for k, v in
            channels.fanout_reduce_stats().items()}
    # the workers answer a range that came by the host with no attachment:
    # whatever the merge gives, numpy made it, and the counter says so
    assert grew["host_merges"] == (0 if cntl.failed() else 1)
    assert grew["programs"] == 0


# ---- the span ----------------------------------------------------------------

@pytest.fixture
def session(tmp_path):
    import jax
    span.layer_spans_reset()
    jax.profiler.start_trace(str(tmp_path))
    yield
    if span.layer_on():
        jax.profiler.stop_trace()
    span.layer_spans_reset()


def test_the_reduce_span_lies_in_the_finalizing_merge_under_the_fanout(
        mesh, deployment, session):
    import jax
    method, channel = deployment
    pc = fanout(channel)
    block, _ = seeded(mesh, 0, 1 << 20)
    since = span.layer_mark().ns
    cntl, _ = push_pull(pc, method, block)
    assert not cntl.failed(), cntl.error_text
    jax.profiler.stop_trace()
    spans = span.layer_spans(since)
    (parent,) = [s for s in spans if s.name == "brpc.fanout"]
    (final,) = [s for s in spans if s.name == "brpc.fanout.merge" and s.m]
    (reduce,) = [s for s in spans if s.name == "brpc.fanout.reduce"]
    assert reduce.cause_id == parent.span_id
    assert final.start_ns <= reduce.start_ns <= reduce.end_ns <= final.end_ns
    assert (reduce.n, reduce.m) == (WORKERS << 20, WORKERS)
    assert 0 <= reduce.cpu_ns <= reduce.end_ns - reduce.start_ns
    assert "_fanout_mark" not in cntl.__dict__


def test_a_gathers_span_opens_where_the_result_is_read(mesh, deployment,
                                                       session):
    import jax
    method, channel = deployment
    pc = fanout(channel, pushpull.KeyedSumMerger(
        merge=channels.MERGE_CONCAT, dtype="float32"))
    block, _ = seeded(mesh, 0, 1 << 16)
    since = span.layer_mark().ns
    cntl, _ = push_pull(pc, method, block)
    assert not cntl.failed(), cntl.error_text
    assert not [s for s in span.layer_spans(since)
                if s.name == "brpc.fanout.reduce"]
    read_from = span.layer_mark().ns
    assert cntl.fanout_result.shape == (WORKERS << 14,)
    jax.profiler.stop_trace()
    spans = span.layer_spans(since)
    (parent,) = [s for s in spans if s.name == "brpc.fanout"]
    (reduce,) = [s for s in spans if s.name == "brpc.fanout.reduce"]
    assert reduce.start_ns >= read_from >= parent.end_ns
    assert reduce.cause_id == parent.span_id


def test_no_session_no_span_and_the_totals_are_on_vars(mesh, deployment):
    from brpc_tpu import bvar
    method, channel = deployment
    span.layer_spans_reset()
    block, _ = seeded(mesh, 0, 1 << 16)
    assert not push_pull(fanout(channel), method, block)[0].failed()
    assert [s for s in span.layer_spans()
            if s.name == "brpc.fanout.reduce"] == []
    assert tuple(channels.fanout_reduce_stats()) == REDUCE_KEYS
    assert {f"rpc_fanout_reduce_{k}" for k in REDUCE_KEYS} \
        <= set(bvar.list_exposed())
    # not keys of fanout_stats(): its nine stay nine
    assert len(channels.fanout_stats()) == 9
    assert not set(REDUCE_KEYS) & set(channels.fanout_stats())
