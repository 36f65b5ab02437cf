"""The per-member RPC loop of an operand fan-out with a DEVICE operand
(channels/parallel_channel.py, channels/collective_fanout.py): the system
against a plain numpy reference on seeded data.

A device array operand — or a list whose rows are device arrays or IOBufs of
DEVICE refs — rides the loop as DEVICE refs end to end: the mappers hand each
sub-call refs, the merger keeps each sub-reply's refs by sub-channel INDEX,
``cntl.fanout_attachment`` is those refs in order, ``cntl.fanout_result`` one
array on the operand's device, and ``fanout_stats()["host_operand_bytes"]``
does not move.  A numpy operand's result, type and counters are the parent's.
The sub-channels all reach ONE server (upstream parallel_echo's default, and
the benchmark's ``parallel_echo_local``), but where a test says otherwise.
"""
import threading
import time

import numpy as np
import pytest

import brpc_tpu.policy  # noqa: F401  (registers protocols)
from brpc_tpu import channels, ici, rpc
from brpc_tpu.butil.iobuf import IOBuf
from brpc_tpu.channels import collective_fanout as cf
from brpc_tpu.rpc import errors
from brpc_tpu.rpc import fault_injection as fi
from brpc_tpu.rpc import span
from tests.echo_pb2 import EchoRequest, EchoResponse

KEY = 0x5A
SLOW, FAIL = 0xA1, 0xA2         # a row's first byte the handler acts on
WIDTHS = (2, 3, 4)
SHARDS = (4096, 65536, 262144)


def reference(rows: np.ndarray, merge: str, dtype) -> np.ndarray:
    """What the fan-out returns, by numpy alone: every member answers its
    attachment xor KEY; the merge is over the members' answers as
    ``dtype``."""
    answers = [(np.ascontiguousarray(r).view(np.uint8) ^ np.uint8(KEY))
               .view(dtype) for r in rows]
    if merge == channels.MERGE_SUM:
        out = answers[0].copy()
        for a in answers[1:]:
            out = out + a
        return out
    if merge == channels.MERGE_CONCAT:
        return np.concatenate(answers, axis=0)
    return np.stack(answers)


class FanService(rpc.Service):
    """Answers the attachment xor KEY from where it lives: DEVICE refs are
    computed on their device and answered as device arrays, host bytes as
    host bytes.  ``SLOW`` in front holds the answer back until the test lets it go, ``FAIL`` fails it."""
    SERVICE_NAME = "DevFan"

    def __init__(self):
        self.order = []
        self.release = lambda: True     # when a SLOW answer may go
        self.lock = threading.Lock()

    @rpc.method(EchoRequest, EchoResponse)
    def Xor(self, cntl, request, response, done):
        import jax.numpy as jnp
        att = cntl.request_attachment
        refs = att.device_refs()
        if refs and att.device_bytes() == len(att):
            outs = [r.block.data.reshape(-1)[r.offset:r.offset + r.length]
                    ^ jnp.uint8(KEY) for r in refs]
            first = int(np.asarray(outs[0][:1])[0]) ^ KEY
            for o in outs:
                cntl.response_attachment.append_device_array(o)
        else:
            x = np.frombuffer(att.to_bytes(), np.uint8)
            first = int(x[0])
            cntl.response_attachment.append((x ^ np.uint8(KEY)).tobytes())
        if first == FAIL:
            cntl.set_failed(errors.EINTERNAL, "told to fail")
        with self.lock:
            self.order.append(first)
        response.message = request.message
        if first != SLOW:
            done()
            return

        def later():                    # no handler thread is held for it
            deadline = time.monotonic() + 10
            while not self.release() and time.monotonic() < deadline:
                time.sleep(0.002)
            done()

        threading.Thread(target=later, daemon=True).start()


@pytest.fixture(scope="module")
def mesh():
    import jax
    m = ici.IciMesh(jax.devices())
    before = ici.IciMesh._default
    ici.IciMesh.set_default(m)
    yield m
    ici.IciMesh.set_default(before)


@pytest.fixture(scope="module")
def deployment(mesh):
    service = FanService()
    server = rpc.Server()
    server.add_service(service)
    assert server.start("ici://0") == 0
    channel = rpc.Channel()
    assert channel.init("ici://0", options=rpc.ChannelOptions(
        ici_local_device=0, max_retry=0, timeout_ms=30000,
        connection_type="pooled")) == 0
    yield service, channel
    channel.close()
    server.stop()


def fanout(channel, width, mapping, merge, dtype, shard_shape=None,
           fail_limit=-1):
    pc = channels.ParallelChannel(fail_limit=fail_limit)
    mapper = channels.ShardingCallMapper() \
        if mapping == channels.MAP_SHARD else channels.ReplicateFanoutMapper()
    merger = channels.CollectiveMerger(merge=merge, dtype=dtype,
                                       shard_shape=shard_shape)
    for _ in range(width):              # upstream's -same_channel
        pc.add_channel(channel, mapper=mapper, merger=merger)
    return pc


def call(pc, operand, done=None):
    cntl = rpc.Controller()
    cntl.fanout_operand = operand
    pc.call_method("DevFan.Xor", cntl, EchoRequest(message="k"),
                   EchoResponse(), done=done)
    return cntl


def seeded(seed, width, nbytes, dtype):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, 256, (width, nbytes), dtype=np.uint8)
    rows[:, 0] &= 0x7F                  # never SLOW or FAIL by chance
    return rows.view(dtype)


def on_device(mesh, x, dev=0):
    import jax
    return jax.block_until_ready(jax.device_put(x, mesh.device(dev)))


def same(got, want) -> bool:
    """Bit for bit (random float32 rows hold NaNs)."""
    got = np.asarray(got)
    return got.dtype == want.dtype and got.shape == want.shape \
        and got.tobytes() == want.tobytes()


def host_bytes(att: IOBuf) -> bytes:
    return b"".join(bytes(np.asarray(r.block.data).reshape(-1).view(np.uint8)
                          [r.offset:r.offset + r.length])
                    for r in att.device_refs())


CASES = [(channels.MAP_SHARD, channels.MERGE_CONCAT, "uint8"),
         (channels.MAP_SHARD, channels.MERGE_SUM, "uint32"),
         (channels.MAP_REPLICATE, channels.MERGE_GATHER, "float32")]


@pytest.mark.parametrize("shard", SHARDS)
@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("mapping,merge,dtype", CASES,
                         ids=["shard_concat", "shard_sum",
                              "replicate_gather"])
def test_device_operand_equals_the_reference_and_never_meets_the_host(
        mesh, deployment, mapping, merge, dtype, width, shard):
    _, channel = deployment
    rows = seeded(width * 1000 + shard, width, shard, dtype)
    if mapping == channels.MAP_REPLICATE:
        operand, sent = rows[0], np.stack([rows[0]] * width)
    else:
        operand, sent = rows, rows
    want = reference(sent, merge, dtype)
    pc = fanout(channel, width, mapping, merge, dtype)
    before = channels.fanout_stats()
    cntl = call(pc, on_device(mesh, operand))
    after = channels.fanout_stats()
    assert not cntl.failed(), cntl.error_text
    assert cntl.fanout_route == "rpc"
    # the gathered refs: every member's answer, in sub-channel order, all of
    # it device memory on the operand's device, and nothing made of it yet
    att = cntl.fanout_attachment
    assert len(att) == att.device_bytes() == width * shard
    assert all(set(r.block.data.devices()) == {mesh.device(0)}
               for r in att.device_refs())
    assert host_bytes(att) == reference(
        sent, channels.MERGE_CONCAT, np.uint8).tobytes()
    if merge != channels.MERGE_SUM:
        assert "fanout_result" not in cntl.__dict__
    got = cntl.fanout_result            # asked for: ONE array, on the device
    assert set(got.devices()) == {mesh.device(0)}
    assert got.dtype == want.dtype and got.shape == want.shape
    assert same(got, want)
    assert cntl.fanout_result is got    # made once
    delta = {k: after[k] - before[k] for k in after}
    sent_bytes = sent.nbytes
    assert delta == {"calls": 1, "sub_calls": width, "sub_calls_failed": 0,
                     "merges": width, "partial_results": 0,
                     "device_operand_bytes": sent_bytes,
                     "host_operand_bytes": 0, "route_rpc": 1,
                     "route_collective": 0}


@pytest.mark.parametrize("shard", SHARDS[:2])
@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("mapping,merge,dtype", CASES,
                         ids=["shard_concat", "shard_sum",
                              "replicate_gather"])
def test_numpy_operand_keeps_its_result_type_and_counts_host_bytes(
        deployment, mapping, merge, dtype, width, shard):
    _, channel = deployment
    rows = seeded(width * 2000 + shard, width, shard, dtype)
    if mapping == channels.MAP_REPLICATE:
        operand, sent = rows[0], np.stack([rows[0]] * width)
    else:
        operand, sent = rows, rows
    want = reference(sent, merge, dtype)
    pc = fanout(channel, width, mapping, merge, dtype)
    before = channels.fanout_stats()
    cntl = call(pc, operand)
    after = channels.fanout_stats()
    assert not cntl.failed(), cntl.error_text
    assert cntl.fanout_route == "rpc"
    assert type(cntl.fanout_result) is np.ndarray
    assert cntl.fanout_result.dtype == want.dtype
    assert same(cntl.fanout_result, want)
    assert cntl.fanout_attachment is None
    delta = {k: after[k] - before[k] for k in after}
    assert delta["host_operand_bytes"] == 2 * sent.nbytes   # out and back
    assert delta["device_operand_bytes"] == 0
    assert (delta["sub_calls"], delta["merges"]) == (width, width)


@pytest.mark.parametrize("mapping,merge,dtype", CASES,
                         ids=["shard_concat", "shard_sum",
                              "replicate_gather"])
def test_an_operand_spread_over_devices_keeps_the_host_path(
        mesh, deployment, mapping, merge, dtype):
    """An array sharded over several devices has no one block to point refs
    into: it rides as the parent's host bytes, and the counter says so."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    _, channel = deployment
    width, shard = 2, SHARDS[0]
    rows = seeded(7000 + shard, width, shard, dtype)
    spread = NamedSharding(
        Mesh(np.array([mesh.device(0), mesh.device(1)]), ("x",)), P("x"))
    if mapping == channels.MAP_REPLICATE:
        host, sent = rows[0], np.stack([rows[0]] * width)
    else:
        host, sent = rows, rows
    operand = jax.device_put(host, spread)
    assert len(operand.devices()) == 2
    want = reference(sent, merge, dtype)
    pc = fanout(channel, width, mapping, merge, dtype)
    before = channels.fanout_stats()
    cntl = call(pc, operand)
    after = channels.fanout_stats()
    assert not cntl.failed(), cntl.error_text
    assert type(cntl.fanout_result) is np.ndarray
    assert same(cntl.fanout_result, want)
    assert cntl.fanout_attachment is None
    assert after["host_operand_bytes"] - before["host_operand_bytes"] \
        == 2 * sent.nbytes
    assert after["device_operand_bytes"] == before["device_operand_bytes"]


@pytest.mark.parametrize("rows_as", ["device_arrays", "iobufs"])
@pytest.mark.parametrize("width", WIDTHS)
def test_rows_that_are_device_arrays_or_refs_into_one_block(
        mesh, deployment, width, rows_as):
    """``fanout_operand`` a list: a row that is a flat uint8 device array is
    passed whole, a row that is an IOBuf (here: cuts of ONE block, partial
    refs) as it is — no device program is run to make either."""
    _, channel = deployment
    shard = 16384
    rows = seeded(width * 3000, width, shard, np.uint8)
    block = on_device(mesh, rows.reshape(-1))
    if rows_as == "iobufs":
        whole = IOBuf()
        whole.append_device_array(block)
        operand = [whole.cut(shard) for _ in range(width)]
        assert all(r.device_refs()[0].block.data is block for r in operand)
    else:
        operand = [on_device(mesh, r) for r in rows]
    pc = fanout(channel, width, channels.MAP_SHARD, channels.MERGE_CONCAT,
                "uint8")
    before = channels.fanout_stats()
    cntl = call(pc, operand)
    after = channels.fanout_stats()
    assert not cntl.failed(), cntl.error_text
    assert after["host_operand_bytes"] == before["host_operand_bytes"]
    assert after["device_operand_bytes"] - before["device_operand_bytes"] \
        == width * shard
    want = reference(rows, channels.MERGE_CONCAT, np.uint8)
    assert host_bytes(cntl.fanout_attachment) == want.tobytes()
    assert np.array_equal(np.asarray(cntl.fanout_result), want)


def test_a_device_arrays_rows_are_refs_into_one_flat_block(mesh):
    """The cut of a device array runs at most one program a fan-out: every
    row is a partial ref into ONE flat block (the array itself where it is
    flat uint8 already)."""
    rows = seeded(7, 4, 4096, np.uint32)
    cntl = rpc.Controller()
    cntl.fanout_operand = on_device(mesh, rows)
    mapper = channels.ShardingCallMapper()
    subs = [mapper.map_fanout(i, "m", None, cntl) for i in range(4)]
    refs = [s.attachment.device_refs() for s in subs]
    assert all(len(r) == 1 for r in refs)
    assert len({id(r[0].block) for r in refs}) == 1
    assert [(r[0].offset, r[0].length) for r in refs] == [
        (i * 4096, 4096) for i in range(4)]
    assert host_bytes(subs[2].attachment) == rows[2].tobytes()
    flat = on_device(mesh, np.arange(8192, dtype=np.uint8))
    cntl = rpc.Controller()
    cntl.fanout_operand = flat
    sub = channels.ReplicateFanoutMapper().map_fanout(0, "m", None, cntl)
    assert sub.attachment.device_refs()[0].block.data is flat


def test_index_order_when_replies_arrive_in_reverse(mesh, deployment,
                                                    monkeypatch):
    """Shard 0's answer is held back until the other three are MERGED: the
    result is in sub-channel order all the same."""
    service, channel = deployment
    rows = seeded(11, 4, 8192, np.uint8)
    rows[0, 0] = SLOW
    merged, real = [], channels.CollectiveMerger.merge_sub

    def recording(self, parent_cntl, index, sub_cntl, response):
        merged.append(index)
        return real(self, parent_cntl, index, sub_cntl, response)

    monkeypatch.setattr(channels.CollectiveMerger, "merge_sub", recording)
    monkeypatch.setattr(service, "release", lambda: len(merged) >= 3)
    pc = fanout(channel, 4, channels.MAP_SHARD, channels.MERGE_CONCAT,
                "uint8")
    cntl = call(pc, on_device(mesh, rows))
    assert not cntl.failed(), cntl.error_text
    assert sorted(merged[:3]) == [1, 2, 3] and merged[3] == 0
    want = reference(rows, channels.MERGE_CONCAT, np.uint8)
    assert host_bytes(cntl.fanout_attachment) == want.tobytes()
    assert np.array_equal(np.asarray(cntl.fanout_result), want)


@pytest.mark.parametrize("operand_on", ["device", "host"])
def test_a_failing_shard_fails_the_operation_with_no_result(
        mesh, deployment, operand_on):
    _, channel = deployment
    rows = seeded(13, 4, 8192, np.uint8)
    rows[2, 0] = FAIL
    pc = fanout(channel, 4, channels.MAP_SHARD, channels.MERGE_CONCAT,
                "uint8", fail_limit=1)
    before = channels.fanout_stats()
    cntl = call(pc, on_device(mesh, rows) if operand_on == "device"
                else rows)
    assert cntl.failed() and cntl.error_code_ == errors.ETOOMANYFAILS
    assert cntl.fanout_result is None and cntl.fanout_attachment is None
    time.sleep(0.3)                     # the other three may still land
    after = channels.fanout_stats()
    assert after["sub_calls_failed"] - before["sub_calls_failed"] == 1
    assert after["partial_results"] == before["partial_results"]
    # without a fail_limit the shard is missed at the end: still no result
    pc = fanout(channel, 4, channels.MAP_SHARD, channels.MERGE_CONCAT,
                "uint8")
    cntl = call(pc, on_device(mesh, rows) if operand_on == "device"
                else rows)
    assert cntl.failed() and cntl.error_code_ == errors.ERESPONSE
    assert cntl.fanout_result is None and cntl.fanout_attachment is None
    assert channels.fanout_stats()["partial_results"] \
        == before["partial_results"]


def test_an_async_device_fanout_ends_in_done(mesh, deployment):
    _, channel = deployment
    rows = seeded(17, 3, 4096, np.uint8)
    pc = fanout(channel, 3, channels.MAP_SHARD, channels.MERGE_CONCAT,
                "uint8")
    ended = threading.Event()
    cntl = call(pc, on_device(mesh, rows), done=lambda c: ended.set())
    assert ended.wait(10)
    assert not cntl.failed(), cntl.error_text
    assert np.array_equal(np.asarray(cntl.fanout_result),
                          reference(rows, channels.MERGE_CONCAT, np.uint8))


def test_a_reply_from_the_host_is_merged_on_the_host_and_counted(
        mesh, deployment, monkeypatch):
    """A member that answers a device request with host bytes: the result
    is the host path's, and ``host_operand_bytes`` says so."""
    _, channel = deployment
    rows = seeded(19, 2, 4096, np.uint8)
    real = rpc.Controller._peek_response_attachment

    def from_the_host(self):
        att = real(self)
        if att is not None and att.device_bytes():
            data = att.to_bytes()
            att.clear()
            att.append(data)
        return att

    monkeypatch.setattr(rpc.Controller, "_peek_response_attachment",
                        from_the_host)
    pc = fanout(channel, 2, channels.MAP_SHARD, channels.MERGE_CONCAT,
                "uint8")
    before = channels.fanout_stats()
    cntl = call(pc, on_device(mesh, rows))
    after = channels.fanout_stats()
    assert not cntl.failed(), cntl.error_text
    assert type(cntl.fanout_result) is np.ndarray
    assert np.array_equal(cntl.fanout_result,
                          reference(rows, channels.MERGE_CONCAT, np.uint8))
    assert cntl.fanout_attachment.device_bytes() == 0
    assert after["host_operand_bytes"] - before["host_operand_bytes"] \
        == rows.nbytes


# ---- a mid-call degrade of the collective route ---------------------------

class Scale(rpc.Service):
    SERVICE_NAME = "DevScale"

    @rpc.method(EchoRequest, EchoResponse)
    def Twice(self, cntl, request, response, done):
        import jax.numpy as jnp
        att = cntl.request_attachment
        assert att.device_bytes() == len(att), "the row came by the host"
        for r in att.device_refs():
            x = r.block.data.reshape(-1)[r.offset:r.offset + r.length]
            cntl.response_attachment.append_device_array(
                (x.view(jnp.float32) * 2.0).view(jnp.uint8))
        done()


DEVS = (4, 5, 6, 7)             # ici://0 is the module's one server


@pytest.mark.parametrize("how", ["sync", "async"])
def test_a_mid_call_degrade_with_a_device_operand_stays_on_the_devices(
        mesh, how):
    def call(cntl):
        if how == "sync":
            return pc.call_method("DevScale.Twice", cntl,
                                  EchoRequest(message="x"), EchoResponse())
        ended = threading.Event()
        pc.call_method("DevScale.Twice", cntl, EchoRequest(message="x"),
                       EchoResponse(), done=lambda c: ended.set())
        assert ended.wait(60)

    servers = []
    for d in DEVS:
        s = rpc.Server()
        s.add_service(Scale())
        s.register_collective("DevScale.Twice", lambda x: x * 2.0,
                              merge=channels.MERGE_GATHER,
                              mapping=channels.MAP_SHARD)
        assert s.start(f"ici://{d}") == 0
        servers.append(s)
    plane = cf.CollectiveFanoutPlane.instance()
    if plane.health()["down"]:
        cf.registry().serve(99)
        cf.registry().withdraw(99)
    pc = channels.ParallelChannel()
    mapper = channels.ShardingCallMapper()
    merger = channels.CollectiveMerger(merge=channels.MERGE_GATHER,
                                       dtype="float32", shard_shape=(128,))
    chans = []
    try:
        for d in DEVS:
            ch = rpc.Channel()
            assert ch.init(f"ici://{d}", options=rpc.ChannelOptions(
                ici_local_device=DEVS[0])) == 0
            pc.add_channel(ch, mapper=mapper, merger=merger)
            chans.append(ch)
        op = np.arange(4 * 128, dtype=np.float32).reshape(4, 128)
        dev_op = on_device(mesh, op, DEVS[0])
        first = channels.fanout_stats()
        cntl = rpc.Controller()
        cntl.fanout_operand = dev_op
        call(cntl)
        assert not cntl.failed() and cntl.fanout_route == "collective"
        before = channels.fanout_stats()
        assert (before["route_collective"] - first["route_collective"],
                before["route_rpc"] - first["route_rpc"]) == (1, 0)
        plan = fi.FabricFaultPlan(collective_kill_device=DEVS[2])
        fi.install_fabric(plan)
        try:
            cntl = rpc.Controller()
            cntl.fanout_operand = dev_op
            call(cntl)
        finally:
            fi.install_fabric(None)
        after = channels.fanout_stats()
        assert not cntl.failed(), cntl.error_text
        assert cntl.fanout_route == "rpc"
        # counted once, under the route that carried it to its end
        assert (after["calls"] - before["calls"],
                after["route_collective"] - before["route_collective"],
                after["route_rpc"] - before["route_rpc"]) == (1, 0, 1)
        assert after["host_operand_bytes"] == before["host_operand_bytes"]
        assert after["device_operand_bytes"] \
            - before["device_operand_bytes"] == op.nbytes
        att = cntl.fanout_attachment
        assert att.device_bytes() == len(att) == op.nbytes
        got = cntl.fanout_result
        assert set(got.devices()) == {mesh.device(DEVS[0])}
        assert np.array_equal(np.asarray(got), op * 2.0)
    finally:
        for ch in chans:
            ch.close()
        for s in servers:
            s.stop()
        cf.registry().serve(99)         # an epoch move: the route revives
        cf.registry().withdraw(99)


# ---- the four spans --------------------------------------------------------

@pytest.fixture
def session(tmp_path):
    import jax
    span.layer_spans_reset()
    jax.profiler.start_trace(str(tmp_path))
    yield
    if span.layer_on():
        jax.profiler.stop_trace()
    span.layer_spans_reset()


def test_the_four_spans_in_order_with_each_sub_call_under_its_issue(
        mesh, deployment, session):
    import jax
    _, channel = deployment
    rows = seeded(23, 4, 4096, np.uint8)
    pc = fanout(channel, 4, channels.MAP_SHARD, channels.MERGE_CONCAT,
                "uint8")
    operand = on_device(mesh, rows)
    since = span.layer_mark().ns
    cntl = call(pc, operand)
    assert not cntl.failed(), cntl.error_text
    jax.profiler.stop_trace()
    spans = span.layer_spans(since)
    parent = [s for s in spans if s.name == "brpc.fanout"]
    assert len(parent) == 1 and parent[0].n == 4
    parent = parent[0]
    issues = [s for s in spans if s.name == "brpc.fanout.issue"]
    assert [s.cause_id for s in issues] == [parent.span_id] * 4
    assert [s.n for s in issues] == [4096] * 4      # a sub-call's bytes
    assert all(a.end_ns <= b.start_ns for a, b in zip(issues, issues[1:]))
    calls = [s for s in spans if s.name == "brpc.call"]
    assert sorted(s.cause_id for s in calls) == sorted(
        s.span_id for s in issues)                  # one under each issue
    for c in calls:
        i = next(s for s in issues if s.span_id == c.cause_id)
        assert i.start_ns <= c.start_ns and c.end_ns <= i.end_ns
    (wait,) = [s for s in spans if s.name == "brpc.fanout.wait"]
    assert wait.cause_id == parent.span_id
    assert wait.start_ns >= issues[-1].end_ns
    merges = [s for s in spans if s.name == "brpc.fanout.merge"]
    assert all(s.cause_id == parent.span_id for s in merges)
    assert sorted(s.n for s in merges if s.m == 0) == [0, 1, 2, 3]
    (final,) = [s for s in merges if s.m == 1]
    assert final.n == 4 and final.start_ns >= max(
        s.end_ns for s in merges if s.m == 0)
    assert parent.start_ns <= issues[0].start_ns
    assert final.end_ns <= parent.end_ns <= wait.end_ns


def test_no_session_no_span(deployment, mesh):
    _, channel = deployment
    span.layer_spans_reset()
    pc = fanout(channel, 2, channels.MAP_SHARD, channels.MERGE_CONCAT,
                "uint8")
    assert not call(pc, on_device(mesh, seeded(29, 2, 4096,
                                               np.uint8))).failed()
    assert [s for s in span.layer_spans()
            if s.name.startswith("brpc.fanout")] == []


def test_the_totals_are_on_vars():
    from brpc_tpu import bvar
    names = {f"rpc_fanout_{k}" for k in channels.fanout_stats()}
    assert names <= set(bvar.list_exposed())
