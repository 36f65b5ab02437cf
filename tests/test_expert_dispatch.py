"""Expert-parallel dispatch and combine on the per-member loop
(channels/expert_dispatch.py: ``ExpertDispatchMapper``,
``ExpertCombineMerger``, the programs ``brpc_ep_pack`` and
``brpc_ep_combine``) at a small size — hidden 256, 64 tokens, 16 experts,
top-4, 4 ranks — against the numpy reference of the benchmark's deployment
``deepep_ep8_local`` (``benchmarks/reference/ExpertRank.py``), byte for byte:
on seeded routing and on a routing that leaves a rank without a token; the
FP8 codec over every code; the combine's rank order and its precision; the
share each rank adds; the counters and the two spans.

The ranks, the gate and the message rule are the benchmark's own
(``benchmarks/services/ExpertRank.py``, ``benchmarks/clients/ep_dispatch.py``);
the cell's rehearsal is tests/benchmarks/test_ep8_dispatch_combine_cell.py's.
"""
import ml_dtypes
import numpy as np
import pytest

import brpc_tpu.policy  # noqa: F401  (registers protocols)
from brpc_tpu import channels, ici, rpc
from brpc_tpu.channels import byte_words
from brpc_tpu.channels import expert_dispatch as ep
from brpc_tpu.rpc import errors
from brpc_tpu.rpc import span
from benchmarks.clients import ep_dispatch as client
from benchmarks.reference import ExpertRank as reference
from benchmarks.reference import payload
from benchmarks.services import ExpertRank as service
from benchmarks.services.messages import Request, Response

HIDDEN, TOKENS, EXPERTS, TOP_K, RANKS = 256, 64, 16, 4, 4
SEED = 2 ** 31 + 42
SHAPE = dict(experts=EXPERTS, ranks=RANKS)
EP_KEYS = ("pack_programs", "count_reads", "rows_dispatched",
           "padded_bytes", "skipped_ranks", "combine_programs",
           "combine_input_bytes", "host_fallbacks")


@pytest.fixture(scope="module")
def mesh():
    import jax
    m = ici.IciMesh(jax.devices())
    before = ici.IciMesh._default
    ici.IciMesh.set_default(m)
    yield m
    ici.IciMesh.set_default(before)


class FailingRank(rpc.Service):
    """The benchmark's ranks, but that rank ``bad`` fails its sub-call."""
    SERVICE_NAME = "FailingExpertRank"
    bad = 1

    @rpc.method(Request, Response)
    def ExpertRank(self, cntl, request, response, done):
        if request.message.endswith(f"#{self.bad}"):
            cntl.set_failed(errors.EINTERNAL, "told to fail")
        else:
            refs = cntl.request_attachment.device_refs()
            cntl.response_attachment.append_device_array(service.answer(
                tuple(r.block.data for r in refs),
                tuple((r.offset, r.length) for r in refs), HIDDEN, TOP_K))
            response.message = request.message
        done()


@pytest.fixture
def deployment(mesh):
    ranks = service.build(None, hidden=HIDDEN, top_k=TOP_K)
    server = rpc.Server()
    server.add_service(ranks)
    server.add_service(FailingRank())
    assert server.start("ici://0") == 0
    channel = rpc.Channel()
    assert channel.init("ici://0", options=rpc.ChannelOptions(
        ici_local_device=0, max_retry=0, timeout_ms=60000,
        connection_type="pooled")) == 0
    yield f"{ranks.service_name()}.ExpertRank", channel
    channel.close()
    server.stop()


def fanout(channel, merger=None):
    pc = channels.ParallelChannel(fail_limit=1)
    mapper = client.RankMapper(experts=EXPERTS, top_k=TOP_K, ranks=RANKS)
    merger = merger or client.KeyedCombineMerger()
    for _ in range(RANKS):
        pc.add_channel(channel, mapper=mapper, merger=merger)
    return pc


def seeded(index, tokens=TOKENS):
    """Block ``index`` of the seeded set: the batch's words, on the host."""
    return payload.block(SEED, 0, index, tokens * HIDDEN * 2)


def batch(host, mesh, routing=None):
    """The benchmark's gate on the chip, or ``routing`` (ids, weights) laid
    over its ``x``."""
    import jax
    import jax.numpy as jnp
    x, idx, w = client.gate(jax.device_put(jnp.asarray(host), mesh.device(0)),
                            hidden=HIDDEN, experts=EXPERTS, top_k=TOP_K)
    if routing is not None:
        idx, w = (jax.device_put(jnp.asarray(a), mesh.device(0))
                  for a in routing)
    return channels.ExpertBatch(x, idx, w)


def wanted(host, routing=None):
    x, ids, weights = reference.gate(host, HIDDEN, EXPERTS, TOP_K)
    if routing is not None:
        ids, weights = routing
    parts = reference.contributions_of(x, ids, weights, **SHAPE)
    return reference.combined(parts), parts


def dispatch(pc, method, operand, key="op"):
    cntl = rpc.Controller()
    cntl.fanout_operand = operand
    resp = pc.call_method(method, cntl, Request(message=key), Response())
    return cntl, resp


def grown(before):
    return {k: v - before[k] for k, v in ep.expert_dispatch_stats().items()}


def without_rank(host, rank):
    """A routing of the seeded batch in which ``rank`` holds no token's
    expert: each token's experts drawn from the other ranks'."""
    rng = np.random.default_rng(7)
    per = EXPERTS // RANKS
    others = [e for e in range(EXPERTS) if e // per != rank]
    ids = np.stack([rng.choice(others, TOP_K, replace=False)
                    for _ in range(TOKENS)]).astype(np.int32)
    weights = (rng.integers(1, 257, ids.shape) / 256).astype(np.float32)
    return ids, weights


# ---- the codec and the byte views ---------------------------------------------

def test_fp8_decode_is_ml_dtypes_bit_for_bit_over_all_256_codes():
    import jax
    codes = np.arange(256, dtype=np.uint8)
    got = np.asarray(jax.jit(ep.fp8_decode)(codes))
    want = codes.view(ml_dtypes.float8_e4m3fn).astype(np.float32)
    nan = np.isnan(want)
    assert nan.sum() == 2 and np.isnan(got[nan]).all()
    assert np.array_equal(got[~nan].view(np.uint32),
                          want[~nan].view(np.uint32))


@pytest.mark.parametrize("values", ["every_bfloat16", "float32_normals"])
def test_fp8_encode_rounds_as_ml_dtypes(values):
    import jax
    if values == "every_bfloat16":
        v = np.arange(1 << 16, dtype=np.uint32).astype(np.uint16) \
            .view(ml_dtypes.bfloat16).astype(np.float32)
    else:
        rng = np.random.default_rng(3)
        v = (rng.standard_normal(1 << 16)
             * rng.choice([1e-3, 1.0, 100.0], 1 << 16)).astype(np.float32)
    v = v[np.isfinite(v) & (np.abs(v) <= 448)]
    got = np.asarray(jax.jit(ep.fp8_encode)(v))
    assert np.array_equal(got, v.astype(ml_dtypes.float8_e4m3fn)
                          .view(np.uint8))


def test_the_scale_exponent_is_the_least_power_of_two_over_amax_by_448():
    import jax
    amax = np.float32([1e-4, 0.125, 1.0, 1.75, 1.7500001, 1.99, 448.0,
                       449.0, 3.5e4])
    k = np.asarray(jax.jit(ep.fp8_scale_exponents)(amax))
    want = np.ceil(np.log2(amax.astype(np.float64) / 448.0))
    assert np.array_equal(k, want.astype(np.int32))


@pytest.mark.parametrize("nbytes", [4096, 12288, 1 << 20])
def test_word_bytes_is_the_inverse_of_words(nbytes):
    import jax
    import jax.numpy as jnp
    b = np.random.default_rng(nbytes).integers(0, 256, nbytes, np.uint8)
    w = jax.jit(lambda a: byte_words.words(a, jnp.uint16))(b)
    back = np.asarray(jax.jit(byte_words.word_bytes)(w))
    assert np.array_equal(back, b)
    assert np.array_equal(np.asarray(jax.jit(byte_words.word_bytes)(
        b.view("<u2"))), b)


def test_the_gate_on_the_chip_is_the_references(mesh):
    host = seeded(0)
    x, idx, w = batch(host, mesh)
    rx, rids, rw = reference.gate(host, HIDDEN, EXPERTS, TOP_K)
    assert np.array_equal(np.asarray(x).astype(np.float32), rx)
    assert np.array_equal(np.asarray(idx), rids)
    assert np.array_equal(np.asarray(w), rw)
    assert (np.abs(rx) >= 0.125).all() and (np.abs(rx) < 2).all()


def test_capacity_holds_the_uniform_mean_and_eight_sigmas_in_whole_tiles():
    assert ep.record_bytes(7168, 8) == 7488
    assert round(ep.rank_share(256, 8, 8), 3) == 0.662
    cap = ep.capacity_for(4096, 7168, 256, 8, 8)
    assert cap == 2960 and (cap * 7168 * 2) % 4096 == 0
    assert ep.capacity_for(TOKENS, HIDDEN, EXPERTS, TOP_K, RANKS) == TOKENS


# ---- the system against the reference, end to end ---------------------------

@pytest.mark.parametrize("routing", ["seeded", "rank_2_empty"])
def test_dispatch_and_combine_equal_the_reference_byte_for_byte(
        mesh, deployment, routing):
    method, channel = deployment
    host = seeded(1)
    table = without_rank(host, 2) if routing == "rank_2_empty" else None
    before = ep.expert_dispatch_stats(), channels.fanout_stats()
    cap = ep.capacity_for(TOKENS, HIDDEN, EXPERTS, TOP_K, RANKS)
    cntl, resp = dispatch(fanout(channel), method,
                          batch(host, mesh, table), key="op1")
    assert not cntl.failed(), cntl.error_text
    assert resp.message == "op1"
    got = cntl.fanout_result
    assert got.dtype == ml_dtypes.bfloat16 and got.shape == (TOKENS, HIDDEN)
    assert set(got.devices()) == {mesh.device(0)}
    want, parts = wanted(host, table)
    assert np.asarray(got).tobytes() == want.tobytes()
    grew = grown(before[0])
    fan = {k: v - before[1][k] for k, v in channels.fanout_stats().items()}
    skipped = 1 if table is not None else 0
    sent = [int((p != 0).any(axis=1).sum()) for p in parts]
    assert grew["pack_programs"] == grew["count_reads"] == 1
    assert grew["combine_programs"] == 1 and grew["host_fallbacks"] == 0
    assert grew["skipped_ranks"] == skipped
    assert grew["rows_dispatched"] == sum(sent)
    record = ep.record_bytes(HIDDEN, TOP_K)
    assert grew["padded_bytes"] == sum(cap - n for n in sent if n) \
        * (record + 2 * HIDDEN)
    assert grew["combine_input_bytes"] == (RANKS - skipped) * cap * HIDDEN * 2
    assert fan["sub_calls"] == RANKS - skipped
    assert fan["device_operand_bytes"] == (RANKS - skipped) * cap * record
    assert fan["host_operand_bytes"] == fan["partial_results"] == 0


def test_a_rank_past_its_buffer_is_packed_again_at_every_token(
        mesh, deployment):
    """1,024 tokens every one of which has its experts on rank 0: past the
    buffer of the uniform bound (864 records)."""
    method, channel = deployment
    tokens = 1024
    assert ep.capacity_for(tokens, HIDDEN, EXPERTS, TOP_K, RANKS) == 864
    host = seeded(2, tokens)
    rng = np.random.default_rng(5)
    per = EXPERTS // RANKS
    table = (np.stack([rng.choice(per, TOP_K, replace=False)
                       for _ in range(tokens)]).astype(np.int32),
             (rng.integers(1, 257, (tokens, TOP_K)) / 256).astype(np.float32))
    before = ep.expert_dispatch_stats()
    cntl, _ = dispatch(fanout(channel), method, batch(host, mesh, table))
    assert not cntl.failed(), cntl.error_text
    assert np.asarray(cntl.fanout_result).tobytes() == \
        wanted(host, table)[0].tobytes()
    grew = grown(before)
    assert grew["pack_programs"] == 2 and grew["count_reads"] == 1
    assert grew["skipped_ranks"] == RANKS - 1


def test_a_failed_rank_fails_the_operation_with_no_result(mesh, deployment):
    _, channel = deployment
    before = channels.fanout_stats()
    cntl, _ = dispatch(fanout(channel), "FailingExpertRank.ExpertRank",
                       batch(seeded(3), mesh))
    assert cntl.failed()
    assert "fanout_result" not in cntl.__dict__
    assert channels.fanout_stats()["partial_results"] \
        == before["partial_results"]


def test_a_host_operand_is_packed_on_the_chip_and_counted(mesh, deployment):
    method, channel = deployment
    host = seeded(4)
    operand = channels.ExpertBatch(*(np.asarray(a) for a in batch(host,
                                                                  mesh)))
    before = ep.expert_dispatch_stats(), channels.fanout_stats()
    cntl, _ = dispatch(fanout(channel), method, operand)
    assert not cntl.failed(), cntl.error_text
    assert np.asarray(cntl.fanout_result).tobytes() == \
        wanted(host)[0].tobytes()
    assert grown(before[0])["host_fallbacks"] == 1
    assert channels.fanout_stats()["host_operand_bytes"] \
        > before[1]["host_operand_bytes"]


def test_a_reply_from_the_host_is_combined_by_numpy_and_counted(
        mesh, deployment):
    method, channel = deployment

    class HostRankTwo(client.KeyedCombineMerger):
        def merge_sub(self, parent_cntl, index, sub_cntl, response):
            if index == 2:
                att = sub_cntl._peek_response_attachment()
                data = att.to_bytes()
                att.clear()
                att.append(data)
            return super().merge_sub(parent_cntl, index, sub_cntl, response)

    host = seeded(5)
    before = ep.expert_dispatch_stats()
    cntl, _ = dispatch(fanout(channel, merger=HostRankTwo()), method,
                       batch(host, mesh))
    assert not cntl.failed(), cntl.error_text
    got = cntl.fanout_result
    assert isinstance(got, np.ndarray)
    assert got.tobytes() == wanted(host)[0].tobytes()
    grew = grown(before)
    assert grew["host_fallbacks"] == 1 and grew["combine_programs"] == 0


# ---- the combine's order, precision and shares -------------------------------

def test_a_combine_accumulated_in_bfloat16_is_not_the_reference():
    host = seeded(6)
    want, parts = wanted(host)
    low = parts[0].astype(ml_dtypes.bfloat16)
    for p in parts[1:]:
        low = low + p.astype(ml_dtypes.bfloat16)
    wrong = np.count_nonzero(low.view(np.uint16) != want.view(np.uint16))
    assert wrong > want.size // 20


def test_the_combine_adds_in_rank_order(mesh):
    """Rows 1, 2**-8, 2**-24, 2**-24 of one token: in rank order float32
    keeps 1 + 2**-8 (two ties to even), which rounds to 1.0 in bfloat16;
    in reverse order the sum is 1 + 2**-8 + 2**-23, which rounds up."""
    import jax
    vals = [1.0, 2.0 ** -8, 2.0 ** -24, 2.0 ** -24]
    cap = 8
    place = np.full((RANKS, TOKENS), cap, np.int32)
    place[:, 5] = 3
    parts = []
    for v in vals:
        rows = np.zeros((cap, HIDDEN), ml_dtypes.bfloat16)
        rows[3] = v
        parts.append((jax.device_put(rows.view(np.uint8).reshape(-1)),))
    run = ep._combine_program()
    got = np.asarray(run(tuple(parts), place, ranks=(0, 1, 2, 3),
                         hidden=HIDDEN)).astype(np.float32)
    back = np.asarray(run(tuple(parts[::-1]), place, ranks=(0, 1, 2, 3),
                          hidden=HIDDEN)).astype(np.float32)
    assert (got[5] == 1.0).all() and (back[5] == 1.0 + 2.0 ** -7).all()
    assert not np.delete(got, 5, axis=0).any()


def test_each_rank_adds_its_share_once_and_the_shares_are_the_whole():
    """The share test: Σ over ranks of each rank's part is the uncut
    Σ_k w_k · dequant(x) of every token, to the part's one bfloat16
    rounding; a part dropped, doubled or taken for another rank's is not."""
    host = seeded(7)
    x, ids, weights = reference.gate(host, HIDDEN, EXPERTS, TOP_K)
    _, parts = wanted(host)
    whole = reference.dequantized(x).astype(np.float64) \
        * weights.astype(np.float64).sum(axis=1)[:, None]
    total = sum(p.astype(np.float64) for p in parts)
    assert np.allclose(total, whole, rtol=RANKS * 2.0 ** -8, atol=0)
    for r in range(RANKS):
        assert not np.allclose(total - parts[r], whole, rtol=2.0 ** -8)
        assert not np.allclose(total + parts[r], whole, rtol=2.0 ** -8)
        nxt = parts[(r + 1) % RANKS]
        assert not np.allclose(total - parts[r] + nxt, whole, rtol=2.0 ** -8)


def test_the_combine_program_is_the_references_on_given_replies(mesh):
    """``brpc_ep_combine`` alone, with a hand-made handle: rank order,
    the fill of a token a rank did not get, one rounding."""
    import jax
    rng = np.random.default_rng(11)
    cap = 8
    rows = [rng.standard_normal((cap, HIDDEN)).astype(ml_dtypes.bfloat16)
            for _ in range(3)]
    place = np.full((3, TOKENS), cap, np.int32)
    for r in range(3):
        place[r, rng.choice(TOKENS, cap, replace=False)] = np.arange(cap)
    parts = tuple((jax.device_put(rw.view(np.uint8).reshape(-1)),)
                  for rw in rows)
    got = ep._combine_program()(parts, place, ranks=(0, 1, 2),
                                hidden=HIDDEN)
    want = ep.combine_on_host({r: rows[r].tobytes() for r in range(3)},
                              place, HIDDEN)
    acc = np.zeros((TOKENS, HIDDEN), np.float32)
    for r in range(3):
        hit = place[r] < cap
        acc[hit] += rows[r][place[r][hit]].astype(np.float32)
    assert np.asarray(got).tobytes() == want.tobytes() \
        == acc.astype(ml_dtypes.bfloat16).tobytes()


# ---- the counters and the spans ----------------------------------------------

@pytest.fixture
def session(tmp_path):
    import jax
    span.layer_spans_reset()
    jax.profiler.start_trace(str(tmp_path))
    yield
    if span.layer_on():
        jax.profiler.stop_trace()
    span.layer_spans_reset()


def test_the_dispatch_and_combine_spans_lie_under_the_fanout(
        mesh, deployment, session):
    import jax
    method, channel = deployment
    since = span.layer_mark().ns
    cntl, _ = dispatch(fanout(channel), method, batch(seeded(8), mesh))
    assert not cntl.failed(), cntl.error_text
    jax.profiler.stop_trace()
    spans = span.layer_spans(since)
    (parent,) = [s for s in spans if s.name == "brpc.fanout"]
    issues = [s for s in spans if s.name == "brpc.fanout.issue"
              and s.cause_id == parent.span_id]
    (final,) = [s for s in spans if s.name == "brpc.fanout.merge" and s.m]
    (pack,) = [s for s in spans if s.name == "brpc.fanout.dispatch"]
    (combine,) = [s for s in spans if s.name == "brpc.fanout.combine"]
    first = min(issues, key=lambda s: s.start_ns)
    assert pack.cause_id == first.span_id and pack.n == TOKENS
    assert 0 <= pack.cpu_ns <= pack.end_ns - pack.start_ns
    assert combine.cause_id == parent.span_id and combine.m == RANKS
    assert final.start_ns <= combine.start_ns <= combine.end_ns \
        <= final.end_ns


def test_no_session_no_span_and_the_totals_are_on_vars(mesh, deployment):
    from brpc_tpu import bvar
    method, channel = deployment
    span.layer_spans_reset()
    assert not dispatch(fanout(channel), method,
                        batch(seeded(9), mesh))[0].failed()
    assert not [s for s in span.layer_spans()
                if s.name.startswith("brpc.fanout.")]
    assert tuple(channels.expert_dispatch_stats()) == EP_KEYS
    assert {f"rpc_fanout_ep_{k}" for k in EP_KEYS} \
        <= set(bvar.list_exposed())
