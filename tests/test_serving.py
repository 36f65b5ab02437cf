"""Production serving subsystem (brpc_tpu/serving, ISSUE 14 + the
ISSUE-15 zero-copy KV handoff).

Legs:

  * **PagedKvPool units** — block accounting, byte-exact custody,
    admission-aware eviction order (band before weight before LRU, the
    protected-band fence), pins, and the TIMER-DRIVEN expiry sweep (the
    ISSUE-14 bugfix regression: a parked session on an otherwise-idle
    worker is reclaimed with zero new traffic);
  * **zero-copy KV handoff** (ISSUE 15) — byte parity of the adopted /
    scattered / materialized load routes incl. straddling segments and
    partial-tail zeroing, abort-clean fills, counted pins, the
    snapshot-view materialize bugfix, RPC-level route assertions with
    custody census, and the 2-PROCESS shm claim-to-pool leg;
  * **CoW prefix sharing + outside-the-lock fills** (ISSUE 16) — the
    >= 5x capacity A/B on a 50 %-shared-prefix mix, refcounted dedupe
    accounting, mid-block divergence and ``write_rows`` CoW splits with
    co-owners' bytes intact, refcount-aware eviction order, reload
    keeping other tenants' bytes, read-only views over shared blocks,
    load/load_into locking parity under BOTH fill disciplines, the
    two-thread concurrent-fill stress, the commit-race window
    (last-commit-wins / pinned SessionBusy abort), and the RPC-level
    concurrent LoadKv leg with /status truth and custody census;
  * **ContinuousBatchScheduler units** (manual stepping) — per-step
    admit/retire, tokens bit-exact against the single-process reference
    under staggered joins, interactive preemption preserving progress,
    deadline expiry in the batch queue, compiled-step parity;
  * **service level** — the rebuilt disaggregated workers: batched
    decode end-to-end with the route asserted through the /status
    serving block, LALB prefill→decode routing, pool-saturation sheds
    with retry hints, and the idle-reclaim regression over a real RPC;
  * **autoscaler units** — watermark/hysteresis/cooldown decisions on
    an injected clock;
  * **elastic chaos** (tier-1, one subprocess with a real pod) —
    scale-up + kill + revive + scale-down mid-traffic: zero
    client-visible failures, every completion bit-exact, the pod epoch
    delta asserted.
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import brpc_tpu.policy  # noqa: F401
from brpc_tpu import rpc

pytestmark = pytest.mark.serving

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _model():
    from examples.disagg_serving import model
    return model


def _rows(tokens):
    """Prompt → token-major pool rows (the LoadKv transpose)."""
    m = _model()
    kv = np.asarray(m.toy_kv_blocks(tokens))
    seq = len(tokens)
    return kv.reshape(m.KV_LAYERS, seq, m.KV_DMODEL).transpose(
        1, 0, 2).reshape(seq, m.KV_LAYERS * m.KV_DMODEL)


def _mk_pool(num_blocks=32, block_tokens=8, ttl_s=120.0,
             use_timers=False, now=None, **kw):
    from brpc_tpu.serving import KvPoolOptions, PagedKvPool
    m = _model()
    opts = KvPoolOptions(bytes_per_token=m.KV_LAYERS * m.KV_DMODEL,
                         num_blocks=num_blocks,
                         block_tokens=block_tokens, ttl_s=ttl_s,
                         use_timers=use_timers, **kw)
    return PagedKvPool(opts, now=now)


def _mk_sched(pool, max_batch=8, **kw):
    from brpc_tpu.serving import (BatchSchedulerOptions,
                                  ContinuousBatchScheduler)
    m = _model()
    kw.setdefault("auto_start", False)
    return ContinuousBatchScheduler(
        pool, BatchSchedulerOptions(vocab=m.VOCAB, max_batch=max_batch,
                                    **kw))


class _Sink:
    """Collects one StepRequest outcome."""

    def __init__(self):
        self.tokens = None
        self.error = None

    def emit(self, tokens):
        self.tokens = list(tokens)

    def fail(self, code, text, retry_after_ms):
        self.error = (code, text, retry_after_ms)


def _submit(sched, session, steps, priority=None, tenant="",
            deadline_us=None):
    from brpc_tpu.serving import StepRequest
    sink = _Sink()
    sched.submit(StepRequest(session, steps, sink.emit, sink.fail,
                             priority=priority, tenant=tenant,
                             deadline_us=deadline_us))
    return sink


# ---------------------------------------------------------------------------
# Paged KV pool.
# ---------------------------------------------------------------------------

class TestPagedKvPool:
    def test_load_materialize_byte_exact_and_accounting(self):
        pool = _mk_pool(num_blocks=16, block_tokens=8)
        try:
            t1 = [3 * j % 97 for j in range(20)]     # 3 blocks
            t2 = [5 * j % 89 for j in range(8)]      # 1 block
            r1, r2 = _rows(t1), _rows(t2)
            pool.load("a", r1, last_token=t1[-1])
            pool.load("b", r2, last_token=t2[-1])
            d = pool.describe()
            assert d["blocks_used"] == 4 and d["sessions"] == 2
            assert np.array_equal(pool.materialize("a"), r1)
            assert np.array_equal(pool.materialize("b"), r2)
            s = pool.get("a")
            assert s.seq_len == 20 and s.acc == int(
                r1.sum(dtype=np.int64))
            assert pool.release("a") and not pool.release("a")
            assert pool.describe()["blocks_used"] == 1
        finally:
            pool.close()

    def test_partial_tail_block_zeroed(self):
        # a partially-filled tail block must not leak the previous
        # tenant's bytes or reduction sums
        pool = _mk_pool(num_blocks=2, block_tokens=8)
        try:
            full = [7] * 16                           # both blocks, full
            pool.load("x", _rows(full), last_token=7)
            pool.release("x")
            short = [11] * 9                          # 2 blocks, 7 stale
            s = pool.load("y", _rows(short), last_token=11)
            tail_blk = int(s.blocks[1])
            assert pool._pos_sums[tail_blk, 1:].sum() == 0
            assert np.array_equal(pool.materialize("y"), _rows(short))
        finally:
            pool.close()

    def test_lru_eviction_within_band_and_touch(self):
        pool = _mk_pool(num_blocks=4, block_tokens=8)
        try:
            # DISTINCT content per session: identical rows would
            # prefix-share one physical block (ISSUE 16) and the pool
            # would never feel the pressure this test is about
            for i, name in enumerate(("old", "mid", "new")):
                pool.load(name, _rows([10 + i] * 8), last_token=1,
                          priority=2)
                time.sleep(0.002)
            pool.touch("old")                 # now "mid" is LRU
            pool.load("D", _rows([2] * 16), last_token=2, priority=2)
            assert pool.get("mid") is None
            assert pool.get("old") is not None
            assert pool.evicted_reason("mid") == "pressure"
        finally:
            pool.close()

    def test_batch_evicted_before_interactive(self):
        pool = _mk_pool(num_blocks=3, block_tokens=8)
        try:
            pool.load("inter", _rows([1] * 8), last_token=1, priority=0)
            time.sleep(0.002)
            pool.load("batch", _rows([2] * 8), last_token=2, priority=3)
            # interactive is OLDER, but the batch band absorbs pressure
            pool.load("new", _rows([3] * 16), last_token=3, priority=1)
            assert pool.get("batch") is None
            assert pool.get("inter") is not None
        finally:
            pool.close()

    def test_tenant_weight_tiebreak_from_admission(self):
        from brpc_tpu.rpc.admission import AdmissionOptions
        from brpc_tpu.serving import KvPoolOptions, PagedKvPool
        m = _model()
        adm = AdmissionOptions(tenant_weights={"gold": 8, "bronze": 1})
        opts = KvPoolOptions.from_admission(
            adm, bytes_per_token=m.KV_LAYERS * m.KV_DMODEL,
            num_blocks=3, block_tokens=8, use_timers=False)
        assert opts.tenant_weights == {"gold": 8, "bronze": 1}
        pool = PagedKvPool(opts)
        try:
            # same band; bronze is NEWER but lighter — evicted first
            pool.load("g", _rows([1] * 8), last_token=1, priority=2,
                      tenant="gold")
            time.sleep(0.002)
            pool.load("b", _rows([2] * 8), last_token=2, priority=2,
                      tenant="bronze")
            pool.load("n", _rows([3] * 16), last_token=3, priority=2)
            assert pool.get("b") is None
            assert pool.get("g") is not None
            assert any(k.startswith("evicted_pressure[bronze]")
                       for k in pool.describe()["by_tenant"])
        finally:
            pool.close()

    def test_requester_cannot_evict_more_protected_band(self):
        from brpc_tpu.serving import PoolSaturated
        pool = _mk_pool(num_blocks=2, block_tokens=8)
        try:
            pool.load("inter", _rows([1] * 16), last_token=1,
                      priority=0)
            with pytest.raises(PoolSaturated):
                pool.load("batch", _rows([2] * 8), last_token=2,
                          priority=3)
            assert pool.get("inter") is not None
        finally:
            pool.close()

    def test_pinned_never_evicted_or_expired(self):
        from brpc_tpu.serving import PoolSaturated
        pool = _mk_pool(num_blocks=2, block_tokens=8, ttl_s=0.0)
        try:
            pool.load("run", _rows([1] * 16), last_token=1, priority=3)
            assert pool.pin("run")
            with pytest.raises(PoolSaturated):
                pool.load("x", _rows([2] * 8), last_token=2, priority=0)
            assert pool.expire_idle() == 0    # pinned: ttl ignored
            pool.unpin("run")
            assert pool.expire_idle() == 1
        finally:
            pool.close()

    def test_timer_sweep_reclaims_idle_session_without_traffic(self):
        """THE ISSUE-14 regression: expiry is timer-driven — a parked
        session on an otherwise-idle pool is reclaimed on time with
        ZERO further loads or decodes (the old example swept only
        inside LoadKv)."""
        pool = _mk_pool(num_blocks=4, block_tokens=8, ttl_s=0.15,
                        use_timers=True, sweep_interval_s=0.05)
        try:
            pool.load("parked", _rows([1] * 8), last_token=1)
            deadline = time.monotonic() + 5.0
            while pool.sessions() and time.monotonic() < deadline:
                time.sleep(0.02)
            assert pool.sessions() == 0, "idle session never reclaimed"
            assert pool.expirations.get_value() >= 1
            assert pool.describe()["blocks_free"] == 4
        finally:
            pool.close()

    def test_reload_of_pinned_session_refused(self):
        """Re-prefilling a session that is PINNED in the step roster is
        refused (SessionBusy): freeing a rostered session's blocks
        would hand them to the new bytes mid-program — the running
        gather would read the replacement's KV (review finding)."""
        from brpc_tpu.serving import SessionBusy
        pool = _mk_pool(num_blocks=8, block_tokens=8)
        try:
            r1 = _rows([1] * 8)
            pool.load("s", r1, last_token=1)
            assert pool.pin("s")
            with pytest.raises(SessionBusy):
                pool.load("s", _rows([2] * 8), last_token=2)
            # the rostered table is untouched
            assert np.array_equal(pool.materialize("s"), r1)
            pool.unpin("s")
            pool.load("s", _rows([2] * 8), last_token=2)  # now fine
            assert np.array_equal(pool.materialize("s"), _rows([2] * 8))
        finally:
            pool.close()

    def test_zero_length_session_rejected(self):
        pool = _mk_pool()
        try:
            with pytest.raises(ValueError):
                pool.load("empty", np.zeros(
                    (0, pool.options.bytes_per_token), np.uint8),
                    last_token=0)
        finally:
            pool.close()

    def test_manual_expiry_with_injected_clock(self):
        clock = [100.0]
        pool = _mk_pool(num_blocks=4, block_tokens=8, ttl_s=10.0,
                        now=lambda: clock[0])
        try:
            pool.load("s", _rows([1] * 8), last_token=1)
            clock[0] = 109.0
            assert pool.expire_idle() == 0
            clock[0] = 111.0
            assert pool.expire_idle() == 1
            assert pool.evicted_reason("s") == "expired"
        finally:
            pool.close()


# ---------------------------------------------------------------------------
# Zero-copy KV handoff (ISSUE 15): attachment bytes land DIRECTLY in
# pool blocks — byte parity across all three load routes (straddling
# segments, partial tails, prior-tenant zeroing), custody census,
# abort-clean fills, and the snapshot-view bugfix pins.
# ---------------------------------------------------------------------------

def _wire(tokens):
    """Prompt → the layer-major wire payload LoadKv receives."""
    return np.asarray(_model().toy_kv_blocks(tokens))


class TestKvZeroCopyHandoff:
    def _adopt(self, pool, session, tokens, segments, **kw):
        from brpc_tpu.butil.iobuf import IOBuf
        from brpc_tpu.serving import load_wire_attachment
        m = _model()
        buf = IOBuf()
        for seg in segments:
            buf.append_user_data(memoryview(seg))
        kw.setdefault("last_token", tokens[-1])
        return load_wire_attachment(pool, buf, session, len(tokens),
                                    m.KV_LAYERS, m.KV_DMODEL, **kw)

    def test_three_routes_byte_parity_incl_straddle(self):
        """adopted (host segs) vs scattered (device segs) vs
        materialized (load) produce IDENTICAL pool state — stored rows,
        pos_sums arena, and acc — including a multi-segment source cut
        at boundaries that straddle blocks, tokens, and even a single
        layer row."""
        from brpc_tpu.butil.iobuf import IOBuf
        from brpc_tpu.serving import (kv_load_stats,
                                      load_wire_attachment, wire_source)
        m = _model()
        tokens = [(7 * j) % 499 for j in range(21)]     # partial tail
        blob = _wire(tokens).tobytes()
        pool = _mk_pool(num_blocks=16, block_tokens=8)
        try:
            s0 = kv_load_stats()
            # adopted: one host segment
            self._adopt(pool, "one", tokens, [blob])
            # adopted: segments cut mid-token and mid-layer-row
            cuts = [0, 13, 777, 781, 5000, 5003, len(blob)]
            segs = [blob[cuts[i]:cuts[i + 1]]
                    for i in range(len(cuts) - 1)]
            self._adopt(pool, "straddle", tokens, segs)
            # scattered: the device-array shape (loopback plane)
            buf = IOBuf()
            buf.append_device_array(m.toy_kv_blocks(tokens))
            load_wire_attachment(pool, buf, "dev", 21, m.KV_LAYERS,
                                 m.KV_DMODEL, last_token=tokens[-1])
            # scattered with an OFFSET device ref (a cut moved the ref,
            # not the bytes): only the referenced slice crosses D2H
            import jax.numpy as jnp
            padded = jnp.concatenate([
                jnp.zeros(7, jnp.uint8),
                jnp.asarray(np.frombuffer(blob, np.uint8))])
            buf2 = IOBuf()
            buf2.append_device_array(padded)
            buf2.pop_front(7)
            load_wire_attachment(pool, buf2, "devcut", 21, m.KV_LAYERS,
                                 m.KV_DMODEL, last_token=tokens[-1])
            # materialized: the PR-14 reference
            ref = pool.load("ref", _rows(tokens), last_token=tokens[-1])
            want = _rows(tokens)
            for name in ("one", "straddle", "dev", "devcut"):
                s = pool.get(name)
                assert np.array_equal(pool.materialize(name), want), name
                assert s.acc == ref.acc, name
                for k in range(len(s.blocks)):
                    assert np.array_equal(
                        pool._pos_sums[int(s.blocks[k])],
                        pool._pos_sums[int(ref.blocks[k])]), (name, k)
            s1 = kv_load_stats()
            assert s1["adopted"] - s0["adopted"] == 2
            assert s1["scattered"] - s0["scattered"] == 2
            # one copy pass per adopted/scattered load
            assert s1["copy_bytes"] - s0["copy_bytes"] == 4 * len(blob)
        finally:
            pool.close()

    def test_load_route_consults_plane_health(self):
        """ISSUE 17 seam: when the fabric socket that carried the
        LoadKv is supplied, the adopt-vs-scatter label comes from the
        SHARED route table — every descriptor plane down means the load
        records SCATTERED, a healthy plane (or no sock, the in-process
        path) keeps ADOPTED, and DEVICE-class payloads scatter no
        matter what the planes say."""
        from brpc_tpu.butil.iobuf import IOBuf
        from brpc_tpu.ici import fabric as _  # registers route flags
        from brpc_tpu.ici import route as _route
        from brpc_tpu.serving.kv_source import (ADOPTED, SCATTERED,
                                                wire_source)

        class _Sock:
            def __init__(self, up):
                self.up = up

            def plane_usable(self, plane, nbytes=0):
                return self.up

        # big enough to clear ici_fabric_bulk_host_min (64 KiB): below
        # it candidates() short-circuits to [INLINE] regardless of
        # plane health
        big = bytes(128 * 1024)
        layers, dmodel = 4, 64
        seq = len(big) // (layers * dmodel)

        def host_buf():
            buf = IOBuf()
            buf.append_user_data(memoryview(big))
            return buf

        # no sock: the in-process path, label untouched
        assert wire_source(host_buf(), layers, seq,
                           dmodel).route == ADOPTED
        # healthy descriptor planes: adopt in place
        assert wire_source(host_buf(), layers, seq, dmodel,
                           sock=_Sock(True)).route == ADOPTED
        # every descriptor plane has left UP: the counters must not
        # claim an in-place adoption rode a healthy plane
        assert wire_source(host_buf(), layers, seq, dmodel,
                           sock=_Sock(False)).route == SCATTERED
        # sanity: the fake's truth table IS what candidates() consults
        assert _route.SHM in _route.candidates(_Sock(True), _route.HOST,
                                               len(big))
        assert _route.candidates(_Sock(False), _route.HOST,
                                 len(big)) == [_route.INLINE]
        # DEVICE class scatters even on healthy planes (the D2H
        # crossing is the wire transfer itself)
        import jax.numpy as jnp
        dev = IOBuf()
        dev.append_device_array(
            jnp.zeros(len(big), jnp.uint8))
        assert wire_source(dev, layers, seq, dmodel,
                           sock=_Sock(True)).route == SCATTERED

    def test_partial_tail_zeroed_after_prior_tenant_adoption(self):
        """Tail-zeroing must hold on the ADOPTED path too: a short
        session scattered over a block a longer prior tenant filled
        leaves no stale bytes or reduction sums in the tail."""
        pool = _mk_pool(num_blocks=2, block_tokens=8)
        try:
            full = [7] * 16
            self._adopt(pool, "x", full, [_wire(full).tobytes()])
            pool.release("x")
            short = [11] * 9                       # 2 blocks, 7 stale
            s = self._adopt(pool, "y", short, [_wire(short).tobytes()])
            tail_blk = int(s.blocks[1])
            bpt = pool.options.bytes_per_token
            assert pool._pos_sums[tail_blk, 1:].sum() == 0
            assert pool._store[tail_blk, bpt:].sum() == 0
            assert np.array_equal(pool.materialize("y"), _rows(short))
        finally:
            pool.close()

    def test_fill_abort_returns_blocks_clean(self):
        """A fill that raises mid-load aborts the reservation: blocks
        back on the free list, no session entry, the failure counted —
        the eviction-mid-load / bad-source custody leg."""
        pool = _mk_pool(num_blocks=8, block_tokens=8)
        try:
            free0 = pool.describe()["blocks_free"]
            aborts0 = pool.fill_aborts.get_value()

            def bad_fill(views):
                views[0][0, 0] = 1          # partial write, then die
                raise RuntimeError("source died mid-scatter")

            with pytest.raises(RuntimeError, match="mid-scatter"):
                pool.load_into("victim", 20, bad_fill, last_token=1)
            assert pool.get("victim") is None
            assert pool.describe()["blocks_free"] == free0
            assert pool.fill_aborts.get_value() == aborts0 + 1
            # the pool still loads fine afterwards
            t = [5] * 20
            self._adopt(pool, "after", t, [_wire(t).tobytes()])
            assert np.array_equal(pool.materialize("after"), _rows(t))
            # a RELOAD whose fill aborts keeps the session's PREVIOUS
            # KV valid when the free list covered the reservation (the
            # old table's free is deferred to commit)
            with pytest.raises(RuntimeError, match="mid-scatter"):
                pool.load_into("after", 20, bad_fill, last_token=1)
            assert np.array_equal(pool.materialize("after"), _rows(t))
        finally:
            pool.close()

    def test_free_list_keeps_extent_order_after_churn(self):
        """The descending-sorted free list invariant: after arbitrary
        release order, pops still hand out ASCENDING block runs so
        adopted fills coalesce into few contiguous extents (the perf
        contract load_into's one-strided-pass fill depends on)."""
        pool = _mk_pool(num_blocks=8, block_tokens=8)
        try:
            for name in ("a", "b", "c", "d"):
                pool.load(name, _rows([1] * 16), last_token=1)
            for name in ("c", "a", "d", "b"):   # scrambled release
                pool.release(name)
            assert pool._free == sorted(pool._free, reverse=True)
            s = pool.load("big", _rows([2] * 64), last_token=2)
            assert np.array_equal(
                s.blocks, np.arange(int(s.blocks[0]),
                                    int(s.blocks[0]) + 8))
        finally:
            pool.close()

    def test_snapshot_view_and_straddle_copy(self):
        """THE ISSUE-15 materialize bugfix, pinned both ways: a
        contiguous-extent session snapshots as a READ-ONLY zero-copy
        view (pinned until unpin; release refused while read), and a
        non-contiguous session keeps the defensive copy."""
        pool = _mk_pool(num_blocks=4, block_tokens=8)
        try:
            t = [3] * 16
            pool.load("v", _rows(t), last_token=3)
            rows, seq, last, is_view = pool.snapshot("v", view=True)
            assert is_view and not rows.flags.writeable
            assert np.shares_memory(rows, pool._store)
            assert np.array_equal(rows, _rows(t))
            # pinned: eviction fenced; a racing release is DEFERRED to
            # the last unpin, never dropped and never freed mid-read
            assert pool.expire_idle(now=pool._now() + 1e9) == 0
            assert pool.release("v") is True      # accepted, deferred
            assert pool.get("v") is not None      # ...but not yet freed
            pool.unpin("v")                       # last reader out
            assert pool.get("v") is None          # now freed
            assert pool.release("v") is False     # idempotent: gone
            # force non-contiguous: fill, punch a hole, reload bigger
            pool.load("f1", _rows([1] * 8), last_token=1)
            pool.load("f2", _rows([2] * 8), last_token=2)
            pool.load("f3", _rows([3] * 8), last_token=3)
            pool.release("f2")
            pool.release("f1")
            pool.load("nc", _rows([4] * 24), last_token=4)  # 0,1,3
            s = pool.get("nc")
            assert not np.array_equal(
                s.blocks, np.arange(int(s.blocks[0]),
                                    int(s.blocks[0]) + 3))
            rows, _seq, _last, is_view = pool.snapshot("nc", view=True)
            assert not is_view
            assert not np.shares_memory(rows, pool._store)
            assert np.array_equal(rows, _rows([4] * 24))
            # no pin owed on the copy path
            assert pool.release("nc") is True
            # legacy 3-tuple surface unchanged; materialize stays
            # copy-only (it cannot carry the is-a-pin-owed flag)
            pool.load("old", _rows(t), last_token=3)
            snap = pool.snapshot("old")
            assert len(snap) == 3
            mat = pool.materialize("old")
            assert not np.shares_memory(mat, pool._store)
        finally:
            pool.close()

    def test_pins_are_counted_not_boolean(self):
        """A roster pin and a snapshot-view pin on the SAME session
        nest: releasing one must not unfence the other (the pinned
        bool→count change)."""
        pool = _mk_pool(num_blocks=4, block_tokens=8)
        try:
            pool.load("s", _rows([1] * 8), last_token=1)
            assert pool.pin("s")                      # roster
            rows, *_rest, is_view = pool.snapshot("s", view=True)
            assert is_view                            # + view pin
            pool.unpin("s")                           # view done
            # still fenced by the roster pin
            assert pool.expire_idle(now=pool._now() + 1e9) == 0
            assert pool.release("s") is True          # deferred again
            assert pool.get("s") is not None
            # a deferred-released session is LOGICALLY gone to new
            # readers: no new pin, no new snapshot — only the old
            # pinned reader drains it
            assert pool.pin("s") is False
            assert pool.snapshot("s") is None
            pool.unpin("s")                           # roster out: freed
            assert pool.get("s") is None
        finally:
            pool.close()

    def test_rpc_routes_asserted_and_custody_drains(self):
        """Service level: LoadKv over loopback rides the scattered
        route (DEVICE block), the flag-off leg rides materialized, and
        over the NATIVE-ICI plane the parked att handle is taken
        segment-wise — byte-exact decode on every route, with the att
        table and device-ref registry drained after each (the census
        fixture enforces it again at teardown)."""
        import gc

        from brpc_tpu.butil import flags as _fl
        from brpc_tpu.ici import native_plane as npl
        from brpc_tpu.serving import kv_load_stats
        from examples.example_echo_pb2 import EchoRequest, EchoResponse
        m = _model()
        tokens = [(17 * j) % 499 for j in range(40)]
        want = m.reference_generate(tokens, 9)

        def load(ch, session):
            kv = m.toy_kv_blocks(tokens)
            cntl = rpc.Controller()
            cntl.request_attachment.append_device_array(kv)
            ch.call_method("Decode.LoadKv", cntl, EchoRequest(
                message=json.dumps({"session": session,
                                    "seq_len": len(tokens),
                                    "last_token": tokens[-1]})),
                EchoResponse)
            assert not cntl.failed(), cntl.error_text

        def decode(ch, session):
            cntl = rpc.Controller()
            resp = ch.call_method("Decode.Decode", cntl, EchoRequest(
                message=json.dumps({"session": session, "steps": 9,
                                    "mode": "sync"})), EchoResponse)
            assert not cntl.failed(), cntl.error_text
            return json.loads(resp.message)["tokens"]

        from examples.disagg_serving.workers import DecodeService
        for plane, addr in (("loopback", "mem://kv-route"),
                            ("ici", "ici://5")):
            server = rpc.Server()
            svc = DecodeService()
            server.add_service(svc)
            assert server.start(addr) == 0
            ch = rpc.Channel()
            ch.init(addr, options=rpc.ChannelOptions(timeout_ms=30000))
            try:
                s0 = kv_load_stats()
                load(ch, "r1")
                assert decode(ch, "r1") == want, plane
                s1 = kv_load_stats()
                assert s1["scattered"] - s0["scattered"] == 1, plane
                assert s1["materialized"] == s0["materialized"], plane
                # flag-off leg: the PR-14 path byte-for-byte
                _fl.set_flag("serving_kv_adopt", False)
                try:
                    load(ch, "r2")
                finally:
                    _fl.set_flag("serving_kv_adopt", True)
                assert decode(ch, "r2") == want, plane
                s2 = kv_load_stats()
                assert s2["materialized"] - s1["materialized"] == 1
                gc.collect()
                assert npl.registry().live() == 0, plane
                assert npl.att_table_live() == 0, plane
                # the /status serving block carries the route counters
                blk = svc.describe_serving()
                assert blk["kv_load"]["scattered"] >= 1
            finally:
                ch.close()
                svc.close()
                server.stop()

    def test_saturated_adopted_load_sheds_clean(self):
        """PoolSaturated during an ADOPTED load (reservation refused
        before any fill): the RPC sheds with a retry hint and no
        custody leaks — the eviction-mid-load RPC leg."""
        from brpc_tpu.rpc import errors
        from brpc_tpu.serving import KvPoolOptions
        from examples.disagg_serving.workers import DecodeService
        from examples.example_echo_pb2 import EchoRequest, EchoResponse
        m = _model()
        server = rpc.Server()
        svc = DecodeService(pool_options=KvPoolOptions(
            bytes_per_token=m.KV_LAYERS * m.KV_DMODEL,
            num_blocks=2, block_tokens=8, use_timers=False))
        server.add_service(svc)
        assert server.start("mem://kv-shed") == 0
        ch = rpc.Channel()
        ch.init("mem://kv-shed",
                options=rpc.ChannelOptions(timeout_ms=30000,
                                           max_retry=0))
        try:
            def load(session, tokens, priority):
                kv = m.toy_kv_blocks(tokens)
                cntl = rpc.Controller()
                cntl.priority = priority
                cntl.request_attachment.append_device_array(kv)
                ch.call_method("Decode.LoadKv", cntl, EchoRequest(
                    message=json.dumps({"session": session,
                                        "seq_len": len(tokens),
                                        "last_token": tokens[-1]})),
                    EchoResponse)
                return cntl

            assert not load("inter", [1] * 16, 0).failed()
            cntl = load("batch", [2] * 8, 3)
            assert cntl.failed() and cntl.error_code_ == errors.ELIMIT
            assert cntl.retry_after_ms > 0
            assert svc.live_sessions() == 1
        finally:
            ch.close()
            svc.close()
            server.stop()


# ---------------------------------------------------------------------------
# Copy-on-write prefix sharing + outside-the-lock fills (ISSUE 16).
# ---------------------------------------------------------------------------

class TestKvPrefixSharing:
    def test_capacity_on_shared_prefix_mix_ab(self):
        """The acceptance A/B at pool level: a 50 %-shared-prefix mix
        (sessions alternate two 96-token system prompts + a unique
        4-token tail) fits >= 5x more concurrent sessions at fixed
        arena size with sharing ON than OFF, with zero byte mismatches
        across the whole resident set on both legs."""
        from brpc_tpu.butil import flags as _fl
        from brpc_tpu.serving import PoolSaturated
        pre_a = [(7 * j) % 499 for j in range(96)]     # 12 full blocks
        pre_b = [(11 * j + 3) % 499 for j in range(96)]

        def mk(i):
            pre = pre_a if i % 2 == 0 else pre_b
            return pre + [(13 * i + j + 1) % 499 for j in range(4)]

        cap = {}
        try:
            for flag in (True, False):
                _fl.set_flag("serving_kv_prefix_share", flag)
                pool = _mk_pool(num_blocks=64, block_tokens=8)
                loaded = []
                try:
                    i = 0
                    while i < 200:
                        toks = mk(i)
                        name = f"cap{i}"
                        try:
                            pool.load(name, _rows(toks),
                                      last_token=toks[-1])
                        except PoolSaturated:
                            break
                        # pinned: capacity under load, not LRU churn
                        assert pool.pin(name)
                        loaded.append((name, toks))
                        i += 1
                    for name, toks in loaded:
                        assert np.array_equal(pool.materialize(name),
                                              _rows(toks)), name
                    cap[flag] = len(loaded)
                    d = pool.describe()["prefix"]
                    if flag:
                        # both 12-block prompts fully shared
                        assert d["shared_blocks"] == 24
                        assert d["sharing_ratio"] > 2.0
                        assert d["prefix_hits"] > 0
                    else:
                        assert d["shared_blocks"] == 0
                        assert d["prefix_hits"] == 0
                finally:
                    for name, _ in loaded:
                        pool.unpin(name)
                    pool.close()
        finally:
            _fl.set_flag("serving_kv_prefix_share", True)
        assert cap[True] >= 5 * cap[False], cap

    def test_identical_sessions_share_all_full_blocks(self):
        pool = _mk_pool(num_blocks=16, block_tokens=8)
        try:
            toks = [(3 * j) % 499 for j in range(16)]  # 2 FULL blocks
            a = pool.load("a", _rows(toks), last_token=toks[-1])
            free1 = len(pool._free)
            b = pool.load("b", _rows(toks), last_token=toks[-1])
            assert np.array_equal(a.blocks, b.blocks)
            # the second load kept ZERO new physical blocks
            assert len(pool._free) == free1
            assert all(pool._refs[int(x)] == 2 for x in a.blocks)
            d = pool.describe()["prefix"]
            assert d["shared_blocks"] == 2 and d["prefix_hits"] == 2
            assert d["logical_blocks"] == 4
            assert d["physical_blocks"] == 2
            assert d["sharing_ratio"] == 2.0
            # releasing one owner keeps the other byte-exact (refcount
            # order: the physical free happens at ZERO, not at first)
            pool.release("a")
            assert np.array_equal(pool.materialize("b"), _rows(toks))
            pool.release("b")
            assert len(pool._free) == 16
            assert not pool._refs and not pool._prefix_index \
                and not pool._block_hash
        finally:
            pool.close()

    def test_cow_divergence_mid_block_and_write_split(self):
        pool = _mk_pool(num_blocks=16, block_tokens=8)
        try:
            pre = [(5 * j) % 499 for j in range(8)]    # 1 full block
            ta = pre + [7, 8, 9, 10]
            tb = pre + [7, 8, 99, 10]      # diverges MID second block
            a = pool.load("a", _rows(ta), last_token=ta[-1])
            b = pool.load("b", _rows(tb), last_token=tb[-1])
            assert int(a.blocks[0]) == int(b.blocks[0])   # shared
            assert int(a.blocks[1]) != int(b.blocks[1])   # private
            assert np.array_equal(pool.materialize("a"), _rows(ta))
            assert np.array_equal(pool.materialize("b"), _rows(tb))
            # a SHORTER session still shares the longer one's prefix
            c = pool.load("c", _rows(pre), last_token=pre[-1])
            assert int(c.blocks[0]) == int(a.blocks[0])
            # in-place mutation of the shared block CoW-splits: the
            # co-owners' bytes survive untouched
            splits0 = pool.cow_splits.get_value()
            new_row = np.full((1, pool.options.bytes_per_token), 7,
                              np.uint8)
            assert pool.write_rows("b", 0, new_row) == 1
            assert pool.cow_splits.get_value() == splits0 + 1
            assert int(pool.get("b").blocks[0]) != int(a.blocks[0])
            assert np.array_equal(pool.materialize("a"), _rows(ta))
            assert np.array_equal(pool.materialize("c"), _rows(pre))
            got = pool.materialize("b")
            assert np.array_equal(got[0], new_row[0])
            assert np.array_equal(got[1:], _rows(tb)[1:])
            # the reduction arena followed the write
            assert pool.get("b").acc == int(got.sum(dtype=np.int64))
        finally:
            pool.close()

    def test_shared_block_eviction_refcount_order(self):
        """Evicting one co-owner of a shared prefix frees NOTHING (the
        victim simulation knows); pressure that needs those blocks
        takes BOTH owners, and a pinned co-owner saturates instead."""
        from brpc_tpu.serving import PoolSaturated
        pool = _mk_pool(num_blocks=4, block_tokens=8)
        try:
            toks = [(9 * j) % 499 for j in range(16)]  # 2 full blocks
            pool.load("a", _rows(toks), last_token=1, priority=3)
            time.sleep(0.002)
            pool.load("b", _rows(toks), last_token=1, priority=3)
            big = [(2 * j + 1) % 499 for j in range(24)]   # 3 blocks
            # with one co-owner PINNED the shared blocks cannot free:
            # a typed shed, never a corrupting eviction
            assert pool.pin("b")
            with pytest.raises(PoolSaturated):
                pool.load("big", _rows(big), last_token=1, priority=2)
            pool.unpin("b")
            # unpinned: evicting LRU "a" alone frees nothing, so the
            # picker takes BOTH
            pool.load("big", _rows(big), last_token=1, priority=2)
            assert pool.get("a") is None and pool.get("b") is None
            assert np.array_equal(pool.materialize("big"), _rows(big))
            assert len(pool._free) == 1
        finally:
            pool.close()

    def test_reload_shared_prefix_keeps_other_tenants_bytes(self):
        pool = _mk_pool(num_blocks=16, block_tokens=8)
        try:
            pre = [(3 * j + 1) % 499 for j in range(16)]
            ta = pre + [5, 6, 7]
            tb = pre + [8, 9, 10]
            pool.load("a", _rows(ta), last_token=1)
            pool.load("b", _rows(tb), last_token=1)
            hits0 = pool.prefix_hits.get_value()
            # reload b with DIFFERENT content: a's bytes survive
            tb2 = [(7 * j + 2) % 499 for j in range(20)]
            pool.load("b", _rows(tb2), last_token=1)
            assert np.array_equal(pool.materialize("a"), _rows(ta))
            assert np.array_equal(pool.materialize("b"), _rows(tb2))
            # reload b BACK to the shared prefix: dedupes against a
            pool.load("b", _rows(tb), last_token=1)
            assert pool.prefix_hits.get_value() >= hits0 + 2
            assert int(pool.get("b").blocks[0]) == \
                int(pool.get("a").blocks[0])
            assert np.array_equal(pool.materialize("a"), _rows(ta))
            assert np.array_equal(pool.materialize("b"), _rows(tb))
        finally:
            pool.close()

    def test_readonly_view_over_shared_blocks(self):
        pool = _mk_pool(num_blocks=16, block_tokens=8)
        try:
            toks = [(13 * j + 5) % 499 for j in range(16)]  # 2 FULL
            pool.load("a", _rows(toks), last_token=toks[-1])
            b = pool.load("b", _rows(toks), last_token=toks[-1])
            # a fully-shared run is still one ascending extent
            assert b.contiguous
            rows, seq, last, is_view = pool.snapshot("b", view=True)
            assert is_view and not rows.flags.writeable
            with pytest.raises((ValueError, RuntimeError)):
                rows[0, 0] = 1
            assert np.array_equal(rows, _rows(toks))
            pool.unpin("b")
        finally:
            pool.close()

    def test_load_and_load_into_parity_both_disciplines(self):
        """The locking-parity satellite: load() delegates to
        load_into(), so both surfaces ride ONE reserve/fill/commit
        shape — identical session state and identical fill-route
        counters under BOTH fill disciplines."""
        from brpc_tpu.butil import flags as _fl
        toks = [(11 * j) % 499 for j in range(20)]
        rows = _rows(toks)
        try:
            for conc in (True, False):
                _fl.set_flag("serving_kv_concurrent_fill", conc)
                pool = _mk_pool(num_blocks=16, block_tokens=8)
                try:
                    a = pool.load("a", rows, last_token=toks[-1])

                    def fill(views):
                        off = 0
                        for v in views:
                            v[:] = rows[off:off + v.shape[0]]
                            off += v.shape[0]

                    b = pool.load_into("b", len(toks), fill,
                                       last_token=toks[-1])
                    route = (pool.unlocked_fills if conc
                             else pool.locked_fills)
                    other = (pool.locked_fills if conc
                             else pool.unlocked_fills)
                    assert route.get_value() == 2
                    assert other.get_value() == 0
                    assert a.acc == b.acc and a.seq_len == b.seq_len
                    assert np.array_equal(pool.materialize("a"),
                                          pool.materialize("b"))
                    # identical content: b shared a's FULL blocks
                    assert np.array_equal(a.blocks[:2], b.blocks[:2])
                finally:
                    pool.close()
        finally:
            _fl.set_flag("serving_kv_concurrent_fill", True)

    def test_concurrent_load_into_stress(self):
        """Two threads load/materialize/release disjoint session sets
        concurrently (fills outside the lock): byte-exact, no
        double-free, census intact after full release."""
        pool = _mk_pool(num_blocks=64, block_tokens=8)
        errors = []
        N = 40

        def worker(tag, salt):
            try:
                for i in range(N):
                    toks = [(7 * j + 31 * i + salt) % 499
                            for j in range(12 + (i % 3) * 8)]
                    name = f"{tag}{i}"
                    pool.load(name, _rows(toks), last_token=toks[-1])
                    got = pool.materialize(name)
                    if not np.array_equal(got, _rows(toks)):
                        errors.append(f"{name}: byte mismatch")
                    pool.release(name)
            except Exception as e:   # pragma: no cover
                errors.append(f"{tag}: {e!r}")

        ts = [threading.Thread(target=worker, args=("x", 1)),
              threading.Thread(target=worker, args=("y", 2))]
        try:
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            assert errors == []
            # no double-free, no leak: every block back exactly once,
            # the descending order invariant intact
            assert len(pool._free) == 64
            assert pool._free == sorted(pool._free, reverse=True)
            assert len(set(pool._free)) == 64
            assert not pool._refs
            assert pool.describe()["prefix"]["unlocked_fills"] == 2 * N
        finally:
            pool.close()

    def test_concurrent_fill_no_longer_serializes(self):
        """The concurrency claim asserted structurally: with the flag
        ON a second session's load COMPLETES while another fill is
        parked inside the pool; with the flag OFF the same load cannot
        finish until the stalled fill releases the pool lock."""
        from brpc_tpu.butil import flags as _fl
        toks_a = [(3 * j + 1) % 499 for j in range(16)]
        toks_b = [(5 * j + 2) % 499 for j in range(16)]
        try:
            for conc in (True, False):
                _fl.set_flag("serving_kv_concurrent_fill", conc)
                pool = _mk_pool(num_blocks=32, block_tokens=8)
                in_fill = threading.Event()
                unblock = threading.Event()
                done_b = threading.Event()
                try:
                    def slow_fill(views):
                        rows = _rows(toks_a)
                        off = 0
                        for v in views:
                            v[:] = rows[off:off + v.shape[0]]
                            off += v.shape[0]
                        in_fill.set()
                        assert unblock.wait(10)

                    ta = threading.Thread(
                        target=lambda: pool.load_into(
                            "a", len(toks_a), slow_fill,
                            last_token=toks_a[-1]))
                    ta.start()
                    assert in_fill.wait(10)
                    tb = threading.Thread(target=lambda: (
                        pool.load("b", _rows(toks_b),
                                  last_token=toks_b[-1]),
                        done_b.set()))
                    tb.start()
                    if conc:
                        assert done_b.wait(5), \
                            "concurrent fill serialized"
                    else:
                        assert not done_b.wait(0.3), \
                            "locked fill should serialize"
                    unblock.set()
                    ta.join(10)
                    tb.join(10)
                    assert done_b.is_set()
                    assert np.array_equal(pool.materialize("a"),
                                          _rows(toks_a))
                    assert np.array_equal(pool.materialize("b"),
                                          _rows(toks_b))
                    d = pool.describe()["prefix"]
                    if conc:
                        assert d["unlocked_fills"] == 2
                        assert d["locked_fills"] == 0
                    else:
                        assert d["locked_fills"] == 2
                        assert d["unlocked_fills"] == 0
                finally:
                    unblock.set()
                    pool.close()
        finally:
            _fl.set_flag("serving_kv_concurrent_fill", True)

    def test_commit_race_last_commit_wins_and_pinned_abort(self):
        """Two loaders race ONE session id across the fill window: the
        later commit wins when the incumbent is unpinned; a PINNED
        incumbent aborts the late fill with SessionBusy — blocks
        returned, incumbent bytes intact, race counted either way."""
        from brpc_tpu.serving import SessionBusy
        pool = _mk_pool(num_blocks=32, block_tokens=8)
        toks_slow = [(3 * j + 2) % 499 for j in range(12)]
        toks_fast = [(9 * j + 4) % 499 for j in range(12)]
        try:
            for pinned in (False, True):
                in_fill = threading.Event()
                unblock = threading.Event()
                result = {}

                def slow_fill(views):
                    rows = _rows(toks_slow)
                    off = 0
                    for v in views:
                        v[:] = rows[off:off + v.shape[0]]
                        off += v.shape[0]
                    in_fill.set()
                    assert unblock.wait(10)

                def racer():
                    try:
                        pool.load_into("s", len(toks_slow), slow_fill,
                                       last_token=toks_slow[-1])
                        result["ok"] = True
                    except SessionBusy:
                        result["busy"] = True

                races0 = pool.commit_races.get_value()
                t = threading.Thread(target=racer)
                t.start()
                assert in_fill.wait(10)
                # the fast loader commits the same id mid-fill
                pool.load("s", _rows(toks_fast),
                          last_token=toks_fast[-1])
                if pinned:
                    assert pool.pin("s")
                free_before = len(pool._free)
                unblock.set()
                t.join(10)
                assert pool.commit_races.get_value() == races0 + 1
                if pinned:
                    assert result.get("busy") and "ok" not in result
                    assert np.array_equal(pool.materialize("s"),
                                          _rows(toks_fast))
                    pool.unpin("s")
                else:
                    assert result.get("ok")
                    assert np.array_equal(pool.materialize("s"),
                                          _rows(toks_slow))
                # either way the loser's 2 blocks came back
                assert len(pool._free) == free_before + 2
                pool.release("s")
                result.clear()
            assert len(pool._free) == 32 and not pool._refs
        finally:
            pool.close()

    def test_pin_during_reload_fill_window_aborts_commit(self):
        """A same-session reload whose OLD entry gets PINNED (roster
        or snapshot view) during the outside-the-lock fill window must
        not free the pinned blocks at commit: the late fill aborts
        with SessionBusy, the incumbent's bytes stay intact, and the
        reservation returns clean.  The reserve-time pinned check
        cannot see this pin — only the commit-time re-check can."""
        from brpc_tpu.serving import SessionBusy
        pool = _mk_pool(num_blocks=32, block_tokens=8)
        toks_old = [(3 * j + 2) % 499 for j in range(12)]
        toks_new = [(9 * j + 4) % 499 for j in range(12)]
        in_fill = threading.Event()
        unblock = threading.Event()
        result = {}
        try:
            pool.load("s", _rows(toks_old), last_token=toks_old[-1])

            def slow_fill(views):
                rows = _rows(toks_new)
                off = 0
                for v in views:
                    v[:] = rows[off:off + v.shape[0]]
                    off += v.shape[0]
                in_fill.set()
                assert unblock.wait(10)

            def reloader():
                try:
                    pool.load_into("s", len(toks_new), slow_fill,
                                   last_token=toks_new[-1])
                    result["ok"] = True
                except SessionBusy:
                    result["busy"] = True

            t = threading.Thread(target=reloader)
            t.start()
            assert in_fill.wait(10)
            # the old entry enters a roster/view mid-fill
            assert pool.pin("s")
            races0 = pool.commit_races.get_value()
            free_before = len(pool._free)
            unblock.set()
            t.join(10)
            assert result.get("busy") and "ok" not in result
            # our own deferred_old is not a two-loader race
            assert pool.commit_races.get_value() == races0
            assert np.array_equal(pool.materialize("s"),
                                  _rows(toks_old))
            assert len(pool._free) == free_before + 2
            pool.unpin("s")
            pool.release("s")
            assert len(pool._free) == 32 and not pool._refs
        finally:
            unblock.set()
            pool.close()

    def test_write_rows_never_evicts_writing_session(self):
        """``write_rows`` needing a free block for a CoW split must
        never evict the session it is mutating (the writer's stale
        last_used made it the likely LRU pick), and when the eviction
        takes the block's last CO-OWNER the refcount re-check writes
        IN PLACE instead of stranding a 0-refcount block off both the
        free list and every table."""
        pool = _mk_pool(num_blocks=4, block_tokens=8)
        try:
            shared = [(9 * j) % 499 for j in range(16)]  # 2 full blocks
            other = [(2 * j + 1) % 499 for j in range(16)]
            pool.load("a", _rows(shared), last_token=shared[-1])
            time.sleep(0.002)
            pool.load("b", _rows(shared), last_token=shared[-1])
            time.sleep(0.002)
            pool.load("c", _rows(other), last_token=other[-1])
            assert not pool._free   # a+b share 2 blocks, c owns 2
            splits0 = pool.cow_splits.get_value()
            new_row = np.full((1, pool.options.bytes_per_token), 7,
                              np.uint8)
            # "a" is the unpinned LRU candidate — the bug evicted it
            # out from under its own write
            assert pool.write_rows("a", 0, new_row) == 0
            s = pool.get("a")
            assert s is not None, "writer evicted itself"
            got = pool.materialize("a")
            assert np.array_equal(got[0], new_row[0])
            assert np.array_equal(got[1:], _rows(shared)[1:])
            # the eviction took co-owner "b", so the re-check wrote in
            # place: no split, no stranded block — census exact
            assert pool.cow_splits.get_value() == splits0
            assert pool.get("b") is None
            assert all(pool._refs[int(x)] == 1 for x in s.blocks)
            assert len(pool._free) + len(pool._refs) == 4
            pool.release("a")
            assert len(pool._free) == 4 and not pool._refs \
                and not pool._prefix_index and not pool._block_hash
        finally:
            pool.close()

    def test_write_rows_split_after_eviction_keeps_coowner(self):
        """When the eviction for a split frees a THIRD session (the
        co-owner is pinned and survives), the refcount re-check still
        splits and the co-owner's bytes stay intact."""
        pool = _mk_pool(num_blocks=4, block_tokens=8)
        try:
            shared = [(9 * j) % 499 for j in range(16)]
            other = [(2 * j + 1) % 499 for j in range(16)]
            pool.load("a", _rows(shared), last_token=shared[-1])
            pool.load("b", _rows(shared), last_token=shared[-1])
            pool.load("c", _rows(other), last_token=other[-1])
            assert pool.pin("a")
            splits0 = pool.cow_splits.get_value()
            new_row = np.full((1, pool.options.bytes_per_token), 7,
                              np.uint8)
            assert pool.write_rows("b", 0, new_row) == 1
            assert pool.cow_splits.get_value() == splits0 + 1
            # "c" paid for the split block; pinned "a" is untouched
            assert pool.get("c") is None
            assert np.array_equal(pool.materialize("a"), _rows(shared))
            got = pool.materialize("b")
            assert np.array_equal(got[0], new_row[0])
            assert np.array_equal(got[1:], _rows(shared)[1:])
            assert int(pool.get("b").blocks[0]) != \
                int(pool.get("a").blocks[0])
            pool.unpin("a")
            pool.release("a")
            pool.release("b")
            assert len(pool._free) == 4 and not pool._refs
        finally:
            pool.close()

    def test_rpc_concurrent_loadkv_shares_prefix_and_status(self):
        """Service level: two CONCURRENT LoadKv RPCs ride the
        outside-the-lock fill (route-asserted from counter deltas),
        the identical prompts prefix-share one set of physical
        blocks, both decodes are byte-exact, /status carries the new
        truth, and custody drains."""
        import gc

        from brpc_tpu.ici import native_plane as npl
        from examples.disagg_serving.workers import DecodeService
        from examples.example_echo_pb2 import EchoRequest, EchoResponse
        m = _model()
        tokens = [(19 * j) % 499 for j in range(48)]
        want = m.reference_generate(tokens, 7)
        server = rpc.Server()
        svc = DecodeService()
        server.add_service(svc)
        assert server.start("mem://kv-prefix") == 0
        ch = rpc.Channel()
        ch.init("mem://kv-prefix",
                options=rpc.ChannelOptions(timeout_ms=30000))
        try:
            p0 = svc.describe_serving()["pool"]["prefix"]
            errs = []

            def load(session):
                try:
                    kv = m.toy_kv_blocks(tokens)
                    cntl = rpc.Controller()
                    cntl.request_attachment.append_device_array(kv)
                    ch.call_method("Decode.LoadKv", cntl, EchoRequest(
                        message=json.dumps(
                            {"session": session,
                             "seq_len": len(tokens),
                             "last_token": tokens[-1]})),
                        EchoResponse)
                    if cntl.failed():
                        errs.append(cntl.error_text)
                except Exception as e:   # pragma: no cover
                    errs.append(repr(e))

            ts = [threading.Thread(target=load, args=(f"p{i}",))
                  for i in range(2)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            assert errs == []
            p1 = svc.describe_serving()["pool"]["prefix"]
            assert p1["unlocked_fills"] - p0["unlocked_fills"] == 2
            assert p1["locked_fills"] == p0["locked_fills"]
            assert p1["shared_blocks"] >= 1
            assert p1["prefix_hits"] - p0["prefix_hits"] >= 1
            assert p1["sharing_ratio"] > 1.0
            for i in range(2):
                cntl = rpc.Controller()
                resp = ch.call_method(
                    "Decode.Decode", cntl, EchoRequest(
                        message=json.dumps({"session": f"p{i}",
                                            "steps": 7,
                                            "mode": "sync"})),
                    EchoResponse)
                assert not cntl.failed(), cntl.error_text
                assert json.loads(resp.message)["tokens"] == want
            gc.collect()
            assert npl.registry().live() == 0
            assert npl.att_table_live() == 0
        finally:
            ch.close()
            svc.close()
            server.stop()


# ---------------------------------------------------------------------------
# Tiered KV memory: host-tier spill / restore (ISSUE 19).
# ---------------------------------------------------------------------------

class TestKvTiers:
    def test_pressure_demotes_and_restore_is_byte_exact(self):
        """The tentpole invariant: the victim the PR-16 picker would
        have EVICTED instead demotes to the host tier, and the next
        lookup faults it back in byte-exact."""
        pool = _mk_pool(num_blocks=4, block_tokens=8, host_blocks=8)
        try:
            ta = [(3 * j) % 499 for j in range(16)]
            tb = [(5 * j + 1) % 499 for j in range(16)]
            pool.load("a", _rows(ta), last_token=ta[-1])
            pool.load("b", _rows(tb), last_token=tb[-1])
            # pressure: "a" (LRU) demotes instead of dying
            tc = [(7 * j + 2) % 499 for j in range(16)]
            pool.load("c", _rows(tc), last_token=tc[-1])
            assert pool.spilled_sessions() == ["a"]
            assert pool.evicted_reason("a") == "spilled"
            d = pool.describe()["tiers"]
            assert d["demotions"] == 1 and d["spilled_sessions"] == 1
            assert d["spilled_blocks"] == 2
            assert d["host_blocks_free"] == 6
            # restore (transparent, via materialize→get): "b" demotes
            # to make device room, "a" comes back byte-exact
            assert np.array_equal(pool.materialize("a"), _rows(ta))
            assert "a" not in pool.spilled_sessions()
            d = pool.describe()["tiers"]
            assert d["restores"] == 1 and d["restore_p50_us"] > 0
            assert d["plane"]["state"] == "up"
            # nobody died: zero evictions, all three sessions live
            assert pool.evictions.get_value() == 0
            for name, toks in (("a", ta), ("b", tb), ("c", tc)):
                assert np.array_equal(pool.materialize(name),
                                      _rows(toks)), name
        finally:
            pool.close()

    def test_spill_off_flag_is_the_pr16_eviction_ab(self):
        from brpc_tpu.butil import flags as _fl
        pool = _mk_pool(num_blocks=2, block_tokens=8, host_blocks=8)
        try:
            _fl.set_flag("serving_kv_spill", False)
            ta = [3] * 16
            pool.load("a", _rows(ta), last_token=3)
            pool.load("b", _rows([5] * 16), last_token=5)
            assert pool.spilled_sessions() == []
            assert pool.get("a") is None
            assert pool.evicted_reason("a") == "pressure"
        finally:
            _fl.set_flag("serving_kv_spill", True)
            pool.close()

    def test_corrupt_host_copy_degrades_to_reprefill(self):
        """Byte verification on restore: a corrupted host block makes
        the restore ABORT into a typed "corrupt" re-prefill shed —
        wrong bytes are never published, and the plane stays up
        (corruption is not plane death)."""
        pool = _mk_pool(num_blocks=2, block_tokens=8, host_blocks=4)
        try:
            ta = [(3 * j) % 499 for j in range(16)]
            pool.load("a", _rows(ta), last_token=ta[-1])
            pool.load("b", _rows([5] * 16), last_token=5)   # spills a
            assert pool.spilled_sessions() == ["a"]
            hb = int(pool._spilled["a"].hblocks[0])
            pool._host_store[hb, 7] ^= 0xFF                 # flip a byte
            assert pool.get("a") is None
            assert pool.materialize("a") is None
            assert pool.evicted_reason("a") == "corrupt"
            # the restore's own reservation demoted "b" first; only
            # "a"'s corrupt record died
            assert pool.spilled_sessions() == ["b"]
            d = pool.describe()["tiers"]
            assert d["restore_corrupt"] == 1 and d["restores"] == 0
            assert d["plane"]["state"] == "up"
            # "a"'s 2 host blocks reclaimed; "b" still holds 2
            assert d["host_blocks_free"] == 2
            # the surviving session's bytes never moved
            assert np.array_equal(pool.materialize("b"), _rows([5] * 16))
            assert pool.describe()["tiers"]["host_blocks_free"] == 4
        finally:
            pool.close()

    def test_shared_prefix_spills_once_restores_n(self):
        """A refcounted shared block spills ONE host copy and restores
        N sessions: demote both co-owners, census the host arena, then
        restore both and assert the dedupe re-shares the blocks."""
        pool = _mk_pool(num_blocks=8, block_tokens=8, host_blocks=4)
        try:
            toks = [(3 * j) % 499 for j in range(16)]   # 2 FULL blocks
            pool.load("a", _rows(toks), last_token=toks[-1])
            pool.load("b", _rows(toks), last_token=toks[-1])
            assert pool.describe()["prefix"]["shared_blocks"] == 2
            assert pool.spill("a") and pool.spill("b")
            d = pool.describe()["tiers"]
            assert d["spilled_sessions"] == 2
            # the 2 shared device blocks took 2 host blocks TOTAL (one
            # copy each), not 4 — the co-owner rode the _spill_map
            assert d["host_blocks_free"] == 2
            assert d["spilled_blocks"] == 2
            assert all(r == 2 for r in pool._host_refs.values())
            # restore both: first re-registers, second dedupes onto it
            assert np.array_equal(pool.materialize("a"), _rows(toks))
            assert np.array_equal(pool.materialize("b"), _rows(toks))
            sa, sb = pool.get("a"), pool.get("b")
            assert np.array_equal(sa.blocks, sb.blocks)
            assert all(pool._refs[int(x)] == 2 for x in sa.blocks)
            d = pool.describe()["tiers"]
            assert d["restores"] == 2 and d["spilled_sessions"] == 0
            assert d["host_blocks_free"] == 4 and not pool._host_refs
        finally:
            pool.close()

    def test_pinned_session_refuses_spill(self):
        pool = _mk_pool(num_blocks=4, block_tokens=8, host_blocks=4)
        try:
            from brpc_tpu.serving import SessionBusy
            pool.load("a", _rows([3] * 16), last_token=3)
            assert pool.pin("a")
            with pytest.raises(SessionBusy):
                pool.spill("a")
            pool.unpin("a")
            assert pool.spill("a")
            assert pool.spilled_sessions() == ["a"]
        finally:
            pool.close()

    def test_picker_prefers_whole_shared_set_over_unshared(self):
        """Satellite 2: with demotion available the picker takes the
        whole shared-owner GROUP (higher per-victim yield once the set
        completes) before any unshared live session, and the cumulative
        free-bytes simulation stays exact: what the picker promised is
        exactly what demotion freed."""
        pool = _mk_pool(num_blocks=6, block_tokens=8, host_blocks=8)
        try:
            toks = [(3 * j) % 499 for j in range(16)]   # 2 full blocks
            pool.load("s1", _rows(toks), last_token=toks[-1])
            pool.load("s2", _rows(toks), last_token=toks[-1])  # shares
            pool.load("u", _rows([7] * 16), last_token=7)  # unshared
            # census: s1+s2 share 2 physical, u owns 2 → 2 free
            assert len(pool._free) == 2
            free_before = len(pool._free)
            victims = pool._pick_victims_locked(4, pool.options
                                                .default_priority,
                                                spill=True)
            names = [v.session for v in victims]
            # the SHARED SET first — both owners, before the unshared
            assert set(names[:2]) == {"s1", "s2"}
            assert names[2] == "u"
            # drive the actual demotion through pressure and assert the
            # simulation was exact: 4 blocks wanted, 4 blocks freed
            big = [(11 * j) % 499 for j in range(48)]   # 6 blocks
            pool.load("big", _rows(big), last_token=big[-1])
            # promised 4 freed + 2 already free == exactly the 6 taken
            assert free_before == 2 and len(pool._free) == 0
            assert set(pool.spilled_sessions()) == {"s1", "s2", "u"}
            assert np.array_equal(pool.materialize("big"), _rows(big))
        finally:
            pool.close()

    def test_capacity_under_pressure_ab_spill_retains_more(self):
        """Acceptance A/B: same arena, same load pattern — spill-on
        retains STRICTLY more live (still-retrievable) sessions than
        spill-off, and every retained session is byte-exact."""
        from brpc_tpu.butil import flags as _fl
        alive = {}
        try:
            for flag in (True, False):
                _fl.set_flag("serving_kv_spill", flag)
                pool = _mk_pool(num_blocks=8, block_tokens=8,
                                host_blocks=32)
                sessions = {}
                try:
                    for i in range(16):
                        toks = [(7 * i + j) % 499 for j in range(16)]
                        pool.load(f"s{i}", _rows(toks),
                                  last_token=toks[-1])
                        sessions[f"s{i}"] = toks
                    live = 0
                    for name, toks in sessions.items():
                        got = pool.materialize(name)
                        if got is not None:
                            assert np.array_equal(got, _rows(toks)), name
                            live += 1
                    alive[flag] = live
                finally:
                    pool.close()
        finally:
            _fl.set_flag("serving_kv_spill", True)
        # spill-on keeps EVERY session retrievable; spill-off can only
        # hold what the device arena holds
        assert alive[True] == 16
        assert alive[True] > alive[False], alive

    def test_spill_plane_faults_latch_and_revive(self):
        """Chaos at the pool level: an injected demote-IO failure
        latches the spill plane down (pressure degrades to PR-16
        eviction — no client hangs on a dead host arena), and the
        timer latch revives it through the standard counters."""
        from brpc_tpu.butil import flags as _fl
        from brpc_tpu.ici import route
        pool = _mk_pool(num_blocks=2, block_tokens=8, host_blocks=8)
        try:
            _fl.set_flag("serving_kv_spill_reprobe_s", 0.1)
            before = route.plane_stats()
            pool.inject_spill_fault("demote")
            pool.load("a", _rows([3] * 16), last_token=3)
            pool.load("b", _rows([5] * 16), last_token=5)  # pressure
            # demote failed → fell back to eviction, plane latched
            assert pool.spilled_sessions() == []
            assert pool.evicted_reason("a") == "pressure"
            d = pool.describe()["tiers"]
            assert d["plane"]["state"] == "down"
            assert d["plane"]["reason"] == "demote_io"
            pool.inject_spill_fault(None)
            # while latched, pressure KEEPS evicting (fast, no retry
            # storm at the failing arena)
            pool.load("c", _rows([7] * 16), last_token=7)
            assert pool.spilled_sessions() == []
            time.sleep(0.15)       # the timer latch lapses
            pool.load("e", _rows([11] * 16), last_token=11)
            assert pool.spilled_sessions() == ["c"]
            after = route.plane_stats()
            assert after["spill_down"] >= before.get("spill_down",
                                                     0) + 1
            assert after["spill_reprobe"] >= before.get("spill_reprobe",
                                                        0) + 1
            assert after["spill_revived"] >= before.get("spill_revived",
                                                        0) + 1
            assert pool.describe()["tiers"]["plane"]["state"] == "up"
        finally:
            _fl.set_flag("serving_kv_spill_reprobe_s", 0.25)
            pool.close()

    def test_restore_io_fault_keeps_host_copy_and_sheds(self):
        pool = _mk_pool(num_blocks=2, block_tokens=8, host_blocks=8)
        try:
            from brpc_tpu.butil import flags as _fl
            _fl.set_flag("serving_kv_spill_reprobe_s", 0.05)
            ta = [(3 * j) % 499 for j in range(16)]
            pool.load("a", _rows(ta), last_token=ta[-1])
            assert pool.spill("a")
            pool.inject_spill_fault("restore")
            assert pool.get("a") is None          # shed, not corrupt
            assert pool.spilled_sessions() == ["a"]   # record intact
            assert pool.describe()["tiers"]["plane"]["reason"] \
                == "restore_io"
            pool.inject_spill_fault(None)
            time.sleep(0.1)
            assert np.array_equal(pool.materialize("a"), _rows(ta))
        finally:
            _fl.set_flag("serving_kv_spill_reprobe_s", 0.25)
            pool.close()

    def test_restore_saturated_stays_spilled(self):
        """No device room even after pressure (everything pinned): the
        restore refuses, the session STAYS host-resident, and the
        scheduler-visible reason is the retryable "spilled"."""
        pool = _mk_pool(num_blocks=2, block_tokens=8, host_blocks=8)
        try:
            ta = [(3 * j) % 499 for j in range(16)]
            pool.load("a", _rows(ta), last_token=ta[-1])
            assert pool.spill("a")
            pool.load("b", _rows([5] * 16), last_token=5)
            assert pool.pin("b")                  # device arena fenced
            assert pool.get("a") is None
            assert pool.spilled_sessions() == ["a"]
            assert pool.evicted_reason("a") == "spilled"
            pool.unpin("b")
            assert np.array_equal(pool.materialize("a"), _rows(ta))
        finally:
            pool.close()

    def test_host_arena_reclaim_drops_oldest_spilled(self):
        """Host arena full: demoting one more session reclaims the
        most sheddable SPILLED session (band→weight→LRU, typed
        "pressure" shed) rather than refusing the demotion."""
        _clock = [100.0]
        pool = _mk_pool(num_blocks=2, block_tokens=8, host_blocks=2,
                        now=lambda: _clock[0])
        try:
            ta = [(3 * j) % 499 for j in range(16)]
            pool.load("a", _rows(ta), last_token=ta[-1])
            _clock[0] += 1
            pool.load("b", _rows([5] * 16), last_token=5)  # spills a
            assert pool.spilled_sessions() == ["a"]
            _clock[0] += 1
            pool.load("c", _rows([7] * 16), last_token=7)  # spills b
            # host arena (2 blocks) could not hold both: "a" died for
            # real to make room for "b"
            assert pool.spilled_sessions() == ["b"]
            assert pool.evicted_reason("a") == "pressure"
            d = pool.describe()["tiers"]
            assert d["host_evictions"] == 1 and d["demotions"] == 2
            assert np.array_equal(pool.materialize("b"), _rows([5] * 16))
        finally:
            pool.close()

    def test_release_of_spilled_session_frees_host_blocks(self):
        pool = _mk_pool(num_blocks=2, block_tokens=8, host_blocks=4)
        try:
            pool.load("a", _rows([3] * 16), last_token=3)
            assert pool.spill("a")
            assert pool.release("a")
            assert pool.spilled_sessions() == []
            assert pool.describe()["tiers"]["host_blocks_free"] == 4
            assert not pool.release("a")
        finally:
            pool.close()

    def test_scheduler_decodes_through_restored_session(self):
        """Service-level truth: a spilled session submitted to the
        scheduler restores transparently and the tokens are bit-exact
        against the never-spilled reference."""
        m = _model()
        pool = _mk_pool(num_blocks=8, block_tokens=8, host_blocks=8)
        sched = _mk_sched(pool, max_batch=4)
        try:
            toks = [(3 * j) % 499 for j in range(16)]
            want = m.reference_generate(toks, 6)
            pool.load("a", _rows(toks), last_token=toks[-1])
            assert pool.spill("a")
            sink = _submit(sched, "a", 6)
            for _ in range(10):
                sched.step_once()
                if sink.tokens is not None or sink.error:
                    break
            assert sink.error is None, sink.error
            assert sink.tokens == want
            assert pool.describe()["tiers"]["restores"] == 1
        finally:
            sched.stop()
            pool.close()


# ---------------------------------------------------------------------------
# Custody-sweep regressions (ISSUE 20): the true positives the static
# custody pass found, each drivable — these fail on the pre-fix shape.
# ---------------------------------------------------------------------------

class TestCustodyRegressions:
    def _assert_no_reserve_outstanding(self, pool):
        from brpc_tpu.butil import custody_ledger
        held = [r for r in custody_ledger.outstanding()
                if r["resource"] == "kv.reserve"
                and r["key"][0] == id(pool)]
        assert held == [], held

    @pytest.mark.parametrize("concurrent", [True, False])
    def test_session_construction_failure_aborts_reservation(
            self, monkeypatch, concurrent):
        """Sweep true positive (load_into): _extent_views and the
        _KvSession construction sit between reserve and commit — a
        raise there leaked the reservation pre-fix (blocks off the
        free list forever).  Both fill disciplines now route every
        edge through the abort."""
        from brpc_tpu.butil import flags as _fl
        from brpc_tpu.serving import kv_pool as kp
        pool = _mk_pool(num_blocks=4, block_tokens=8)
        try:
            _fl.set_flag("serving_kv_concurrent_fill", concurrent)
            free0 = len(pool._free)
            aborts0 = pool.fill_aborts.get_value()

            real = kp._KvSession

            def boom(*a, **kw):
                raise MemoryError("allocator pressure mid-load")

            monkeypatch.setattr(kp, "_KvSession", boom)
            with pytest.raises(MemoryError):
                pool.load("s1", _rows([3] * 16), last_token=3)
            monkeypatch.setattr(kp, "_KvSession", real)
            # the reservation aborted clean: free list restored, abort
            # counted, no ledger hold, and the pool still loads at
            # full capacity
            assert len(pool._free) == free0
            assert pool.fill_aborts.get_value() == aborts0 + 1
            self._assert_no_reserve_outstanding(pool)
            assert pool.get("s1") is None
            toks = [(3 * j) % 499 for j in range(16)]
            pool.load("s1", _rows(toks), last_token=toks[-1])
            assert np.array_equal(pool.materialize("s1"), _rows(toks))
        finally:
            _fl.set_flag("serving_kv_concurrent_fill", True)
            pool.close()

    def test_restore_copy_failure_releases_reservation_and_host_refs(
            self, monkeypatch):
        """Sweep true positive (_restore): the outside-the-lock
        host→device copy can raise (allocator pressure); pre-fix that
        leaked the device reservation AND the restore's host refs.
        Every outcome now resolves through _finish_restore_locked —
        the exception propagates, the session stays spilled, and the
        host copy restores byte-exact afterwards."""
        from brpc_tpu.serving import kv_pool as kp
        pool = _mk_pool(num_blocks=4, block_tokens=8, host_blocks=4)
        try:
            toks = [(3 * j) % 499 for j in range(16)]
            pool.load("a", _rows(toks), last_token=toks[-1])
            assert pool.spill("a")
            free0 = len(pool._free)

            real = kp.zlib.crc32

            def boom(data, chain=0):
                raise MemoryError("copy failed mid-restore")

            monkeypatch.setattr(kp.zlib, "crc32", boom)
            with pytest.raises(MemoryError):
                pool.get("a")
            monkeypatch.setattr(kp.zlib, "crc32", real)
            # reservation returned, host record + refs intact, no
            # ledger hold; the next lookup restores byte-exact
            assert len(pool._free) == free0
            assert pool.spilled_sessions() == ["a"]
            self._assert_no_reserve_outstanding(pool)
            assert np.array_equal(pool.materialize("a"), _rows(toks))
            assert pool.describe()["tiers"]["restores"] == 1
        finally:
            pool.close()


# ---------------------------------------------------------------------------
# Live cross-worker migration (ISSUE 19).
# ---------------------------------------------------------------------------

class TestKvMigration:
    def _mk_pair(self):
        src = _mk_pool(num_blocks=8, block_tokens=8)
        dst = _mk_pool(num_blocks=8, block_tokens=8)
        return src, dst

    def _sender(self, dst):
        def send(meta, payload):
            rows = np.frombuffer(payload, np.uint8).reshape(
                meta["seq_len"], dst.options.bytes_per_token)
            dst.load(meta["session"], rows,
                     last_token=meta["last_token"],
                     tenant=meta["tenant"], priority=meta["priority"])
            return True, "", False
        return send

    def test_migrate_out_cutover_then_release(self):
        """Custody: the cutover flip runs while the SOURCE copy is
        still resident; only after it does the source release."""
        from brpc_tpu.serving import migrate_out
        src, dst = self._mk_pair()
        try:
            toks = [(3 * j) % 499 for j in range(16)]
            src.load("m1", _rows(toks), last_token=toks[-1])
            order = []

            def flip():
                assert src.get("m1") is not None   # source still live
                order.append("flip")
            ok, err = migrate_out(src, "m1", self._sender(dst),
                                  on_cutover=flip)
            assert ok, err
            assert order == ["flip"]
            assert src.get("m1") is None           # released after
            assert np.array_equal(dst.materialize("m1"), _rows(toks))
        finally:
            src.close()
            dst.close()

    def test_shed_abort_keeps_source_no_plane_event(self):
        from brpc_tpu.ici import route
        from brpc_tpu.serving import migrate_out, migration_stats
        src, dst = self._mk_pair()
        try:
            toks = [(3 * j) % 499 for j in range(16)]
            src.load("m1", _rows(toks), last_token=toks[-1])
            before = route.plane_stats()
            a0 = migration_stats()["aborts"]

            def shed(meta, payload):
                return False, "kv pool saturated (shed)", True
            ok, err = migrate_out(src, "m1", shed)
            assert not ok and "saturated" in err
            assert migration_stats()["aborts"] == a0 + 1
            # a clean shed does NOT latch the plane
            after = route.plane_stats()
            assert after.get("migrate_down", 0) \
                == before.get("migrate_down", 0)
            assert np.array_equal(src.materialize("m1"), _rows(toks))
        finally:
            src.close()
            dst.close()

    def test_transfer_deadline_latches_and_revives(self):
        """Satellite 1 (the PR-17 residue): a HUNG peer is detected by
        the transfer-deadline latch — the migrate plane goes down with
        no client in the blast radius, later migrations refuse FAST,
        and the timer latch revives through reprobe/revived."""
        from brpc_tpu.butil import flags as _fl
        from brpc_tpu.ici import route
        from brpc_tpu.serving import migrate_out
        src, dst = self._mk_pair()
        gate = threading.Event()
        try:
            _fl.set_flag("serving_migrate_reprobe_s", 0.1)
            toks = [(3 * j) % 499 for j in range(16)]
            src.load("m1", _rows(toks), last_token=toks[-1])
            before = route.plane_stats()

            def hung(meta, payload):
                gate.wait(5.0)
                return True, "", False
            t0 = time.monotonic()
            ok, err = migrate_out(src, "m1", hung, deadline_ms=150)
            assert not ok and "deadline" in err
            assert time.monotonic() - t0 < 2.0
            # latched: the next migrate refuses in microseconds, no
            # send is even attempted
            calls = []
            ok, err = migrate_out(
                src, "m1", lambda m, p: calls.append(1) or (True, "",
                                                            False))
            assert not ok and "latched" in err and not calls
            # the source never stopped serving
            assert np.array_equal(src.materialize("m1"), _rows(toks))
            gate.set()
            time.sleep(0.15)
            ok, err = migrate_out(src, "m1", self._sender(dst))
            assert ok, err
            after = route.plane_stats()
            assert after["migrate_down"] >= before.get("migrate_down",
                                                       0) + 1
            assert after["migrate_revived"] \
                >= before.get("migrate_revived", 0) + 1
            assert np.array_equal(dst.materialize("m1"), _rows(toks))
        finally:
            _fl.set_flag("serving_migrate_reprobe_s", 0.5)
            gate.set()
            src.close()
            dst.close()

    def test_peer_unreachable_latches_plane(self):
        from brpc_tpu.butil import flags as _fl
        from brpc_tpu.serving import migrate_out, migration_stats
        src, dst = self._mk_pair()
        try:
            _fl.set_flag("serving_migrate_reprobe_s", 0.05)
            toks = [3] * 16
            src.load("m1", _rows(toks), last_token=3)

            def dead(meta, payload):
                raise ConnectionError("connection refused")
            ok, err = migrate_out(src, "m1", dead)
            assert not ok and "ConnectionError" in err
            st = migration_stats()
            assert st["plane"]["state"] == "down"
            assert st["plane"]["reason"] == "peer_unreachable"
            assert np.array_equal(src.materialize("m1"), _rows(toks))
            # the latch is PROCESS-wide: heal it (timer lapse + probe)
            # so later migration tests start from an UP plane
            from brpc_tpu.serving.migration import migrate_health
            time.sleep(0.1)
            assert migrate_health().usable()
        finally:
            _fl.set_flag("serving_migrate_reprobe_s", 0.5)
            src.close()
            dst.close()

    def test_scheduler_fence_refuses_decoding_session(self):
        from brpc_tpu.serving import migrate_out
        src, dst = self._mk_pair()
        sched = _mk_sched(src, max_batch=4)
        try:
            toks = [(3 * j) % 499 for j in range(16)]
            src.load("m1", _rows(toks), last_token=toks[-1])
            _submit(sched, "m1", 50)
            sched.step_once()  # roster admits m1 → owned
            ok, err = migrate_out(src, "m1", self._sender(dst),
                                  scheduler=sched)
            assert not ok and "decoding" in err
        finally:
            sched.stop()
            src.close()
            dst.close()

    def test_spilled_session_migrates_via_restore(self):
        """A migration is a READ: a host-parked session restores
        first, then ships — the destination gets device-verified
        bytes, never the raw host copy."""
        from brpc_tpu.serving import migrate_out
        src = _mk_pool(num_blocks=4, block_tokens=8, host_blocks=4)
        dst = _mk_pool(num_blocks=8, block_tokens=8)
        try:
            toks = [(3 * j) % 499 for j in range(16)]
            src.load("m1", _rows(toks), last_token=toks[-1])
            assert src.spill("m1")
            ok, err = migrate_out(src, "m1", self._sender(dst))
            assert ok, err
            assert src.describe()["tiers"]["restores"] == 1
            assert src.spilled_sessions() == []
            assert np.array_equal(dst.materialize("m1"), _rows(toks))
        finally:
            src.close()
            dst.close()

    def test_router_affinity_bind_rebind_unbind(self):
        """The cutover surface: rebind is the atomic routing flip and
        reports the previous binding so the caller releases the source
        AFTER the flip."""
        from brpc_tpu.serving import LoadAwareRouter
        r = LoadAwareRouter(["ici://0", "ici://1"])
        try:
            assert r.session_url("s") is None
            r.bind_session("s", "ici://0")
            assert r.session_url("s") == "ici://0"
            assert r.rebind("s", "ici://1") == "ici://0"
            assert r.session_url("s") == "ici://1"
            assert r.rebind("new", "ici://0") is None
            d = r.describe()
            assert d["sessions_bound"] == 2 and d["rebinds"] == 1
            r.unbind("s")
            assert r.session_url("s") is None
            # cardinality cap: binds never grow without bound
            for i in range(r.MAX_BOUND_SESSIONS + 10):
                r.bind_session(f"x{i}", "ici://0")
            assert r.describe()["sessions_bound"] \
                <= r.MAX_BOUND_SESSIONS
        finally:
            r.close()

    def test_autoscaler_drain_runs_before_scale_down(self):
        from brpc_tpu.serving import (AutoscalerOptions,
                                      LoadThresholdAutoscaler)
        order = []
        a = LoadThresholdAutoscaler(
            load_fn=lambda: 0.0, size_fn=lambda: 2,
            scale_up=lambda: True,
            scale_down=lambda: order.append("down") or True,
            drain=lambda: order.append("drain"),
            options=AutoscalerOptions(samples_to_scale=1,
                                      cooldown_s=0.0))
        assert a.tick(now=1.0) == "down"
        assert order == ["drain", "down"]
        # a raising drain logs and the scale-down still proceeds
        order.clear()

        def bad_drain():
            order.append("drain")
            raise RuntimeError("migrate failed")
        a2 = LoadThresholdAutoscaler(
            load_fn=lambda: 0.0, size_fn=lambda: 2,
            scale_up=lambda: True,
            scale_down=lambda: order.append("down") or True,
            drain=bad_drain,
            options=AutoscalerOptions(samples_to_scale=1,
                                      cooldown_s=0.0))
        assert a2.tick(now=1.0) == "down"
        assert order == ["drain", "down"]


# ---------------------------------------------------------------------------
# Continuous-batching scheduler (manual stepping).
# ---------------------------------------------------------------------------

class TestContinuousBatchScheduler:
    def _load(self, pool, session, tokens, **kw):
        pool.load(session, _rows(tokens), last_token=tokens[-1], **kw)

    def test_tokens_bit_exact_with_staggered_joins(self):
        m = _model()
        pool = _mk_pool(num_blocks=32, block_tokens=8)
        sched = _mk_sched(pool, max_batch=8)
        try:
            specs = {f"s{i}": ([(7 * i + j) % 997
                                for j in range(16 + 11 * i)], 5 + 3 * i)
                     for i in range(3)}
            sinks = {}
            for s, (tokens, steps) in specs.items():
                self._load(pool, s, tokens)
                sinks[s] = _submit(sched, s, steps)
            for _ in range(4):
                sched.step_once()
            # a session JOINS mid-stream, between steps
            late = [(13 * j) % 499 for j in range(21)]
            specs["late"] = (late, 6)
            self._load(pool, "late", late)
            sinks["late"] = _submit(sched, "late", 6)
            for _ in range(20):
                sched.step_once()
            for s, (tokens, steps) in specs.items():
                assert sinks[s].tokens == m.reference_generate(
                    tokens, steps), f"session {s} diverged"
            d = sched.describe()
            assert d["retired"] == 4 and d["steps"] > 0
            assert d["batch_occupancy_avg"] > 1.0
        finally:
            sched.stop()
            pool.close()

    def test_max_batch_admits_per_step(self):
        pool = _mk_pool()
        sched = _mk_sched(pool, max_batch=2)
        try:
            sinks = []
            for i in range(3):
                tokens = [(i + j) % 97 for j in range(8)]
                self._load(pool, f"s{i}", tokens)
                sinks.append(_submit(sched, f"s{i}", 2))
            assert sched.step_once() == 2          # roster capped at 2
            assert sched.active() == 2 and sched.queued() == 1
            sched.step_once()                      # first two retire
            assert sched.step_once() == 1          # third admitted
            sched.step_once()
            assert all(s.tokens is not None for s in sinks)
        finally:
            sched.stop()
            pool.close()

    def test_interactive_preemption_preserves_progress(self):
        m = _model()
        pool = _mk_pool()
        sched = _mk_sched(pool, max_batch=1, interactive_priority_max=1)
        try:
            batch_toks = [3 * j % 97 for j in range(16)]
            self._load(pool, "batch", batch_toks, priority=3)
            b = _submit(sched, "batch", 10, priority=3)
            for _ in range(3):
                sched.step_once()
            assert sched.active() == 1
            inter_toks = [5 * j % 89 for j in range(8)]
            self._load(pool, "inter", inter_toks, priority=0)
            i = _submit(sched, "inter", 4, priority=0)
            # next boundary: batch preempted mid-decode, interactive in
            sched.step_once()
            assert sched.preempted.get_value() == 1
            for _ in range(12):
                sched.step_once()
            assert i.tokens == m.reference_generate(inter_toks, 4)
            # the preempted session RESUMED from its next token
            assert b.tokens == m.reference_generate(batch_toks, 10)
        finally:
            sched.stop()
            pool.close()

    def test_deadline_expired_in_queue(self):
        from brpc_tpu.rpc import errors
        pool = _mk_pool()
        sched = _mk_sched(pool, max_batch=4)
        try:
            self._load(pool, "s", [1] * 8)
            sink = _submit(sched, "s", 4,
                           deadline_us=time.monotonic_ns() // 1000 - 10)
            sched.step_once()
            assert sink.error is not None
            assert sink.error[0] == errors.ERPCTIMEDOUT
            assert sched.expired.get_value() == 1
        finally:
            sched.stop()
            pool.close()

    def test_unknown_and_evicted_session_refusals(self):
        from brpc_tpu.rpc import errors
        pool = _mk_pool(num_blocks=1, block_tokens=8)
        sched = _mk_sched(pool)
        try:
            sink = _submit(sched, "ghost", 4)
            sched.step_once()
            assert sink.error[0] == errors.EREQUEST
            self._load(pool, "victim", [1] * 8, priority=3)
            self._load(pool, "usurper", [2] * 8, priority=0)  # evicts
            sink2 = _submit(sched, "victim", 4)
            sched.step_once()
            assert sink2.error[0] == errors.ELIMIT
            assert "re-prefill" in sink2.error[1]
        finally:
            sched.stop()
            pool.close()

    def test_duplicate_submit_refused_and_custody_safe(self):
        """A retry storm re-issuing a Decode whose first copy is still
        running is REFUSED: two roster entries on one session would let
        the first completion release the pool blocks the second still
        gathers through (cross-tenant bytes after block reuse — the
        soak caught this as a token mismatch)."""
        from brpc_tpu.rpc import errors
        m = _model()
        pool = _mk_pool()
        sched = _mk_sched(pool, max_batch=4)
        try:
            tokens = [9 * j % 97 for j in range(12)]
            self._load(pool, "dup", tokens)
            first = _submit(sched, "dup", 6)
            second = _submit(sched, "dup", 6)
            assert second.error is not None
            assert second.error[0] == errors.EREQUEST
            assert "duplicate" in second.error[1]
            for _ in range(8):
                sched.step_once()
            assert first.tokens == m.reference_generate(tokens, 6)
            # ownership released at completion: a FRESH submit works
            third = _submit(sched, "dup", 3)
            for _ in range(5):
                sched.step_once()
            assert third.tokens == m.reference_generate(tokens, 3)
        finally:
            sched.stop()
            pool.close()

    def test_compiled_step_parity(self):
        """The jit-compiled XLA step produces the numpy step's tokens
        bit for bit (the TPU-pod shape, parity-pinned)."""
        from brpc_tpu.butil import flags as fl
        m = _model()
        pool = _mk_pool(num_blocks=32, block_tokens=8)
        sched = _mk_sched(pool, max_batch=4)
        saved = fl.get_flag("serving_compiled_step")
        fl.set_flag("serving_compiled_step", True)
        try:
            sinks = {}
            specs = {}
            for i in range(3):
                tokens = [(11 * i + j) % 499 for j in range(10 + 7 * i)]
                specs[f"c{i}"] = (tokens, 6)
                self._load(pool, f"c{i}", tokens)
                sinks[f"c{i}"] = _submit(sched, f"c{i}", 6)
            for _ in range(10):
                sched.step_once()
            for s, (tokens, steps) in specs.items():
                assert sinks[s].tokens == m.reference_generate(
                    tokens, steps)
            assert sched.describe()["compiled_step"] is True
        finally:
            fl.set_flag("serving_compiled_step", saved)
            sched.stop()
            pool.close()

    def test_step_loop_survives_a_step_exception(self):
        """One bad roster must not wedge the worker: the loop fails the
        crashed roster with EINTERNAL and keeps serving (review
        finding: an unguarded step thread died permanently and every
        later Decode queued forever)."""
        from brpc_tpu.rpc import errors
        m = _model()
        pool = _mk_pool()
        sched = _mk_sched(pool, max_batch=4, auto_start=True)
        try:
            boom = {"armed": True}
            orig = sched._step_numpy

            def exploding(bt):
                if boom["armed"]:
                    boom["armed"] = False
                    raise RuntimeError("injected step fault")
                return orig(bt)

            sched._step_numpy = exploding
            tokens = [3 * j % 97 for j in range(8)]
            self._load(pool, "crash", tokens)
            sink = _submit(sched, "crash", 4)
            deadline = time.monotonic() + 5.0
            while sink.error is None and time.monotonic() < deadline:
                time.sleep(0.01)
            assert sink.error is not None, "crashed roster never failed"
            assert sink.error[0] == errors.EINTERNAL
            # the loop is ALIVE: a fresh session decodes bit-exact
            self._load(pool, "after", tokens)
            sink2 = _submit(sched, "after", 4)
            deadline = time.monotonic() + 5.0
            while sink2.tokens is None and sink2.error is None \
                    and time.monotonic() < deadline:
                time.sleep(0.01)
            assert sink2.tokens == m.reference_generate(tokens, 4)
        finally:
            sched.stop()
            pool.close()

    def test_stop_fails_pending_with_elogoff(self):
        from brpc_tpu.rpc import errors
        pool = _mk_pool()
        sched = _mk_sched(pool)
        try:
            self._load(pool, "s", [1] * 8)
            sink = _submit(sched, "s", 4)
            sched.stop()
            assert sink.error[0] == errors.ELOGOFF
            late = _submit(sched, "s", 4)
            assert late.error[0] == errors.ELOGOFF
        finally:
            pool.close()


# ---------------------------------------------------------------------------
# Service level: the rebuilt disaggregated workers.
# ---------------------------------------------------------------------------

class TestServingServices:
    def _decode_worker(self, name, **kw):
        from examples.disagg_serving.workers import DecodeService
        server = rpc.Server()
        svc = DecodeService(**kw)
        server.add_service(svc)
        assert server.start(f"mem://{name}") == 0
        return server, svc

    def _load_session(self, ch, session, tokens, priority=None,
                      tenant=""):
        from examples.example_echo_pb2 import EchoRequest, EchoResponse
        m = _model()
        kv = np.asarray(m.toy_kv_blocks(tokens)).tobytes()
        cntl = rpc.Controller()
        if priority is not None:
            cntl.priority = priority
        if tenant:
            cntl.tenant = tenant
        cntl.request_attachment.append(kv)
        ch.call_method("Decode.LoadKv", cntl, EchoRequest(
            message=json.dumps({"session": session,
                                "seq_len": len(tokens),
                                "last_token": tokens[-1]})),
            EchoResponse)
        return cntl

    def test_batched_decode_end_to_end_route_asserted(self):
        """N concurrent Decode RPCs share the step loop: every reply
        bit-exact, batch occupancy > 1, and the route asserted through
        the /status serving block."""
        from examples.example_echo_pb2 import EchoRequest, EchoResponse
        m = _model()
        server, svc = self._decode_worker("serv-batched")
        ch = rpc.Channel()
        ch.init("mem://serv-batched",
                options=rpc.ChannelOptions(timeout_ms=30000))
        try:
            # 200-step sessions: lifetimes of several ms, far beyond
            # client-thread start stagger even under suite-wide CPU
            # contention — the roster genuinely overlaps (a 12-step
            # variant measured occupancy exactly 1.0 on a loaded host)
            specs = {f"b{i}": ([(3 * i + j) % 997
                                for j in range(24 + 8 * i)], 200)
                     for i in range(6)}
            for s, (tokens, _) in specs.items():
                assert not self._load_session(ch, s, tokens).failed()
            results = {}
            lock = threading.Lock()

            def decode(s, steps):
                cntl = rpc.Controller()
                resp = ch.call_method("Decode.Decode", cntl,
                                      EchoRequest(message=json.dumps(
                                          {"session": s,
                                           "steps": steps})),
                                      EchoResponse)
                with lock:
                    results[s] = (cntl.failed(), cntl.error_text,
                                  json.loads(resp.message)["tokens"]
                                  if not cntl.failed() else None)

            threads = [threading.Thread(target=decode, args=(s, steps))
                       for s, (_, steps) in specs.items()]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for s, (tokens, steps) in specs.items():
                failed, err, toks = results[s]
                assert not failed, (s, err)
                assert toks == m.reference_generate(tokens, steps), s
            d = svc.describe_serving()
            assert d["scheduler"]["retired"] == 6
            assert d["scheduler"]["batch_occupancy_avg"] > 1.0
            assert svc.live_sessions() == 0    # released on completion
            # the /status page carries the serving block
            ctype, body = server._builtin.dispatch("status")
            blk = json.loads(body)["serving"]["Decode"]
            assert blk["scheduler"]["steps"] > 0
            assert blk["pool"]["blocks_total"] > 0
        finally:
            ch.close()
            svc.close()
            server.stop()

    def test_sync_mode_matches_batch_mode(self):
        from examples.example_echo_pb2 import EchoRequest, EchoResponse
        m = _model()
        server, svc = self._decode_worker("serv-sync")
        ch = rpc.Channel()
        ch.init("mem://serv-sync",
                options=rpc.ChannelOptions(timeout_ms=30000))
        try:
            tokens = [(17 * j) % 499 for j in range(40)]
            want = m.reference_generate(tokens, 9)
            for mode in ("sync", "batch"):
                s = f"m-{mode}"
                assert not self._load_session(ch, s, tokens).failed()
                cntl = rpc.Controller()
                body = {"session": s, "steps": 9}
                if mode == "sync":
                    body["mode"] = "sync"
                resp = ch.call_method("Decode.Decode", cntl,
                                      EchoRequest(message=json.dumps(
                                          body)), EchoResponse)
                assert not cntl.failed(), cntl.error_text
                assert json.loads(resp.message)["tokens"] == want, mode
        finally:
            ch.close()
            svc.close()
            server.stop()

    def test_pool_saturated_sheds_with_retry_hint(self):
        from brpc_tpu.rpc import errors
        from brpc_tpu.serving import KvPoolOptions
        m = _model()
        server, svc = self._decode_worker(
            "serv-sat", pool_options=KvPoolOptions(
                bytes_per_token=m.KV_LAYERS * m.KV_DMODEL,
                num_blocks=2, block_tokens=8))
        ch = rpc.Channel()
        ch.init("mem://serv-sat",
                options=rpc.ChannelOptions(timeout_ms=30000,
                                           max_retry=0))
        try:
            # interactive KV owns the pool; a batch load is SHED with a
            # retry hint, not failed into the unknown
            assert not self._load_session(ch, "inter", [1] * 16,
                                          priority=0).failed()
            cntl = self._load_session(ch, "batch", [2] * 8, priority=3,
                                      tenant="bulk")
            assert cntl.failed() and cntl.error_code_ == errors.ELIMIT
            assert cntl.retry_after_ms > 0
            assert svc.live_sessions() == 1
        finally:
            ch.close()
            svc.close()
            server.stop()

    def test_idle_worker_reclaims_parked_session_without_traffic(self):
        """THE ISSUE-14 regression at the RPC level: LoadKv parks a
        session, NO further traffic of any kind arrives, and the
        worker's pool reclaims it by timer."""
        from brpc_tpu.serving import KvPoolOptions
        m = _model()
        server, svc = self._decode_worker(
            "serv-idle", pool_options=KvPoolOptions(
                bytes_per_token=m.KV_LAYERS * m.KV_DMODEL,
                num_blocks=8, block_tokens=8, ttl_s=0.15,
                sweep_interval_s=0.05))
        ch = rpc.Channel()
        ch.init("mem://serv-idle",
                options=rpc.ChannelOptions(timeout_ms=30000))
        try:
            assert not self._load_session(ch, "parked",
                                          [3] * 12).failed()
            assert svc.live_sessions() == 1
            deadline = time.monotonic() + 5.0
            while svc.live_sessions() and time.monotonic() < deadline:
                time.sleep(0.02)
            assert svc.live_sessions() == 0, \
                "parked session not reclaimed on an idle worker"
            assert svc.sessions_expired >= 1
        finally:
            ch.close()
            svc.close()
            server.stop()

    def test_rpc_press_serving_mode(self):
        """The open-loop session generator (tools/rpc_press --serving):
        mixed tenants at a fixed arrival rate, per-tenant tokens/s in
        the summary, and the in-process pool/scheduler occupancy
        reported through the serving status block."""
        import io

        import jax
        from brpc_tpu.tools.rpc_press import run_press_serving
        from examples.disagg_serving.workers import (start_decode_worker,
                                                     start_prefill_worker,
                                                     start_router)
        devs = jax.devices()
        prefill = start_prefill_worker("ici://7", device=devs[7])
        decode = start_decode_worker("mem://press-dec")
        router = start_router("mem://press-router", "ici://7",
                              ["mem://press-dec"])
        try:
            res = run_press_serving(
                "mem://press-router", duration=1.5, arrival_rps=40.0,
                batch_ratio=2, seq_range="16-32", steps_range="4-16",
                out=io.StringIO())
            assert res["issued"] >= 20, res
            for tenant in ("inter", "bulk"):
                c = res["per_tenant"][tenant]
                assert c["ok"] > 0 and c["failures"] == 0, res
                assert c["session_tokens_per_s_p50"] > 0, res
            assert res["tokens_per_s"] > 0
            blk = next(v for k, v in res["serving_status"].items()
                       if "Decode" in k)
            assert blk["pool"]["blocks_total"] > 0
            assert blk["scheduler"]["steps"] > 0
            # the ISSUE-16 prefix block rides the summary (same
            # in-process gate as serving_status)
            pfx = next(v for k, v in res["kv_prefix"].items()
                       if "Decode" in k)
            assert pfx["sharing_ratio"] >= 1.0
            assert pfx["unlocked_fills"] > 0     # the default route
            for key in ("shared_blocks", "prefix_hits", "cow_splits"):
                assert key in pfx
            # the ISSUE-19 tiers block rides the same gate
            tiers = next(v for k, v in res["kv_tiers"].items()
                         if "Decode" in k)
            for key in ("demotions", "restores", "restore_p50_us",
                        "spilled_sessions", "migration"):
                assert key in tiers
            assert tiers["migration"]["scope"] == "process"
        finally:
            for server in (router, prefill, decode):
                for svc in server._services.values():
                    if hasattr(svc, "close"):
                        svc.close()
                server.stop()

    def test_lalb_router_shifts_load_to_fast_worker(self):
        """The divided-weight loop: feedback drives selection — a slow
        worker's share collapses."""
        from brpc_tpu.serving import LoadAwareRouter
        router = LoadAwareRouter(["mem://lalb-fast", "mem://lalb-slow"])
        try:
            for _ in range(40):
                router.feedback("mem://lalb-fast", 0, 1000)
                router.feedback("mem://lalb-slow", 0, 50000)
            picks = {"mem://lalb-fast": 0, "mem://lalb-slow": 0}
            for _ in range(300):
                url = router.pick()
                picks[url] += 1
                router.feedback(url, 0,
                                1000 if url.endswith("fast") else 50000)
            assert picks["mem://lalb-fast"] > 0.65 * 300, picks
            d = router.describe()
            assert d["balancer"] == "la"
            assert d["weights"]["mem://lalb-fast"] > \
                d["weights"]["mem://lalb-slow"]
        finally:
            router.close()

    def test_router_retries_dead_decode_worker(self):
        """A Generate whose chosen decode worker is DEAD re-prefills
        against another one — zero client-visible failures (the elastic
        chaos contract's unit half)."""
        import jax
        from examples.disagg_serving.workers import (start_decode_worker,
                                                     start_prefill_worker,
                                                     start_router)
        from examples.example_echo_pb2 import EchoRequest, EchoResponse
        m = _model()
        devs = jax.devices()
        prefill = start_prefill_worker("ici://6", device=devs[6])
        alive = start_decode_worker("mem://rr-alive")
        dead = start_decode_worker("mem://rr-dead")
        router = start_router("mem://rr-router", "ici://6",
                              ["mem://rr-dead", "mem://rr-alive"])
        servers = [router, prefill, alive]
        try:
            # the dead worker stops before any traffic: whichever
            # attempt picks it fails and the router must recover
            for svc in dead._services.values():
                if hasattr(svc, "close"):
                    svc.close()
            dead.stop()
            ch = rpc.Channel()
            ch.init("mem://rr-router",
                    options=rpc.ChannelOptions(timeout_ms=60000))
            tokens = [(7 * j) % 499 for j in range(32)]
            for _ in range(4):
                cntl = rpc.Controller()
                resp = ch.call_method(
                    "Router.Generate", cntl,
                    EchoRequest(message=json.dumps(
                        {"tokens": tokens, "steps": 6})), EchoResponse)
                assert not cntl.failed(), cntl.error_text
                out = json.loads(resp.message)
                assert out["tokens"] == m.reference_generate(tokens, 6)
                assert out["decode_worker"] == "mem://rr-alive"
            ch.close()
        finally:
            for server in servers:
                for svc in server._services.values():
                    if hasattr(svc, "close"):
                        svc.close()
                server.stop()


# ---------------------------------------------------------------------------
# Autoscaler units (injected clock + load).
# ---------------------------------------------------------------------------

class TestAutoscaler:
    def _mk(self, loads, size0=1, **kw):
        from brpc_tpu.serving import (AutoscalerOptions,
                                      LoadThresholdAutoscaler)
        state = {"size": size0, "ups": 0, "downs": 0, "i": 0}

        def load_fn():
            i = min(state["i"], len(loads) - 1)
            state["i"] += 1
            return loads[i]

        def up():
            state["size"] += 1
            state["ups"] += 1
            return True

        def down():
            state["size"] -= 1
            state["downs"] += 1
            return True

        opts = AutoscalerOptions(**kw)
        a = LoadThresholdAutoscaler(load_fn, lambda: state["size"],
                                    up, down, options=opts)
        return a, state

    def test_hysteresis_and_cooldown(self):
        a, st = self._mk([0.9, 0.9, 0.9, 0.9, 0.9],
                         samples_to_scale=2, cooldown_s=10.0,
                         max_size=4)
        assert a.tick(now=0.0) is None      # 1 high sample: not yet
        assert a.tick(now=1.0) == "up"      # 2 consecutive: scale
        assert st["size"] == 2
        assert a.tick(now=2.0) is None      # cooldown holds
        assert a.tick(now=3.0) is None
        # sustained high load keeps accumulating through the cooldown:
        # the next action fires the moment the cooldown lifts
        assert a.tick(now=12.0) == "up"
        assert a.tick(now=13.0) is None     # new cooldown holds again
        assert st["ups"] == 2

    def test_scale_down_and_min_size(self):
        a, st = self._mk([0.1] * 6, size0=2, samples_to_scale=2,
                         cooldown_s=0.0, min_size=1)
        assert a.tick(now=0.0) is None
        assert a.tick(now=1.0) == "down"
        assert st["size"] == 1
        # at min_size: low load never goes below
        assert a.tick(now=2.0) is None
        assert a.tick(now=3.0) is None
        assert st["size"] == 1

    def test_max_size_and_mid_band_resets_runs(self):
        a, st = self._mk([0.9, 0.5, 0.9, 0.9], samples_to_scale=2,
                         cooldown_s=0.0, max_size=2)
        assert a.tick(now=0.0) is None
        assert a.tick(now=1.0) is None      # mid-band sample reset
        assert a.tick(now=2.0) is None
        assert a.tick(now=3.0) == "up"
        assert st["size"] == 2
        d = a.describe()
        assert d["scale_ups"] == 1 and d["size"] == 2
        assert "load" in d["last"]


# ---------------------------------------------------------------------------
# 2-process shm claim-to-pool (ISSUE 15): the KV payload crosses the
# fabric's shared-memory ring and the zero-copy CLAIM is consumed
# DIRECTLY into the decode worker's pool blocks — route asserted on
# both layers (rpc_fabric_route shm bytes AND serving_kv_load_adopted),
# decode byte-exact against the single-process reference.
# ---------------------------------------------------------------------------

_KV_SHM_CHILD = r"""
import json, os, sys, time
sys.path.insert(0, %(repo)r)
sys.path.insert(0, os.path.join(%(repo)r, "tests"))
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np

pid = int(sys.argv[1]); coord = sys.argv[2]
from brpc_tpu.ici.fabric import FabricNode, FabricSocket
node = FabricNode.initialize(coord, num_processes=2, process_id=pid)
kv = node._kv
import brpc_tpu.policy
from brpc_tpu import rpc, ici
from brpc_tpu.rpc.socket import list_sockets
from brpc_tpu.ici.route import route_stats
from examples.disagg_serving import model as m
from examples.example_echo_pb2 import EchoRequest, EchoResponse
mesh = ici.IciMesh(); ici.IciMesh.set_default(mesh)

SEQ, STEPS, N = 512, 7, 4
PAYLOAD = m.kv_nbytes(SEQ)

def fabric_socks():
    return [s for s in list_sockets() if isinstance(s, FabricSocket)]

if pid == 0:
    from brpc_tpu.serving import KvPoolOptions, kv_load_stats
    from examples.disagg_serving.workers import DecodeService
    server = rpc.Server()
    svc = DecodeService(pool_options=KvPoolOptions(
        bytes_per_token=m.KV_LAYERS * m.KV_DMODEL, num_blocks=256,
        block_tokens=16, use_timers=False))
    server.add_service(svc)
    assert server.start("ici://0") == 0
    kv.key_value_set("kvshm_srv_up", "1")
    kv.wait_at_barrier("kvshm_done", 180000)
    # route truth, decode-worker side: the claims came off the shm
    # ring AND landed in the pool via the adopted route (no per-session
    # host materialization)
    socks = fabric_socks()
    assert socks and socks[0].shm_bound(), "server socket has no shm ring"
    assert socks[0].shm_bytes_claimed >= N * PAYLOAD, \
        socks[0].shm_bytes_claimed
    st = kv_load_stats()
    # host-bulk sessions rode the ring and were consumed in place
    # (adopted); the device-payload session re-emerged as a DEVICE
    # array on this side and scattered.  NOTHING materialized.
    assert st["adopted"] >= N, st
    assert st["scattered"] >= 1, st
    assert st["materialized"] == 0, st
    # exactly one copy pass per session, either route
    assert st["copy_bytes"] == \
        (st["adopted"] + st["scattered"]) * PAYLOAD, st
    blk = svc.describe_serving()
    assert blk["kv_load"]["adopted"] >= N
    svc.close(); server.stop()
    print("KVSHM0_OK", flush=True)
else:
    kv.blocking_key_value_get("kvshm_srv_up", 60000)
    local_dev = next(i for i, d in enumerate(jax.devices())
                     if d.process_index == pid)
    ch = rpc.Channel()
    ch.init("ici://0", options=rpc.ChannelOptions(timeout_ms=120000,
                                                  max_retry=0))
    # i < N: the KV crosses as HOST bulk bytes — the ring carries them
    # and the receiver's zero-copy claim is consumed straight into the
    # pool (adopted).  i == N: the device-payload shape — the fabric
    # re-emerges it as a DEVICE array on the server, which scatters.
    for i in range(N + 1):
        tokens = [(11 * i + j) %% 997 for j in range(SEQ)]
        payload = m.toy_kv_blocks(tokens, device=jax.devices()[local_dev])
        jax.block_until_ready(payload)
        cntl = rpc.Controller()
        if i < N:
            cntl.request_attachment.append(np.asarray(payload).tobytes())
        else:
            cntl.request_attachment.append_device_array(payload)
        ch.call_method("Decode.LoadKv", cntl, EchoRequest(
            message=json.dumps({"session": "s%%d" %% i, "seq_len": SEQ,
                                "last_token": tokens[-1]})), EchoResponse)
        assert not cntl.failed(), cntl.error_text
        dc = rpc.Controller()
        resp = ch.call_method("Decode.Decode", dc, EchoRequest(
            message=json.dumps({"session": "s%%d" %% i,
                                "steps": STEPS, "mode": "sync"})),
            EchoResponse)
        assert not dc.failed(), dc.error_text
        got = json.loads(resp.message)["tokens"]
        assert got == m.reference_generate(tokens, STEPS), \
            "claim-to-pool decode mismatch at session %%d" %% i
    s = fabric_socks()[0]
    assert s.shm_bound(), "client socket has no shm ring"
    assert s.shm_bytes_sent >= N * PAYLOAD, s.shm_bytes_sent
    rs = route_stats()
    assert rs.get("shm", {}).get("bytes", 0) >= N * PAYLOAD, rs
    kv.wait_at_barrier("kvshm_done", 180000)
    ch.close()
    print("KVSHM1_OK", flush=True)
"""


def test_kv_shm_claim_lands_in_pool_2proc():
    """The adopted route end to end across TWO processes: prefill-side
    KV bytes ride the fabric's shm ring, the receiver's zero-copy ring
    claim scatters straight into PagedKvPool blocks (adopted counter +
    shm route counters assert both layers), and sync decode reproduces
    the single-process reference bit-exact."""
    from test_fabric import _run_pair
    outs = _run_pair(_KV_SHM_CHILD % {"repo": REPO}, timeout=300)
    assert "KVSHM0_OK" in outs[0]
    assert "KVSHM1_OK" in outs[1]


# ---------------------------------------------------------------------------
# Elastic chaos: scale-up + kill + revive + scale-down mid-traffic, one
# subprocess hosting a real (1-member) pod so the epoch is observable.
# ---------------------------------------------------------------------------

_ELASTIC_CHAOS_CHILD = r"""
import json, os, sys, threading, time
sys.path.insert(0, %(repo)r)
sys.path.insert(0, os.path.join(%(repo)r, "tests"))
import jax
jax.config.update("jax_platforms", "cpu")
coord = sys.argv[1]

from brpc_tpu.ici.fabric import FabricNode
node = FabricNode.initialize(coord, num_processes=1, process_id=0)
import brpc_tpu.policy
from brpc_tpu import rpc, ici
from brpc_tpu.ici.pod import Pod
mesh = ici.IciMesh(); ici.IciMesh.set_default(mesh)
pod = Pod.join("serving-chaos")

from brpc_tpu.serving import (AutoscalerOptions, BatchSchedulerOptions,
                              KvPoolOptions, LoadThresholdAutoscaler)
from examples.disagg_serving.model import (KV_DMODEL, KV_LAYERS, VOCAB,
                                           reference_generate)
from examples.disagg_serving.workers import (DecodeService,
                                             start_prefill_worker,
                                             start_router)
from examples.example_echo_pb2 import EchoRequest, EchoResponse

BPT = KV_LAYERS * KV_DMODEL

def mk_decode(dev_url):
    server = rpc.Server()
    svc = DecodeService(
        pool_options=KvPoolOptions(bytes_per_token=BPT, num_blocks=512,
                                   block_tokens=16),
        sched_options=BatchSchedulerOptions(vocab=VOCAB, max_batch=4))
    server.add_service(svc)
    assert server.start(dev_url) == 0
    return server, svc

prefill = start_prefill_worker("ici://0")
dec_a, svc_a = mk_decode("ici://1")
router = start_router("mem://chaos-router", "ici://0", ["ici://1"])
rsvc = next(iter(router._services.values()))
epoch0 = pod.epoch(refresh=True)

# ---- elastic mechanism: the autoscaler's scale callbacks ----------------
workers = {"ici://1": (dec_a, svc_a)}
wlock = threading.Lock()

def current_load():
    with wlock:
        svcs = [s for (_, s) in workers.values()]
    if not svcs:
        return 1.0
    load = 0.0
    for s in svcs:
        d = s.scheduler.describe()
        load += (d["active"] + sum(d["pending_by_band"])) \
            / max(d["max_batch"], 1)
    return load / len(svcs)

def scale_up():
    with wlock:
        if "ici://2" in workers:
            return False
        server, svc = mk_decode("ici://2")
        workers["ici://2"] = (server, svc)
    rsvc.add_decode_target("ici://2")
    return True

def scale_down():
    with wlock:
        if "ici://2" not in workers:
            return False
        server, svc = workers.pop("ici://2")
    rsvc.remove_decode_target("ici://2")
    time.sleep(0.1)
    server.stop(grace_s=1.0)
    svc.close()
    return True

def size_fn():
    with wlock:
        return len(workers)

scaler = LoadThresholdAutoscaler(
    current_load, size_fn, scale_up, scale_down,
    options=AutoscalerOptions(high_water=0.75, low_water=0.1,
                              interval_s=0.1, samples_to_scale=2,
                              cooldown_s=1.5, min_size=1, max_size=2),
    pod=pod)
scaler.start()

# ---- traffic ------------------------------------------------------------
stop_evt = threading.Event()
stats = {"ok": 0, "shed": 0, "fail": 0, "mismatch": 0}
slock = threading.Lock()
# every completion, checked against the reference once the traffic is
# over: a reference computed under slock during the traffic serialized
# the clients behind it, and the decode workers then never looked loaded
completions = []
ch_opts = rpc.ChannelOptions(timeout_ms=30000)

def client(wid, priority, pace_s, steps):
    ch = rpc.Channel(); ch.init("mem://chaos-router", options=ch_opts)
    i = 0
    while not stop_evt.is_set():
        tokens = [(wid * 31 + i * 7 + j) %% 997 for j in range(24)]
        i += 1
        cntl = rpc.Controller()
        cntl.priority = priority
        cntl.tenant = "inter" if priority == 0 else "bulk"
        resp = ch.call_method("Router.Generate", cntl,
                              EchoRequest(message=json.dumps(
                                  {"tokens": tokens, "steps": steps})),
                              EchoResponse)
        with slock:
            if cntl.failed():
                if cntl.error_code_ == rpc.errors.ELIMIT:
                    stats["shed"] += 1
                else:
                    stats["fail"] += 1
                    sys.stderr.write("CLIENT FAIL: %%s %%s\n"
                                     %% (cntl.error_code_,
                                        cntl.error_text))
            else:
                completions.append(
                    (tokens, steps, json.loads(resp.message)["tokens"]))
        if pace_s:
            time.sleep(pace_s)
    ch.close()

# batch sessions are LONG (10,000 tokens): they live tens of ms in the
# roster, so 6 concurrent batch clients genuinely saturate max_batch=4
# and the load signal (roster + queue pressure) crosses the high-water
# mark — the toy decode is otherwise too fast to ever look loaded (at
# 400 tokens a session left the roster in ~20 ms, before the next one's
# prefill ended, and only the first wave could cross the mark)
threads = [threading.Thread(target=client, args=(w, 0, 0.05, 6))
           for w in range(2)]
threads += [threading.Thread(target=client, args=(10 + w, 3, 0.0, 10000))
            for w in range(6)]
for t in threads: t.start()

def wait_for(pred, timeout, what):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.05)
    raise AssertionError("timeout waiting for " + what)

try:
    # phase 1: the batch flood pushes load over the high-water mark and
    # the autoscaler scales decode ici://2 up (epoch bump via advertise)
    wait_for(lambda: scaler.scale_ups.get_value() >= 1, 30.0,
             "scale-up (load=%%s)" %% current_load())
    wait_for(lambda: "ici://2" in rsvc._router.targets(), 5.0,
             "router membership")
    time.sleep(1.0)

    # phase 2: KILL decode A mid-traffic (no drain).  In-flight
    # sessions on A fail server-side; the router re-prefills them on B
    # — zero client-visible failures.
    dec_a.stop(grace_s=0)
    svc_a.close()
    rsvc.remove_decode_target("ici://1")
    with wlock:
        workers.pop("ici://1", None)
    time.sleep(1.5)

    # phase 3: REVIVE A (restart on the same device; advertise bumps
    # the epoch again) and hand it back to the router
    dec_a2, svc_a2 = mk_decode("ici://1")
    with wlock:
        workers["ici://1"] = (dec_a2, svc_a2)
    rsvc.add_decode_target("ici://1")
    time.sleep(1.0)
finally:
    # phase 4: drop the batch flood; load falls under the low-water
    # mark and the autoscaler scales ici://2 back down
    stop_evt.set()
for t in threads: t.join()
wait_for(lambda: scaler.scale_downs.get_value() >= 1, 20.0,
         "scale-down (load=%%s)" %% current_load())

scaler.stop()
for tokens, steps, toks in completions:
    if toks == reference_generate(tokens, steps):
        stats["ok"] += 1
    else:
        stats["mismatch"] += 1
epoch1 = pod.epoch(refresh=True)
desc = pod.describe()
assert "autoscaler" in desc, "autoscaler missing from pod describe"

result = {
    "ok": stats["ok"], "shed": stats["shed"], "fail": stats["fail"],
    "mismatch": stats["mismatch"],
    "epoch_delta": epoch1 - epoch0,
    "scale_ups": scaler.scale_ups.get_value(),
    "scale_downs": scaler.scale_downs.get_value(),
    "router": rsvc.describe_serving()["router"],
}
print("CHAOS_RESULT " + json.dumps(result), flush=True)

for server, svc in list(workers.values()):
    svc.close(); server.stop()
for svc in router._services.values():
    if hasattr(svc, "close"): svc.close()
router.stop()
for svc in prefill._services.values():
    if hasattr(svc, "close"): svc.close()
prefill.stop()
pod.leave()
"""


class TestElasticChaosServing:
    def test_scale_up_kill_revive_scale_down_under_traffic(self):
        """The tier-1 elastic chaos leg: a 1-member pod serving mixed
        interactive/batch traffic scales a decode worker up on load,
        survives a KILL of the original worker, revives it, and scales
        back down — zero client-visible failures, every completion
        bit-exact, the epoch delta covering every membership
        transition."""
        from netalloc import alloc_port
        coord = f"127.0.0.1:{alloc_port('serving_chaos')}"
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env.pop("JAX_NUM_PROCESSES", None)
        proc = subprocess.Popen(
            [sys.executable, "-c",
             _ELASTIC_CHAOS_CHILD % {"repo": REPO}, coord],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        try:
            out, _ = proc.communicate(timeout=180)
        except subprocess.TimeoutExpired:
            proc.kill()
            out, _ = proc.communicate()
        assert proc.returncode == 0, out[-4000:]
        line = next(l for l in out.splitlines()
                    if l.startswith("CHAOS_RESULT "))
        res = json.loads(line[len("CHAOS_RESULT "):])
        # zero client-visible failures; batch sheds allowed (that IS
        # the absorb-the-pressure contract), mismatches never
        assert res["fail"] == 0, res
        assert res["mismatch"] == 0, res
        assert res["ok"] > 20, res
        assert res["scale_ups"] >= 1 and res["scale_downs"] >= 1, res
        # every transition moved the epoch: initial 3 advertises are in
        # epoch0; up(+1) kill-withdraw(+1) revive(+1) down(+>=1)
        assert res["epoch_delta"] >= 4, res
        # the router retried around the kill rather than surfacing it
        assert res["router"]["generate_failures"] == 0, res
