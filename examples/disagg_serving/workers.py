"""Disaggregated prefill/decode workers, rebuilt on ``brpc_tpu.serving``.

Three roles, each an ordinary brpc_tpu server — same wire surface as
the original example (JSON bodies in EchoRequest.message, bulk bytes in
attachments), but the decode side is now the REAL serving subsystem
(ROADMAP item 3), not a one-RPC-one-token toy:

  * **PrefillService** (``Prefill``): prompt → quantized KV blocks on
    its own device, HANDED OFF to the router-chosen decode worker as a
    DEVICE-payload attachment (``DecodeService.LoadKv``).  Cross-process
    this rides the fabric's sequenced device plane or the shm ring; on
    the native-ici plane the attachment moves under PR-12 custody (one
    parked handle).  Wherever it lands, LoadKv scatters the wire bytes
    DIRECTLY into the paged pool's reserved blocks (ISSUE 15): shm
    claims are consumed in place, parked handles taken segment-wise —
    one copy pass, no per-session host materialization
    (``serving_kv_load_*`` counters carry the per-route truth).
  * **DecodeService** (``LoadKv`` / ``Decode``): KV pages into a
    :class:`~brpc_tpu.serving.PagedKvPool` (admission-aware eviction,
    TimerThread expiry — an idle worker reclaims parked sessions with
    zero traffic, the ISSUE-14 bugfix) and tokens stream out of a
    :class:`~brpc_tpu.serving.ContinuousBatchScheduler`: one batched
    step per tick over every active session, admit/retire/preempt
    between steps.  ``Decode`` is an ASYNC handler — the RPC completes
    from the step loop when the session's tokens are done, so N
    concurrent sessions share each step instead of serializing.
    ``{"mode": "sync"}`` keeps the old one-RPC-one-shot path (the
    baseline the tests compare the batched path with).
  * **RouterService** (``Generate``): the front door — prefill via any
    LB channel, decode worker chosen by the LALB divided-weight
    balancer (:class:`~brpc_tpu.serving.LoadAwareRouter`): every decode
    outcome feeds the balancer, a dead/slow worker's weight collapses
    within one request time, and failures RETRY against another worker
    (re-prefill) so elastic scale-down/kill stays invisible to clients.
    ``decode_targets`` may be the original explicit dict, a list, or a
    naming url (``pod://name``) for elastic membership.
"""
from __future__ import annotations

import json
import sys
import threading
import time
from typing import Dict, Optional, Union

sys.path.insert(0, __file__.rsplit("/", 3)[0])   # repo root

import numpy as np

import brpc_tpu.policy  # noqa: F401  (registers protocols)
from brpc_tpu import rpc
from brpc_tpu.butil import debug_sync as _dbg
from brpc_tpu.serving import (BatchSchedulerOptions,
                              ContinuousBatchScheduler, KvPoolOptions,
                              LoadAwareRouter, PagedKvPool, PoolSaturated,
                              SessionBusy, StepRequest, kv_load_stats,
                              load_token_major_attachment,
                              load_wire_attachment, migrate_out)
from brpc_tpu.serving import kv_source as _kv_source
from brpc_tpu.serving import migration as _migration
from examples.example_echo_pb2 import EchoRequest, EchoResponse

from .model import (KV_DMODEL, KV_LAYERS, VOCAB, kv_nbytes, toy_decode,
                    toy_kv_blocks)

BYTES_PER_TOKEN = KV_LAYERS * KV_DMODEL


def _reply(response, done, **kw) -> None:
    response.message = json.dumps(kw)
    done()


class PrefillService(rpc.Service):
    SERVICE_NAME = "Prefill"

    _GUARDED_BY = {"_channels": "_lock", "prefills": "_lock",
                   "handoff_bytes": "_lock", "handoff_ns": "_lock"}

    def __init__(self, device=None,
                 channel_options: Optional[rpc.ChannelOptions] = None):
        self.device = device
        self.channel_options = channel_options or rpc.ChannelOptions(
            timeout_ms=60000)
        self._channels: Dict[str, rpc.Channel] = {}
        self._lock = _dbg.make_lock("PrefillService._lock")
        self.prefills = 0
        self.handoff_bytes = 0
        self.handoff_ns = 0      # cumulative LoadKv round-trip time

    def _channel_to(self, target: str) -> rpc.Channel:
        with self._lock:
            ch = self._channels.get(target)
            if ch is None:
                ch = rpc.Channel()
                ch.init(target, options=self.channel_options)
                self._channels[target] = ch
            return ch

    def close(self) -> None:
        with self._lock:
            chans, self._channels = list(self._channels.values()), {}
        for ch in chans:
            ch.close()

    @rpc.method(EchoRequest, EchoResponse)
    def Prefill(self, cntl, request, response, done):
        req = json.loads(request.message)
        session = req["session"]
        tokens = req["tokens"]
        decode_target = req["decode"]
        import jax
        t0 = time.perf_counter_ns()
        kv = toy_kv_blocks(tokens, device=self.device)
        jax.block_until_ready(kv)
        t1 = time.perf_counter_ns()
        # the KV-cache handoff: device payload to the decode worker
        # (the inbound call's priority/tenant/deadline budget cascade
        # onto this outbound call — PR-10 request context)
        ch = self._channel_to(decode_target)
        hand = rpc.Controller()
        hand.request_attachment.append_device_array(kv)
        load = EchoRequest(message=json.dumps(
            {"session": session, "seq_len": len(tokens),
             "last_token": tokens[-1]}))
        ch.call_method("Decode.LoadKv", hand, load, EchoResponse)
        t2 = time.perf_counter_ns()
        if hand.failed():
            cntl.retry_after_ms = hand.retry_after_ms
            cntl.set_failed(hand.error_code_,
                            f"kv handoff failed: {hand.error_text}")
            done()
            return
        with self._lock:
            self.prefills += 1
            self.handoff_bytes += kv_nbytes(len(tokens))
            self.handoff_ns += t2 - t1
        _reply(response, done, session=session,
               kv_bytes=kv_nbytes(len(tokens)),
               prefill_us=(t1 - t0) // 1000,
               handoff_us=(t2 - t1) // 1000)


class DecodeService(rpc.Service):
    SERVICE_NAME = "Decode"

    # ("loads" stays out of the guard map: the analyzer would match the
    # attribute name on any receiver, including json.loads — the counter
    # is still only written under _lock)
    _GUARDED_BY = {"kv_bytes_in": "_lock", "decode_steps": "_lock",
                   "_channels": "_lock"}

    def __init__(self, device=None,
                 pool_options: Optional[KvPoolOptions] = None,
                 sched_options: Optional[BatchSchedulerOptions] = None,
                 channel_options: Optional[rpc.ChannelOptions] = None):
        self.device = device
        self.pool = PagedKvPool(pool_options or KvPoolOptions(
            bytes_per_token=BYTES_PER_TOKEN, num_blocks=1024,
            block_tokens=16))
        self.scheduler = ContinuousBatchScheduler(
            self.pool, sched_options or BatchSchedulerOptions(
                vocab=VOCAB, max_batch=64))
        self._lock = _dbg.make_lock("DecodeService._lock")
        self.channel_options = channel_options or rpc.ChannelOptions(
            timeout_ms=60000)
        self._channels: Dict[str, rpc.Channel] = {}   # migrate peers
        #: chaos hook (ISSUE 19): an UNSET Event here black-holes
        #: MigrateIn — the handler parks until the test releases it,
        #: so the source's transfer-deadline latch is what fires
        self.migrate_in_gate: Optional[threading.Event] = None
        self.loads = 0
        self.kv_bytes_in = 0
        self.decode_steps = 0

    @property
    def sessions_expired(self) -> int:
        """TTL-reclaimed session count — now the pool's TIMER-driven
        policy (the old inline LoadKv sweep parked KV forever on an
        idle worker)."""
        return self.pool.expirations.get_value()

    def live_sessions(self) -> int:
        return self.pool.sessions()

    def close(self) -> None:
        self.scheduler.stop()
        self.pool.close()
        with self._lock:
            chans, self._channels = list(self._channels.values()), {}
        for ch in chans:
            ch.close()

    def _channel_to(self, target: str) -> rpc.Channel:
        with self._lock:
            ch = self._channels.get(target)
            if ch is None:
                ch = rpc.Channel()
                ch.init(target, options=self.channel_options)
                self._channels[target] = ch
            return ch

    def describe_serving(self) -> dict:
        """The /status serving block: step rate, batch occupancy, pool
        pages, evictions by reason/tenant, KV-load routes.  Unlike the
        per-instance scheduler/pool blocks, ``kv_load`` is the
        PROCESS-WIDE route ledger (the counters live in
        ``serving/kv_source.py``) — with several decode workers in one
        process it sums all of them, and says so via ``scope``."""
        return {"scheduler": self.scheduler.describe(),
                "pool": self.pool.describe(),
                "kv_load": {**kv_load_stats(), "scope": "process"}}

    @rpc.method(EchoRequest, EchoResponse)
    def LoadKv(self, cntl, request, response, done):
        req = json.loads(request.message)
        session = req["session"]
        seq_len = req["seq_len"]
        if seq_len <= 0:
            cntl.set_failed(rpc.errors.EREQUEST,
                            f"seq_len must be >= 1, got {seq_len}")
            done()
            return
        want = kv_nbytes(seq_len)
        att = cntl.request_attachment
        # len() answers from the descriptor total on every plane —
        # a parked NativeAttachment is NOT materialized by this check
        if len(att) != want:
            cntl.set_failed(rpc.errors.EREQUEST,
                            f"kv size {len(att)} != {want}")
            done()
            return
        try:
            if _kv_source.adopt_enabled():
                # ISSUE 15: the wire bytes scatter DIRECTLY into the
                # reserved pool blocks — shm ring claims consumed in
                # place (slot retired right after the fill), parked
                # native att segments taken block-wise, ONE copy pass;
                # the layer-major → token-major transpose happens
                # inside the strided scatter, never as its own pass
                load_wire_attachment(
                    self.pool, att, session, seq_len, KV_LAYERS,
                    KV_DMODEL, last_token=req["last_token"],
                    tenant=cntl.tenant or req.get("tenant", ""),
                    priority=cntl.priority)
                # drop the attachment refs NOW: the ring claim's
                # consume-to-release credit returns on this line, not
                # at controller recycle
                att.clear()
            else:
                # the PR-14 path, byte-for-byte (the A/B leg):
                # materialize (copy 1), transpose-reshape (copy 2),
                # pool fill (copy 3)
                blob = att.to_bytes()
                rows = np.frombuffer(blob, np.uint8).reshape(
                    KV_LAYERS, seq_len, KV_DMODEL).transpose(
                    1, 0, 2).reshape(seq_len, BYTES_PER_TOKEN)
                self.pool.load(session, rows,
                               last_token=req["last_token"],
                               tenant=cntl.tenant or req.get("tenant",
                                                             ""),
                               priority=cntl.priority)
                _kv_source.stats.record(_kv_source.MATERIALIZED, want, 3)
        except PoolSaturated:
            # memory pressure with nothing evictable in an equal-or-
            # less-protected band: a shed, not a failure
            cntl.retry_after_ms = 20
            cntl.set_failed(rpc.errors.ELIMIT,
                            "kv pool saturated (shed): retry later")
            done()
            return
        except SessionBusy as e:
            # re-prefill raced the running decode: retry once it
            # completes — freeing the rostered blocks mid-program
            # would corrupt the batched step.  Since ISSUE 16 this is
            # also the COMMIT-TIME abort of an outside-the-lock fill
            # (a concurrent LoadKv won the session id and its entry
            # got pinned before our re-check) — same shed, same retry
            cntl.retry_after_ms = 10
            cntl.set_failed(rpc.errors.ELIMIT, str(e))
            done()
            return
        with self._lock:
            self.loads += 1
            self.kv_bytes_in += want
        _reply(response, done, session=session, loaded=want)

    @rpc.method(EchoRequest, EchoResponse)
    def MigrateIn(self, cntl, request, response, done):
        """Destination half of a live migration (ISSUE 19): a peer
        pool's TOKEN-MAJOR block payload lands through the ordinary
        reserve/fill-outside-the-lock/commit path.  Refusals are the
        same retryable sheds as LoadKv — a saturated or busy
        destination aborts the migration cleanly, the SOURCE copy
        stays authoritative, no plane event."""
        gate = self.migrate_in_gate
        if gate is not None:
            gate.wait()          # chaos: black-hole until released
        req = json.loads(request.message)
        session = req["session"]
        seq_len = req["seq_len"]
        bpt = self.pool.options.bytes_per_token
        want = seq_len * bpt
        att = cntl.request_attachment
        if seq_len <= 0 or len(att) != want:
            cntl.set_failed(rpc.errors.EREQUEST,
                            f"migrate payload {len(att)} != {want}")
            done()
            return
        try:
            load_token_major_attachment(
                self.pool, att, session, seq_len,
                last_token=req["last_token"],
                tenant=req.get("tenant", ""),
                priority=req.get("priority"))
            att.clear()
        except PoolSaturated:
            cntl.retry_after_ms = 20
            cntl.set_failed(rpc.errors.ELIMIT,
                            "kv pool saturated (shed): migration "
                            "refused, source stays authoritative")
            done()
            return
        except SessionBusy as e:
            cntl.retry_after_ms = 10
            cntl.set_failed(rpc.errors.ELIMIT, str(e))
            done()
            return
        _migration.stats.migrations_in << 1
        with self._lock:
            self.kv_bytes_in += want
        _reply(response, done, session=session, loaded=want)

    @rpc.method(EchoRequest, EchoResponse)
    def MigrateOut(self, cntl, request, response, done):
        """Source half: ship one session to the ``dest`` decode worker
        (``Decode.MigrateIn`` there) under the transfer-deadline
        plane-health latch.  The source copy serves until the
        destination commits; only then is it released — an abort at
        any point leaves the source authoritative and reads as a
        retryable shed to the caller."""
        req = json.loads(request.message)
        session = req["session"]
        dest = req["dest"]
        ch = self._channel_to(dest)

        def send(meta, payload):
            mc = rpc.Controller()
            mc.request_attachment.append(payload)
            ch.call_method("Decode.MigrateIn", mc,
                           EchoRequest(message=json.dumps(meta)),
                           EchoResponse)
            if mc.failed():
                # ELIMIT from the destination is a clean shed
                # (saturated/busy), not a dead peer
                return (False, mc.error_text,
                        mc.error_code_ == rpc.errors.ELIMIT)
            return True, "", False
        ok, err = migrate_out(
            self.pool, session, send, scheduler=self.scheduler,
            deadline_ms=req.get("deadline_ms"))
        if not ok:
            # every abort is a shed: the source copy still serves, a
            # retry (here or around a re-prefill) stays cheap
            cntl.retry_after_ms = 10
            cntl.set_failed(rpc.errors.ELIMIT,
                            f"migration failed (shed): {err}")
            done()
            return
        _reply(response, done, session=session, migrated=True,
               dest=dest)

    @rpc.method(EchoRequest, EchoResponse)
    def Decode(self, cntl, request, response, done):
        req = json.loads(request.message)
        session = req["session"]
        steps = req["steps"]
        release = req.get("release", True)
        if steps <= 0:
            _reply(response, done, session=session, tokens=[])
            return
        if req.get("mode") == "sync":
            self._decode_sync(cntl, session, steps, release, response,
                              done)
            return
        self.pool.touch(session)
        deadline_us = None
        if cntl.deadline_left_ms:
            deadline_us = (time.monotonic_ns() // 1000
                           + cntl.deadline_left_ms * 1000)

        def emit(tokens):
            with self._lock:
                self.decode_steps += len(tokens)
            if release:
                self.pool.release(session)
            _reply(response, done, session=session, tokens=tokens)

        def fail(code, text, retry_after_ms):
            if retry_after_ms:
                cntl.retry_after_ms = retry_after_ms
            cntl.set_failed(code, text)
            done()

        # ASYNC: the RPC completes from the step loop when this
        # session's tokens are done — the handler thread is free
        self.scheduler.submit(StepRequest(
            session, steps, emit, fail, priority=cntl.priority,
            tenant=cntl.tenant, deadline_us=deadline_us))

    def _decode_sync(self, cntl, session, steps, release, response,
                     done) -> None:
        """The pre-batching one-RPC-one-shot path (the tests' baseline):
        read the session out of the pool and decode inline.  The read
        is a zero-copy VIEW when the session's blocks are one
        contiguous extent (the ISSUE-15 materialize bugfix) — pinned
        for exactly the decode, unpinned before the release."""
        snap = self.pool.snapshot(session, view=True)
        if snap is None:
            reason = self.pool.evicted_reason(session)
            if reason is not None:
                cntl.retry_after_ms = 1
                cntl.set_failed(rpc.errors.ELIMIT,
                                f"kv {reason}-evicted: re-prefill")
            else:
                cntl.set_failed(rpc.errors.EREQUEST,
                                f"unknown session {session!r}")
            done()
            return
        rows, seq_len, last_token, is_view = snap
        try:
            # token-major rows → the model's layer-major flat layout
            flat = rows.reshape(seq_len, KV_LAYERS, KV_DMODEL).transpose(
                1, 0, 2).reshape(-1)
            toks = toy_decode(flat, seq_len, last_token, steps)
        finally:
            if is_view:
                self.pool.unpin(session)
        with self._lock:
            self.decode_steps += steps
        if release:
            self.pool.release(session)
        else:
            self.pool.touch(session)
        _reply(response, done, session=session, tokens=toks)


class RouterService(rpc.Service):
    SERVICE_NAME = "Router"

    _GUARDED_BY = {"_next_session": "_lock", "retries": "_lock",
                   "generate_failures": "_lock"}

    #: decode attempts per Generate (the elastic-chaos survival knob:
    #: a killed worker's in-flight sessions re-prefill elsewhere)
    MAX_DECODE_ATTEMPTS = 3

    def __init__(self, prefill_targets: str,
                 decode_targets: Union[Dict[str, str], list, str],
                 channel_options: Optional[rpc.ChannelOptions] = None):
        """``prefill_targets``: naming url (or single endpoint) for the
        prefill pool.  ``decode_targets``: explicit dict/list of decode
        worker urls, or a naming url (``pod://name``) for elastic
        membership — either way the LALB divided-weight balancer picks
        the worker and every outcome feeds back."""
        opts = channel_options or rpc.ChannelOptions(timeout_ms=60000,
                                                     max_retry=2)
        from brpc_tpu.policy.naming import is_naming_url
        self._prefill = rpc.Channel()
        self._prefill.init(prefill_targets,
                           "rr" if is_naming_url(prefill_targets) else "",
                           options=opts)
        if isinstance(decode_targets, dict):
            decode_targets = list(decode_targets)
        self._router = LoadAwareRouter(decode_targets,
                                       channel_options=opts)
        self._lock = _dbg.make_lock("RouterService._lock")
        self._next_session = 0
        self.retries = 0
        self.generate_failures = 0

    def close(self) -> None:
        self._prefill.close()
        self._router.close()

    # elastic membership (the autoscaler's registration surface; a
    # naming-url router tracks pod:// transitions by itself)
    def add_decode_target(self, url: str) -> bool:
        return self._router.add_target(url)

    def remove_decode_target(self, url: str) -> bool:
        return self._router.remove_target(url)

    def describe_serving(self) -> dict:
        with self._lock:
            extra = {"retries": self.retries,
                     "generate_failures": self.generate_failures}
        return {"router": {**self._router.describe(), **extra}}

    @rpc.method(EchoRequest, EchoResponse)
    def Generate(self, cntl, request, response, done):
        req = json.loads(request.message)
        tokens = req["tokens"]
        steps = req.get("steps", 8)
        with self._lock:
            self._next_session += 1
            base_session = self._next_session
        tried: set = set()
        last_err = (rpc.errors.EINTERNAL, "no decode worker available")
        for attempt in range(self.MAX_DECODE_ATTEMPTS):
            decode_url = self._router.pick(exclude=tried)
            if decode_url is None:
                break
            # one session id per attempt: a retry re-prefills, never
            # half-reuses a dead worker's parked KV.  When the retry
            # lands on the SAME worker, its LoadKv dedupes against the
            # original session's still-parked blocks (ISSUE 16 prefix
            # sharing) — the re-prefill's full blocks commit as
            # refcount bumps, not new arena pages
            session = f"s{base_session}" if attempt == 0 \
                else f"s{base_session}r{attempt}"
            pc = rpc.Controller()
            t_pre = time.perf_counter_ns()
            pre_resp = self._prefill.call_method(
                "Prefill.Prefill", pc,
                EchoRequest(message=json.dumps(
                    {"session": session, "tokens": tokens,
                     "decode": decode_url})), EchoResponse)
            pre_us = (time.perf_counter_ns() - t_pre) // 1000
            if pc.failed():
                if pc.error_code_ == rpc.errors.ELIMIT \
                        and "kv handoff failed" not in pc.error_text:
                    # the PREFILL admission shed this tenant: not the
                    # decode worker's fault — pass the shed (and its
                    # backoff hint) straight to the client.  (An ELIMIT
                    # whose text says the HANDOFF failed is the decode
                    # side's — saturated pool, busy session — and falls
                    # through to the punish-and-retry path below.)
                    if pc.retry_after_ms:
                        cntl.retry_after_ms = pc.retry_after_ms
                    cntl.set_failed(pc.error_code_,
                                    f"prefill shed: {pc.error_text}")
                    done()
                    return
                # the handoff INSIDE prefill failed against this decode
                # worker (dead/saturated): punish its weight and retry
                # another one.  The REAL elapsed time matters: LALB's
                # error punishment scales with the reported latency, so
                # a 0-µs error sample would INFLATE the dead worker's
                # weight instead of collapsing it
                self._router.feedback(decode_url, pc.error_code_,
                                      max(pre_us, 1))
                tried.add(decode_url)
                last_err = (pc.error_code_,
                            f"prefill failed: {pc.error_text}")
                with self._lock:
                    self.retries += 1
                continue
            pre = json.loads(pre_resp.message)
            dc = rpc.Controller()
            t0 = time.perf_counter_ns()
            dec_resp = self._router.channel(decode_url).call_method(
                "Decode.Decode", dc,
                EchoRequest(message=json.dumps(
                    {"session": session, "steps": steps,
                     "release": req.get("release", True),
                     **({"mode": req["mode"]} if "mode" in req
                        else {})})),
                EchoResponse)
            lat_us = (time.perf_counter_ns() - t0) // 1000
            self._router.feedback(decode_url, dc.error_code_
                                  if dc.failed() else 0, lat_us)
            if dc.failed():
                # ELIMIT is a SHED, not a dead worker: an evicted/
                # expired session just needs a re-prefill (possibly on
                # the SAME worker — with one worker, excluding it would
                # turn a recoverable shed into a client-visible
                # failure), and a saturated pool is already being
                # steered away from by the LALB weight punishment.
                # Anything else (dead socket, drain) excludes the
                # worker from this call's retries.
                if dc.error_code_ != rpc.errors.ELIMIT:
                    tried.add(decode_url)
                last_err = (dc.error_code_,
                            f"decode failed: {dc.error_text}")
                if dc.retry_after_ms:
                    cntl.retry_after_ms = dc.retry_after_ms
                with self._lock:
                    self.retries += 1
                continue
            toks = json.loads(dec_resp.message)["tokens"]
            _reply(response, done, session=session, tokens=toks,
                   decode_worker=decode_url,
                   kv_bytes=pre.get("kv_bytes", 0))
            return
        with self._lock:
            self.generate_failures += 1
        cntl.set_failed(last_err[0], last_err[1])
        done()


def start_prefill_worker(addr: str, device=None,
                         options: Optional[rpc.ServerOptions] = None
                         ) -> rpc.Server:
    server = rpc.Server(options)
    server.add_service(PrefillService(device=device))
    rc = server.start(addr)
    assert rc == 0, f"prefill worker start failed: {rc}"
    return server


def start_decode_worker(addr: str, device=None,
                        options: Optional[rpc.ServerOptions] = None,
                        pool_options: Optional[KvPoolOptions] = None,
                        sched_options: Optional[
                            BatchSchedulerOptions] = None
                        ) -> rpc.Server:
    server = rpc.Server(options)
    server.add_service(DecodeService(device=device,
                                     pool_options=pool_options,
                                     sched_options=sched_options))
    rc = server.start(addr)
    assert rc == 0, f"decode worker start failed: {rc}"
    return server


def start_router(addr: str, prefill_targets: str,
                 decode_targets: Union[Dict[str, str], list, str]
                 ) -> rpc.Server:
    server = rpc.Server()
    server.add_service(RouterService(prefill_targets, decode_targets))
    rc = server.start(addr)
    assert rc == 0, f"router start failed: {rc}"
    return server
