#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the ici:// RPC path still starts
and runs on the TPU.

    python chip_smoke.py               one chip: native core build, device-
                                       attachment echo sweep over ici://0,
                                       a handler that computes on the chip
                                       (+ tcp host->HBM ingest), streaming
                                       with device chunks, an operand
                                       fan-out whose operand is a device
                                       array, the compiled serving step
    python chip_smoke.py --multichip   four chips: ONLY the cross-chip path
                                       and what it is compared with (device
                                       plane relocation, mesh collectives,
                                       compiled collective fan-out)

One process does everything and holds the chip; no child needs jax.  Any
phase that raises, any comparison that fails, or a platform other than
"tpu" ends the run with a non-zero exit code and WITHOUT the result line.
The last stdout line of a good run is one JSON object:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Every timing printed on earlier lines is a host-clock SMOKE TIMING (first
calls, compilation inside) — never a benchmark figure.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import time

import jax
import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

KB, MB, GB = 1 << 10, 1 << 20, 1 << 30

# (attachment bytes, unary calls) — the rdma_performance attachment sweep
# up to the BASELINE's 1 GB figure.  At or under the native send window
# (4 MB) a call rides the native tier; above it, the Python ici
# plane drains the payload through its credit window.
ECHO_SWEEP = ((4 * KB, 200), (1 * MB, 100), (64 * MB, 6), (1 * GB, 2))
STREAM_SWEEP = ((64 * KB, 24), (1 * MB, 24))     # (chunk bytes, frames)
HANDLER_BYTES = 1 * MB                           # HBM attachment, phase 3
FANOUT_WIDTH, FANOUT_SHARD = 4, 16 * MB          # fanout_4x16m's operation
TCP_INGEST_BYTES = 4 * MB                        # host attachment over tcp
XCHIP_SWEEP = ((4 * KB, 20), (64 * MB, 4))       # --multichip echo
ALLREDUCE_BYTES_PER_CHIP = 256 * MB              # 1 GiB over four chips
RING_BYTES_PER_CHIP = 16 * MB


WATCHDOG_S = 1140


class SmokeFailure(AssertionError):
    pass


def _watchdog_fired() -> None:
    print(f"chip_smoke: still running after {WATCHDOG_S}s — giving up",
          file=sys.stderr, flush=True)
    os._exit(124)


def say(msg: str) -> None:
    print(msg, flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def fmt_bytes(n: int) -> str:
    for unit, name in ((GB, "GiB"), (MB, "MiB"), (KB, "KiB")):
        if n >= unit:
            return f"{n / unit:g}{name}"
    return f"{n}B"


# ---------------------------------------------------------------------------
# set-up: native core from tracked sources, compile cache, compile meter
# ---------------------------------------------------------------------------

def build_native_core() -> None:
    """Forced rebuild from native/*.cpp — an untracked or stale .so on
    disk is never what this run loads."""
    from brpc_tpu.butil import native
    check(native._lib is None, "the native core was loaded before its "
          "rebuild: a stale library would be what runs")
    t0 = time.monotonic()
    res = subprocess.run(
        ["make", "-B", "-C", os.path.join(REPO, "native"),
         "libbrpc_tpu_core.so"], capture_output=True, text=True)
    if res.returncode != 0:
        raise SmokeFailure("native core build failed:\n" + res.stderr[-4000:])
    check(native.available(), "native core built but did not load")
    say(f"[native] libbrpc_tpu_core.so rebuilt from tracked sources in "
        f"{time.monotonic() - t0:.1f}s and loaded")


class CompileMeter:
    """Backend-compile seconds and persistent-cache traffic of this
    process, from jax's own monitoring events."""

    def __init__(self):
        import jax.monitoring as mon
        self.compile_s = 0.0
        self.programs = 0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.programs += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def line(self, cache_dir: str) -> str:
        state = "warm" if self.hits and not self.misses else (
            "cold" if not self.hits else "mixed")
        return (f"[compile] programs={self.programs} "
                f"backend_compile_s={self.compile_s:.2f} "
                f"persistent_cache_hits={self.hits} misses={self.misses} "
                f"({state}) cache_dir={cache_dir}")


def echo_types():
    import brpc_tpu.policy  # noqa: F401  (registers protocols)
    from brpc_tpu import rpc
    from examples.example_echo_pb2 import EchoRequest, EchoResponse
    return rpc, EchoRequest, EchoResponse


def device_payload(rng, nbytes: int, device):
    host = rng.integers(0, 256, size=nbytes, dtype=np.uint8)
    arr = jax.device_put(host, device)
    jax.block_until_ready(arr)
    return host, arr


def attachment_to_host(att, want_devices=None):
    """Response attachment -> one host uint8 array; every device block's
    residence is checked against ``want_devices`` on the way."""
    parts = []
    for i in range(att.backing_block_num()):
        r = att.backing_block(i)
        data = r.block.data
        if hasattr(data, "devices"):
            if want_devices is not None:
                check(set(data.devices()) == set(want_devices),
                      f"attachment block resident on {data.devices()}, "
                      f"expected {want_devices}")
            host = np.asarray(data).reshape(-1).view(np.uint8)
            parts.append(host[r.offset:r.offset + r.length])
        else:
            check(want_devices is None,
                  "attachment block is host memory, expected device")
            parts.append(np.frombuffer(
                r.block.host_view(r.offset, r.length), np.uint8))
    return np.concatenate(parts) if parts else np.zeros(0, np.uint8)


def assert_clean_counters(where: str) -> None:
    """No hidden second route was taken anywhere so far."""
    from brpc_tpu.bthread.device_waiter import DeviceEventDispatcher
    from brpc_tpu.ici import device_plane as dp
    from brpc_tpu.ici import native_plane
    st = dp.plane().stats()
    check(st["fallbacks"] == 0 and st["build_failures"] == 0
          and st["match_timeouts"] == 0,
          f"{where}: device plane degraded: {st}")
    check(DeviceEventDispatcher.instance().failures() == 0,
          f"{where}: a device completion failed")
    check(native_plane._g_relocate_failures.get_value() == 0,
          f"{where}: a native relocation upcall failed")


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def phase_echo(rng, dev) -> None:
    """Unary echo over ici://0 with an HBM-resident attachment."""
    rpc, EchoRequest, EchoResponse = echo_types()
    from brpc_tpu.ici.transport import ici_transport_stats

    class EchoService(rpc.Service):
        @rpc.method(EchoRequest, EchoResponse)
        def Echo(self, cntl, request, response, done):
            response.message = request.message
            cntl.response_attachment.append(cntl.request_attachment)
            done()

    opts = rpc.ServerOptions()
    opts.usercode_inline = True     # the echo handler never blocks: it
    server = rpc.Server(opts)       # runs on the delivering thread
    server.add_service(EchoService())
    check(server.start("ici://0") == 0, "server start on ici://0")
    try:
        nb = server._native_ici
        check(nb is not None, "native ici tier did not bind on ici://0")
        ch = rpc.Channel()
        ch.init("ici://0", options=rpc.ChannelOptions(
            timeout_ms=600000, max_retry=0, ici_local_device=0))
        for nbytes, calls in ECHO_SWEEP:
            host, x = device_payload(rng, nbytes, dev)
            native_before = nb.requests()
            _, dev_bytes_before = ici_transport_stats()
            lat = []
            for i in range(calls):
                cntl = rpc.Controller()
                cntl.request_attachment.append_device_array(x)
                t0 = time.perf_counter()
                resp = ch.call_method("EchoService.Echo", cntl,
                                      EchoRequest(message=f"m{i}"),
                                      EchoResponse)
                lat.append(time.perf_counter() - t0)
                check(not cntl.failed(),
                      f"echo {fmt_bytes(nbytes)} call {i}: "
                      f"{cntl.error_text}")
                check(resp.message == f"m{i}", "echo message mismatch")
                att = cntl.response_attachment
                check(len(att) == nbytes and att.device_bytes() == nbytes,
                      f"echo {fmt_bytes(nbytes)}: response attachment is "
                      f"{len(att)}B of which {att.device_bytes()}B device")
                if i in (0, calls - 1):      # the D2H compare is the cost
                    got = attachment_to_host(att, want_devices=[dev])
                    check(np.array_equal(got, host),
                          f"echo {fmt_bytes(nbytes)} call {i}: bytes differ")
            native_calls = nb.requests() - native_before
            _, dev_bytes = ici_transport_stats()
            window = ch._native_ici.window_bytes if ch._native_ici else 0
            if nbytes + 65536 <= window:
                check(native_calls == calls,
                      f"echo {fmt_bytes(nbytes)}: {native_calls}/{calls} "
                      "calls on the native tier")
                route = "native tier"
            else:
                check(dev_bytes - dev_bytes_before >= 2 * nbytes * calls,
                      f"echo {fmt_bytes(nbytes)}: ici transport counted "
                      f"{dev_bytes - dev_bytes_before} device bytes")
                route = "python ici plane (above the native window)"
            lat.sort()
            say(f"[echo] {fmt_bytes(nbytes):>7} x{calls}: byte-exact, "
                f"resident on {dev}, route={route}, smoke timing "
                f"median {lat[len(lat) // 2] * 1e6:.0f}us "
                f"max {lat[-1] * 1e6:.0f}us")
            del x, host
    finally:
        server.stop()
    assert_clean_counters("echo")


def phase_device_handler(rng, dev) -> None:
    """A handler that USES the attachment on the chip: one jitted program
    over the payload, the reply parked on its device completion."""
    import jax.numpy as jnp
    rpc, EchoRequest, EchoResponse = echo_types()
    from brpc_tpu.bthread.device_waiter import (DeviceEventDispatcher,
                                                device_on_ready)
    nbytes, tcp_bytes = HANDLER_BYTES, TCP_INGEST_BYTES

    @jax.jit
    def transform(x):
        y = x ^ jnp.uint8(0x5A)
        return y, jnp.sum(y.astype(jnp.uint32))

    class ChipService(rpc.Service):
        @rpc.method(EchoRequest, EchoResponse)
        def Transform(self, cntl, request, response, done):
            att = cntl.request_attachment
            refs = att.device_refs()
            if refs:
                check(len(refs) == 1 and refs[0].length == len(att),
                      "expected one whole device block")
                x = refs[0].block.data
                response.message = "hbm"
            else:                       # host bytes off the wire -> HBM
                x = jax.device_put(
                    np.frombuffer(att.to_bytes(), np.uint8), dev)
                response.message = "ingest"
            y, s = transform(x)
            cntl.response_attachment.append_device_array(y)

            def reply():
                response.message += f":{int(s)}"
                done()
            device_on_ready([y, s], reply)

    def call(ch, method_att, label):
        host, fill = method_att
        cntl = rpc.Controller()
        fill(cntl.request_attachment)
        resp = ch.call_method("ChipService.Transform", cntl,
                              EchoRequest(message=label), EchoResponse)
        check(not cntl.failed(), f"{label}: {cntl.error_text}")
        want = host ^ np.uint8(0x5A)
        kind, _, checksum = resp.message.partition(":")
        check(int(checksum) == int(want.astype(np.uint64).sum()),
              f"{label}: device checksum {checksum} != numpy")
        return kind, cntl, want

    completions_before = sum(DeviceEventDispatcher.instance().stats()
                             .values())
    handoffs_before = DeviceEventDispatcher.instance().handoffs()
    ici_server = rpc.Server()
    ici_server.add_service(ChipService())
    check(ici_server.start("ici://0") == 0, "server start on ici://0")
    tcp_server = rpc.Server()
    tcp_server.add_service(ChipService())
    check(tcp_server.start("tcp://127.0.0.1:0") == 0, "tcp server start")
    try:
        ch = rpc.Channel()
        ch.init("ici://0", options=rpc.ChannelOptions(
            timeout_ms=120000, max_retry=0, ici_local_device=0))
        host, x = device_payload(rng, nbytes, dev)
        for i in range(8):
            kind, cntl, want = call(
                ch, (host, lambda a: a.append_device_array(x)), f"hbm{i}")
            check(kind == "hbm", "handler saw no device block")
            got = attachment_to_host(cntl.response_attachment,
                                     want_devices=[dev])
            check(np.array_equal(got, want), f"hbm{i}: transform differs")
        say(f"[handler] {fmt_bytes(nbytes)} HBM attachment x8: jitted "
            f"xor+sum on {dev}, answer equals numpy, reply waited on the "
            f"device poller")

        tch = rpc.Channel()
        tch.init(f"tcp://127.0.0.1:{tcp_server.listen_port}",
                 options=rpc.ChannelOptions(timeout_ms=120000, max_retry=0))
        thost = rng.integers(0, 256, size=tcp_bytes, dtype=np.uint8)
        for i in range(3):
            kind, cntl, want = call(
                tch, (thost, lambda a: a.append(thost.tobytes())),
                f"tcp{i}")
            check(kind == "ingest", "tcp handler did not ingest host bytes")
            got = np.frombuffer(cntl.response_attachment.to_bytes(),
                                np.uint8)
            check(np.array_equal(got, want), f"tcp{i}: transform differs")
        say(f"[handler] {fmt_bytes(tcp_bytes)} host attachment over "
            f"tcp://127.0.0.1 x3: host->HBM ingest, same program, answer "
            f"equals numpy")
    finally:
        ici_server.stop()
        tcp_server.stop()
    completions = sum(DeviceEventDispatcher.instance().stats().values()) \
        - completions_before
    check(completions >= 11,
          f"only {completions} completions went through the device poller")
    handoffs = DeviceEventDispatcher.instance().handoffs() - handoffs_before
    check(handoffs == 11,
          f"{handoffs} of 11 handler completions were served off the poller")
    assert_clean_counters("device handler")


def phase_streaming(rng, dev) -> None:
    """Streaming RPC over ici://0 with device chunks, echoed back."""
    rpc, EchoRequest, EchoResponse = echo_types()

    class StreamingService(rpc.Service):
        @rpc.method(EchoRequest, EchoResponse)
        def StartStream(self, cntl, request, response, done):
            class EchoBack(rpc.StreamInputHandler):
                stream = None

                def on_received_messages(self, sid, msgs):
                    for m in msgs:
                        self.stream.write(m, timeout=60)

            handler = EchoBack()
            handler.stream = rpc.stream_accept(
                cntl, rpc.StreamOptions(handler=handler,
                                        max_buf_size=64 * MB))
            response.message = "accepted"
            done()

    class Collector(rpc.StreamInputHandler):
        def __init__(self, expect):
            self.got, self.expect = [], expect
            self.done = threading.Event()

        def on_received_messages(self, sid, msgs):
            self.got.extend(msgs)
            if len(self.got) >= self.expect:
                self.done.set()

    server = rpc.Server()
    server.add_service(StreamingService())
    check(server.start("ici://0") == 0, "server start on ici://0")
    try:
        ch = rpc.Channel()
        ch.init("ici://0", options=rpc.ChannelOptions(
            timeout_ms=120000, max_retry=0, ici_local_device=0))
        for chunk, frames in STREAM_SWEEP:
            sent = [device_payload(rng, chunk, dev)
                    for _ in range(frames)]
            collector = Collector(frames)
            cntl = rpc.Controller()
            stream = rpc.stream_create(cntl, rpc.StreamOptions(
                handler=collector, max_buf_size=64 * MB))
            ch.call_method("StreamingService.StartStream", cntl,
                           EchoRequest(message="go"), EchoResponse)
            check(not cntl.failed(), f"stream open: {cntl.error_text}")
            check(stream.wait_connected(30), "stream did not connect")
            from brpc_tpu.butil.iobuf import IOBuf
            for _, arr in sent:
                buf = IOBuf()
                buf.append_device_array(arr)
                check(stream.write(buf, timeout=60) == 0, "stream write")
            check(collector.done.wait(120),
                  f"stream: {len(collector.got)}/{frames} frames back")
            for i, (host, _) in enumerate(sent):
                got = attachment_to_host(collector.got[i],
                                         want_devices=[dev])
                check(np.array_equal(got, host),
                      f"stream frame {i} ({fmt_bytes(chunk)}) differs or "
                      f"is out of order")
            stream.close()
            say(f"[stream] {frames} frames x {fmt_bytes(chunk)} device "
                f"chunks over ici://0: byte-exact, in order, resident on "
                f"{dev}")
    finally:
        server.stop()
    assert_clean_counters("streaming")


def phase_operand_fanout(rng, dev) -> None:
    """An operand fan-out on the per-member loop whose ``fanout_operand`` is
    a jax array on the chip: the mapper's rows are refs into one block, the
    merger gathers by index, ``cntl.fanout_result`` is ONE device array
    (a gather, a device add) — against numpy bit for bit (random bytes as
    float32 hold NaNs and denormals, which the TPU's own concatenate does
    not keep), with no byte on the host."""
    import functools
    import jax.numpy as jnp
    rpc, EchoRequest, EchoResponse = echo_types()
    from brpc_tpu import channels
    width, shard = FANOUT_WIDTH, FANOUT_SHARD

    @functools.partial(jax.jit, static_argnums=1)
    def xored(blocks, cuts):
        rows = [b.reshape(-1)[at:at + n] for b, (at, n) in zip(blocks, cuts)]
        return (rows[0] if len(rows) == 1 else jnp.concatenate(rows)) \
            ^ jnp.uint8(0x5A)

    class ShardService(rpc.Service):
        @rpc.method(EchoRequest, EchoResponse)
        def Xor(self, cntl, request, response, done):
            att = cntl.request_attachment
            check(len(att) and att.device_bytes() == len(att),
                  "a shard came by the host")
            refs = att.device_refs()
            cntl.response_attachment.append_device_array(xored(
                tuple(r.block.data for r in refs),
                tuple((r.offset, r.length) for r in refs)))
            response.message = request.message
            done()

    server = rpc.Server()
    server.add_service(ShardService())
    check(server.start("ici://0") == 0, "server start on ici://0")
    try:
        ch = rpc.Channel()
        ch.init("ici://0", options=rpc.ChannelOptions(
            timeout_ms=120000, max_retry=0, ici_local_device=0,
            connection_type="pooled"))
        host = rng.integers(0, 256, size=(width, shard), dtype=np.uint8)
        answers = host ^ np.uint8(0x5A)
        floats = (host[1].view(np.uint32) >> 9).astype(np.float32) / 64
        cases = (
            ("shard+concat uint8", channels.MAP_SHARD,
             channels.MERGE_CONCAT, "uint8", host, answers.reshape(-1)),
            ("shard+sum uint32", channels.MAP_SHARD, channels.MERGE_SUM,
             "uint32", host.view(np.uint32),
             answers.view(np.uint32).sum(axis=0, dtype=np.uint32)),
            ("shard+gather float32", channels.MAP_SHARD,
             channels.MERGE_GATHER, "float32", host.view(np.float32),
             answers.view(np.float32)),
            ("replicate+gather float32", channels.MAP_REPLICATE,
             channels.MERGE_GATHER, "float32", host[0].view(np.float32),
             np.stack([answers[0].view(np.float32)] * width)),
            # well-formed floats: the operand is the bytes that the xor
            # turns into ``floats``, and every member answers ``floats``
            ("replicate+sum float32", channels.MAP_REPLICATE,
             channels.MERGE_SUM, "float32",
             (floats.view(np.uint8) ^ np.uint8(0x5A)).view(np.float32),
             functools.reduce(np.add, [floats] * width)))
        # the float32 gathers carry NaNs of every payload and denormals: what
        # the TPU's concatenate of floats would change, and the words built
        # in the replies' byte tiling (channels/byte_words.py) must not
        bits = answers.view(np.uint32)
        exponent, mantissa = bits & 0x7F800000, bits & 0x7FFFFF
        payloads = np.unique(mantissa[(exponent == 0x7F800000)
                                      & (mantissa != 0)])
        denormals = np.count_nonzero((exponent == 0) & (mantissa != 0))
        check(payloads.size > 100 and denormals > 100,
              f"the float32 cases hold {payloads.size} NaN payloads and "
              f"{denormals} denormals")
        view_before = channels.fanout_view_stats()
        reduce_before = channels.fanout_reduce_stats()
        for label, mapping, merge, dtype, operand, want in cases:
            pc = channels.ParallelChannel(fail_limit=1)
            mapper = channels.ShardingCallMapper() \
                if mapping == channels.MAP_SHARD \
                else channels.ReplicateFanoutMapper()
            merger = channels.CollectiveMerger(merge=merge, dtype=dtype)
            for _ in range(width):
                pc.add_channel(ch, mapper=mapper, merger=merger)
            arr = jax.block_until_ready(jax.device_put(operand, dev))
            before = channels.fanout_stats()
            cntl = rpc.Controller()
            cntl.fanout_operand = arr
            t0 = time.monotonic()
            pc.call_method("ShardService.Xor", cntl,
                           EchoRequest(message=label), EchoResponse())
            check(not cntl.failed(), f"fan-out {label}: {cntl.error_text}")
            check(cntl.fanout_route == "rpc", f"route {cntl.fanout_route}")
            got = jax.block_until_ready(cntl.fanout_result)
            took = time.monotonic() - t0
            after = channels.fanout_stats()
            check(hasattr(got, "devices") and set(got.devices()) == {dev},
                  f"fan-out {label}: result not one array on {dev}")
            got = np.asarray(got)
            check(got.dtype == want.dtype and got.shape == want.shape,
                  f"fan-out {label}: {got.dtype}{got.shape}, numpy gives "
                  f"{want.dtype}{want.shape}")
            differ = int(np.count_nonzero(
                got.reshape(-1).view(np.uint8)
                != want.reshape(-1).view(np.uint8)))
            check(differ == 0, f"fan-out {label}: {differ} bytes of "
                               f"{want.nbytes} differ from numpy")
            att = cntl.fanout_attachment
            check(att.device_bytes() == len(att) == width * shard,
                  f"fan-out {label}: gathered refs are not device memory")
            delta = {k: after[k] - before[k] for k in after}
            check(delta["host_operand_bytes"] == 0
                  and delta["partial_results"] == 0
                  and delta["sub_calls"] == delta["merges"] == width
                  and delta["device_operand_bytes"] == width * shard,
                  f"fan-out {label}: counters {delta}")
            # a 4-byte result's words are built in the replies' own tiling
            views = channels.fanout_view_stats()
            reduced = channels.fanout_reduce_stats()
            grew = {k: views[k] - view_before[k] for k in views}
            blocks = reduced["input_blocks"] - reduce_before["input_blocks"] \
                if dtype != "uint8" else 0
            view_before, reduce_before = views, reduced
            check(blocks >= width * (dtype != "uint8") and grew == {
                "in_layout_blocks": blocks, "relayout_blocks": 0},
                f"fan-out {label}: view counters {grew}, {blocks} blocks")
            say(f"[fanout] {label}: {width} x {fmt_bytes(shard)} from a "
                f"device operand, result on {dev} == numpy, 0 host bytes, "
                f"{took * 1e3:.0f} ms (smoke timing)")
    finally:
        server.stop()
    assert_clean_counters("operand fan-out")


def phase_serving_step(ticks: int = 10) -> None:
    """ContinuousBatchScheduler with the step as ONE compiled program,
    tokens equal to the numpy step and to the model's reference decode."""
    from brpc_tpu.butil import flags as fl
    from brpc_tpu.serving import (BatchSchedulerOptions,
                                  ContinuousBatchScheduler, KvPoolOptions,
                                  PagedKvPool, StepRequest)
    from examples.disagg_serving import model as m

    prompts = {f"s{i}": ([(7 * i + j) % 997 for j in range(24 + 9 * i)],
                         5 + i) for i in range(4)}
    kv_host = {s: np.asarray(m.toy_kv_blocks(toks))
               for s, (toks, _) in prompts.items()}

    def run(compiled: bool):
        fl.set_flag("serving_compiled_step", compiled)
        pool = PagedKvPool(KvPoolOptions(
            bytes_per_token=m.KV_LAYERS * m.KV_DMODEL, num_blocks=64,
            block_tokens=8, use_timers=False))
        sched = ContinuousBatchScheduler(pool, BatchSchedulerOptions(
            vocab=m.VOCAB, max_batch=8, auto_start=False))
        out = {}
        try:
            for s, (toks, steps) in prompts.items():
                seq = len(toks)
                rows = kv_host[s].reshape(
                    m.KV_LAYERS, seq, m.KV_DMODEL).transpose(
                    1, 0, 2).reshape(seq, m.KV_LAYERS * m.KV_DMODEL)
                pool.load(s, rows, last_token=toks[-1])
                sched.submit(StepRequest(
                    s, steps,
                    lambda t, s=s: out.__setitem__(s, list(t)),
                    lambda code, text, retry, s=s: out.__setitem__(
                        s, ("failed", code, text))))
            for _ in range(ticks):
                sched.step_once()
            check(sched.describe()["compiled_step"] is compiled,
                  "scheduler did not take the requested step route")
            return out
        finally:
            sched.stop()
            pool.close()

    saved = fl.get_flag("serving_compiled_step")
    try:
        compiled, plain = run(True), run(False)
    finally:
        fl.set_flag("serving_compiled_step", saved)
    for s, (toks, steps) in prompts.items():
        ref = m.toy_decode(kv_host[s], len(toks), toks[-1], steps)
        check(compiled.get(s) == plain.get(s) == ref,
              f"serving session {s}: compiled {compiled.get(s)} numpy "
              f"{plain.get(s)} reference {ref}")
    say(f"[serving] {len(prompts)} sessions, {ticks} ticks: compiled step "
        f"tokens == numpy step == reference decode")


def run_one_chip(rng) -> None:
    dev = jax.devices()[0]
    from brpc_tpu.ici.mesh import IciMesh
    IciMesh.set_default(IciMesh([dev]))
    phase_echo(rng, dev)
    phase_device_handler(rng, dev)
    phase_streaming(rng, dev)
    phase_operand_fanout(rng, dev)
    phase_serving_step()


# ---------------------------------------------------------------------------
# four chips (--multichip): the cross-chip path and its plain references
# ---------------------------------------------------------------------------

def phase_cross_device_echo(rng, mesh) -> None:
    """Server on ici://1, caller on device 0: the request relocates 0->1
    and the response 1->0 through the device plane's compiled transfer
    program; compared with a plain jax.device_put of the same bytes."""
    rpc, EchoRequest, EchoResponse = echo_types()
    from brpc_tpu.butil import flags as fl
    from brpc_tpu.ici import device_plane as dp
    d0, d1 = mesh.device(0), mesh.device(1)
    check(d0 != d1, "ici://0 and ici://1 alias one chip")
    seen = {}

    class EchoService(rpc.Service):
        @rpc.method(EchoRequest, EchoResponse)
        def Echo(self, cntl, request, response, done):
            seen["devices"] = [set(r.block.data.devices())
                               for r in cntl.request_attachment
                               .device_refs()]
            response.message = request.message
            cntl.response_attachment.append(cntl.request_attachment)
            done()

    plane = dp.plane()
    saved_threshold = fl.get_flag("ici_device_plane_threshold")
    fl.set_flag("ici_device_plane_threshold", 4 * KB)   # 4 KB rides it too
    server = rpc.Server()
    server.add_service(EchoService())
    check(server.start("ici://1") == 0, "server start on ici://1")
    try:
        ch = rpc.Channel()
        ch.init("ici://1", options=rpc.ChannelOptions(
            timeout_ms=600000, max_retry=0, ici_local_device=0))
        for nbytes, calls in XCHIP_SWEEP:
            host, x = device_payload(rng, nbytes, d0)
            before = plane.stats()
            lat = []
            for i in range(calls):
                cntl = rpc.Controller()
                cntl.request_attachment.append_device_array(x)
                t0 = time.perf_counter()
                ch.call_method("EchoService.Echo", cntl,
                               EchoRequest(message="x"), EchoResponse)
                lat.append(time.perf_counter() - t0)
                check(not cntl.failed(),
                      f"xchip echo {fmt_bytes(nbytes)}: "
                      f"{cntl.error_text}")
                check(all(d == {d1} for d in seen["devices"])
                      and seen["devices"],
                      f"handler saw the attachment on "
                      f"{seen['devices']}, expected {d1}")
                got = attachment_to_host(cntl.response_attachment,
                                         want_devices=[d0])
                check(np.array_equal(got, host),
                      f"xchip echo {fmt_bytes(nbytes)} call {i}: "
                      f"bytes differ")
            after = plane.stats()
            moved = after["transfers"] - before["transfers"]
            check(moved >= 2 * calls,
                  f"device plane ran {moved} transfers for {calls} "
                  f"cross-chip echoes")
            # the plain reference: the same bytes by device_put
            t0 = time.perf_counter()
            ref = jax.device_put(x, d1)
            back = jax.device_put(ref, d0)
            jax.block_until_ready(back)
            ref_s = time.perf_counter() - t0
            check(set(ref.devices()) == {d1}
                  and np.array_equal(np.asarray(back), host),
                  "device_put reference differs")
            # the send window cuts a frame above it into pieces, so
            # the programs that ran are keyed by the PIECE sizes
            sizes = sorted({k[1] for k in plane._programs})
            lat.sort()
            say(f"[xchip-echo] {fmt_bytes(nbytes):>6} "
                f"x{calls}: 0->1->0 byte-exact, handler on {d1}, "
                f"reply on {d0}, {moved} device-plane transfers "
                f"(program sizes so far: "
                f"{[fmt_bytes(b) for b in sizes]}); smoke timing rpc "
                f"median {lat[len(lat) // 2] * 1e3:.2f}ms vs "
                f"device_put round trip {ref_s * 1e3:.2f}ms")
            # the whole payload as ONE posted work request: the
            # transfer program at the attachment's full size
            t0 = time.perf_counter()
            t = plane.transfer_local(x, 0, 1)
            check(t.wait(120) == 0, f"whole-payload transfer: {t.error}")
            whole_s = time.perf_counter() - t0
            check(set(t.out.devices()) == {d1}
                  and np.array_equal(np.asarray(t.out), host),
                  "whole-payload transfer differs")
            ma = plane._program(nbytes, nbytes, 0, 1)[0].memory_analysis()
            say(f"[xchip-plane] {fmt_bytes(nbytes):>6} "
                f"as one work request 0->1: byte-exact on {d1}; "
                f"program argument={fmt_bytes(ma.argument_size_in_bytes)} "
                f"output={fmt_bytes(ma.output_size_in_bytes)} "
                f"temp={fmt_bytes(ma.temp_size_in_bytes)} per chip "
                f"(argument/payload = "
                f"{ma.argument_size_in_bytes / nbytes:g}x); "
                f"smoke timing of the post {whole_s * 1e3:.2f}ms "
                f"(compile inside)")
            del t
            del x, host
    finally:
        fl.set_flag("ici_device_plane_threshold", saved_threshold)
        server.stop()
    st = plane.stats()
    check(st["transfers"] > 0 and st["fallbacks"] == 0
          and st["build_failures"] == 0 and st["match_timeouts"] == 0,
          f"device plane counters: {st}")
    say(f"[xchip-echo] device plane counters: {st}")
    assert_clean_counters("cross-device echo")


def sharded_small_ints(mesh, per_chip_bytes: int, seed: int):
    """(n, rows, 2048) f32, one row block per chip, made ON the chips.
    Small integers: every partial sum is exact in f32, so a reduction is
    compared by equality whatever its order."""
    import jax.numpy as jnp
    n, cols = mesh.size, 2048
    rows = per_chip_bytes // 4 // cols
    make = jax.jit(
        lambda: jax.random.randint(jax.random.key(seed), (n, rows, cols),
                                   -8, 8).astype(jnp.float32),
        out_shardings=mesh.shard_along_axis())
    x = make()
    jax.block_until_ready(x)
    check(len({s.device for s in x.addressable_shards}) == n,
          "operand shards do not sit on distinct chips")
    return x


def phase_all_reduce(mesh) -> None:
    """Collectives.all_reduce on 1 GiB total against jnp.sum of the same
    data (and a strip of it against numpy on the host)."""
    import jax.numpy as jnp
    from brpc_tpu.ici.collective import Collectives
    n = mesh.size
    x = sharded_small_ints(mesh, ALLREDUCE_BYTES_PER_CHIP, 1)
    t0 = time.perf_counter()
    got = Collectives(mesh).all_reduce(x)
    jax.block_until_ready(got)
    first_s = time.perf_counter() - t0
    want = jax.jit(lambda a: jnp.sum(a, axis=0))(x)
    check(bool(jnp.array_equal(got, want)),
          "Collectives.all_reduce differs from jnp.sum")
    check(np.array_equal(np.asarray(got[:4]),
                         np.asarray(x[:, :4]).sum(axis=0)),
          "Collectives.all_reduce differs from numpy on the host")
    check(len(got.sharding.device_set) == n,
          "all_reduce result is not on every chip")
    say(f"[collective] all_reduce {fmt_bytes(x.nbytes)} total "
        f"({fmt_bytes(x.nbytes // n)} f32 per chip) over {n} chips == "
        f"jnp.sum == numpy; smoke timing first call {first_s:.2f}s "
        f"(compile inside)")


def phase_pallas_ring(mesh) -> None:
    """The Pallas ring kernels, compiled by Mosaic (interpret off),
    against jnp.sum and the operand itself."""
    import jax.numpy as jnp
    from brpc_tpu.ici import pallas_ring
    n = mesh.size
    x = sharded_small_ints(mesh, RING_BYTES_PER_CHIP, 2)
    want_sum = jax.jit(lambda a: jnp.sum(a, axis=0))(x)
    got = pallas_ring.ring_all_reduce(x, mesh, interpret=False)
    jax.block_until_ready(got)
    for d in range(n):
        check(bool(jnp.array_equal(got[d], want_sum)),
              f"pallas ring_all_reduce row {d} differs from jnp.sum")
    say(f"[pallas-ring] ring_all_reduce {fmt_bytes(x.nbytes // n)} per "
        f"chip over {n} chips, interpret=False: every row == jnp.sum")
    gathered = pallas_ring.ring_all_gather(x, mesh, interpret=False)
    jax.block_until_ready(gathered)
    for d in range(n):
        check(bool(jnp.array_equal(gathered[d], x)),
              f"pallas ring_all_gather row {d} differs from the operand")
    say(f"[pallas-ring] ring_all_gather {fmt_bytes(x.nbytes // n)} per "
        f"chip over {n} chips, interpret=False: every row == the operand")


def phase_fanout(mesh) -> None:
    """A ParallelChannel over four members answered by ONE lowered
    program, against the same call through the per-sub-channel RPC loop."""
    rpc, EchoRequest, EchoResponse = echo_types()
    from brpc_tpu import channels
    from brpc_tpu.butil import flags as fl
    from brpc_tpu.ici import route as iroute
    shard = 4096
    n = mesh.size

    class FanSvc(rpc.Service):
        SERVICE_NAME = "Fan"

        @rpc.method(EchoRequest, EchoResponse)
        def Scale(self, cntl, request, response, done):
            x = np.frombuffer(cntl.request_attachment.to_bytes(),
                              np.float32)
            cntl.response_attachment.append(
                (x * 2.0).astype(np.float32).tobytes())
            done()

    servers = []
    try:
        for d in range(n):
            s = rpc.Server()
            s.add_service(FanSvc())
            s.register_collective("Fan.Scale", lambda x: x * 2.0,
                                  merge=channels.MERGE_GATHER,
                                  mapping=channels.MAP_SHARD)
            check(s.start(f"ici://{d}") == 0, f"server start ici://{d}")
            servers.append(s)
        pc = channels.ParallelChannel()
        mapper = channels.ShardingCallMapper()
        merger = channels.CollectiveMerger(
            merge=channels.MERGE_GATHER, dtype="float32",
            shard_shape=(shard,))
        for d in range(n):
            ch = rpc.Channel()
            ch.init(f"ici://{d}")
            pc.add_channel(ch, mapper=mapper, merger=merger)
        op = np.arange(n * shard, dtype=np.float32).reshape(n, shard)

        def call():
            cntl = rpc.Controller()
            cntl.fanout_operand = op
            pc.call_method("Fan.Scale", cntl, EchoRequest(message="x"),
                           EchoResponse())
            check(not cntl.failed(), f"fan-out: {cntl.error_text}")
            return cntl

        before = dict(iroute.collective_stats())
        lowered = call()
        after = dict(iroute.collective_stats())
        check(lowered.fanout_route == "collective",
              f"fan-out took route {lowered.fanout_route!r}; counters "
              f"{after}")
        fl.set_flag("ici_fanout_collective", False)
        try:
            looped = call()
        finally:
            fl.set_flag("ici_fanout_collective", True)
        check(looped.fanout_route == "rpc", "per-member loop not taken")
        a = np.asarray(lowered.fanout_result)
        b = np.asarray(looped.fanout_result)
        check(a.shape == b.shape == (n, shard) and np.array_equal(a, b)
              and np.array_equal(a, op * 2.0),
              "lowered fan-out and per-member loop disagree")
        moved = {k: after[k] - before.get(k, 0) for k in after
                 if after[k] != before.get(k, 0)}
        say(f"[fanout] ParallelChannel over {n} members: ONE lowered "
            f"program == per-sub-channel RPC loop == x*2; route counters "
            f"moved: {moved}")
    finally:
        for s in servers:
            s.stop()


def run_multichip(rng) -> None:
    from brpc_tpu.ici.mesh import IciMesh
    devs = jax.devices()
    check(len(devs) >= 4, f"--multichip needs four chips, found {len(devs)}")
    mesh = IciMesh(devs[:4])
    IciMesh.set_default(mesh)
    check(len({mesh.device(k) for k in range(4)}) == 4,
          "ici://0..3 do not name four distinct chips")
    say(f"[mesh] {[str(d) for d in mesh.devices]}")
    # XLA-scheduled programs first, the hand-scheduled Pallas kernels
    # last: what the compiler schedules is on record before a kernel of
    # ours gets the chance to wedge a DMA
    phase_cross_device_echo(rng, mesh)
    phase_all_reduce(mesh)
    phase_fanout(mesh)
    phase_pallas_ring(mesh)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="four chips: run ONLY the cross-chip path and "
                         "its plain references")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every payload (default 0)")
    args = ap.parse_args(argv)

    t_start = time.monotonic()
    # the whole run, compilation included, stays inside 1200 s: a phase
    # that wedges ends the process (exit 124, no result line)
    watchdog = threading.Timer(WATCHDOG_S, _watchdog_fired)
    watchdog.daemon = True
    watchdog.start()

    from brpc_tpu.butil import compile_cache
    cache_dir = compile_cache.enable()
    meter = CompileMeter()
    devs = jax.devices()
    dev0 = devs[0]
    if dev0.platform != "tpu":
        print(f"chip_smoke: jax found no TPU (platform={dev0.platform!r}); "
              f"this script only passes on the chip", file=sys.stderr)
        return 2
    say(f"[device] platform={dev0.platform} kind={dev0.device_kind} "
        f"count={len(devs)} jax={jax.__version__}")
    build_native_core()
    rng = np.random.default_rng(args.seed)
    if args.multichip:
        run_multichip(rng)
    else:
        run_one_chip(rng)
    say(meter.line(cache_dir))
    say(f"[done] all phases passed in {time.monotonic() - t_start:.0f}s "
        f"(smoke timing)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev0.platform, "kind": dev0.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except SystemExit as e:             # argparse: --help, bad option
        rc = e.code if isinstance(e.code, int) else 1
    except BaseException:               # the boundary: report, then leave
        import traceback
        traceback.print_exc()
        rc = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)      # daemon pollers/servers never hold the exit hostage
