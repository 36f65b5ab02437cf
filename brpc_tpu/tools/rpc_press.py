"""rpc_press: protocol-generic load generator.

Reference: tools/rpc_press — fires requests at a target qps (or max), from a
JSON request body, reporting qps/latency through bvar.  Usage:

    python -m brpc_tpu.tools.rpc_press --server mem://echo \
        --method EchoService.Echo --request '{"message":"x"}' \
        --qps 1000 --duration 5 [--proto tests/echo_pb2:EchoRequest,EchoResponse]

``--server`` also accepts a comma-separated endpoint list
(``mem://a,mem://b`` / ``ici://0,ici://2``) or a naming url
(``mesh://``, ``pod://name``, ``list://...``): one channel per resolved
endpoint, workers spread round-robin, and the summary — including the
graceful-SIGINT one — reports per-endpoint sent/error/qps counts, so a
pod or overload run can drive N servers from one process and see which
member misbehaved.

Mixed-class load (the admission-control adversary): ``--priority`` takes
a single band (``--priority 2``) or a ``band:weight,...`` mix
(``--priority 0:1,3:3`` = one critical per three sheddable); ``--tenant``
takes a name or a ``tenant:weight,...`` mix (``--tenant a:2,b:1``).
Each request draws its (priority, tenant) from the weighted mixes, and
the summary adds per-class sent/shed(ELIMIT)/error/latency so an
overloaded server's shed fairness is visible from the load generator.
"""
from __future__ import annotations

import argparse
import importlib
import json
import signal
import sys
import threading
import time
from typing import List, Optional


def _load_classes(spec: str):
    mod_name, _, names = spec.partition(":")
    req_name, _, resp_name = names.partition(",")
    mod = importlib.import_module(mod_name.replace("/", ".").rstrip(".py"))
    return getattr(mod, req_name), getattr(mod, resp_name)


def parse_weighted_mix(spec: str, *, int_keys: bool = False) -> list:
    """``"a:2,b:1"`` → [("a", 2), ("b", 1)]; a bare ``"a"`` is weight 1.
    With ``int_keys`` the keys are parsed as ints (priority bands).
    Returns an expanded selection wheel: each class repeated weight
    times, so ``wheel[i % len(wheel)]`` draws the mix deterministically."""
    wheel = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, w = part.partition(":")
        try:
            weight = int(w) if w else 1
        except ValueError:
            raise SystemExit(f"rpc_press: bad weight in {part!r}")
        if weight < 1:
            raise SystemExit(f"rpc_press: weight must be >= 1 in {part!r}")
        key = name.strip()
        if int_keys:
            try:
                key = int(key)
            except ValueError:
                raise SystemExit(f"rpc_press: bad priority in {part!r}")
        wheel.extend([key] * weight)
    return wheel


def resolve_targets(server: str) -> List[str]:
    """One endpoint url per target channel — the shared
    policy.naming.resolve_servers (naming url / comma list / single
    endpoint), with empty resolution as the CLI's hard exit."""
    from ..policy.naming import resolve_servers
    try:
        return resolve_servers(server)
    except ValueError as e:
        raise SystemExit(f"rpc_press: {e}")


BULK_PLANES = ("auto", "shm", "uds", "inline")


def apply_bulk_plane(mode: str) -> None:
    """Pin the fabric bulk tier for this process: "auto" keeps the route
    table's preference (shm > uds/tcp > inline), "shm" force-enables the
    shm flag (it already outranks the rest; whether a ring actually
    bound is visible in the summary's per-route counters — the /dev/shm
    capability probe cannot be forced), "uds" disables the shm ring so
    payloads take the socket conn, "inline" disables both descriptor
    planes so everything rides the control channel."""
    if mode not in BULK_PLANES:
        raise SystemExit(f"rpc_press: unknown --bulk-plane {mode!r} "
                         f"(choose from {', '.join(BULK_PLANES)})")
    if mode == "auto":
        return
    import brpc_tpu.ici.fabric  # noqa: F401 — defines the ici_fabric_* flags
    from brpc_tpu.butil import flags as _fl
    if mode == "shm":
        _fl.set_flag("ici_fabric_shm", True)
    elif mode == "uds":
        _fl.set_flag("ici_fabric_shm", False)
    elif mode == "inline":
        _fl.set_flag("ici_fabric_shm", False)
        _fl.set_flag("ici_fabric_bulk", False)


USERCODE_POOLS = ("auto", "pthread", "subinterp", "off")


def apply_usercode_pool(mode: str) -> None:
    """Pin the usercode-pool backend for servers hosted IN THIS process
    (mem:// targets, self-hosted ici:// members): "auto" keeps each
    server's configured resolution, "pthread"/"subinterp" override the
    default backend before those servers start, "off" just records the
    pin (a load generator cannot un-pool a remote server).  The summary
    reports the probed isolation capability either way, plus per-server
    pool stats for every in-process server that carries a pool."""
    if mode not in USERCODE_POOLS:
        raise SystemExit(f"rpc_press: unknown --usercode-pool {mode!r} "
                         f"(choose from {', '.join(USERCODE_POOLS)})")
    if mode in ("pthread", "subinterp"):
        from brpc_tpu.rpc import usercode_pool as _up
        try:
            _up.set_default_kind(mode)
        except ValueError as e:
            raise SystemExit(f"rpc_press: {e}")


def collect_usercode_pool_stats() -> dict:
    """The summary's pool block: the process isolation capability
    (probe record incl. the no-scaling reason) + describe() of every
    in-process server's pool (loopback registry + native ici
    bindings)."""
    from brpc_tpu.rpc.usercode_pool import probe_isolation
    out: dict = {"isolation": probe_isolation()._asdict(), "servers": {}}
    seen = set()
    try:
        from brpc_tpu.rpc import loopback
        with loopback._servers_lock:
            servers = list(loopback._servers.items())
        for name, srv in servers:
            pool = getattr(srv, "usercode_pool", None)
            if pool is not None and hasattr(pool, "describe") \
                    and id(srv) not in seen:
                seen.add(id(srv))
                out["servers"][f"mem://{name}"] = pool.describe()
    except Exception:
        pass
    try:
        from brpc_tpu.ici import native_plane
        with native_plane._server_bindings_lock:
            bindings = list(native_plane._server_bindings.items())
        for dev, b in bindings:
            pool = getattr(b._server, "usercode_pool", None)
            if pool is not None and hasattr(pool, "describe") \
                    and id(b._server) not in seen:
                seen.add(id(b._server))
                out["servers"][f"ici://{dev}"] = pool.describe()
    except Exception:
        pass
    return out


def run_press_fanout(server: str, method: str, n: int,
                     duration: float = 5.0, concurrency: int = 2,
                     shard_bytes: int = 512, out=sys.stderr) -> dict:
    """``--fanout N``: drive ONE ParallelChannel over the first N
    resolved members (pod://name, mesh://, a comma list) with a
    sharded operand per call — the compiled collective route where the
    members registered a device handler, the per-member RPC loop where
    they did not (or the route degraded).  The summary reports fan-out
    p50/p99 plus PER-ROUTE call counts and the route-table event
    counters, so a degraded pod is visible from the load generator."""
    import numpy as np

    import brpc_tpu.policy  # noqa: F401
    from brpc_tpu import rpc, bvar, channels
    targets = resolve_targets(server)
    if len(targets) < n:
        raise SystemExit(f"rpc_press: --fanout {n} needs {n} members, "
                         f"resolved {len(targets)}")
    targets = targets[:n]
    pc = channels.ParallelChannel()
    mapper = channels.ShardingCallMapper()
    merger = channels.CollectiveMerger(merge=channels.MERGE_GATHER,
                                       dtype="uint8",
                                       shard_shape=(shard_bytes,))
    for t in targets:
        ch = rpc.Channel()
        ch.init(t, options=rpc.ChannelOptions(timeout_ms=10000))
        pc.add_channel(ch, mapper=mapper, merger=merger)
    op = np.arange(n * shard_bytes, dtype=np.uint8).reshape(n,
                                                            shard_bytes)
    recorder = bvar.LatencyRecorder()
    sent = [0]
    errors_count = [0]
    routes: dict = {}
    lock = threading.Lock()
    deadline = time.monotonic() + duration
    stop_evt = threading.Event()
    prev_sigint = None
    try:
        prev_sigint = signal.signal(signal.SIGINT,
                                    lambda *_: stop_evt.set())
    except ValueError:
        pass

    def worker():
        while not stop_evt.is_set() and time.monotonic() < deadline:
            cntl = rpc.Controller()
            cntl.fanout_operand = op
            t0 = time.perf_counter_ns()
            pc.call_method(method, cntl, b"", None)
            lat_us = (time.perf_counter_ns() - t0) // 1000
            route = cntl.fanout_route or "none"
            with lock:
                sent[0] += 1
                routes[route] = routes.get(route, 0) + 1
                if cntl.failed():
                    errors_count[0] += 1
                else:
                    recorder << lat_us

    threads = [threading.Thread(target=worker)
               for _ in range(max(concurrency, 1))]
    t_start = time.monotonic()
    for t in threads: t.start()
    for t in threads: t.join()
    elapsed = time.monotonic() - t_start
    if prev_sigint is not None:
        try:
            signal.signal(signal.SIGINT, prev_sigint)
        except ValueError:
            pass
    from brpc_tpu.bvar import SamplerCollector
    SamplerCollector.instance().sample_once()
    result = {
        "fanout": n,
        "members": targets,
        "sent": sent[0],
        "errors": errors_count[0],
        "qps": round(sent[0] / elapsed, 1) if elapsed else 0.0,
        "fanout_p50_us": recorder.latency_percentile(0.5),
        "fanout_p99_us": recorder.latency_percentile(0.99),
        "avg_latency_us": round(recorder.latency(), 1),
        "per_route": routes,
        "interrupted": stop_evt.is_set(),
    }
    try:
        from brpc_tpu.ici.route import collective_stats
        cs = collective_stats()
        if cs:
            result["route_counters"] = cs
    except Exception:
        pass
    print(json.dumps(result), file=out)
    return result


def collect_serving_stats() -> dict:
    """The serving summary block: describe_serving() of every serving
    service hosted IN THIS process (loopback registry + native ici
    bindings) — pool occupancy, step rate, batch occupancy, router
    weights.  Remote-only runs report an empty dict (the /status page
    on the server carries the same block)."""
    out: dict = {}
    seen = set()

    def scan(server, label):
        if id(server) in seen:
            return
        seen.add(id(server))
        for name, svc in server.services().items():
            fn = getattr(svc, "describe_serving", None)
            if callable(fn):
                try:
                    out[f"{label}/{name}"] = fn()
                except Exception:
                    pass
    try:
        from brpc_tpu.rpc import loopback
        with loopback._servers_lock:
            servers = list(loopback._servers.items())
        for name, srv in servers:
            scan(srv, f"mem://{name}")
    except Exception:
        pass
    try:
        from brpc_tpu.ici import native_plane
        with native_plane._server_bindings_lock:
            bindings = list(native_plane._server_bindings.items())
        for dev, b in bindings:
            scan(b._server, f"ici://{dev}")
    except Exception:
        pass
    return out


def run_press_serving(server: str, duration: float = 5.0,
                      arrival_rps: float = 20.0, batch_ratio: int = 3,
                      seq_range: str = "32-96", steps_range: str = "8-64",
                      max_sessions_inflight: int = 64, verify: bool = False,
                      out=sys.stderr) -> dict:
    """``--serving``: OPEN-LOOP session generator against a serving
    router (``Router.Generate``).  Sessions arrive at a fixed rate
    regardless of completions (the arrival clock never waits for the
    server — the load shape a shedding admission layer must absorb),
    drawn from a mixed population: 1 INTERACTIVE session (priority 0,
    tenant "inter", short decode) per ``batch_ratio`` BATCH sessions
    (priority 3, tenant "bulk", long decode).  The summary reports
    per-tenant session counts, shed/failure split, per-session
    tokens/s p50/p99, end-to-end latency, and the serving /status
    block (pool occupancy, step rate, batch occupancy) for every
    in-process serving server, plus each in-process pool's
    ``kv_prefix`` CoW block (shared_blocks / prefix_hits /
    sharing_ratio, ISSUE 16) and ``kv_tiers`` tiered-memory block
    (spilled sessions, demote/restore round trips, the spill plane
    row, and the process-wide migration ledger, ISSUE 19)."""
    import concurrent.futures
    import json as _json

    import brpc_tpu.policy  # noqa: F401
    from brpc_tpu import rpc
    from brpc_tpu.rpc import errors as rpc_errors
    lo_seq, _, hi_seq = seq_range.partition("-")
    lo_steps, _, hi_steps = steps_range.partition("-")
    lo_seq, hi_seq = int(lo_seq), int(hi_seq or lo_seq)
    lo_steps, hi_steps = int(lo_steps), int(hi_steps or lo_steps)
    targets = resolve_targets(server)
    channels = []
    for t in targets:
        ch = rpc.Channel()
        ch.init(t, options=rpc.ChannelOptions(timeout_ms=30000,
                                              max_retry=0))
        channels.append(ch)
    try:
        from examples.example_echo_pb2 import EchoRequest, EchoResponse
    except ImportError:
        import os as _os
        sys.path.insert(0, _os.getcwd())
        from examples.example_echo_pb2 import EchoRequest, EchoResponse

    # plain lists, not bvar percentiles: per-session tokens/s can be
    # a small number (long batch decodes) and the latency-percentile
    # buckets would quantize it to 0
    classes = {
        "inter": {"sessions": 0, "ok": 0, "shed": 0, "fail": 0,
                  "tokens": 0, "lat": [], "tps": []},
        "bulk": {"sessions": 0, "ok": 0, "shed": 0, "fail": 0,
                 "tokens": 0, "lat": [], "tps": []},
    }
    lock = threading.Lock()
    mismatches = [0]
    stop_evt = threading.Event()
    prev_sigint = None
    try:
        prev_sigint = signal.signal(signal.SIGINT,
                                    lambda *_: stop_evt.set())
    except ValueError:
        pass

    def one_session(i: int) -> None:
        is_batch = (i % (batch_ratio + 1)) != 0
        tenant = "bulk" if is_batch else "inter"
        # deterministic per-index draws (no RNG: replayable load)
        seq = lo_seq + (i * 13) % max(hi_seq - lo_seq + 1, 1)
        steps = (hi_steps if is_batch
                 else lo_steps + (i * 7) % max(
                     min(hi_steps // 2, hi_steps) - lo_steps + 1, 1))
        tokens = [(i * 31 + j) % 997 for j in range(seq)]
        cntl = rpc.Controller()
        cntl.priority = 3 if is_batch else 0
        cntl.tenant = tenant
        t0 = time.perf_counter_ns()
        resp = channels[i % len(channels)].call_method(
            "Router.Generate", cntl,
            EchoRequest(message=_json.dumps(
                {"tokens": tokens, "steps": steps})), EchoResponse)
        lat_us = (time.perf_counter_ns() - t0) // 1000
        got = None
        if not cntl.failed():
            got = _json.loads(resp.message)["tokens"]
            if verify:
                from examples.disagg_serving.model import \
                    reference_generate
                if got != reference_generate(tokens, steps):
                    with lock:
                        mismatches[0] += 1
        with lock:
            c = classes[tenant]
            c["sessions"] += 1
            if cntl.failed():
                if cntl.error_code_ in (rpc_errors.ELIMIT,
                                        rpc_errors.ELOGOFF):
                    c["shed"] += 1
                else:
                    c["fail"] += 1
            else:
                c["ok"] += 1
                c["tokens"] += len(got)
                c["lat"].append(lat_us)
                if lat_us > 0:
                    c["tps"].append(len(got) * 1e6 / lat_us)

    pool = concurrent.futures.ThreadPoolExecutor(
        max_workers=max_sessions_inflight)
    interval = 1.0 / max(arrival_rps, 0.1)
    t_start = time.monotonic()
    deadline = t_start + duration
    next_fire = t_start
    i = 0
    issued = 0
    while not stop_evt.is_set():
        now = time.monotonic()
        if now >= deadline:
            break
        if now < next_fire:
            time.sleep(min(next_fire - now, 0.01))
            continue
        # OPEN loop: the arrival clock advances whether or not the
        # previous sessions completed
        next_fire += interval
        pool.submit(one_session, i)
        issued += 1
        i += 1
    pool.shutdown(wait=True)
    elapsed = time.monotonic() - t_start
    if prev_sigint is not None:
        try:
            signal.signal(signal.SIGINT, prev_sigint)
        except ValueError:
            pass
    def pct(vals, q):
        if not vals:
            return -1.0
        vals = sorted(vals)
        return round(vals[min(int(len(vals) * q), len(vals) - 1)], 1)

    total_tokens = sum(c["tokens"] for c in classes.values())
    result = {
        "serving": True,
        "targets": targets,
        "arrival_rps": arrival_rps,
        "issued": issued,
        "elapsed_s": round(elapsed, 2),
        "tokens_per_s": round(total_tokens / elapsed, 1) if elapsed
        else 0.0,
        "verify": verify,
        "mismatches": mismatches[0],
        "interrupted": stop_evt.is_set(),
        "per_tenant": {
            name: {
                "sessions": c["sessions"], "ok": c["ok"],
                "shed": c["shed"], "failures": c["fail"],
                "tokens": c["tokens"],
                "latency_p50_us": pct(c["lat"], 0.5),
                "latency_p99_us": pct(c["lat"], 0.99),
                "session_tokens_per_s_p50": pct(c["tps"], 0.5),
                "session_tokens_per_s_p99": pct(c["tps"], 0.99),
            } for name, c in classes.items()},
    }
    stats = collect_serving_stats()
    if stats:
        result["serving_status"] = stats
        # kv-load route counts (ISSUE 15): which path carried the
        # sessions' KV bytes into the pool — adopted (host claims in
        # place) / scattered (device segs / parked native handles) /
        # materialized (the PR-14 fallback) — plus the host-copy-passes
        # byte counter.  Gated like serving_status: the counters are
        # process-global, so a remote-only press run would otherwise
        # report its own all-zero locals as the server's route truth.
        try:
            from brpc_tpu.serving import kv_load_stats
            result["kv_load_routes"] = kv_load_stats()
        except Exception:
            pass
        # prefix-sharing truth (ISSUE 16): each in-process pool's CoW
        # block — shared_blocks / prefix_hits / cow_splits / the
        # physical-vs-logical sharing_ratio / fill-route counters —
        # lifted out of the per-service describe_serving() blocks so a
        # press run can assert capacity claims without scraping
        # /status.  Same in-process gate as serving_status: remote-only
        # runs omit it instead of reporting local zeros.
        prefix = {
            label: blk["pool"]["prefix"]
            for label, blk in stats.items()
            if isinstance(blk.get("pool"), dict)
            and "prefix" in blk["pool"]}
        if prefix:
            result["kv_prefix"] = prefix
        # tiered-memory truth (ISSUE 19): each in-process pool's
        # host-tier block — resident vs spilled sessions, demote /
        # restore round trips with restore_p50_us, the spill
        # plane-health row, and the process-wide migration ledger
        # (migrations in/out, cutovers, aborts, bytes_moved).  Same
        # in-process gate: remote-only runs omit it.
        tiers = {
            label: blk["pool"]["tiers"]
            for label, blk in stats.items()
            if isinstance(blk.get("pool"), dict)
            and "tiers" in blk["pool"]}
        if tiers:
            result["kv_tiers"] = tiers
    print(json.dumps(result), file=out)
    for ch in channels:
        ch.close()
    return result


def apply_shm_stripes(n: int) -> None:
    """``--shm-stripes N``: force the striped shm plane (ISSUE 12) —
    N SPSC ring pairs per segment, round-robin for unary frames,
    stream-id affinity for streams.  0 keeps auto (1 on 1-core hosts).
    Whether stripes actually carried bytes is visible in the summary's
    ``rpc_fabric_route_shm_stripe_*`` counters — asserted, not
    assumed."""
    if n <= 0:
        return
    import brpc_tpu.ici.fabric  # noqa: F401 — defines ici_shm_stripes
    from brpc_tpu.butil import flags as _fl
    _fl.set_flag("ici_shm_stripes", n)


def run_press(server: str, method: str, request_json: str,
              qps: int = 0, duration: float = 5.0, concurrency: int = 8,
              proto: Optional[str] = None, protocol: str = "tpu_std",
              priority: Optional[str] = None, tenant: Optional[str] = None,
              max_retry: Optional[int] = None,
              bulk_plane: str = "auto", shm_stripes: int = 0,
              usercode_pool: str = "auto",
              out=sys.stderr) -> dict:
    import brpc_tpu.policy  # noqa: F401 — registers protocols
    from brpc_tpu import rpc, bvar
    from brpc_tpu.codec import json2pb
    from brpc_tpu.rpc import errors as rpc_errors
    apply_bulk_plane(bulk_plane)
    apply_shm_stripes(shm_stripes)
    apply_usercode_pool(usercode_pool)

    if proto:
        req_cls, resp_cls = _load_classes(proto)
        request = json2pb.dict_to_pb(json.loads(request_json or "{}"), req_cls)
    else:
        req_cls = resp_cls = None
        request = (request_json or "").encode()

    pri_wheel = parse_weighted_mix(priority, int_keys=True) \
        if priority else []
    tenant_wheel = parse_weighted_mix(tenant) if tenant else []
    # a stride coprime with the tenant wheel decorrelates it from the
    # priority wheel (equal lengths would pin each band to one tenant)
    ten_stride = 1
    if tenant_wheel:
        ten_stride = next(s for s in (7, 11, 13, 17, 19, 23, 1)
                          if len(tenant_wheel) % s != 0 or s == 1)
    targets = resolve_targets(server)
    channels = []
    for t in targets:
        copts = rpc.ChannelOptions(protocol=protocol, timeout_ms=10000)
        if max_retry is not None:
            copts.max_retry = max_retry
        ch = rpc.Channel()
        ch.init(t, options=copts)
        channels.append(ch)
    recorder = bvar.LatencyRecorder()
    errors_count = [0]
    sent = [0]
    per_ep = {t: {"sent": 0, "errors": 0} for t in targets}
    # per (priority, tenant) class: sent / shed (ELIMIT) / errors /
    # latency recorder — an overload run's fairness view
    per_class: dict = {}
    lock = threading.Lock()
    deadline = time.monotonic() + duration
    interval = concurrency / qps if qps > 0 else 0.0
    # graceful SIGINT (reference tools/rpc_press): ^C stops ISSUING, the
    # in-flight calls run to completion, and the final latency/QPS
    # summary still prints — instead of a KeyboardInterrupt mid-run that
    # loses the whole measurement.  Installable only from the main
    # thread; elsewhere the default (hard) behavior is kept.
    stop_evt = threading.Event()
    prev_sigint = None
    try:
        prev_sigint = signal.signal(signal.SIGINT,
                                    lambda *_: stop_evt.set())
    except ValueError:
        pass

    def worker(wid: int):
        next_fire = time.monotonic()
        i = 0
        while not stop_evt.is_set() and time.monotonic() < deadline:
            if interval:
                now = time.monotonic()
                if now < next_fire:
                    time.sleep(min(next_fire - now, 0.05))
                    continue
                next_fire += interval
            # workers spread across the endpoint list round-robin, each
            # starting at its own offset so N workers cover N endpoints
            # even with concurrency == len(targets)
            idx = (wid + i) % len(targets)
            cntl = rpc.Controller()
            pri = pri_wheel[(wid + i) % len(pri_wheel)] if pri_wheel \
                else None
            ten = tenant_wheel[(wid + ten_stride * i) % len(tenant_wheel)] \
                if tenant_wheel else ""
            if pri is not None:
                cntl.priority = pri
            if ten:
                cntl.tenant = ten
            i += 1
            t0 = time.perf_counter_ns()
            channels[idx].call_method(method, cntl, request, resp_cls)
            lat_us = (time.perf_counter_ns() - t0) // 1000
            shed = (cntl.error_code_ == rpc_errors.ELIMIT
                    and cntl.retry_after_ms > 0)
            with lock:
                sent[0] += 1
                per_ep[targets[idx]]["sent"] += 1
                if pri_wheel or tenant_wheel:
                    ckey = f"p{pri if pri is not None else '-'}" + \
                        (f"/{ten}" if ten else "")
                    cls = per_class.get(ckey)
                    if cls is None:
                        cls = per_class[ckey] = {
                            "sent": 0, "shed": 0, "errors": 0,
                            "rec": bvar.LatencyRecorder()}
                    cls["sent"] += 1
                    if shed:
                        cls["shed"] += 1
                    elif cntl.failed():
                        cls["errors"] += 1
                    else:
                        cls["rec"] << lat_us
                if cntl.failed():
                    errors_count[0] += 1
                    per_ep[targets[idx]]["errors"] += 1
                else:
                    recorder << lat_us
    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(concurrency)]
    t_start = time.monotonic()
    for t in threads: t.start()
    for t in threads: t.join()      # interrupted workers drain in-flight
    elapsed = time.monotonic() - t_start
    if prev_sigint is not None:
        try:
            signal.signal(signal.SIGINT, prev_sigint)
        except ValueError:
            pass
    from brpc_tpu.bvar import SamplerCollector
    SamplerCollector.instance().sample_once()
    result = {
        "sent": sent[0],
        "errors": errors_count[0],
        "qps": round(sent[0] / elapsed, 1),
        "avg_latency_us": round(recorder.latency(), 1),
        "max_latency_us": recorder.max_latency(),
        "p99_latency_us": recorder.latency_percentile(0.99),
        "elapsed_s": round(elapsed, 2),
        "interrupted": stop_evt.is_set(),
        "bulk_plane": bulk_plane,
        "shm_stripes": shm_stripes,
        "usercode_pool": usercode_pool,
    }
    # isolation capability + per-in-process-server pool stats (ROADMAP
    # 4c): a SKIPping host records WHY it cannot scale
    try:
        result["usercode_pool_stats"] = collect_usercode_pool_stats()
    except Exception:
        pass
    # which byte mover actually carried the run's payloads (ici/route.py
    # counters; empty off the fabric) — the "chosen route" in the summary
    try:
        from brpc_tpu.ici.route import route_stats
        rs = route_stats()
        if rs:
            result["routes"] = rs
    except Exception:
        pass
    if len(targets) > 1:
        result["per_endpoint"] = {
            t: {**c, "qps": round(c["sent"] / elapsed, 1)}
            for t, c in per_ep.items()}
    if per_class:
        result["per_class"] = {
            k: {"sent": c["sent"], "shed": c["shed"],
                "errors": c["errors"],
                "avg_latency_us": round(c["rec"].latency(), 1),
                "p99_latency_us": c["rec"].latency_percentile(0.99)}
            for k, c in sorted(per_class.items())}
    print(json.dumps(result), file=out)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--server", required=True,
                    help="endpoint, comma-separated endpoint list, or "
                         "naming url (mesh://, pod://name, list://…)")
    ap.add_argument("--method", default=None,
                    help="full method name (required except with "
                         "--serving, which drives Router.Generate)")
    ap.add_argument("--request", default="{}")
    ap.add_argument("--qps", type=int, default=0, help="0 = unthrottled")
    ap.add_argument("--duration", type=float, default=5.0)
    ap.add_argument("--concurrency", type=int, default=8)
    ap.add_argument("--proto", default=None,
                    help="module:RequestCls,ResponseCls")
    ap.add_argument("--protocol", default="tpu_std")
    ap.add_argument("--priority", default=None,
                    help="priority band (0=critical..3=sheddable) or a "
                         "band:weight mix, e.g. '0:1,3:3'")
    ap.add_argument("--tenant", default=None,
                    help="tenant name or tenant:weight mix, e.g. 'a:2,b:1'")
    ap.add_argument("--max-retry", type=int, default=None,
                    help="per-call retry budget (shed retries honor the "
                         "server's retry_after_ms hint)")
    ap.add_argument("--bulk-plane", default="auto", choices=BULK_PLANES,
                    help="pin the fabric bulk tier for this run: auto "
                         "(route table: shm > uds/tcp > inline), shm, "
                         "uds (shm off), inline (both descriptor planes "
                         "off); the summary reports per-route counters")
    ap.add_argument("--usercode-pool", default="auto",
                    choices=USERCODE_POOLS,
                    help="pin the usercode-pool backend for servers "
                         "hosted in this process (auto keeps each "
                         "server's resolution; off records the pin); "
                         "the summary reports the probed isolation "
                         "capability and per-server pool stats")
    ap.add_argument("--shm-stripes", type=int, default=0,
                    help="force N shm ring stripes per segment (0 = "
                         "auto: 1 on 1-core hosts, else min(4, cores)); "
                         "per-stripe counters appear in the summary's "
                         "routes")
    ap.add_argument("--fanout", type=int, default=0,
                    help="drive ONE ParallelChannel over the first N "
                         "resolved members (compiled collective route "
                         "where registered, per-member RPCs otherwise); "
                         "summary adds fan-out p50/p99 and per-route "
                         "call counts")
    ap.add_argument("--fanout-shard-bytes", type=int, default=512,
                    help="bytes per member shard in --fanout mode")
    ap.add_argument("--serving", action="store_true",
                    help="open-loop serving session generator against "
                         "a Router.Generate front door: mixed "
                         "interactive/batch tenants at a fixed arrival "
                         "rate; summary reports per-tenant tokens/s "
                         "p50/p99 and pool occupancy")
    ap.add_argument("--serving-arrival-rps", type=float, default=20.0,
                    help="session arrivals per second (open loop: the "
                         "clock never waits for completions)")
    ap.add_argument("--serving-batch-ratio", type=int, default=3,
                    help="batch sessions per interactive session")
    ap.add_argument("--serving-seq", default="32-96",
                    help="prompt length range, e.g. 32-96")
    ap.add_argument("--serving-steps", default="8-64",
                    help="decode steps range: interactive draws from "
                         "the low half, batch takes the high bound")
    ap.add_argument("--serving-verify", action="store_true",
                    help="verify every completion against the "
                         "single-process reference (slow: reference "
                         "prefill per session)")
    args = ap.parse_args(argv)
    if args.serving:
        run_press_serving(args.server, duration=args.duration,
                          arrival_rps=args.serving_arrival_rps,
                          batch_ratio=args.serving_batch_ratio,
                          seq_range=args.serving_seq,
                          steps_range=args.serving_steps,
                          max_sessions_inflight=max(args.concurrency, 8),
                          verify=args.serving_verify, out=sys.stdout)
        return 0
    if not args.method:
        raise SystemExit("rpc_press: --method is required "
                         "(except with --serving)")
    if args.fanout > 0:
        run_press_fanout(args.server, args.method, args.fanout,
                         duration=args.duration,
                         concurrency=args.concurrency,
                         shard_bytes=args.fanout_shard_bytes,
                         out=sys.stdout)
        return 0
    run_press(args.server, args.method, args.request, args.qps,
              args.duration, args.concurrency, args.proto, args.protocol,
              priority=args.priority, tenant=args.tenant,
              max_retry=args.max_retry, bulk_plane=args.bulk_plane,
              shm_stripes=args.shm_stripes,
              usercode_pool=args.usercode_pool, out=sys.stdout)
    return 0


if __name__ == "__main__":
    sys.exit(main())
