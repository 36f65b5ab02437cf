"""One cell, once: bring the deployment up, make the payload sets, warm up,
drive the closed loop for the window, hand back what was seen.

The window drives each mix entry's client (``clients/<name>.py``, the
loader's default where the entry names none) against ``rpc.Server``s on the
configuration's ``ici://k`` endpoints.  What an operation does is the
client's; what its latency is, and whether it was answered, is decided here
alone, once for every client: the clock runs from before the client's
``call`` until the device blocks of the attachment it returns are ready, and
the four per-operation checks are made on what it returned.
"""
from __future__ import annotations

import contextlib
import random
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax

from . import counters, traffic
from .loader import Cell, client_module, control_module, service_module


class Spans:
    """Boundary stamps of a traced run, on one ``perf_counter_ns`` clock,
    kept in memory under (boundary, call key)."""

    def __init__(self):
        self.at: Dict[str, Dict[str, int]] = {}

    def stamp(self, boundary: str, key: str) -> None:
        self.at.setdefault(boundary, {})[key] = time.perf_counter_ns()


@dataclass
class ClientContext:
    """What a client module's ``open`` is given, once per caller and mix
    entry."""
    rpc: Any                            # the program's ``brpc_tpu.rpc``
    channel: Any                        # this caller's own ``rpc.Channel``
    method: str                         # the full method name of the entry
    thread: int
    options: Dict[str, Any]             # the mix entry's ``client_options``


@dataclass
class Sampled:
    """A finished call kept for the comparison with the reference."""
    key: str
    call: traffic.Call
    message: str
    attachment: Any


@dataclass
class CallerLog:
    """What one caller saw.  ``calls`` rows are
    (start_ns, ready_ns, mix, nbytes, ok, key)."""
    calls: List[Tuple[int, int, int, int, bool, str]] = field(
        default_factory=list)
    faults: Dict[str, int] = field(default_factory=dict)
    first_errors: List[str] = field(default_factory=list)
    sampled: Dict[int, List[Sampled]] = field(default_factory=dict)

    def fault(self, kind: str, text: str) -> None:
        self.faults[kind] = self.faults.get(kind, 0) + 1
        if len(self.first_errors) < 3:
            self.first_errors.append(f"{kind}: {text}")


@dataclass
class Window:
    """Everything the reductions and the comparison read."""
    cell: Cell
    seed: int
    setup_s: float
    setup_parts: Dict[str, float]       # seconds since process start, at each
    start_ns: int
    end_ns: int
    logs: List[CallerLog]
    counters: Dict[str, int]            # the program's counts over the window
    compiles_in_window: int
    spans: Optional[Spans]
    trace_dir: Optional[str]
    trace_slice_ns: Optional[Tuple[int, int]]
    memory_peak_bytes: Optional[int]
    caller_device: Any
    devices: List[Any]

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    def calls(self):
        for log in self.logs:
            yield from log.calls


class Deployment:
    """Servers, channels and payload sets of one configuration, alive from
    set-up until ``close``."""

    def __init__(self, cell: Cell, seed: int, spans: Optional[Spans],
                 control: Optional[str]):
        import brpc_tpu.policy  # noqa: F401  (registers the protocols)
        from brpc_tpu import rpc
        from brpc_tpu.ici.mesh import IciMesh
        self.rpc = rpc
        self.cell = cell
        self.seed = seed
        cfg, wl = cell.config, cell.workload
        self.devices = jax.devices()[:cell.chips]
        self.mesh = IciMesh(self.devices)
        IciMesh.set_default(self.mesh)
        self.caller_device = self.mesh.device(cfg["caller_device"])
        self.control = control_module(control) if control else None
        self.servers: List[Any] = []
        self.channels: List[Any] = []
        self.sets: Dict[str, List[Any]] = {}
        try:
            self._start_servers(spans)
            self.method_names = {
                m: f"{self._services[m].service_name()}.{m}"
                for m in cell.methods()}
            options = dict(cfg["channel_options"],
                           **wl.get("channel_options", {}))
            if self.control and hasattr(self.control, "channel_options"):
                options = self.control.channel_options(options)
            for t in range(wl["threads"]):
                s = traffic.server_of(wl, t, len(cfg["servers"]))
                ch = rpc.Channel()
                if ch.init(cfg["servers"][s]["endpoint"],
                           options=rpc.ChannelOptions(**options)) != 0:
                    raise RuntimeError(
                        f"channel init to {cfg['servers'][s]['endpoint']}")
                self.channels.append(ch)
            for name, spec in traffic.set_specs(wl).items():
                from .resident import make_set
                self.sets[name] = make_set(seed, spec.set_id, spec.count,
                                           spec.block_bytes,
                                           self.caller_device)
        except BaseException:
            self.close()
            raise

    def _start_servers(self, spans: Optional[Spans]) -> None:
        rpc = self.rpc
        modules = {m: service_module(m) for m in self.cell.methods()}
        wanted = [mod.SERVER_OPTIONS for mod in modules.values()]
        if any(w != wanted[0] for w in wanted):
            raise RuntimeError(f"the mix's services want different server "
                               f"options: {wanted}")
        self._services = {}
        for entry in self.cell.config["servers"]:
            opts = rpc.ServerOptions()
            for k, v in wanted[0].items():
                setattr(opts, k, v)
            server = rpc.Server(opts)
            for m, mod in modules.items():
                service = mod.build(spans)
                if self.control and hasattr(self.control, "wrap_service"):
                    service = self.control.wrap_service(service)
                self._services[m] = service
                server.add_service(service)
            if server.start(entry["endpoint"]) != 0:
                raise RuntimeError(f"server start on {entry['endpoint']}")
            self.servers.append(server)
            if self.mesh.device(entry["device"]) not in self.devices:
                raise RuntimeError(f"{entry['endpoint']} names no chip of "
                                   f"this cell")

    def close(self) -> None:
        for ch in self.channels:
            ch.close()
        for server in self.servers:
            server.stop()
        self.servers = []
        self.channels = []
        self.sets = {}


class Caller:
    """One closed-loop client thread: queue depth 1 on its own channel."""

    def __init__(self, dep: Deployment, thread: int, sample: int,
                 traced: bool):
        self.dep = dep
        self.thread = thread
        self.schedule = traffic.schedule(dep.cell.workload, dep.seed, thread)
        self.sample = sample
        self.pick = random.Random((dep.seed << 20) ^ (thread << 8) ^ 0xC4)
        self.seen: Dict[int, int] = {}
        self.n = 0
        self.traced = traced
        self.log = CallerLog()
        # one opened client per mix entry, resolved here and never again
        self.clients: List[Any] = []
        try:
            for m, name in zip(dep.cell.workload["mix"], dep.cell.clients()):
                self.clients.append(client_module(name).open(ClientContext(
                    rpc=dep.rpc, channel=dep.channels[thread],
                    method=dep.method_names[m["method"]],
                    thread=thread, options=m.get("client_options", {}))))
        except BaseException:
            self.close()
            raise
        self.operations = [c.call for c in self.clients]

    def close(self) -> None:
        for c in self.clients:
            c.close()
        self.clients = []

    def one_call(self, phase: str = "w",
                 call: Optional[traffic.Call] = None) -> bool:
        dep, log = self.dep, self.log
        if call is None:
            call = next(self.schedule)
        key = f"{phase}{self.thread:02d}.{self.n:09d}"
        self.n += 1
        block = dep.sets[call.set_name][call.block]
        operate = self.operations[call.mix]
        ok = True
        t0 = time.perf_counter_ns()
        note = jax.profiler.TraceAnnotation("bench.call." + call.method) \
            if self.traced else contextlib.nullcontext()
        try:
            with note:
                message, att = operate(key, block)
            refs = att.device_refs()
            jax.block_until_ready([r.block.data for r in refs])
        except Exception as e:       # an operation that raises has failed
            t1 = time.perf_counter_ns()
            log.fault("failed_calls", f"{key}: {type(e).__name__}: {e}")
            log.calls.append((t0, t1, call.mix, call.nbytes, False, key))
            return False
        t1 = time.perf_counter_ns()
        if len(att) != call.nbytes or att.device_bytes() != call.nbytes:
            log.fault("short_replies",
                      f"{key}: reply attachment {len(att)}B of which "
                      f"{att.device_bytes()}B device, sent {call.nbytes}B")
            ok = False
        want = {dep.caller_device}
        if any(set(r.block.data.devices()) != want for r in refs):
            log.fault("misplaced_replies",
                      f"{key}: reply resident on "
                      f"{[r.block.data.devices() for r in refs]}")
            ok = False
        if not message.startswith(key):
            log.fault("misordered_replies",
                      f"{key}: reply says {message[:40]!r}")
            ok = False
        log.calls.append((t0, t1, call.mix, call.nbytes, ok, key))
        if ok and phase == "w":
            self._maybe_keep(Sampled(key, call, message, att))
        return ok

    def _maybe_keep(self, s: Sampled) -> None:
        """Reservoir of ``sample`` finished calls per mix entry, drawn from
        the seed."""
        kept = self.log.sampled.setdefault(s.call.mix, [])
        i = self.seen.get(s.call.mix, 0)
        self.seen[s.call.mix] = i + 1
        if len(kept) < self.sample:
            kept.append(s)
        else:
            j = self.pick.randrange(i + 1)
            if j < self.sample:
                kept[j] = s

    def run_until(self, deadline_ns: int, barrier: threading.Barrier,
                  phase: str) -> None:
        barrier.wait()
        while time.perf_counter_ns() < deadline_ns:
            self.one_call(phase)


# reuses of a call-id slot before the warm-up: past 512, where the id's
# varint reaches the length it keeps for the next 32,000 calls on the slot
CALL_ID_REUSES = 600


def age_call_ids(slots: int, reuses: int = CALL_ID_REUSES) -> None:
    """Put the program's call-id pool where a long-lived process has it.

    A call's correlation id is (slot version << 32 | slot) and the version
    grows by 2 with every reuse of the slot, so the id's varint in the frame
    header grows a byte after 4 and after 512 calls on a slot.  The Python ici
    plane cuts a frame into window pieces at exact byte offsets and compiles
    one slice program per exact piece size: a frame one byte longer means
    four new programs, in the middle of the window.  ``slots`` ids are held
    together and released, ``reuses`` times, so that the slots the callers
    will draw (the pool hands out the last released first) are past the
    boundary before the warm-up learns the frame's sizes."""
    from brpc_tpu.bthread import id as call_id
    for _ in range(reuses):
        held = [call_id.create() for _ in range(slots)]
        for cid in held:
            call_id.unlock_and_destroy(cid)


def _warm_up(dep: Deployment, callers: List[Caller], meter,
             seconds: float) -> None:
    """Every (method, size, server) pair of the cell until no program is
    compiled any more, then all callers together for a moment."""
    for c in callers:
        each = traffic.one_of_each(dep.cell.workload, dep.seed, c.thread)
        quiet = 0
        for _ in range(200):
            before = meter.programs
            for call in each:
                c.one_call("u", call)
                if c.log.faults.get("failed_calls"):
                    raise RuntimeError(
                        f"warm-up call failed: {c.log.first_errors}")
            quiet = quiet + 1 if meter.programs == before else 0
            if quiet >= 2:
                break
        else:
            raise RuntimeError("warm-up: programs never stopped compiling")
    barrier = threading.Barrier(len(callers))
    deadline = time.perf_counter_ns() + int(seconds * 1e9)
    _run_threads(callers, deadline, barrier, "u")
    failed = [e for c in callers if c.log.faults.get("failed_calls")
              for e in c.log.first_errors]
    if failed:
        raise RuntimeError(f"warm-up calls failed: {failed[:3]}")


def _run_threads(callers: List[Caller], deadline_ns: int,
                 barrier: threading.Barrier, phase: str,
                 meanwhile=None) -> None:
    """All callers from one barrier until the deadline; ``meanwhile`` runs
    on this thread while they do."""
    errors: List[BaseException] = []

    def body(c: Caller):
        try:
            c.run_until(deadline_ns, barrier, phase)
        except BaseException as e:      # reported by the parent below
            errors.append(e)
            barrier.abort()

    threads = [threading.Thread(target=body, args=(c,), daemon=True,
                                name=f"bench-caller-{c.thread}")
               for c in callers]
    for t in threads:
        t.start()
    if meanwhile is not None:
        meanwhile()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]


def memory_peak(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_window(cell: Cell, seed: int, seconds: float, traced: bool,
               process_start: float, meter, trace_dir: Optional[str],
               control: Optional[str] = None,
               marks: Optional[Dict[str, float]] = None) -> Window:
    """Set-up, warm-up, the measured window; the deployment is closed and
    the payload sets are freed before this returns.  ``marks`` are the
    caller's own stamps of the set-up so far (seconds since process start)."""
    wl = cell.workload
    spans = Spans() if traced else None
    parts = dict(marks or {})
    parts["before_deployment"] = time.perf_counter() - process_start
    dep = Deployment(cell, seed, spans, control)
    parts["deployment_and_sets"] = time.perf_counter() - process_start
    callers: List[Caller] = []
    more = wl.get("counters", [])
    try:
        for t in range(wl["threads"]):
            callers.append(Caller(dep, t, wl["sample_per_thread"], traced))
        age_call_ids(wl["threads"])
        _warm_up(dep, callers, meter, wl["warmup_seconds"])
        parts["warm_up"] = time.perf_counter() - process_start
        for c in callers:               # warm-up calls are not the window's
            c.log = CallerLog()
            c.seen = {}
        before = counters.read(dep.servers, more)
        programs_before = meter.programs
        trace_slice: List[int] = []

        def trace_a_slice():
            """On the main thread while the callers run: trace a few
            seconds from a second into the window."""
            length = min(wl["trace_seconds"], seconds / 2)
            time.sleep(min(1.0, seconds / 4))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            trace_slice.append(time.perf_counter_ns())
            time.sleep(length)
            trace_slice.append(time.perf_counter_ns())
            jax.profiler.stop_trace()

        barrier = threading.Barrier(len(callers) + 1)
        setup_s = time.perf_counter() - process_start
        start_ns = time.perf_counter_ns()
        deadline = start_ns + int(seconds * 1e9)

        def meanwhile():
            barrier.wait()
            if traced:
                trace_a_slice()

        _run_threads(callers, deadline, barrier, "w", meanwhile=meanwhile)
        end_ns = max([t1 for c in callers for (_, t1, *_r) in c.log.calls],
                     default=time.perf_counter_ns())
        after = counters.read(dep.servers, more)
        peak = memory_peak(dep.devices)
        return Window(
            cell=cell, seed=seed, setup_s=setup_s, setup_parts=parts,
            start_ns=start_ns,
            end_ns=end_ns, logs=[c.log for c in callers],
            counters=counters.delta(before, after),
            compiles_in_window=meter.programs - programs_before,
            spans=spans, trace_dir=trace_dir if traced else None,
            trace_slice_ns=tuple(trace_slice) if trace_slice else None,
            memory_peak_bytes=peak, caller_device=dep.caller_device,
            devices=dep.devices)
    finally:
        for c in callers:
            c.close()
        dep.close()


def describe(window: Window) -> Dict[str, Any]:
    """Sample counts and first faults, for the lines before the last."""
    calls = list(window.calls())
    by_mix: Dict[int, int] = {}
    for c in calls:
        by_mix[c[2]] = by_mix.get(c[2], 0) + 1
    return {"calls": len(calls), "calls_by_mix": by_mix,
            "window_s": window.seconds,
            "first_errors": [e for log in window.logs
                             for e in log.first_errors][:6]}
