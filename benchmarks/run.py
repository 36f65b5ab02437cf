#!/usr/bin/env python3
"""One process, one cell, once: load, warm up, measure, print, exit.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device`` and,
traced, ``breakdown``; ``checks`` comes last in it and holds each number that
was compared beside its limit.  The same numbers are the last lines of
standard error.  No TPU, fewer chips than the cell asks for, no native core
or a device kind without published peaks: exit code 2 and no result line.

``--rehearse`` (tests and the sandbox only) runs the same code on the CPU at
the tiny sizes the workload file gives under ``rehearse``; the line then says
``"platform": "cpu"`` and every device number is null.  ``--control <name>``
(the proof of the comparison only) runs the cell with one guarantee broken
underneath; such a run has to come out not correct.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH_DIR)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

EXIT_NO_DEVICE = 2


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", default=None)
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the trace under benchmarks/.trace and list "
                         "its planes and lines on standard error")
    return ap.parse_args(argv)


def device_check(cell, rehearse: bool):
    """The devices the cell runs on, or ``None`` (after saying why) where
    this machine cannot measure it."""
    import jax
    from brpc_tpu.butil import native
    devs = jax.devices()
    dev0 = devs[0]
    if not rehearse and dev0.platform != "tpu":
        say(f"run.py: jax found no TPU (platform={dev0.platform!r}); a cell "
            f"is measured on the chip only (--rehearse runs it on the CPU)")
        return None
    if len(devs) < cell.chips:
        say(f"run.py: cell {cell.name} needs {cell.chips} chips, jax found "
            f"{len(devs)}")
        return None
    if not native.available():
        say("run.py: the native core (native/libbrpc_tpu_core.so) neither "
            "loads nor builds here; there is no stand-in for the native tier")
        return None
    return devs


def main(argv=None, process_start: float = PROCESS_START) -> int:
    args = parse(argv)
    from benchmarks.harness import loader
    try:
        cell = loader.load_cell(args.workload, rehearse=args.rehearse)
    except loader.BenchmarkError as e:
        say(f"run.py: {e}")
        return EXIT_NO_DEVICE

    import jax
    from benchmarks.harness import check, driver, readers, xplane
    from benchmarks.harness.meter import CompileMeter
    marks = {"imports": time.perf_counter() - process_start}
    if not args.rehearse:
        # JAX_COMPILATION_CACHE_DIR where it is set, else <checkout>/.jax_cache
        from brpc_tpu.butil import compile_cache
        say(f"[cache] {compile_cache.enable()}")
    meter = CompileMeter()
    devs = device_check(cell, args.rehearse)
    if devs is None:
        return EXIT_NO_DEVICE
    marks["devices_and_native_core"] = time.perf_counter() - process_start
    dev0 = devs[0]
    peaks = None
    if not args.rehearse:
        try:
            peaks = loader.peaks(dev0.device_kind)
        except loader.BenchmarkError as e:
            say(f"run.py: {e}")
            return EXIT_NO_DEVICE

    trace_dir = os.path.join(BENCH_DIR, ".trace", cell.name)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
    window = driver.run_window(
        cell, args.seed, args.seconds, bool(args.trace), process_start,
        meter, trace_dir, control=args.control, marks=marks)
    info = driver.describe(window)
    say(f"[window] {info['calls']} calls (by mix entry "
        f"{info['calls_by_mix']}) in {info['window_s']:.3f}s; set-up "
        f"{window.setup_s:.2f}s (reached at "
        f"{ {k: round(v, 2) for k, v in window.setup_parts.items()} }); compile {meter.line()}; in the window "
        f"{window.compiles_in_window} programs compiled")
    for e in info["first_errors"]:
        say(f"[fault] {e}")

    reduction = None
    if args.trace:
        path = xplane.newest_trace(trace_dir)
        if path is not None:
            reduction = xplane.reduce_file(
                path, [d.id for d in window.devices])
            if args.keep_trace:
                for line in xplane.describe_file(path):
                    say(f"[xplane] {line}")
        if not args.keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
    on_chip = dev0.platform == "tpu"
    if not on_chip:
        reduction = None                # a CPU trace says nothing of a chip

    t_compare = time.perf_counter()
    numbers, wrong_sampled = check.compare(window)
    say(f"[compare] {numbers['replies_compared']['value']} replies against "
        f"the reference in {time.perf_counter() - t_compare:.2f}s")
    correct = check.verdict(numbers)
    view = readers.View(window=window, reduction=reduction, peaks=peaks)
    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        if not on_chip and m.source == "device_trace":
            value = None                # a rehearsal has no device number
        else:
            value = readers.read(m, view)
            if value is None:           # nothing to read: left out
                continue
        metrics[m.name] = {"value": value, "unit": m.unit}

    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devs),
              "memory_peak_bytes": window.memory_peak_bytes
              if on_chip else None}
    result = {"correct": correct,
              "attempted": info["calls"],
              "failed": sum(1 for c in window.calls() if not c[4])
              + wrong_sampled,
              "metrics": metrics, "device": device}
    if args.trace:
        device["busy_s"] = reduction.mean_busy_s() if reduction else None
        device["window_s"] = reduction.window_s if reduction else None
        if reduction and reduction.busy_s:
            busiest = max(reduction.busy_s, key=reduction.busy_s.get)
            result["breakdown"] = {
                "device_ops": [list(o) for o in reduction.ops[busiest]],
                "idle_gaps": [list(g) for g in reduction.idle_gaps]}
        elif not on_chip:
            result["breakdown"] = {"device_ops": [], "idle_gaps": []}
    result["checks"] = {k: {"value": n["value"], "limit": n["limit"]}
                        for k, n in numbers.items()}
    if args.trace and on_chip and not device["busy_s"]:
        say("run.py: the trace shows no operation on the device")
        return 1
    print(json.dumps(result), flush=True)
    for line in check.lines(numbers):
        say(line)
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except SystemExit as e:             # argparse: --help, a bad option
        rc = e.code if isinstance(e.code, int) else 1
    except BaseException:               # the boundary: report, then leave
        import traceback
        traceback.print_exc()
        rc = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)    # the program's daemon pollers never hold the exit hostage
