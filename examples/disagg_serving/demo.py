"""Runnable disaggregated-serving demo (single process, virtual mesh).

Starts one prefill worker (ici://1), two decode workers (ici://2,
ici://3), and a router (mem://), then generates a few completions and
verifies them against the single-process reference — the KV handoff
crossed the device plane, the tokens must be bit-identical.

    python -m examples.disagg_serving.demo

For the cross-process (pod) flavor — every worker its own process, KV
blocks crossing the fabric's sequenced device plane — see README.md and
tests/test_pod.py.
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, __file__.rsplit("/", 3)[0])

# the virtual 8-device CPU mesh (the tests' fixture): without it a bare
# CPU jax exposes ONE device, every worker lands on it, and the KV
# handoff never needs to cross anything
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")


def main() -> int:
    import jax
    import brpc_tpu.policy  # noqa: F401
    from brpc_tpu import rpc
    from brpc_tpu.butil import flags as _fl
    import brpc_tpu.ici.device_plane  # noqa: F401 — defines the flags
    from examples.example_echo_pb2 import EchoRequest, EchoResponse
    from examples.disagg_serving.model import reference_generate
    from examples.disagg_serving.workers import (
        start_prefill_worker, start_decode_worker, start_router)

    # the device plane engages for the KV handoff on this host-memory
    # mesh (the identical datapath CI exercises; on TPU it is on by
    # default)
    _fl.set_flag("ici_device_plane_host_mesh", True)
    _fl.set_flag("ici_device_plane_threshold", 64 * 1024)
    # rpcz: the router→prefill→decode trace — including the KV
    # handoff's device-plane transfer spans — prints at the end
    _fl.set_flag("rpcz_enabled", True)

    devs = jax.devices()
    # trace fidelity: the native IN-PROCESS ici fast path creates client
    # spans only (no server span, no propagation into the handler —
    # ROADMAP item 1 keeps the whole native path native); the Python
    # plane traces end to end, and cross-process pods ride it anyway
    wopts = rpc.ServerOptions()
    wopts.native_ici = False
    prefill = start_prefill_worker("ici://1", device=devs[1 % len(devs)],
                                   options=wopts)
    decode_a = start_decode_worker("ici://2", device=devs[2 % len(devs)],
                                   options=rpc.ServerOptions(
                                       native_ici=False))
    decode_b = start_decode_worker("ici://3", device=devs[3 % len(devs)],
                                   options=rpc.ServerOptions(
                                       native_ici=False))
    router = start_router("mem://disagg-router", "ici://1",
                          {"ici://2": "ici://2", "ici://3": "ici://3"})
    try:
        ch = rpc.Channel()
        ch.init("mem://disagg-router",
                options=rpc.ChannelOptions(timeout_ms=60000))
        ok = 0
        for i in range(4):
            tokens = [(7 * i + j) % 997 for j in range(96 + 16 * i)]
            cntl = rpc.Controller()
            resp = ch.call_method(
                "Router.Generate", cntl,
                EchoRequest(message=json.dumps(
                    {"tokens": tokens, "steps": 8})), EchoResponse)
            assert not cntl.failed(), cntl.error_text
            out = json.loads(resp.message)
            want = reference_generate(tokens, 8)
            assert out["tokens"] == want, (out["tokens"], want)
            ok += 1
            print(f"prompt {i}: {out['kv_bytes']} KV bytes -> "
                  f"{out['decode_worker']} -> tokens {out['tokens'][:4]}…"
                  f" verified")
        from brpc_tpu.ici.device_plane import DevicePlane
        stats = DevicePlane.instance().stats()
        print("device plane:", stats)
        # the serving subsystem's route assertion: the decode worker's
        # /status serving block (continuous-batching scheduler + paged
        # pool) — tokens came through the step loop, not a sync path
        for srv in (decode_a, decode_b):
            for name, svc in srv._services.items():
                if hasattr(svc, "describe_serving"):
                    d = svc.describe_serving()
                    print(f"serving[{name}@{srv.listen_endpoint}]: "
                          f"steps={d['scheduler']['steps']} "
                          f"pool_blocks_used={d['pool']['blocks_used']}")
        assert stats["transfers"] > 0, (
            "KV handoff never crossed the device plane", stats)
        # the last request's trace as one tree (single process here;
        # across a pod the SAME query on any member stitches every
        # process's spans — docs/OBSERVABILITY.md)
        import time as _time
        from brpc_tpu.rpc.span import find_trace
        from brpc_tpu.rpc.builtin.pod_scope import stitch_tree
        _time.sleep(0.2)                  # transfer completions store
        spans = [s.describe() for s in find_trace(cntl.trace_id)]
        for s in spans:
            s["aligned_start_us"] = s["start_real_us"]

        def show(node, depth=0):
            print("  " * depth
                  + f"rpcz {node['side']:>8} {node['method']} "
                    f"{node['latency_us']}us "
                    f"({len(node['annotations'])} annotations)")
            for c in node["children"]:
                show(c, depth + 1)

        for root in stitch_tree(spans):
            show(root)
        assert any(s["side"] == "transfer" for s in spans), (
            "KV handoff transfer spans missing from the trace")
        print(f"disagg_serving demo: {ok}/4 completions verified "
              f"({stats['transfers']} device-plane transfers)")
        ch.close()
        return 0
    finally:
        router.stop()
        decode_a.stop()
        decode_b.stop()
        prefill.stop()


if __name__ == "__main__":
    from brpc_tpu.butil import compile_cache
    compile_cache.enable()
    sys.exit(main())
