"""Disaggregated prefill/decode serving (examples/disagg_serving).

In-process flavor on the virtual mesh: prefill and decode workers on
different mesh devices, the KV-cache handoff crossing the device plane,
tokens verified bit-exact against the single-process reference.  The
cross-process (pod) flavor is exercised by tests/test_pod.py.
"""
import json

import pytest

import brpc_tpu.policy  # noqa: F401
from brpc_tpu import rpc


@pytest.fixture()
def _plane_flags():
    from brpc_tpu.butil import flags as fl
    import brpc_tpu.ici.device_plane  # noqa: F401 — defines the flags
    saved = {k: fl.get_flag(k) for k in
             ("ici_device_plane_host_mesh", "ici_device_plane_threshold")}
    fl.set_flag("ici_device_plane_host_mesh", True)
    fl.set_flag("ici_device_plane_threshold", 64 * 1024)
    yield
    for k, v in saved.items():
        fl.set_flag(k, v)


class TestDisaggServing:
    def _stack(self, tag: str):
        import jax
        from examples.disagg_serving.workers import (
            start_prefill_worker, start_decode_worker, start_router)
        devs = jax.devices()
        prefill = start_prefill_worker("ici://4", device=devs[4])
        decode = start_decode_worker("ici://5", device=devs[5])
        router = start_router(f"mem://disagg-{tag}", "ici://4",
                              {"ici://5": "ici://5"})
        return prefill, decode, router

    def _teardown(self, prefill, decode, router):
        # close every service that carries resources: channels (router,
        # prefill) AND the decode worker's step loop + paged pool
        for server in (router, prefill, decode):
            for svc in server._services.values():
                if hasattr(svc, "close"):
                    svc.close()
        router.stop()
        decode.stop()
        prefill.stop()

    def test_generate_matches_reference_over_device_plane(self,
                                                          _plane_flags):
        from examples.example_echo_pb2 import EchoRequest, EchoResponse
        from examples.disagg_serving.model import (reference_generate,
                                                   kv_nbytes)
        from brpc_tpu.ici.device_plane import DevicePlane
        prefill, decode, router = self._stack("ref")
        try:
            plane = DevicePlane.instance()
            before = plane.stats()["transfers"]
            ch = rpc.Channel()
            ch.init("mem://disagg-ref",
                    options=rpc.ChannelOptions(timeout_ms=60000))
            tokens = [(13 * j) % 997 for j in range(128)]
            cntl = rpc.Controller()
            resp = ch.call_method(
                "Router.Generate", cntl,
                EchoRequest(message=json.dumps(
                    {"tokens": tokens, "steps": 12})), EchoResponse)
            assert not cntl.failed(), cntl.error_text
            out = json.loads(resp.message)
            assert out["tokens"] == reference_generate(tokens, 12)
            assert out["kv_bytes"] == kv_nbytes(len(tokens))
            # the KV handoff actually crossed the device plane
            assert plane.stats()["transfers"] > before
            ch.close()
        finally:
            self._teardown(prefill, decode, router)

    def test_sessions_release_and_multiple_prompts(self, _plane_flags):
        from examples.example_echo_pb2 import EchoRequest, EchoResponse
        from examples.disagg_serving.model import reference_generate
        prefill, decode, router = self._stack("multi")
        try:
            dec_svc = next(iter(decode._services.values()))
            ch = rpc.Channel()
            ch.init("mem://disagg-multi",
                    options=rpc.ChannelOptions(timeout_ms=60000))
            for i in range(3):
                tokens = [(7 * i + j) % 499 for j in range(96)]
                cntl = rpc.Controller()
                resp = ch.call_method(
                    "Router.Generate", cntl,
                    EchoRequest(message=json.dumps(
                        {"tokens": tokens, "steps": 6})), EchoResponse)
                assert not cntl.failed(), cntl.error_text
                assert json.loads(resp.message)["tokens"] == \
                    reference_generate(tokens, 6)
            # decode released every session after its Decode
            assert dec_svc.live_sessions() == 0
            assert dec_svc.loads == 3
            ch.close()
        finally:
            self._teardown(prefill, decode, router)
