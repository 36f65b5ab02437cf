"""Echo over the ici:// device fabric with an HBM-resident payload —
the TPU-native counterpart of example/rdma_performance's latency mode."""
from __future__ import annotations

import time

from examples.common import EchoRequest, EchoResponse, start_echo_server, rpc


def main() -> None:
    import jax
    import jax.numpy as jnp
    from brpc_tpu.ici.mesh import IciMesh

    mesh = IciMesh.default()
    server = start_echo_server("ici://0")
    try:
        ch = rpc.Channel()
        ch.init("ici://0", options=rpc.ChannelOptions(timeout_ms=10000))
        payload = jax.device_put(jnp.arange(65536, dtype=jnp.uint8),
                                 mesh.device(min(1, mesh.size - 1)))
        jax.block_until_ready(payload)
        lats = []
        for i in range(30):
            cntl = rpc.Controller()
            cntl.request_attachment.append_device_array(payload)
            t0 = time.perf_counter_ns()
            ch.call_method("EchoService.Echo", cntl,
                           EchoRequest(message="device"), EchoResponse)
            lats.append((time.perf_counter_ns() - t0) / 1000)
            assert not cntl.failed(), cntl.error_text
        lats.sort()
        from brpc_tpu.ici.transport import ici_transport_stats
        total, device_bytes = ici_transport_stats()
        print(f"ici echo with 64KB HBM payload: p50={lats[len(lats)//2]:.0f}us "
              f"p99={lats[-1]:.0f}us; fabric moved {device_bytes} "
              f"device bytes without host copies")
    finally:
        server.stop()


if __name__ == "__main__":
    from brpc_tpu.butil import compile_cache
    compile_cache.enable()
    main()
