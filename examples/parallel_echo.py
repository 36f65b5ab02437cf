"""ParallelChannel fan-out — example/parallel_echo_c++ (BASELINE config 4),
plus the TPU-native collective lowering of the same call shape."""
from __future__ import annotations

from examples.common import EchoRequest, EchoResponse, start_echo_server, rpc
from brpc_tpu import channels


class ConcatMerger(channels.ResponseMerger):
    def merge(self, response, sub_response):
        response.message = (response.message + "|" + sub_response.message
                            if response.message else sub_response.message)
        return self.MERGED


def main() -> None:
    servers = [start_echo_server(f"mem://example-par-{i}", tag=f"s{i}")
               for i in range(4)]
    try:
        pchan = channels.ParallelChannel(fail_limit=2)
        for i in range(4):
            ch = rpc.Channel()
            ch.init(f"mem://example-par-{i}")
            pchan.add_channel(ch, merger=ConcatMerger())
        cntl = rpc.Controller()
        resp = EchoResponse()
        pchan.call_method("EchoService.Echo", cntl,
                          EchoRequest(message="fanout"), resp)
        assert not cntl.failed(), cntl.error_text
        print("host-side fan-out merged:", sorted(resp.message.split("|")))
    finally:
        for s in servers:
            s.stop()

    # The same semantics on the device mesh: ONE compiled collective
    import jax.numpy as jnp
    from brpc_tpu.ici.mesh import IciMesh
    mesh = IciMesh.default()
    cc = channels.CollectiveChannel(mesh)
    cc.register("Echo.Sum", lambda row: row * 2,
                merge=channels.MERGE_SUM, mapping=channels.MAP_SHARD)
    x = cc.shard(jnp.ones((mesh.size, 8)))
    y = cc.call("Echo.Sum", x)
    print(f"collective lowering on {mesh.size}-device mesh: "
          f"sum(2*ones) = {float(y[0])} per element")


if __name__ == "__main__":
    from brpc_tpu.butil import compile_cache
    compile_cache.enable()
    main()
