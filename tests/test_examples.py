"""Smoke-run every example (the reference CI builds all examples)."""
import importlib
import sys

import pytest

sys.path.insert(0, "/root/repo")

EXAMPLES = [
    "examples.echo_client_server",
    "examples.multi_threaded_echo",
    "examples.asynchronous_echo",
    "examples.streaming_echo",
    "examples.parallel_echo",
    "examples.partition_echo",
    "examples.selective_echo",
    "examples.backup_request",
    "examples.dynamic_partition_echo",
    "examples.cancel_rpc",
    "examples.ici_echo",
    "examples.http_server",
    "examples.auto_concurrency_limiter",
    "examples.param_server",
    "examples.native_echo",
    "examples.native_async_pool",
    "examples.mongo_service",
    "examples.cascade_echo",
    "examples.grpc_echo",
    "examples.grpc_interop",
    "examples.redis_kv",
    "examples.memcache_client",
    "examples.thrift_echo",
    "examples.nshead_extension",
    "examples.session_data_and_thread_local",
    "examples.multi_threaded_echo_fns",
    "examples.rtmp_relay",
    "examples.allreduce_performance",
]


@pytest.mark.parametrize("mod_name", EXAMPLES)
def test_example_runs(mod_name, capsys):
    mod = importlib.import_module(mod_name)
    if mod_name == "examples.multi_threaded_echo":
        mod.main(threads=4, seconds=0.5)
    elif mod_name == "examples.allreduce_performance":
        mod.main(size_mb=1)
        out = capsys.readouterr().out
        assert "xla psum" in out and "explicit ring" in out
    else:
        mod.main()
