"""The stream layer's own totals (``brpc_tpu.rpc.stream.stream_stats()``:
process-wide, kept past a stream's close), each under ``stream_<key>``.  A
program that has no such counts cannot run a cell that names this module."""
from brpc_tpu.rpc.stream import stream_stats

KEYS = tuple(f"stream_{k}" for k in stream_stats())


def snapshot(servers):
    return {f"stream_{k}": v for k, v in stream_stats().items()}
