"""The fan-out layer's own totals (``brpc_tpu.channels.fanout_stats()``:
process-wide, kept past a channel's close), each under ``fanout_<key>``.  A
program that has no such counts cannot run a cell that names this module."""
from brpc_tpu.channels import fanout_stats

KEYS = tuple(f"fanout_{k}" for k in fanout_stats())


def snapshot(servers):
    return {f"fanout_{k}": v for k, v in fanout_stats().items()}
