"""The totals of the fan-out layer's reduce
(``brpc_tpu.channels.fanout_reduce_stats()``: process-wide, kept past a
channel's close), each under ``fanout_reduce_<key>``.  A program that has no
such counts cannot run a cell that names this module."""
from brpc_tpu.channels import fanout_reduce_stats

KEYS = tuple(f"fanout_reduce_{k}" for k in fanout_reduce_stats())


def snapshot(servers):
    return {f"fanout_reduce_{k}": v
            for k, v in fanout_reduce_stats().items()}
