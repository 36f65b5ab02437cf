"""The expert dispatch's and combine's own totals
(``brpc_tpu.channels.expert_dispatch_stats()``: process-wide, kept past a
channel's close), each under ``fanout_ep_<key>``.  A program that has no
such counts cannot run a cell that names this module."""
from brpc_tpu.channels import expert_dispatch_stats

KEYS = tuple(f"fanout_ep_{k}" for k in expert_dispatch_stats())


def snapshot(servers):
    return {f"fanout_ep_{k}": v for k, v in expert_dispatch_stats().items()}
