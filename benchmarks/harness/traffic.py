"""The one general traffic generator: a workload file's parameters and
--seed in, each caller's endless schedule of calls out.

Every seed gives every caller the same multiset of calls (the mix's weights
over one cycle, every block of its share of a set once per pass) in another
order, so that a seed changes the order of the work and never its amount.
"""
from __future__ import annotations

import random
from dataclasses import dataclass
from typing import Any, Dict, Iterator, List, Tuple


@dataclass(frozen=True)
class SetSpec:
    name: str
    set_id: int
    block_bytes: int
    count: int


@dataclass(frozen=True)
class Call:
    mix: int            # index into the workload's mix
    method: str
    set_name: str
    block: int          # index of the block in its set
    nbytes: int


def set_specs(workload: Dict[str, Any]) -> Dict[str, SetSpec]:
    out = {}
    for name, s in workload["sets"].items():
        count = s["count"] if "count" in s else s["bytes"] // s["block_bytes"]
        if count < workload["threads"]:
            raise ValueError(f"set {name!r} has {count} blocks for "
                             f"{workload['threads']} callers")
        out[name] = SetSpec(name, s["id"], s["block_bytes"], count)
    return out


def server_of(workload: Dict[str, Any], thread: int, n_servers: int) -> int:
    """Which server a caller is bound to for the whole run."""
    if workload["bind"] != "round_robin":
        raise ValueError(f"unknown bind {workload['bind']!r}")
    return thread % n_servers


def _rng(seed: int, thread: int, salt: int) -> random.Random:
    return random.Random((seed << 20) ^ (thread << 8) ^ salt)


def mix_cycle(workload: Dict[str, Any], seed: int, thread: int) -> List[int]:
    """One cycle of mix indices: ``schedule_cycle`` times each weight,
    shuffled by the seed."""
    cycle = [i for i, m in enumerate(workload["mix"])
             for _ in range(m["weight"] * workload["schedule_cycle"])]
    _rng(seed, thread, 1).shuffle(cycle)
    return cycle


def block_order(spec: SetSpec, seed: int, thread: int,
                threads: int) -> List[int]:
    """This caller's share of a set: every ``threads``-th block of one seeded
    permutation, so no two callers ever hold the same block."""
    perm = list(range(spec.count))
    _rng(seed, 0, 2 + spec.set_id).shuffle(perm)
    return perm[thread::threads]


def schedule(workload: Dict[str, Any], seed: int,
             thread: int) -> Iterator[Call]:
    """The caller's calls, for as long as the window asks for them."""
    specs = set_specs(workload)
    mix = workload["mix"]
    cycle = mix_cycle(workload, seed, thread)
    orders = {name: block_order(s, seed, thread, workload["threads"])
              for name, s in specs.items()}
    cursor = {name: 0 for name in specs}
    while True:
        for i in cycle:
            m = mix[i]
            name = m["set"]
            order = orders[name]
            block = order[cursor[name] % len(order)]
            cursor[name] += 1
            yield Call(i, m["method"], name, block, specs[name].block_bytes)


def one_of_each(workload: Dict[str, Any], seed: int,
                thread: int) -> List[Call]:
    """One call of every mix entry, for the warm-up: every (method, size)
    this caller will send."""
    specs = set_specs(workload)
    out = []
    for i, m in enumerate(workload["mix"]):
        spec = specs[m["set"]]
        order = block_order(spec, seed, thread, workload["threads"])
        out.append(Call(i, m["method"], m["set"], order[-1],
                        spec.block_bytes))
    return out


def head(workload: Dict[str, Any], seed: int, thread: int,
         n: int) -> List[Tuple[int, int]]:
    """The first ``n`` calls as (mix, block): what the tests compare."""
    it = schedule(workload, seed, thread)
    return [(c.mix, c.block) for c, _ in zip(it, range(n))]
