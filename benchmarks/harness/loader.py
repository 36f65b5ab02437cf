"""From a cell's name in ``BENCHMARK.json`` to every file that defines it.

A cell is ``workloads/<name>.json`` (the traffic), which names
``configs/<config>.json`` (the deployment); its metrics are the entries of
``BENCHMARK.json`` that list the cell (or list no cell at all): the manifest
alone says what a metric is and which cells report it, and
``metrics/<name>.json`` holds nothing but its ``reader`` (where reading needs
code, a ``metrics/<name>.py`` beside it); its services and references are
found by the method names of the traffic's mix, its clients by the mix
entries' ``client`` (``unary`` where an entry names none) and its further
counters by the workload's ``counters``.  Code is found as data is: under the
first of ``ROOTS`` that has the file.  Nothing here knows a cell by name.
"""
from __future__ import annotations

import copy
import importlib
import importlib.util
import json
import os
import sys
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH_DIR)


# (manifest, benchmark directory) pairs, searched in order for the data
# files; the tests add their fixtures' pair
ROOTS = [(os.path.join(REPO, "BENCHMARK.json"), BENCH_DIR)]


class BenchmarkError(Exception):
    """The benchmark's files do not describe a cell that can be run."""


def _load(path: str) -> Dict[str, Any]:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _find(*parts: str) -> str:
    """``benchmarks/<parts>`` under the first root that has it."""
    for _, bench in ROOTS:
        path = os.path.join(bench, *parts)
        if os.path.exists(path):
            return path
    raise BenchmarkError(f"missing file benchmarks/{'/'.join(parts)}")


def manifest() -> Dict[str, Any]:
    """``BENCHMARK.json``; entries of a later root's manifest are added to
    the groups of the first."""
    man = _load(ROOTS[0][0])
    for more, _ in ROOTS[1:]:
        for group, entries in _load(more).items():
            man[group] = man[group] + entries
    return man


@dataclass
class Metric:
    name: str
    unit: str
    source: str
    reader: Dict[str, Any]              # metrics/<name>.json's only key
    module: Optional[Any] = None        # metrics/<name>.py, where there is one


# the client of a mix entry that names none: one ``Channel.call_method``
DEFAULT_CLIENT = "unary"


@dataclass
class Cell:
    name: str
    chips: int
    workload: Dict[str, Any]
    config: Dict[str, Any]
    config_name: str
    end_to_end: List[Metric] = field(default_factory=list)
    per_layer: List[Metric] = field(default_factory=list)

    def methods(self) -> List[str]:
        return sorted({m["method"] for m in self.workload["mix"]})

    def clients(self) -> List[str]:
        """The client of each mix entry, in the mix's order."""
        return [m.get("client", DEFAULT_CLIENT) for m in self.workload["mix"]]


def _deep_update(base: Dict[str, Any], over: Dict[str, Any]) -> None:
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(base.get(k), dict):
            _deep_update(base[k], v)
        else:
            base[k] = v


_code: Dict[str, Any] = {}             # file path -> its module, loaded once


def _load_code(path: str):
    """The module of one ``.py`` file under a root.  A file of the
    benchmark's own directory is imported as ``benchmarks.<kind>.<name>``
    (the module a plain import gives; its relative imports hold); a further
    root's file is loaded by its path and imports absolutely."""
    if path not in _code:
        kind = os.path.basename(os.path.dirname(path))
        stem = os.path.basename(path)[:-len(".py")]
        if os.path.dirname(os.path.dirname(path)) == BENCH_DIR \
                and stem.isidentifier():
            _code[path] = importlib.import_module(
                f"benchmarks.{kind}.{stem}")
        else:
            label = "".join(c if c.isalnum() else "_" for c in stem)
            spec = importlib.util.spec_from_file_location(
                f"benchmarks_{kind}_{label}_{len(_code)}", path)
            module = importlib.util.module_from_spec(spec)
            sys.modules[spec.name] = module     # dataclasses look it up
            spec.loader.exec_module(module)
            _code[path] = module
    return _code[path]


def _metric(entry: Dict[str, Any]) -> Metric:
    name = entry["name"]
    path = _find("metrics", f"{name}.json")
    code = path[:-len(".json")] + ".py"
    module = _load_code(code) if os.path.exists(code) else None
    return Metric(name=name, unit=entry["unit"], source=entry["source"],
                  reader=_load(path)["reader"], module=module)


def _metrics_of(cell: str, entries: List[Dict[str, Any]]) -> List[Metric]:
    return [_metric(e) for e in entries
            if cell in e.get("workloads", [cell])]


def load_cell(name: str, rehearse: bool = False) -> Cell:
    """The cell as it is run; with ``rehearse`` the workload's ``rehearse``
    overrides (tiny sizes for the CPU) are laid over it."""
    man = manifest()
    entry = next((w for w in man["workloads"] if w["name"] == name), None)
    if entry is None:
        raise BenchmarkError(
            f"no workload {name!r} in BENCHMARK.json (it has: "
            f"{[w['name'] for w in man['workloads']]})")
    workload = _load(_find("workloads", f"{name}.json"))
    if workload["config"] != entry["config"] or \
            workload["traffic"] != entry["traffic"]:
        raise BenchmarkError(
            f"workloads/{name}.json and BENCHMARK.json disagree on the "
            f"cell's config or traffic")
    over = workload.pop("rehearse", {})
    if rehearse:
        _deep_update(workload, copy.deepcopy(over))
    cfg_entry = next((c for c in man["configs"]
                      if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise BenchmarkError(
            f"no config {entry['config']!r} in BENCHMARK.json")
    prefix = "benchmarks/"
    if not cfg_entry["file"].startswith(prefix):
        raise BenchmarkError(f"{cfg_entry['file']} is not under {prefix}")
    config = _load(_find(*cfg_entry["file"][len(prefix):].split("/")))
    if config["chips"] != entry["chips"]:
        raise BenchmarkError(
            f"{cfg_entry['file']} is laid out on {config['chips']} chips, "
            f"the cell asks for {entry['chips']}")
    for spec in workload["sets"].values():      # "bytes": a key of the config
        if isinstance(spec.get("bytes"), str):
            spec["bytes"] = config[spec["bytes"]]
    cell = Cell(name=name, chips=entry["chips"], workload=workload,
                config=config, config_name=entry["config"],
                end_to_end=_metrics_of(name, man["end_to_end"]),
                per_layer=_metrics_of(name, man["per_layer"]))
    _resolve_code(cell)
    return cell


def _resolve_code(cell: Cell) -> None:
    """Every client and counter module the cell names exists, and no key a
    counter module gives is the table's or another module's: said here,
    before a device is touched."""
    from .counters import TABLE_KEYS, second_route
    for client in cell.clients():
        client_module(client)
    taken = {k: "harness/counters.py" for k in TABLE_KEYS}
    for name in cell.workload.get("counters", []):
        for key in counter_module(name).KEYS:
            if key in taken:
                raise BenchmarkError(
                    f"counters/{name}.py gives {key!r}, which "
                    f"{taken[key]} gives already")
            taken[key] = f"counters/{name}.py"
    for key in second_route(cell):
        if key not in taken:
            raise BenchmarkError(
                f"the configuration holds {key!r} at zero and no counter "
                f"module of the cell gives it")


def service_module(method: str):
    return _by_name("services", method)


def reference_module(method: str):
    return _by_name("reference", method)


def control_module(name: str):
    return _by_name("controls", name)


def client_module(name: str):
    return _by_name("clients", name)


def counter_module(name: str):
    return _by_name("counters", name)


def _by_name(kind: str, name: str):
    """``<kind>/<name>.py`` under the first root that has it."""
    return _load_code(_find(kind, f"{name}.py"))


def peaks(device_kind: str) -> Dict[str, Any]:
    """The published peaks of one chip; an unknown kind is an error."""
    table = _load(_find("peaks.json"))["chips"]
    if device_kind not in table:
        raise BenchmarkError(
            f"device kind {device_kind!r} is not in benchmarks/peaks.json "
            f"(it has: {sorted(table)}): no peak, no share of one")
    return table[device_kind]
