"""Find what a cell is made of, by name, from files of its own.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.
Each lives in a file that is found by that name alone:

* ``configs/<config>.json``   the model's sizes and how it is computed;
* ``traffic/<traffic>.json``  the mix's parameters, read by the runner
  it names (``runners/<runner>.py``);
* ``metrics/<metric>.py``     the reader of one per-layer metric;
* ``peaks.json``              the chip's peaks, keyed by ``device_kind``.

The configuration names the adapter of the system under test
(``systems/<system>.py``) and its plain reference
(``references/<reference>.py``). Adding any of these is adding a file;
no existing file changes.
"""

from __future__ import annotations

import importlib.util
import json
import os
from types import ModuleType
from typing import Callable, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


class UnknownDevice(LookupError):
    """A device kind that ``peaks.json`` does not hold."""


def _json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; cells: "
                   f"{[w['name'] for w in bench['workloads']]}")


def metrics_for(bench: dict, workload: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports: those
    that list it under ``workloads``, or that list no cells at all."""
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


def config(name: str, base: str = HERE) -> dict:
    return _json(os.path.join(base, "configs", f"{name}.json"))


def traffic(name: str, base: str = HERE) -> dict:
    return _json(os.path.join(base, "traffic", f"{name}.json"))


def peaks(device_kind: str, base: str = HERE) -> dict:
    table = _json(os.path.join(base, "peaks.json"))
    if device_kind not in table:
        raise UnknownDevice(
            f"device kind {device_kind!r} is not in peaks.json "
            f"({sorted(table)}); add its published peaks with their "
            "source")
    return table[device_kind]


def load_module(path: str, name: Optional[str] = None) -> ModuleType:
    """Import a file by its path (metric files carry dots in their
    names, so they are not importable by module name)."""
    name = name or "benchchip_" + os.path.basename(path).replace(
        ".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def part(kind: str, name: str, base: str = HERE) -> ModuleType:
    """``runners``, ``systems`` or ``references`` module by name."""
    path = os.path.join(base, kind, f"{name}.py")
    if not os.path.exists(path):
        raise KeyError(f"no {kind[:-1]} {name!r} ({path})")
    return load_module(path, f"benchchip_{kind}_{name.replace('-', '_')}")


def metric_reader(name: str, base: str = HERE) -> Callable:
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    path = os.path.join(base, "metrics", f"{name}.py")
    if not os.path.exists(path):
        raise KeyError(f"no reader for per-layer metric {name!r} ({path})")
    return load_module(path).read
