"""Find a cell's files by the names that ``BENCHMARK.json`` gives.

``ROOT`` is the checkout and ``BENCH`` the benchmark's directory in it;
both are read at each call.

A later change adds a configuration, a traffic mix, a per-layer metric or a
reference by adding a file under the matching directory and an entry in
``BENCHMARK.json``; nothing here names one of them.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class UnknownName(KeyError):
    pass


def _checked(name: str) -> str:
    if not isinstance(name, str) or not _NAME.match(name):
        raise UnknownName(f"not a valid name: {name!r}")
    return name


def _json(path: str) -> dict:
    if not os.path.isfile(path):
        raise UnknownName(f"no file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _json(os.path.join(ROOT, "BENCHMARK.json"))


def workload(name: str, bench: dict) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise UnknownName(f"no workload {name!r} in BENCHMARK.json")


def config(name: str, bench: dict) -> dict:
    for c in bench["configs"]:
        if c["name"] == _checked(name):
            return _json(os.path.join(ROOT, c["file"]))
    raise UnknownName(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    return _json(os.path.join(BENCH, "traffic", _checked(name) + ".json"))


def limits(cell: str) -> dict:
    return _json(os.path.join(BENCH, "limits", _checked(cell) + ".json"))


def peaks() -> dict:
    return _json(os.path.join(BENCH, "peaks.json"))


def _module(kind: str, name: str):
    path = os.path.join(BENCH, kind, _checked(name).replace("-", "_")
                        + ".py")
    if not os.path.isfile(path):
        raise UnknownName(f"no file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{os.path.basename(path)[:-3]}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """``metrics/<name>.py``; its ``read(ctx)`` returns a number or None."""
    return _module("metrics", name)


def reference(name: str):
    """``references/<name>.py``: the plain float32 model of a configuration."""
    return _module("references", name)


def flops(name: str):
    """``flops/<name>.py``: ``model_flops_per_step(conf, traffic)``."""
    return _module("flops", name)


def cell_metrics(cell: str, bench: dict, kind: str) -> list:
    """The metrics of ``kind`` ("end_to_end" | "per_layer") this cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]
