"""Finds the benchmark's parts by the names ``BENCHMARK.json`` gives them.

A configuration is ``configs/<name>.json``, a traffic mix
``mixes/<name>.json`` and a per-layer metric a reader
``metrics/<name>.py`` with a ``read(rec)`` function, all beside this file.
A cell, mix or metric is added with files and entries alone.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


def check_name(name: str) -> str:
    if not isinstance(name, str) or not NAME.fullmatch(name):
        raise ValueError(f"illegal name {name!r}: 1 to 64 of A-Z a-z 0-9 "
                         "_ . - and not starting with . or -")
    return name


def _file(kind: str, name: str, suffix: str) -> Path:
    path = HERE / kind / (check_name(name) + suffix)
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    return path


def benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise FileNotFoundError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def config(name: str) -> dict:
    return json.loads(_file("configs", name, ".json").read_text())


def mix(name: str) -> dict:
    return json.loads(_file("mixes", name, ".json").read_text())


def metric_reader(name: str):
    """The ``read(rec) -> float | None`` function of a per-layer metric."""
    path = _file("metrics", name, ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def cell(bench: dict, workload: str) -> tuple[dict, dict, dict]:
    """(workload entry, configuration, mix) of one cell."""
    check_name(workload)
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w, config(w["config"]), mix(w["traffic"])
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def metrics_of(bench: dict, workload: str, trace: bool) -> list[dict]:
    """The metric entries a run of ``workload`` reports: its end-to-end
    metrics untraced, its per-layer metrics traced."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if workload in m.get("workloads", [workload])]
