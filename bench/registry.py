"""Finds the benchmark's parts by the names ``BENCHMARK.json`` gives them.

A configuration is ``configs/<name>.json``, a traffic mix
``mixes/<name>.json``, a per-layer metric a reader ``metrics/<name>.py``
with a ``read(rec)`` function and a sampler, named by a configuration's
``studies.sampler.name``, a plug-in ``samplers/<name>.py`` (its contract
is in ``samplers/__init__.py``), all beside this file.  A cell, mix,
metric or sampler is added with files and entries alone.
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


WHAT = {"configs": "configuration", "mixes": "traffic mix",
        "metrics": "metric reader", "samplers": "sampler plug-in"}


def _file(kind: str, name: str, suffix: str) -> Path:
    path = HERE / kind / (check_name(name) + suffix)
    if not path.is_file():
        raise FileNotFoundError(
            f"no {WHAT[kind]} named {name!r} "
            f"({path.relative_to(HERE.parent).as_posix()})")
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


def _module(prefix: str, name: str, path: Path):
    spec = importlib.util.spec_from_file_location(
        prefix + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metric_reader(name: str):
    """The ``read(rec) -> float | None`` function of a per-layer metric."""
    return _module("bench_metric_", name,
                   _file("metrics", name, ".py")).read


def sampler(name: str):
    """The plug-in module of the sampler a configuration names."""
    if name == "__init__":                  # the contract, no plug-in
        raise FileNotFoundError("no sampler plug-in named '__init__' "
                                "(bench/samplers/__init__.py)")
    return _module("bench_sampler_", name, _file("samplers", name, ".py"))


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
