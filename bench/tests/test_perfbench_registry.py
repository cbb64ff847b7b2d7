"""The harness finds every configuration, mix, metric and sampler plug-in
by its name."""
import json
import re
import shutil

import pytest

import registry
from repro.core.samplers import known_samplers


def test_every_name_in_benchmark_json_has_its_file():
    bench = registry.benchmark()
    for c in bench["configs"]:
        assert registry.config(c["name"])["name"] == c["name"]
        assert (registry.ROOT / c["file"]).is_file()
    for w in bench["workloads"]:
        cell, config, mix = registry.cell(bench, w["name"])
        assert cell is w and config["name"] == w["config"]
        assert mix["loop"] == "open"
    for m in bench["per_layer"]:
        assert callable(registry.metric_reader(m["name"]))


def test_each_cell_reports_setup_another_end_to_end_and_a_layer_metric():
    bench = registry.benchmark()
    for w in bench["workloads"]:
        e2e = {m["name"] for m in registry.metrics_of(bench, w["name"], False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert registry.metrics_of(bench, w["name"], True)


@pytest.mark.parametrize("name", ["a/b", "a b", ".hidden", "-x", "", "é",
                                  "x" * 65, "../configs"])
def test_illegal_names_are_refused(name):
    with pytest.raises(ValueError):
        registry.check_name(name)
    with pytest.raises(ValueError):
        registry.config(name)


def test_unknown_names_are_refused():
    with pytest.raises(FileNotFoundError):
        registry.mix("no-such-mix")
    with pytest.raises(KeyError):
        registry.cell(registry.benchmark(), "no-such-cell")


def test_a_reader_that_finds_nothing_returns_nothing():
    empty = {"trace": None, "launcher": {"compiles": 0, "fsyncs": [None, None],
                                         "call_shapes": []},
             "device": {"kind": "TPU v5 lite"}, "asks_answered": 0,
             "tells_answered": 0}
    got = {m["name"]: registry.metric_reader(m["name"])(empty)
           for m in registry.benchmark()["per_layer"]}
    assert got.pop("compile.window_count") == 0
    assert set(got.values()) == {None}


def test_benchmark_json_is_valid_json_with_the_contract_keys():
    bench = json.loads((registry.ROOT / "BENCHMARK.json").read_text())
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}


CONTRACT = ("SPANS", "warm_shapes", "warm", "Recorder", "load", "check",
            "top", "control_high", "FAULTS")


def test_every_configuration_has_its_sampler_plug_in():
    for c in registry.benchmark()["configs"]:
        name = registry.config(c["name"])["studies"]["sampler"]["name"]
        assert (registry.HERE / "samplers" / f"{name}.py").is_file()
        plug_in = registry.sampler(name)
        assert all(hasattr(plug_in, k) for k in CONTRACT), name
        assert all(callable(f) for f in plug_in.FAULTS.values())


def _samplers_copy(tmp_path, monkeypatch):
    """The registry pointed at a copy of ``samplers/`` that has no plug-in
    for ``gp``, whichever plug-ins the benchmark has."""
    bench = tmp_path / "bench"
    shutil.copytree(registry.HERE / "samplers", bench / "samplers")
    (bench / "samplers" / "gp.py").unlink(missing_ok=True)
    monkeypatch.setattr(registry, "HERE", bench)
    return bench


def test_a_plug_in_dropped_into_samplers_is_found_by_name(tmp_path,
                                                         monkeypatch):
    bench = _samplers_copy(tmp_path, monkeypatch)
    (bench / "samplers" / "toy.py").write_text(
        '"""A sampler no configuration runs."""\nFAULTS = {"none": None}\n')
    assert registry.sampler("toy").FAULTS == {"none": None}
    assert registry.sampler("tpe").__file__ == str(
        bench / "samplers" / "tpe.py")
    with pytest.raises(FileNotFoundError, match=re.escape(
            "no sampler plug-in named 'gp' (bench/samplers/gp.py)")):
        registry.sampler("gp")


@pytest.mark.parametrize("name,error", [
    ("gp", FileNotFoundError),            # no file
    ("__init__", FileNotFoundError),      # the contract, not a plug-in
    ("../configs", ValueError), ("a/b", ValueError), ("", ValueError),
    (".tpe", ValueError), ("x" * 65, ValueError)])
def test_an_unknown_or_illegal_sampler_name_is_refused(name, error, tmp_path,
                                                       monkeypatch):
    _samplers_copy(tmp_path, monkeypatch)
    with pytest.raises(error):
        registry.sampler(name)


@pytest.mark.parametrize("name", known_samplers())
def test_a_plug_in_is_found_for_every_sampler_the_service_registers(
        name, tmp_path, monkeypatch):
    bench = _samplers_copy(tmp_path, monkeypatch)
    (bench / "samplers" / f"{name}.py").write_text(
        f'"""A throwaway plug-in."""\nNAME = {name!r}\n')
    plug_in = registry.sampler(name)
    assert plug_in.NAME == name
    assert plug_in.__file__ == str(bench / "samplers" / f"{name}.py")
