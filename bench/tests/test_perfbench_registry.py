"""The harness finds every configuration, mix and metric by its name."""
import json

import pytest

import registry


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
