"""The trace reduction: busy union, program and kernel time, gap
attribution, on a hand-made trace and on one recorded on the chip."""
import gzip
import json
from pathlib import Path

import pytest

import devtrace
import registry

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "hot_trace.json.gz"


def _trace():
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit__tpe_propose(12)", 1_000, 3_000],
            ["jit__tpe_propose(12)", 10_000, 2_000],
            ["jit_convert_element_type(3)", 20_000, 500]]},
        {"name": "XLA Ops", "events": [
            ["fusion.1", 1_000, 1_000], ["_parzen_kernel", 1_500, 2_000],
            ["_parzen_kernel", 10_000, 2_000], ["copy.2", 11_000, 500],
            ["convert", 20_000, 500]]}]}
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ["PjitFunction(_tpe_propose)", 3_500, 5_000],
        ["TransferToDevice", 12_500, 6_000]]}]}
    return {"planes": [dev, host]}


def test_busy_union_programs_and_kernels():
    r = devtrace.reduce(_trace(), 30_000)
    # ops cover [1000, 3500) + [10000, 12000) + [20000, 20500)
    assert r["busy_s"] == pytest.approx(5_000e-9)
    assert r["window_s"] == pytest.approx(30e-6)
    assert r["programs"]["tpe_propose"] == [2, pytest.approx(5e-6)]
    assert r["programs"]["convert_element_type"][0] == 1
    assert r["kernels"]["parzen"] == [2, pytest.approx(4e-6)]
    assert r["top_ops"][0] == ["_parzen_kernel", pytest.approx(4e-6)]


def test_idle_gaps_go_to_the_host_event_that_covers_most_of_them():
    r = devtrace.reduce(_trace(), 30_000)
    gaps = dict(r["idle_gaps"])
    # [3500, 10000): the dispatch covers 5000 of 6500 ns
    assert gaps["PjitFunction(_tpe_propose)"] == pytest.approx(6_500e-9)
    # [12000, 20000): the transfer covers 6000 of 8000 ns
    assert gaps["TransferToDevice"] == pytest.approx(8_000e-9)
    # [0, 1000) and [20500, 30000): nothing on the host
    assert gaps["host (no event)"] == pytest.approx(10_500e-9)
    assert sum(gaps.values()) == pytest.approx(30e-6 - r["busy_s"])


def test_idle_gaps_leave_the_services_spans_to_idle_by_span():
    # a request span around both gaps would outweigh the JAX events
    # inside them; it is read by ``bench/spans.py`` instead
    t = _trace()
    t["planes"][1]["lines"].append({"name": "lane", "events": [
        ["http.ask", 0, 30_000], ["tpe.propose", 3_000, 17_000]]})
    names = ("http.ask", "tpe.propose")
    assert devtrace.reduce(t, 30_000, names)["idle_gaps"] == \
        devtrace.reduce(_trace(), 30_000)["idle_gaps"]


def test_events_outside_the_window_do_not_count():
    r = devtrace.reduce(_trace(), 5_000)
    assert r["busy_s"] == pytest.approx(2_500e-9)
    assert r["programs"] == {"tpe_propose": [1, pytest.approx(3e-6)]}


def test_no_device_plane_reads_as_nothing_busy():
    t = _trace()
    t["planes"] = t["planes"][1:]
    r = devtrace.reduce(t, 30_000)
    assert r["busy_s"] == 0.0 and r["programs"] == {}
    meta = {"trace": r, "launcher": {"call_shapes": []},
            "device": {"kind": "TPU v5 lite"}, "asks_answered": 3}
    assert registry.metric_reader("device.idle_share")(meta) is None
    assert registry.metric_reader("tpe_propose.device_ms")(meta) is None


def test_recorded_chip_trace():
    # the first 250 ms of a traced window of the hot cell on one TPU v5e,
    # host transposes left out to keep the file small
    trace = json.loads(gzip.decompress(FIXTURE.read_bytes()))
    r = devtrace.reduce(trace, 250_000_000)
    assert r["devices"] == 1
    calls, seconds = r["programs"]["tpe_propose"]
    # each ask runs the proposal program once, and it runs the Parzen
    # kernel twice: on the good rows and on the bad rows
    assert calls == 16 and r["kernels"]["parzen"][0] == 2 * calls
    assert 0 < r["kernels"]["parzen"][1] < seconds
    assert r["top_ops"][0][0].startswith("_parzen_pallas")
    assert 0 < r["busy_s"] < 0.01
    gaps = dict(r["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(0.25 - r["busy_s"])
    # the host waits on results while the device idles
    assert max(gaps, key=gaps.get) == "np.asarray(jax.Array)"
    meta = {"trace": r, "device": {"kind": "TPU v5 lite"},
            "launcher": {"call_shapes": [[[32, 8192, 24, 64], calls]]},
            "asks_answered": calls}
    share = registry.metric_reader("parzen_roofline")(meta)
    assert 0 < share < 100
    assert registry.metric_reader("tpe_propose.calls_per_ask")(meta) == 1.0
    idle = registry.metric_reader("device.idle_share")(meta)
    assert 99 < idle < 100
