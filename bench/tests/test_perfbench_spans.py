"""The reduction of the service's own spans (``bench/spans.py``) and the
host-layer metrics that read it, on a hand-made trace, on a record of a
window traced on the chip and in a traced rehearsal."""
import json
import shutil
from pathlib import Path

import pytest

import registry
import run
import spans
from _rehearse import rehearse

MS = 1_000_000
RECORD = Path(__file__).resolve().parent / "fixtures" / "spans_record.json"

# the spans a run of the TPE configuration reads
NAMES = spans.names(registry.sampler("tpe"))
READERS = ("frontend.lane_ms_per_trial", "frontend.lane_wait_ms",
           "study.lock_wait_ms", "obs_cache.sync_ms", "tpe.host_ms",
           "tpe.readback_ms", "pruner.report_ms", "wal.append_ms")


def _ev(name, a, b):
    return [name, a * MS, (b - a) * MS]


def _trace():
    """A 3 s window: the device runs two ops; a lane serves an ask and a
    tell, another a report; a third thread reads one request and runs a
    compaction longer than 1 s."""
    dev = {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
        _ev("fusion", 100, 200), _ev("_parzen_pallas", 1000, 1100)]}]}
    lane = {"name": "python", "events": [
        _ev("http.ask", 50, 900), _ev("study.lock_wait", 60, 80),
        _ev("tpe.propose", 150, 700), _ev("XlaLinearize", 300, 310),
        _ev("tpe.readback", 500, 700), _ev("http.tell", 2000, 2100),
        _ev("wal.append", 2010, 2030), _ev("wal.fsync", 3100, 3200)]}
    other_lane = {"name": "python", "events": [
        _ev("http.report", 1200, 1300)]}
    io = {"name": "python", "events": [
        _ev("http.read", 20, 40), _ev("wal.compact", 800, 2500)]}
    return {"planes": [dev, {"name": "/host:CPU",
                             "lines": [lane, other_lane, io]}]}


def test_per_name_counts_spans_starting_in_the_window():
    r = spans.reduce(_trace(), 3000 * MS, NAMES)
    assert r["per_name"] == {
        "http.ask": [1, pytest.approx(0.85)],
        "study.lock_wait": [1, pytest.approx(0.02)],
        "tpe.propose": [1, pytest.approx(0.55)],
        "tpe.readback": [1, pytest.approx(0.2)],
        "http.tell": [1, pytest.approx(0.1)],
        "wal.append": [1, pytest.approx(0.02)],
        "http.report": [1, pytest.approx(0.1)],
        "http.read": [1, pytest.approx(0.02)],
        "wal.compact": [1, pytest.approx(1.7)]}      # over 1 s, kept


def test_idle_time_goes_to_the_innermost_span_of_a_request_first():
    r = spans.reduce(_trace(), 3000 * MS, NAMES)
    idle = dict(r["idle_by_span"])
    # gaps [0, 100), [200, 1000), [1100, 3000) ms
    assert idle == {
        "no span": pytest.approx(0.53),            # 0-20, 40-50, 2500-3000
        "http.read": pytest.approx(0.02),
        "http.ask (self)": pytest.approx(0.23),    # 50-60, 80-100, 700-900
        "study.lock_wait": pytest.approx(0.02),
        "tpe.propose": pytest.approx(0.3),         # 200-500
        "tpe.readback": pytest.approx(0.2),
        # 900-1000, 1100-1200, 1300-2000, 2100-2500: no request open
        "wal.compact": pytest.approx(1.3),
        "http.report (self)": pytest.approx(0.1),
        "http.tell (self)": pytest.approx(0.08),
        "wal.append": pytest.approx(0.02)}
    assert sum(idle.values()) == pytest.approx(2.8)
    assert r["idle_by_span"][0][0] == "wal.compact"


def test_no_device_plane_makes_the_whole_window_idle():
    t = _trace()
    t["planes"] = t["planes"][1:]
    idle = dict(spans.reduce(t, 3000 * MS, NAMES)["idle_by_span"])
    assert sum(idle.values()) == pytest.approx(3.0)
    assert idle["tpe.propose"] == pytest.approx(0.35)      # 150-500


def _record(reduced, frontend):
    return {"launcher": {"spans": reduced, "frontend": frontend},
            "trace": None, "asks_answered": 1, "tells_answered": 1}


def _lanes(handled, wait_ns):
    return {"per_lane": [{"handled": handled, "inline": 0,
                          "cache_hits": 0, "queued": 0,
                          "wait_ns": wait_ns, "busy_ns": 0},
                         {"handled": 2, "inline": 2, "cache_hits": 0,
                          "queued": 0, "wait_ns": 0, "busy_ns": 0}]}


def test_readers_on_a_reduced_trace():
    rec = _record(spans.reduce(_trace(), 3000 * MS, NAMES),
                  [_lanes(10, 1_000_000), _lanes(14, 9_000_000)])
    got = {name: registry.metric_reader(name)(rec) for name in READERS}
    assert got == {
        "frontend.lane_ms_per_trial": pytest.approx(1050.0),  # ask+tell+report
        "frontend.lane_wait_ms": pytest.approx(2.0),          # 8 ms / 4
        "study.lock_wait_ms": pytest.approx(20.0),
        "obs_cache.sync_ms": None,                            # no such span
        "tpe.host_ms": pytest.approx(350.0),                  # 550 - 200
        "tpe.readback_ms": pytest.approx(200.0),
        "pruner.report_ms": None,
        "wal.append_ms": pytest.approx(20.0)}


@pytest.mark.parametrize("launcher", [
    {},                                           # a parent's record
    {"spans": None, "frontend": [None, None]},    # tracing never enabled
    {"spans": {"per_name": {}, "idle_by_span": []},
     "frontend": [{"backend": "threaded"}, {"backend": "threaded"}]}])
def test_readers_find_nothing_without_spans_or_counters(launcher):
    rec = {"launcher": launcher, "trace": None, "asks_answered": 5,
           "tells_answered": 5}
    assert {registry.metric_reader(n)(rec) for n in READERS} == {None}


def test_readers_on_a_recorded_chip_record():
    rec = json.loads(RECORD.read_text())
    got = {name: registry.metric_reader(name)(rec) for name in READERS}
    assert got == pytest.approx(rec["metrics"])
    idle = dict(rec["launcher"]["spans"]["idle_by_span"])
    assert sum(idle.values()) == pytest.approx(
        rec["window_s"] - rec["busy_s"])
    assert idle["no span"] < 0.1 * rec["window_s"]


def test_a_traced_rehearsal_hands_the_readers_spans_and_lane_counters():
    # the launcher enables the service's spans for the traced window and
    # collects the lanes' counters at its edges; each reader finds a number
    out = rehearse("campaign-tpe-overload", 2**31 + 23, "--trace", "1")
    assert out["correct"] is True, out["stderr"][-3000:]
    got = {n: (out["metrics"].get(n) or {}).get("value") for n in READERS}
    assert all(isinstance(v, float) and v > 0 for v in got.values()), got


@pytest.mark.parametrize("n_rows,kept", [(3, 3), (10, 10), (14, 10)])
def test_the_breakdown_keeps_ten_rows_and_their_total(n_rows, kept):
    rows = [[f"span{i}", 1.0 / (i + 1)] for i in range(n_rows)]
    got = run.top_rows(rows)
    assert len(got) == kept
    assert sum(s for _n, s in got) == pytest.approx(sum(s for _n, s in rows))
    assert got[:9] == rows[:9]
    if n_rows > kept:
        assert got[-1][0] == "other"


def test_a_plug_in_s_own_spans_are_read_and_left_out_of_idle_gaps(
        tmp_path, monkeypatch):
    # a sampler added with its file alone declares a span the service's
    # list does not name; the launcher's reduction reads it by that name
    import serve
    bench = tmp_path / "bench"
    shutil.copytree(registry.HERE / "samplers", bench / "samplers")
    (bench / "samplers" / "toy.py").write_text(
        '"""A sampler no configuration runs."""\nSPANS = ("toy.solve",)\n')
    monkeypatch.setattr(registry, "HERE", bench)
    toy = registry.sampler("toy")
    t = _trace()
    t["planes"][1]["lines"].append({"name": "python", "events": [
        _ev("toy.solve", 1150, 2900), _ev("XlaLinearize", 1200, 1210)]})
    got = serve.reduce_trace(t, 3000 * MS, toy)
    assert got["spans"]["per_name"]["toy.solve"] == [1, pytest.approx(1.75)]
    assert "tpe.propose" not in got["spans"]["per_name"]
    # 1150-1200, 1300-2000, 2100-2900: no request open, and the toy span
    # opened after the compaction
    idle = dict(got["spans"]["idle_by_span"])
    assert idle["toy.solve"] == pytest.approx(1.55)
    assert sum(idle.values()) == pytest.approx(2.8)
    # the device's gap [1100, 3000) goes to the JAX event inside it
    gaps = dict(got["trace"]["idle_gaps"])
    assert "toy.solve" not in gaps and "wal.compact" not in gaps
    assert gaps["XlaLinearize"] == pytest.approx(1.9)
