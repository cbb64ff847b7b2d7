"""Spans on the served ask/report/tell path (``repro.core.tracing``) and
the HTTP frontend's lane counters under ``GET /api/v2/health``."""
import glob
import json
import os
import threading
import time
import urllib.request

import jax
import pytest

from repro.core import (Client, HopaasServer, HttpServiceRunner,
                        HttpTransport, InMemoryStorage, TokenManager,
                        suggestions, tracing)
from repro.core.durable import DurableStorage

SPANS = ("http.ask", "http.report", "http.tell", "http.request",
         "http.read", "study.lock_wait", "obs_cache.sync", "tpe.propose",
         "tpe.readback", "pruner.should_prune", "wal.append", "wal.fsync",
         "wal.compact")


def _profile_lines(trace_dir: str) -> list[list[tuple]]:
    """Per host line of the profile, its program spans as
    (name, start_ns, end_ns)."""
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    lines = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [(e.name, int(e.start_ns),
                      int(e.start_ns + e.duration_ns))
                     for e in line.events if e.name in SPANS]
            if spans:
                lines.append(spans)
    return lines


def _profiled(trace_dir: str, fn) -> list[list[tuple]]:
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return _profile_lines(trace_dir)


def _children(line: list[tuple], parent: tuple) -> set[str]:
    """Names of the spans on ``line`` inside ``parent``'s interval."""
    return {s[0] for s in line if s is not parent
            and parent[1] <= s[1] and s[2] <= parent[2]}


def _health(runner) -> dict:
    with urllib.request.urlopen(runner.url + "/api/v2/health",
                                timeout=10) as r:
        return json.loads(r.read())


def test_inactive_span_is_one_shared_noop(tmp_path):
    assert not tracing._active
    assert tracing.span("http.ask") is tracing.span("wal.append")

    def work():
        with tracing.span("http.ask"):
            with tracing.span("tpe.propose"):
                time.sleep(0.001)
    assert _profiled(str(tmp_path), work) == []


def test_served_trial_writes_nested_spans(tmp_path):
    storage = DurableStorage(str(tmp_path / "wal"), fsync="off")
    tokens = TokenManager()
    runner = HttpServiceRunner([HopaasServer(storage=storage,
                                             tokens=tokens)],
                               backend="evloop", workers=1).start()
    try:
        client = Client(HttpTransport(runner.host, runner.port),
                        tokens.issue("u"))
        key, _ = client.ensure_study({
            "name": "traced", "direction": "minimize",
            "properties": {"x": suggestions.uniform(0, 1),
                           "y": suggestions.uniform(0, 1)},
            "sampler": {"name": "tpe", "n_startup_trials": 3},
            "pruner": {"name": "median", "n_startup_trials": 1}})
        for i in range(5):       # past the sampler's start-up, unpruned
            t = client.ask(key)
            client.report(t["uid"], 0, -float(i))
            client.tell(t["uid"], -float(i))

        def one_trial():
            t = client.ask(key)
            client.report(t["uid"], 0, 0.5)
            client.tell(t["uid"], 0.5)
        tracing.enable()
        try:
            lines = _profiled(str(tmp_path / "trace"), one_trial)
        finally:
            tracing.disable()
    finally:
        runner.stop()
        storage.close()

    expect = {"http.ask": {"study.lock_wait", "obs_cache.sync",
                           "tpe.propose", "tpe.readback", "wal.append"},
              "http.report": {"study.lock_wait", "pruner.should_prune",
                              "wal.append"},
              "http.tell": {"study.lock_wait", "wal.append"}}
    seen = {}
    for line in lines:
        for span in line:
            if span[0] in expect:
                seen[span[0]] = _children(line, span)
                if span[0] == "http.ask":
                    propose = next(s for s in line if s[0] == "tpe.propose")
                    assert "tpe.readback" in _children(line, propose)
    assert seen == expect
    assert any(s[0] == "http.read" for line in lines for s in line)


def test_health_frontend_lane_counters():
    tokens = TokenManager()
    runner = HttpServiceRunner([HopaasServer(storage=InMemoryStorage(),
                                             tokens=tokens)],
                               backend="evloop", workers=1).start()
    try:
        before = _health(runner)["frontend"]
        client = Client(HttpTransport(runner.host, runner.port),
                        tokens.issue("u"))
        for _ in range(5):
            client.version()
        after = _health(runner)["frontend"]
    finally:
        runner.stop()
    assert after["requests"] >= before["requests"] + 6
    lanes = after["per_lane"]
    assert len(lanes) == after["lanes"]
    assert sum(lane["handled"] for lane in lanes) == after["requests"]
    assert all(set(lane) == {"handled", "inline", "cache_hits", "queued",
                             "wait_ns", "busy_ns"} for lane in lanes)
    # one connection, one request at a time, an in-memory store: every
    # dispatch runs inline on the IO thread and never waits in a queue
    # (the health request that reads the counters is inline, unfinished)
    assert after["inline_requests"] == after["requests"] + 1
    assert all(lane["wait_ns"] == 0 for lane in lanes)
    assert sum(lane["busy_ns"] for lane in lanes) > 0


def test_lane_wait_is_counted_when_dispatch_is_queued(tmp_path):
    storage = DurableStorage(str(tmp_path / "wal"), fsync="off")
    tokens = TokenManager()
    runner = HttpServiceRunner([HopaasServer(storage=storage,
                                             tokens=tokens)],
                               backend="evloop", workers=1).start()
    try:
        client = Client(HttpTransport(runner.host, runner.port),
                        tokens.issue("u"))
        threads = [threading.Thread(target=client.version)
                   for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        assert not any(t.is_alive() for t in threads)
        lanes = _health(runner)["frontend"]["per_lane"]
    finally:
        runner.stop()
        storage.close()
    handled = [lane for lane in lanes if lane["handled"]]
    assert sum(lane["handled"] for lane in handled) >= 4
    assert all(lane["inline"] == 0 and lane["wait_ns"] > 0
               for lane in handled)
