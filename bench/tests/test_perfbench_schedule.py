"""The open-loop schedule is fixed by the seed, and requests are timed from
when they were due."""
import http.server
import json
import threading
import time

import numpy as np
import pytest

import plan as plans
import registry
from loadgen import ASK, REPORT, TELL, Load


ZIPF = {"popularity": {"zipf": 0.99}}


@pytest.mark.parametrize("mix_name,n_studies,change", [
    ("campaign-overload", 1, {}), ("campaign-overload", 1000, ZIPF)])
def test_schedule_is_a_function_of_the_seed(mix_name, n_studies, change):
    mix = dict(registry.mix(mix_name), **change)
    a = plans.schedule(mix, n_studies, 20.0, 2**31 + 17)
    b = plans.schedule(mix, n_studies, 20.0, 2**31 + 17)
    c = plans.schedule(mix, n_studies, 20.0, 5)
    for k in ("due", "study", "think"):
        assert np.array_equal(a[k], b[k])
    assert not np.array_equal(a["due"], c["due"])
    # two seeds do the same work: as many starts, the same think times and
    # the same starts per study, in another order
    assert len(a["due"]) == len(c["due"]) == round(mix["rate"] * 23.0)
    assert np.allclose(np.sort(a["think"].ravel()), np.sort(c["think"].ravel()))
    assert np.array_equal(np.bincount(a["study"], minlength=n_studies),
                          np.bincount(c["study"], minlength=n_studies))
    assert np.all(np.diff(a["due"]) >= 0) and a["due"][-1] < a["end"]
    # the think times keep `in_flight` trials running at `rate`
    assert np.mean(a["think"]) == pytest.approx(
        mix["in_flight"] / mix["rate"], rel=0.05)


def test_deployment_layout_does_not_depend_on_the_seed():
    config = registry.config("campaign-tpe")
    assert [s["n_history"] for s in plans.studies(config, 1)] == [2000]
    # a deployment of many studies draws their histories from its own
    # layout seed, the same under every run seed
    many = dict(config, studies=dict(config["studies"], count=50,
                                     history=[130, 250]))
    a, b = plans.studies(many, 1), plans.studies(many, 2)
    assert [(s["space"], s["n_history"]) for s in a] == \
        [(s["space"], s["n_history"]) for s in b]
    assert all(130 <= s["n_history"] <= 250 for s in a)
    assert len({s["n_history"] for s in a}) > 10
    p1, v1 = plans.history(a[0], 1)
    p2, v2 = plans.history(b[0], 2)
    assert len(p1) == len(p2) == a[0]["n_history"] and p1 != p2
    assert np.array_equal(v1, plans.history(a[0], 1)[1])


def test_zipf_popularity_follows_its_constant():
    mix = dict(registry.mix("campaign-overload"), **ZIPF)
    s = plans.schedule(mix, 1000, 40.0, 3)
    counts = np.bincount(s["study"], minlength=1000)
    w = 1.0 / np.arange(1, 1001) ** 0.99
    assert counts[0] == pytest.approx(len(s["due"]) * w[0] / w.sum(), rel=0.02)
    assert counts[0] > counts[9] > counts[99]


class _SlowService(http.server.BaseHTTPRequestHandler):
    """Answers every v2 call after a fixed delay, one request at a time."""
    protocol_version = "HTTP/1.1"
    delay = 0.05
    lock = threading.Lock()
    n = 0

    def do_POST(self):
        self.body = self.rfile.read(int(self.headers["Content-Length"]))
        with self.lock:
            time.sleep(self.delay)
            type(self).n += 1
            n = self.n
        if self.path.endswith(":ask"):
            body = f'{{"uid": "s:{n}", "params": {{"x": 0.5}}}}'
        elif self.path.endswith(":ask_batch"):
            body = json.dumps({"trials": [
                {"uid": f"s:{n}.{i}", "params": {"x": 0.5}}
                for i in range(json.loads(self.body)["n"])]})
        elif self.path.endswith(":report"):
            body = '{"should_prune": false}'
        else:
            body = '{"state": "completed"}'
        data = body.encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def log_message(self, *a):
        pass


def test_latency_runs_from_due_time_not_send_time():
    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _SlowService)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        # three asks due at once on one connection: the service takes 50 ms
        # each, so the second and third leave late, and their latency
        # counts the wait
        sched = {"due": np.array([0.05, 0.05, 0.05]),
                 "study": np.zeros(3, np.int64),
                 "think": np.full((3, 1), 0.3), "batch": 1, "reports": 1}
        url = f"http://127.0.0.1:{server.server_address[1]}"
        load = Load(url, "t", ["k"], sched, lambda s, p: 1.0,
                    lambda v, k: v, time.perf_counter(), connections=1)
        load.run(stop=10.0, deadline=10.0)
        load.close()
    finally:
        server.shutdown()
        server.server_close()
    asks = sorted((r for r in load.log if r[0] == ASK), key=lambda r: r[2])
    assert [r[4] for r in load.log] == [200] * 9
    assert sum(r[0] == REPORT for r in load.log) == 3
    assert sum(r[0] == TELL for r in load.log) == 3
    late = [r[2] - r[1] for r in asks]
    lat = [r[3] - r[1] for r in asks]
    assert late[0] < 0.02 and late[2] >= 0.09
    assert all(lt >= w + 0.045 for lt, w in zip(lat, late))
    assert lat[2] >= 0.14
    assert len(load.acks) == 6 and len(load.served) == 3


def test_a_batch_ask_starts_one_chain_of_reports_per_trial():
    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _SlowService)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        sched = {"due": np.array([0.01]), "study": np.zeros(1, np.int64),
                 "think": np.array([[0.2, 0.3, 0.4]]), "batch": 3,
                 "reports": 2}
        url = f"http://127.0.0.1:{server.server_address[1]}"
        load = Load(url, "t", ["k"], sched, lambda s, p: 1.0,
                    lambda v, k: v, time.perf_counter(), connections=4)
        load.run(stop=10.0, deadline=10.0)
        load.close()
    finally:
        server.shutdown()
        server.server_close()
    kinds = [r[0] for r in load.log]
    assert kinds.count(ASK) == 1 and kinds.count(REPORT) == 6
    assert kinds.count(TELL) == 3 and len(load.served) == 3


def test_nothing_is_sent_once_the_window_closes():
    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _SlowService)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        # ten asks due at once, one connection, 50 ms each, a window of
        # 0.12 s: the first three are sent, the other seven wait unsent
        sched = {"due": np.full(10, 0.01), "study": np.zeros(10, np.int64),
                 "think": np.full((10, 1), 5.0), "batch": 1, "reports": 1}
        url = f"http://127.0.0.1:{server.server_address[1]}"
        load = Load(url, "t", ["k"], sched, lambda s, p: 1.0,
                    lambda v, k: v, time.perf_counter(), connections=1)
        load.run(stop=0.12, deadline=5.0)
        load.close()
    finally:
        server.shutdown()
        server.server_close()
    sent = [r for r in load.log if r[0] == ASK]
    assert 2 <= len(sent) <= 4 and all(r[4] == 200 for r in sent)
    assert load.unsent == 10 - len(sent)
    assert all(r[2] < 0.12 for r in load.log)


def test_percentile_counts_failures_as_missing():
    assert plans.percentile([1.0, 2.0, 3.0, 4.0], 50) == 2.0
    assert plans.percentile([1.0] * 19 + [float("inf")], 95) == 1.0
    assert plans.percentile([1.0] * 18 + [float("inf")] * 2, 95) == \
        float("inf")
