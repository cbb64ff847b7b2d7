"""Open-loop load over the service's v2 HTTP API.

Each job of the schedule is one ``ask`` (``ask_batch`` when the mix asks
for a batch) due at its start time; every trial it yields then sends its
reports (``should_prune`` verdicts) and its ``tell`` at fixed fractions of
its think time.  A trial's requests go one after another, as a worker's
do, and nothing waits on the service's pace otherwise: a request is sent
when it is due, on the first free keep-alive connection, and its latency
runs from when it was due, so a stall counts against every request it
delays.  How late each request left is kept beside it.

One ``selectors`` loop drives every connection (stdlib only), so the
generator runs in a process of its own and does not convoy with the
server on one interpreter lock.  Nothing is sent once the schedule
reaches ``stop``: above capacity, requests due in the window may still
wait unsent then, and ``unsent`` counts them.  Those sent are waited for
until ``deadline``.
"""
from __future__ import annotations

import heapq
import json
import selectors
import socket
import time
from urllib.parse import urlsplit

import numpy as np

ASK, REPORT, TELL = 0, 1, 2


class _Conn:
    __slots__ = ("sock", "out", "inbuf", "req")

    def __init__(self, host: str, port: int):
        self.sock = socket.create_connection((host, port), timeout=60)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.sock.setblocking(False)
        self.out = b""
        self.inbuf = b""
        self.req = None

    def response(self) -> tuple[int, bytes] | None:
        buf = self.inbuf
        end = buf.find(b"\r\n\r\n")
        if end < 0:
            return None
        i = buf.find(b"Content-Length:", 0, end)
        length = int(buf[i + 15:buf.index(b"\r\n", i)])
        total = end + 4 + length
        if len(buf) < total:
            return None
        self.inbuf = buf[total:]
        return int(buf[9:12]), buf[end + 4:total]


class Trial:
    __slots__ = ("study", "uid", "params", "value", "due", "step", "pruned")

    def __init__(self, study: int, due: list[float]):
        self.study, self.due = study, due
        self.uid = self.params = self.value = None
        self.step = 0
        self.pruned = False


class Load:
    """Drives one schedule (see ``plan.schedule``) against ``url``.

    ``objective(study, params)`` gives a trial's final value and
    ``reported(value, k)`` its k-th report.  After ``run``, ``log`` holds
    one row per request sent: kind, due, sent, done (``inf`` if no answer),
    HTTP status (0 if none); ``served`` the trials asks returned, and
    ``acks`` the acknowledged reports and tells.
    """

    def __init__(self, url: str, token: str, keys: list[str], sched: dict,
                 objective, reported, origin: float, connections: int = 64):
        u = urlsplit(url)
        self.host, self.port = u.hostname, u.port
        self.keys, self.sched = keys, sched
        self.objective, self.reported = objective, reported
        self.origin = origin
        self.head = (f"Host: bench\r\nAuthorization: Bearer {token}\r\n"
                     "Content-Type: application/json\r\n").encode()
        self.conns = [_Conn(self.host, self.port) for _ in range(connections)]
        self.free = list(self.conns)
        self.sel = selectors.DefaultSelector()
        for c in self.conns:
            self.sel.register(c.sock, selectors.EVENT_READ, c)
        self.heap: list[tuple] = []
        self.seq = 0
        self.log: list[tuple] = []
        self.served: list[dict] = []
        self.acks: list[dict] = []
        self.hooks: list[list] = []
        self.outstanding = 0
        self.unsent = 0
        n_reports = self.n_reports = sched["reports"]
        fracs = (np.arange(1, n_reports + 1) / (n_reports + 1)).tolist()
        self.jobs = []
        for j, (due, study) in enumerate(zip(sched["due"].tolist(),
                                             sched["study"].tolist())):
            trials = [Trial(study, [due + f * th for f in fracs] + [due + th])
                      for th in sched["think"][j].tolist()]
            self.jobs.append((due, study, trials))
            self._push(due, ASK, j)

    def at(self, t: float, fn) -> None:
        """Call ``fn()`` from the loop once the schedule reaches ``t``."""
        self.hooks.append([t, fn])

    def _push(self, due: float, kind: int, ref) -> None:
        self.seq += 1
        heapq.heappush(self.heap, (due, self.seq, kind, ref))

    def _request(self, kind: int, ref) -> bytes:
        if kind == ASK:
            _due, study, trials = self.jobs[ref]
            if len(trials) == 1:
                path = f"/api/v2/studies/{self.keys[study]}/trials:ask"
                body = {}
            else:
                path = f"/api/v2/studies/{self.keys[study]}/trials:ask_batch"
                body = {"n": len(trials)}
        else:
            trial: Trial = ref
            if kind == REPORT:
                path = f"/api/v2/trials/{trial.uid}:report"
                body = {"step": trial.step,
                        "value": self.reported(trial.value, trial.step)}
            else:
                path = f"/api/v2/trials/{trial.uid}:tell"
                body = {"value": trial.value,
                        "state": "pruned" if trial.pruned else "completed"}
        data = json.dumps(body).encode()
        return (f"POST {path} HTTP/1.1\r\n".encode() + self.head
                + f"Content-Length: {len(data)}\r\n\r\n".encode() + data)

    def _send(self, c: _Conn, due: float, kind: int, ref, now: float) -> None:
        c.req = [kind, ref, due, now]
        c.out = self._request(kind, ref)
        self.outstanding += 1
        self._flush(c)

    def _flush(self, c: _Conn) -> None:
        try:
            sent = c.sock.send(c.out)
        except (BlockingIOError, InterruptedError):
            sent = 0
        c.out = c.out[sent:]
        self.sel.modify(c.sock, selectors.EVENT_READ
                        | (selectors.EVENT_WRITE if c.out else 0), c)

    def _done(self, c: _Conn, status: int, body: bytes, now: float) -> None:
        kind, ref, due, sent = c.req
        c.req = None
        self.outstanding -= 1
        self.free.append(c)
        self.log.append((kind, due, sent, now, status))
        if status >= 300:
            return                      # the trial's worker gives up
        res = json.loads(body)
        if kind == ASK:
            _due, study, trials = self.jobs[ref]
            got = res["trials"] if len(trials) > 1 else [res]
            for trial, t in zip(trials, got):
                trial.uid, trial.params = t["uid"], t["params"]
                trial.value = self.objective(study, t["params"])
                self.served.append({"study": study, "uid": t["uid"],
                                    "params": t["params"]})
                self._next(trial)
            return
        trial = ref
        if kind == REPORT:
            self.acks.append({"uid": trial.uid, "step": trial.step,
                              "value": self.reported(trial.value,
                                                     trial.step)})
            trial.pruned = trial.pruned or bool(res.get("should_prune"))
            trial.step = self.n_reports if trial.pruned else trial.step + 1
            self._next(trial)
        else:
            self.acks.append({"uid": trial.uid, "state": res.get("state"),
                              "value": trial.value})

    def _next(self, trial: Trial) -> None:
        """Queue the trial's next request: its next report, or its tell
        once the reports are done or the trial was pruned."""
        k = min(trial.step, self.n_reports)
        self._push(trial.due[k], REPORT if k < self.n_reports else TELL,
                   trial)

    def run(self, stop: float, deadline: float) -> None:
        """Send what falls due before ``stop`` (schedule seconds) and wait
        for answers until ``deadline``."""
        clock, origin = time.perf_counter, self.origin
        self.hooks.sort(key=lambda h: h[0])
        while True:
            now = clock() - origin
            while self.hooks and self.hooks[0][0] <= now:
                self.hooks.pop(0)[1]()
            if now >= stop and self.heap:
                self.unsent += sum(1 for h in self.heap if h[0] < stop)
                self.heap.clear()
            while self.heap and self.heap[0][0] <= now and self.free:
                due, _seq, kind, ref = heapq.heappop(self.heap)
                self._send(self.free.pop(), due, kind, ref, now)
            if not self.outstanding and not self.heap and not self.hooks:
                break
            if now >= deadline:
                break
            wake = deadline
            if self.heap and self.free:
                wake = min(wake, self.heap[0][0])
            if self.hooks:
                wake = min(wake, self.hooks[0][0])
            for key, events in self.sel.select(max(0.0, wake - now)):
                c: _Conn = key.data
                if events & selectors.EVENT_WRITE and c.out:
                    self._flush(c)
                if events & selectors.EVENT_READ:
                    self._read(c)
        # whatever is still out never came
        for c in self.conns:
            if c.req is not None:
                kind, _ref, due, sent = c.req
                self.log.append((kind, due, sent, float("inf"), 0))
                c.req = None

    def _read(self, c: _Conn) -> None:
        try:
            chunk = c.sock.recv(1 << 16)
        except (BlockingIOError, InterruptedError):
            return
        if not chunk:
            raise ConnectionError("the service closed a connection")
        c.inbuf += chunk
        out = c.response()
        if out is not None and c.req is not None:
            self._done(c, out[0], out[1], time.perf_counter() - self.origin)

    def close(self) -> None:
        for c in self.conns:
            self.sel.unregister(c.sock)
            c.sock.close()
        self.sel.close()
