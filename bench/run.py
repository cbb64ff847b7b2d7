#!/usr/bin/env python3
"""One run of one benchmark cell of the HOPAAS service on the chip.

    python bench/run.py --workload NAME --seed N --seconds S --trace 0|1
    python bench/run.py --workload NAME --seed N --sweep 40,80,120 \\
        --step-seconds 8                       # knee sweep, no result
    JAX_PLATFORMS=cpu python bench/run.py --workload NAME --seed N \\
        --seconds 3 --trace 0 --rehearse       # tiny sizes, never a result

The cell (``BENCHMARK.json``) names a configuration
(``bench/configs/<name>.json``: the studies and their history) and a mix
(``bench/mixes/<name>.json``: the open-loop traffic); the configuration's
sampler names its plug-in (``bench/samplers/<name>.py``), which holds all
that is particular to one sampler.  This process never imports JAX.  It
starts ``bench/serve.py``, which owns the chip, writes the history, warms
the sampler's shapes and runs ``repro.core.service --workers 1``; then it
resolves the studies over HTTP, runs the schedule's warm-up seconds and
the measured window through ``bench/loadgen.py``.  Afterwards it kills the
service, has the journal replayed by a process of its own, and checks what
the window produced against the plain reference (through the plug-in) and
the replay.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics with ``--trace 1``), ``device``, with ``--trace 1``
``breakdown`` (with ``idle_by_span``, the device's idle time by the
service span open on the host), and last ``checks``: each number
compared with its limit, which are also the last lines on stderr.
Without a TPU, with fewer chips than the cell asks for, or with
``--rehearse``, no result is printed and the exit code is 1.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import queue
import shutil
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request
from pathlib import Path

T_START = time.perf_counter()
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import numpy as np  # noqa: E402

import plan as plans  # noqa: E402
import registry  # noqa: E402
from loadgen import ASK, TELL, Load  # noqa: E402
from spaces import INTS, SPACES, Codec, intermediates  # noqa: E402

DISCRETE = (*INTS, "categorical")

START_S = 1100.0        # service up, history written, shapes compiled
COLLECT_S = 240.0
DRAIN_S = 60.0          # answers due in the window are awaited this long


class RunError(RuntimeError):
    pass


class Launcher:
    """``bench/serve.py`` as a child process; its stdout is drained by a
    thread, banner lines parsed, ``BENCH`` replies queued."""

    def __init__(self, plan_path: str, journal: str, log_path: str,
                 env: dict, extra: list[str]):
        self.log = open(log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, "-u", str(HERE / "serve.py"), "--plan",
             plan_path, "--journal-dir", journal, *extra],
            cwd=HERE.parent, env=env, stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, stderr=self.log, text=True)
        self.replies: queue.Queue = queue.Queue()
        self.banner: dict = {}
        self.ready = threading.Event()
        threading.Thread(target=self._drain, daemon=True).start()

    def _drain(self) -> None:
        for line in self.proc.stdout:
            line = line.rstrip("\n")
            if line.startswith("BENCH "):
                self.replies.put(json.loads(line[6:]))
            elif line.startswith("HOPAAS service at "):
                self.banner["url"] = line.split()[3]
            elif line.startswith("sampler device: "):
                self.banner["device"] = json.loads(line[16:])
            elif line.startswith("API token: "):
                self.banner["token"] = line.split()[2]
                self.ready.set()
        self.replies.put(None)
        self.ready.set()

    def wait_ready(self, timeout: float) -> dict:
        if not self.ready.wait(timeout) or "token" not in self.banner:
            raise RunError("the service did not start:\n" + self.tail())
        return self.banner

    def send(self, msg: dict) -> None:
        self.proc.stdin.write(json.dumps(msg) + "\n")
        self.proc.stdin.flush()

    def reply(self, cmd: str, timeout: float) -> dict:
        deadline = time.monotonic() + timeout
        while True:
            try:
                msg = self.replies.get(
                    timeout=max(0.0, deadline - time.monotonic()))
            except queue.Empty:
                raise RunError(f"no reply to {cmd}")
            if msg is None:
                raise RunError(f"the service died before {cmd}:\n"
                               + self.tail())
            if msg.get("cmd") == cmd:
                if "error" in msg:
                    raise RunError(f"{cmd} failed: {msg['error']}")
                return msg

    def kill(self) -> None:
        """SIGKILL: the service gets no chance to flush, so what it
        acknowledged has to be in the OS already."""
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait(timeout=60)
        if not self.log.closed:
            self.log.close()

    def tail(self, n: int = 25) -> str:
        self.log.flush()
        try:
            with open(self.log.name) as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return ""


def _post(url: str, token: str, path: str, body: dict) -> dict:
    req = urllib.request.Request(
        url + path, data=json.dumps(body).encode(), method="POST",
        headers={"Authorization": f"Bearer {token}",
                 "Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read())


def resolve_studies(url: str, token: str, studies: list[dict]) -> list[str]:
    """Create-or-get every study as its clients would; each must hold the
    history written for it."""
    keys = []
    for s in studies:
        res = _post(url, token, "/api/v2/studies", {
            "name": s["name"], "properties": SPACES[s["space"]](),
            "direction": "minimize", "sampler": s["sampler"],
            "pruner": s["pruner"]})["study"]
        if res["n_completed"] != s["n_history"]:
            raise RunError(f"study {s['name']} holds {res['n_completed']} "
                           f"completed trials, not {s['n_history']}")
        keys.append(res["key"])
    return keys


def child_env(rehearse: bool) -> dict:
    env = dict(os.environ)
    src = str(HERE.parent / "src")
    env["PYTHONPATH"] = src + (os.pathsep + env["PYTHONPATH"]
                               if env.get("PYTHONPATH") else "")
    for var in ("REPRO_WORKERS", "REPRO_REPLICAS", "REPRO_SPECULATE",
                "REPRO_FRONTEND", "REPRO_HPO_KERNELS"):
        env.pop(var, None)                 # the plain single-process path
    # the compile cache at a fixed place inside the checkout
    env["JAX_COMPILATION_CACHE_DIR"] = str(HERE.parent / ".jax_cache")
    if not rehearse:
        env["JAX_PLATFORMS"] = "tpu"       # no silent fallback to the CPU
    return env


def read_back(plan_path: str, journal: str, env: dict) -> dict:
    """The journal of the killed service replayed by a process of its own
    (on the CPU: it needs no chip): every trial the run created."""
    proc = subprocess.run(
        [sys.executable, str(HERE / "serve.py"), "--plan", plan_path,
         "--journal-dir", journal, "--readback"],
        cwd=HERE.parent, env=dict(env, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=COLLECT_S)
    if proc.returncode != 0:
        raise RunError("the journal's replay failed:\n" + proc.stderr[-3000:])
    with open(os.path.join(os.path.dirname(plan_path), "readback.json")) as f:
        return json.load(f)


# ------------------------------------------------------------ the checks
def check_proposals(sampler, calls: list[dict], served: list[dict],
                    studies: list[dict], objective, seed: int) -> dict:
    """The sampled calls against the reference (the plug-in's ``check``),
    with the observations the run holds; the point each call served
    against a proposal the service served; every served proposal inside
    its space."""
    codec = Codec(SPACES[studies[0]["space"]]())
    if any(s["space"] != studies[0]["space"] for s in studies):
        raise RunError("the check takes studies of one space")
    known: dict = {}
    history: set = set()
    for s in studies:
        params, values = plans.history(s, seed)
        for v, u in zip(values.tolist(), codec.to_unit_rows(params)):
            known[v] = u
        history.update(values.tolist())
    points = codec.to_unit_rows([t["params"] for t in served]) \
        if served else np.zeros((0, codec.dim))
    for t, u in zip(served, points):
        known[objective(t["study"], t["params"])] = u
    out = sampler.check(calls, known, history, points)
    by_key: dict = {}
    for t in served:
        by_key.setdefault(_coarse(codec, t["params"]), []).append(t["params"])
    mismatch = 0
    for c in calls:
        p = codec.from_unit(sampler.top(c))
        if not any(codec.same(p, q)
                   for q in by_key.get(_coarse(codec, p), [])):
            mismatch += 1
    out["param_mismatch"] = mismatch
    out["proposals_invalid"] = sum(not codec.in_space(t["params"])
                                   for t in served)
    return out


def _coarse(codec: Codec, params: dict) -> tuple:
    """A key equal for parameters that ``Codec.same`` can call equal."""
    key = []
    for k in codec.names:
        v = params.get(k)
        if codec.props[k]["type"] in DISCRETE:
            key.append(v)
    floats = [k for k in codec.names
              if codec.props[k]["type"] not in DISCRETE]
    if floats and isinstance(params.get(floats[0]), (int, float)):
        key.append(f"{params[floats[0]]:.5e}")
    return tuple(key)


def check_storage(acks: list[dict], readback: dict) -> dict:
    """Every acknowledged tell and report, as the journal's replay has it."""
    tells = reports = 0
    for a in acks:
        rb = readback.get(a["uid"])
        if "state" in a:
            tells += rb is None or rb[0] != a["state"] or rb[1] != a["value"]
        else:
            reports += rb is None or rb[2].get(str(a["step"])) != a["value"]
    return {"tells_lost": tells, "reports_lost": reports}


def judge(found: dict, limits: dict) -> tuple[bool, dict]:
    """Each number beside its limit: counts must be 0, ``rank_gap`` at most
    its limit, and at least one call must have been checked."""
    checks = {}
    for name, value in found.items():
        if name == "sampled_calls":
            checks[name] = {"value": value, "min": 1}
        else:
            checks[name] = {"value": value, "limit": limits.get(name, 0)}
    ok = all(c["value"] >= c["min"] if "min" in c
             else c["value"] <= c["limit"] for c in checks.values())
    return ok, checks


# ----------------------------------------------------------- the numbers
def window_numbers(log: list[tuple], t0: float, t1: float) -> dict:
    """End-to-end numbers of the requests due in [t0, t1)."""
    rows = [r for r in log if t0 <= r[1] < t1]
    ok = [r for r in rows if 200 <= r[4] < 300 and math.isfinite(r[3])]

    def lat(kind):
        return [((r[3] - r[1]) * 1e3 if 200 <= r[4] < 300 else math.inf)
                for r in rows if r[0] == kind]
    asks, tells = lat(ASK), lat(TELL)
    told = sum(1 for r in log if r[0] == TELL and 200 <= r[4] < 300
               and t0 <= r[3] < t1)
    late = sorted((r[2] - r[1]) * 1e3 for r in rows)
    return {"attempted": len(rows), "failed": len(rows) - len(ok),
            "asks": len(asks), "tells": len(tells),
            "ask_p50_ms": plans.percentile(asks, 50),
            "ask_p95_ms": plans.percentile(asks, 95),
            "tell_p95_ms": plans.percentile(tells, 95),
            "trials_per_s": told / (t1 - t0),
            "late_p99_ms": plans.percentile(late, 99),
            "late_max_ms": late[-1] if late else math.nan}


def sweep_table(log: list[tuple], steps: list) -> list[dict]:
    rows = []
    for t0, t1, rate in steps:
        n = window_numbers(log, t0, t1)
        asks = [r for r in log if r[0] == ASK and t0 <= r[1] < t1]
        rows.append({"rate": rate, "asks_due": len(asks),
                     "asks_done_in_step": sum(1 for r in asks if r[3] < t1),
                     "backlog_at_end": sum(1 for r in log
                                           if r[1] < t1 <= r[3]),
                     "trials_per_s": n["trials_per_s"],
                     "ask_p50_ms": n["ask_p50_ms"],
                     "ask_p95_ms": n["ask_p95_ms"],
                     "tell_p95_ms": n["tell_p95_ms"],
                     "late_p99_ms": n["late_p99_ms"],
                     "failed": n["failed"]})
    return rows


def top_rows(rows: list, n: int = 10) -> list:
    """The ``n`` largest ``[name, seconds]`` rows, the rest summed into a
    last ``other`` row so that the rows keep their total."""
    if len(rows) <= n:
        return rows
    return rows[:n - 1] + [["other", sum(s for _n, s in rows[n - 1:])]]


def _json_num(x):
    return None if isinstance(x, float) and not math.isfinite(x) else x


# ----------------------------------------------------------------- main
def run(args) -> int:
    bench = registry.benchmark()
    cell, config, mix = registry.cell(bench, args.workload)
    sampler = registry.sampler(config["studies"]["sampler"]["name"])
    studies = plans.studies(config, args.seed)
    if args.sweep:
        rates = [float(r) for r in args.sweep.split(",")]
        sched = plans.sweep_schedule(
            plans.apply_rehearse(mix) if args.rehearse else mix,
            len(studies), rates, args.step_seconds, args.seed)
        t0, t1 = sched["warm"], sched["end"]
    else:
        sched = plans.schedule(mix, len(studies), args.seconds, args.seed,
                               args.rehearse)
        t0, t1 = sched["warm"], sched["end"]
    tmp = tempfile.mkdtemp(prefix="hopaas-bench-")
    launcher = None
    try:
        plan = {"seed": args.seed, "studies": studies,
                "ranges": plans.history_ranges(studies, sched),
                "batch": sched["batch"], "reports": sched["reports"],
                "service_flags": config["service"],
                "sample_calls": int(mix["sample_calls"])}
        plan_path = os.path.join(tmp, "plan.json")
        with open(plan_path, "w") as f:
            json.dump(plan, f)
        extra = (["--control", args.control] if args.control else []) + \
            (["--fault", args.fault] if args.fault else [])
        journal, env = os.path.join(tmp, "journal"), child_env(args.rehearse)
        launcher = Launcher(plan_path, journal, os.path.join(tmp, "serve.log"),
                            env, extra)
        banner = launcher.wait_ready(START_S)
        device = banner["device"]
        print(f"service: {json.dumps(device)}", file=sys.stderr)
        if not args.rehearse:
            if device.get("platform") != "tpu" or \
                    device.get("count", 0) < cell["chips"]:
                raise RunError(f"no chip for this cell: {json.dumps(device)}")
            if device.get("kernels") != "pallas":
                raise RunError(f"kernels are {device.get('kernels')}")
        url, token = banner["url"], banner["token"]
        keys = resolve_studies(url, token, studies)
        objectives = {}

        def objective(i, params):
            if i not in objectives:
                objectives[i] = plans.objective(studies[i], args.seed)
            return objectives[i](params)

        def reported(value, k):
            return intermediates(value, sched["reports"])[k]

        origin = time.perf_counter() + 0.1
        load = Load(url, token, keys, sched, objective, reported, origin)
        load.at(t0, lambda: launcher.send(
            {"cmd": "window_start", "url": url, "trace": bool(args.trace)}))
        load.at(t1, lambda: launcher.send({"cmd": "window_stop",
                                           "url": url}))
        setup_s = origin + t0 - T_START
        load.run(stop=t1, deadline=t1 + DRAIN_S)
        load.close()
        launcher.reply("window_start", COLLECT_S)
        launcher.reply("window_stop", COLLECT_S)
        launcher.send({"cmd": "collect"})
        got = launcher.reply("collect", COLLECT_S)
        launcher.kill()
        readback = read_back(plan_path, journal, env)
        calls = sampler.load(os.path.join(tmp, "calls.npz"))
    except BaseException:
        if launcher is not None:
            launcher.kill()
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    shutil.rmtree(tmp, ignore_errors=True)

    if args.sweep:
        for row in sweep_table(load.log, sched["steps"]):
            print(json.dumps(row))
        print(f"compiles in the sweep: {got['compiles']}", file=sys.stderr)
        return 0

    t_check = time.perf_counter()
    found = check_proposals(sampler, calls, load.served, studies, objective,
                            args.seed)
    found.update(check_storage(load.acks, readback))
    found["sampled_calls"] = len(calls)
    correct, checks = judge(found, config["correct"])
    nums = window_numbers(load.log, t0, t1)
    rec = {"window": nums, "launcher": got, "device": device,
           "trace": got.get("trace"),
           "asks_answered": sum(1 for r in load.log if r[0] == ASK
                                and t0 <= r[3] < t1 and r[4] < 300),
           "tells_answered": sum(1 for r in load.log if r[0] == TELL
                                 and t0 <= r[3] < t1 and r[4] < 300)}
    metrics = {}
    for m in registry.metrics_of(bench, args.workload, bool(args.trace)):
        if args.trace:
            value = registry.metric_reader(m["name"])(rec)
        elif m["name"] == "setup_s":
            value = setup_s
        else:
            value = nums.get(m["name"])
        if value is not None and not (isinstance(value, float)
                                      and math.isnan(value)):
            metrics[m["name"]] = {"value": _json_num(value),
                                  "unit": m["unit"]}
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"],
           "memory_peak_bytes": got.get("memory_peak_bytes")}
    result = {"correct": correct, "attempted": nums["attempted"],
              "failed": nums["failed"], "metrics": metrics, "device": dev}
    if args.trace and got.get("trace"):
        tr = got["trace"]
        dev["busy_s"], dev["window_s"] = tr["busy_s"], tr["window_s"]
        result["breakdown"] = {"device_ops": tr["top_ops"],
                               "idle_gaps": tr["idle_gaps"]}
        if got.get("spans"):
            result["breakdown"]["idle_by_span"] = top_rows(
                got["spans"]["idle_by_span"])
    result["generator"] = {"late_p99_ms": nums["late_p99_ms"],
                           "late_max_ms": nums["late_max_ms"],
                           "setup_s": setup_s,
                           "unsent_at_close": load.unsent,
                           "check_s": time.perf_counter() - t_check,
                           "compiles_in_window": got["compiles"],
                           "compactions_s": got["compaction_s"],
                           "gc_max_s": max(got["gc_s"], default=0.0),
                           "calls_unmatched": got["calls_unmatched"]}
    result["checks"] = checks
    print(f"metrics: {json.dumps(metrics, default=_json_num)}",
          file=sys.stderr)
    print(f"window: {json.dumps(nums)}", file=sys.stderr)
    for name, c in checks.items():
        bound = (f"min {c['min']}" if "min" in c else f"limit {c['limit']}")
        print(f"check {name}: {c['value']} ({bound})", file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    if args.rehearse:
        print("rehearsal: no result", file=sys.stderr)
        return 1
    print(json.dumps(result, default=_json_num))
    return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, any platform; never prints a result")
    ap.add_argument("--sweep", default=None,
                    help="comma-separated trial rates: a knee sweep")
    ap.add_argument("--step-seconds", type=float, default=8.0)
    ap.add_argument("--control", choices=("high",), default=None,
                    help="run the check's control in the program's place")
    ap.add_argument("--fault", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    try:
        return run(args)
    except (RunError, FileNotFoundError, KeyError, ValueError) as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
