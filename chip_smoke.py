#!/usr/bin/env python3
"""Bring-up smoke of the HOPAAS ask/tell path and its Pallas samplers on
one TPU.

    python chip_smoke.py [--seed N]
    JAX_PLATFORMS=cpu REPRO_HPO_KERNELS=pallas_interpret \\
        python chip_smoke.py --rehearse

Three phases run one after another.  Each is a child process that owns
the chip alone; this process never imports JAX.

(a) kernels  ``parzen_log_density`` and ``matern52_cross`` at the shapes
    the service phase runs (64 candidates x 4,096 observations at D=24,
    256 x 512 at D=12) against a float32 numpy reference of the naive
    formula, and the GP sampler's whole expected-improvement program
    (512 observations, 256 candidates, D=12) against a float64 one.
(b) service  ``python -m repro.core.service --workers 1`` with
    ``JAX_PLATFORMS=tpu``.  Over HTTP on the v2 API: a TPE study in a
    24-dimension mixed space and a GP study in 12 dimensions are grown
    with ``ask_batch``/``tell_batch`` on a seeded synthetic objective,
    then answer 32 single asks and 4 ``ask_batch(16)`` each.  The ask
    phase ends at 4,096 and 512 completed trials: the kernel shapes of
    (a), and GP's limit of 512 observations (past it GP answers with
    quasirandom points).
(c) restart  the service again on the same ``--journal-dir``: counts,
    trial numbering, and the first-ask (compile) time against (b)'s.

Every datum comes from ``--seed``.  The last line of stdout is
``{"ok": true, "device": {...}}`` only when every check passed on a TPU
with the ``pallas`` kernels at full size; otherwise the script prints
``FAIL:`` lines and exits 1.  ``--rehearse`` runs every phase at a tiny
size wherever JAX runs (CPU included) and never prints a result.
"""
from __future__ import annotations

import argparse
import http.client
import json
import math
import os
import signal
import subprocess
import sys
import queue
import tempfile
import threading
import time
from collections import deque
from pathlib import Path
from urllib.parse import urlsplit

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

FULL = {"tpe_trials": 4096, "gp_trials": 512, "build_batch": 256,
        "parzen": (64, 4096, 24), "matern": (256, 512, 12),
        "gp_ei": (512, 256, 12)}
REHEARSE = {"tpe_trials": 320, "gp_trials": 160, "build_batch": 64,
            "parzen": (64, 512, 24), "matern": (256, 256, 12),
            "gp_ei": (128, 64, 12)}
SINGLE_ASKS, BATCH_ASKS, BATCH_N = 32, 4, 16
# Relative to max(1, |ref|) for the log-density, absolute for the
# covariance (values in [0, 1]).  A float32 contraction errs ~1e-5 here;
# one bfloat16 pass over the expanded square errs ~1e-2 or worse.
PARZEN_TOL, MATERN_TOL = 1e-3, 1e-3
# EI error relative to the largest EI: float32 on the CPU errs ~7e-5.
GP_EI_TOL = 1e-3
SERVICE_START_S, REQUEST_S, CHILD_S = 300.0, 900.0, 600.0


class SmokeError(RuntimeError):
    pass


# ------------------------------------------------------------------ (a)
def _naive_parzen(np, x, obs, mask, bw):
    z = (x[:, None, :] - obs[None, :, :]) / bw
    logk = (-0.5 * z * z
            - np.log(bw * np.float32(math.sqrt(2 * math.pi)))).sum(-1)
    logk = np.where(mask[None, :] > 0, logk, np.float32(-np.inf))
    m = logk.max(axis=1, keepdims=True)
    return (m + np.log(np.exp(logk - m).sum(axis=1, keepdims=True)))[:, 0]


def _naive_matern(np, a, b, ls):
    d2 = (((a[:, None, :] - b[None, :, :]) / ls) ** 2).sum(-1)
    s5d = np.float32(math.sqrt(5.0)) * np.sqrt(np.maximum(d2, 1e-12))
    return (1.0 + s5d + s5d * s5d / 3.0) * np.exp(-s5d)


def _naive_gp_ei(np, X, y, mask, cands, ls):
    """``samplers.gp._gp_ei`` step by step in float64."""
    n = max(mask.sum(), 1.0)
    mu0 = (y * mask).sum() / n
    yn = (y - mu0) / math.sqrt(((y - mu0) ** 2 * mask).sum() / n + 1e-12)
    K = np.where(mask[:, None] * mask[None, :] > 0,
                 _naive_matern(np, X, X, ls), 0.0)
    K += np.diag(np.where(mask > 0, 1e-6 + 1e-3, 1.0))
    L = np.linalg.cholesky(K)
    alpha = np.linalg.solve(K, yn * mask)
    Ks = _naive_matern(np, cands, X, ls) * mask[None, :]
    mu = Ks @ alpha
    v = np.linalg.solve(L, Ks.T)
    sd = np.sqrt(np.maximum(1.0 - (v ** 2).sum(0), 1e-9))
    z = (yn[mask > 0].min() - mu) / sd
    Phi = 0.5 * (1 + np.array([math.erf(t / math.sqrt(2)) for t in z]))
    return sd * (z * Phi + np.exp(-0.5 * z * z) / math.sqrt(2 * math.pi))


def _timed(jax, fn):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    return out, first, time.perf_counter() - t0


def kernels_phase(seed: int, sizes: dict) -> dict:
    """Child process: both kernels on this process's device vs numpy."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.kernels import (device_report, matern52_cross,
                                    parzen_log_density)
    from repro.core.samplers.gp import _gp_ei

    report = device_report()
    be = report["kernels"]
    rng = np.random.default_rng(seed)
    f32 = np.float32
    out: dict = {"device": report}

    c, n, d = sizes["parzen"]
    x = rng.uniform(size=(c, d)).astype(f32)
    obs = rng.uniform(size=(n, d)).astype(f32)
    mask = (np.arange(n) < n - n // 16).astype(f32)     # padded tail
    bw = rng.uniform(0.05, 0.5, size=d).astype(f32)     # TPE's clip range
    args = [jnp.asarray(v) for v in (x, obs, mask, bw)]
    got, first, again = _timed(
        jax, lambda: parzen_log_density(*args, backend=be))
    ref = _naive_parzen(np, x, obs, mask, bw)
    err = np.abs(np.asarray(got, np.float64) - ref)
    rel = float((err / np.maximum(1.0, np.abs(ref))).max())
    out["parzen"] = {"shape": [c, n, d], "max_abs": float(err.max()),
                     "max_rel": rel, "tol_rel": PARZEN_TOL,
                     "first_s": first, "again_s": again,
                     "ok": bool(np.isfinite(got).all() and rel <= PARZEN_TOL)}

    a_n, b_n, d = sizes["matern"]
    a = rng.uniform(size=(a_n, d)).astype(f32)
    b = rng.uniform(size=(b_n, d)).astype(f32)
    ls = rng.uniform(0.1, 0.5, size=d).astype(f32)
    args = [jnp.asarray(v) for v in (a, b, ls)]
    got, first, again = _timed(
        jax, lambda: matern52_cross(*args, backend=be))
    ref = _naive_matern(np, a, b, ls)
    err = np.abs(np.asarray(got, np.float64) - ref)
    out["matern"] = {"shape": [a_n, b_n, d], "max_abs": float(err.max()),
                     "max_rel": float((err / np.maximum(1e-6, ref)).max()),
                     "tol_abs": MATERN_TOL, "first_s": first,
                     "again_s": again,
                     "ok": bool(np.isfinite(got).all()
                                and err.max() <= MATERN_TOL)}

    n, c, d = sizes["gp_ei"]
    X = rng.uniform(size=(n, d))
    y = ((X - 0.5) ** 2).sum(1) + 0.01 * rng.normal(size=n)
    mask = (np.arange(n) < n - n // 8).astype(float)    # padded tail
    cands = rng.uniform(size=(c, d))
    ls = np.full(d, 0.25)                               # GPSampler default
    args = [jnp.asarray(v, jnp.float32) for v in (X, y, mask, cands, ls)]
    got, first, again = _timed(jax, lambda: _gp_ei(*args))
    ref = _naive_gp_ei(np, X, y, mask, cands, ls)
    err = np.abs(np.asarray(got, np.float64) - ref)
    rel = float(err.max() / np.abs(ref).max())
    out["gp_ei"] = {"shape": [n, c, d], "max_abs": float(err.max()),
                    "max_rel": rel, "tol_rel": GP_EI_TOL,
                    "first_s": first, "again_s": again,
                    "ok": bool(np.isfinite(got).all() and rel <= GP_EI_TOL)}
    return out


# ------------------------------------------------------------ (b), (c)
def tpe_space() -> dict:
    """24 dims: 16 float (4 loguniform), 4 int, 4 categorical."""
    props: dict = {}
    for i in range(12):
        props[f"w{i:02d}"] = {"type": "uniform", "low": -1.0 - i,
                              "high": 2.0 + i}
    for i in range(4):
        props[f"lr{i}"] = {"type": "loguniform", "low": 1e-5, "high": 0.1}
    for i in range(4):
        props[f"n{i}"] = {"type": "int", "low": 1, "high": 8 + 8 * i}
    acts = ["relu", "gelu", "silu", "tanh", "elu"]
    for i in range(4):
        props[f"c{i}"] = {"type": "categorical", "choices": acts[:2 + i]}
    return props


def gp_space() -> dict:
    """12 dims: 8 float (2 loguniform), 2 int, 2 categorical."""
    props: dict = {}
    for i in range(6):
        props[f"x{i}"] = {"type": "uniform", "low": 0.0, "high": 1.0 + i}
    for i in range(2):
        props[f"lr{i}"] = {"type": "loguniform", "low": 1e-4, "high": 1.0}
    for i in range(2):
        props[f"k{i}"] = {"type": "int", "low": 0, "high": 16}
    for i in range(2):
        props[f"opt{i}"] = {"type": "categorical",
                            "choices": ["sgd", "adam", "lion"]}
    return props


def _unit(spec: dict, v) -> float:
    if spec["type"] == "categorical":
        return spec["choices"].index(v) / max(1, len(spec["choices"]) - 1)
    lo, hi = spec["low"], spec["high"]
    if spec["type"] == "loguniform":
        return (math.log(v) - math.log(lo)) / (math.log(hi) - math.log(lo))
    return (v - lo) / (hi - lo)


class Objective:
    """Seeded weighted quadratic bowl over the unit-mapped parameters."""

    def __init__(self, props: dict, rng):
        self.props = props
        self.opt = {k: float(rng.uniform(0.1, 0.9)) for k in props}
        self.w = {k: float(rng.uniform(0.5, 2.0)) for k in props}

    def __call__(self, params: dict) -> float:
        return sum(self.w[k] * (_unit(s, params[k]) - self.opt[k]) ** 2
                   for k, s in self.props.items())


def check_params(props: dict, params: dict) -> None:
    if set(params) != set(props):
        raise SmokeError(f"proposal keys {sorted(params)} != space")
    for k, s in props.items():
        v = params[k]
        if s["type"] == "categorical":
            ok = v in s["choices"]
        elif s["type"] == "int":
            ok = (isinstance(v, int) and not isinstance(v, bool)
                  and s["low"] <= v <= s["high"])
        else:
            slack = 1e-9 * max(abs(s["low"]), abs(s["high"]))
            ok = (isinstance(v, (int, float)) and math.isfinite(v)
                  and s["low"] - slack <= v <= s["high"] + slack)
        if not ok:
            raise SmokeError(f"proposal {k}={v!r} outside {s}")


class Api:
    """Minimal v2 client on one keep-alive connection (no JAX here)."""

    def __init__(self, url: str, token: str):
        u = urlsplit(url)
        self.conn = http.client.HTTPConnection(u.hostname, u.port,
                                               timeout=REQUEST_S)
        self.headers = {"Authorization": f"Bearer {token}",
                        "Content-Type": "application/json"}

    def call(self, method: str, path: str, body: dict | None = None):
        data = None if body is None else json.dumps(body).encode()
        self.conn.request(method, path, body=data, headers=self.headers)
        resp = self.conn.getresponse()
        blob = resp.read()
        if resp.status >= 300:
            raise SmokeError(f"{method} {path} -> {resp.status}: "
                             f"{blob[:300]!r}")
        return json.loads(blob)

    def close(self) -> None:
        self.conn.close()


class Study:
    """One study driven through the wire, with its own bookkeeping."""

    def __init__(self, api: Api, name: str, props: dict, sampler: dict,
                 objective: Objective):
        self.api, self.props, self.objective = api, props, objective
        res = api.call("POST", "/api/v2/studies",
                       {"name": name, "properties": props,
                        "sampler": sampler, "direction": "minimize"})
        self.key = res["study"]["key"]
        self.next_id = res["study"]["n_trials"]

    def _take(self, trials: list[dict]) -> list[dict]:
        ids = [t["trial_id"] for t in trials]
        if ids != list(range(self.next_id, self.next_id + len(ids))):
            raise SmokeError(f"{self.key}: trial ids {ids[:4]}... do not "
                             f"continue from {self.next_id}")
        self.next_id += len(ids)
        for t in trials:
            check_params(self.props, t["params"])
        if len(trials) > 1 and len({json.dumps(t["params"], sort_keys=True)
                                    for t in trials}) == 1:
            raise SmokeError(f"{self.key}: {len(trials)} identical "
                             "proposals in one batch")
        return trials

    def ask(self) -> tuple[dict, float]:
        t0 = time.perf_counter()
        trial = self.api.call(
            "POST", f"/api/v2/studies/{self.key}/trials:ask", {})
        dt = time.perf_counter() - t0
        return self._take([trial])[0], dt

    def ask_batch(self, n: int) -> tuple[list[dict], float]:
        t0 = time.perf_counter()
        res = self.api.call(
            "POST", f"/api/v2/studies/{self.key}/trials:ask_batch", {"n": n})
        dt = time.perf_counter() - t0
        return self._take(res["trials"]), dt

    def tell(self, trial: dict) -> None:
        self.api.call("POST", f"/api/v2/trials/{trial['uid']}:tell",
                      {"value": self.objective(trial["params"])})

    def tell_batch(self, trials: list[dict]) -> None:
        res = self.api.call("POST", "/api/v2/trials:tell_batch", {
            "tells": [{"trial_uid": t["uid"],
                       "value": self.objective(t["params"])}
                      for t in trials]})
        bad = [r for r in res["results"] if r.get("status", 200) >= 300]
        if bad:
            raise SmokeError(f"{self.key}: tell_batch refused {bad[:2]}")

    def counts(self) -> dict:
        return self.api.call("GET", f"/api/v2/studies/{self.key}")["study"]

    def expect_counts(self, n: int) -> None:
        s = self.counts()
        got = (s["n_trials"], s["n_completed"], s.get("n_running", 0))
        if got != (n, n, 0):
            raise SmokeError(f"{self.key}: (trials, completed, running) "
                             f"= {got}, expected ({n}, {n}, 0)")


def grow(study: Study, target: int, batch: int) -> list[tuple[int, float]]:
    """ask_batch/tell_batch to ``target`` completed trials; returns the
    (history, seconds) of every ask_batch."""
    times = []
    while study.next_id < target:
        history = study.next_id
        trials, dt = study.ask_batch(min(batch, target - history))
        study.tell_batch(trials)
        times.append((history, dt))
    return times


def ask_phase(study: Study) -> dict:
    singles = []
    for _ in range(SINGLE_ASKS):
        trial, dt = study.ask()
        study.tell(trial)
        singles.append(dt)
    batches = []
    for _ in range(BATCH_ASKS):
        trials, dt = study.ask_batch(BATCH_N)
        study.tell_batch(trials)
        batches.append(dt)
    return {"single_first_s": singles[0],
            "single_median_s": sorted(singles[1:])[len(singles[1:]) // 2],
            "batch_first_s": batches[0],
            "batch_median_s": sorted(batches[1:])[len(batches[1:]) // 2]}


class Service:
    """``python -m repro.core.service --workers 1`` as a child process."""

    def __init__(self, journal_dir: str, env: dict):
        self.proc = subprocess.Popen(
            [sys.executable, "-u", "-m", "repro.core.service", "--port", "0",
             "--workers", "1", "--journal-dir", journal_dir,
             "--fsync", "group"],
            cwd=ROOT, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        self.banner: list[str] = []
        self.tail: deque[str] = deque(maxlen=20)
        self.url = self.token = None
        self.device: dict | None = None
        # a reader thread keeps the pipe drained, so the service never
        # blocks on a burst of warnings and a silent hang still times out
        self._lines: queue.Queue = queue.Queue()
        threading.Thread(target=self._drain, daemon=True).start()
        deadline = time.monotonic() + SERVICE_START_S
        while self.token is None:
            try:
                line = self._lines.get(
                    timeout=max(0.0, deadline - time.monotonic()))
            except queue.Empty:
                line = None
            if line is None:
                self.stop()
                tail = "\n".join(self.banner[-15:])
                raise SmokeError(f"service did not start:\n{tail}")
            self.banner.append(line)
            if line.startswith("HOPAAS service at "):
                self.url = line.split()[3]
            elif line.startswith("sampler device: "):
                self.device = json.loads(line[len("sampler device: "):])
            elif line.startswith("API token: "):
                self.token = line.split()[2]

    def _drain(self) -> None:
        for line in self.proc.stdout:
            line = line.rstrip("\n")
            self.tail.append(line)
            if self.token is None:
                self._lines.put(line)
        self._lines.put(None)                      # EOF: the service died

    def stop(self) -> int | None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGINT)    # flushes the WAL tail
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=10)
        return self.proc.returncode


def _child_env(rehearse: bool) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = (str(SRC) + os.pathsep + env["PYTHONPATH"]
                         if env.get("PYTHONPATH") else str(SRC))
    for var in ("REPRO_WORKERS", "REPRO_REPLICAS", "REPRO_SPECULATE"):
        env.pop(var, None)                 # the plain single-process path
    if not rehearse:
        env["JAX_PLATFORMS"] = "tpu"       # no silent fallback to the CPU
    return env


def _cache_entries(device: dict | None) -> int | None:
    path = device and device.get("cache")
    if not path or not os.path.isdir(path):
        return None
    return sum(len(files) for _d, _s, files in os.walk(path))


def _fmt(xs: list[tuple[int, float]]) -> str:
    return ", ".join(f"{h}:{dt:.3f}s" for h, dt in xs)


def service_phases(seed: int, sizes: dict, env: dict, log) -> dict:
    import numpy as np                     # data only; no JAX here

    rng = np.random.default_rng(seed)
    tpe_obj = Objective(tpe_space(), rng)
    gp_obj = Objective(gp_space(), rng)
    asked = SINGLE_ASKS + BATCH_ASKS * BATCH_N
    result: dict = {}
    with tempfile.TemporaryDirectory(prefix="hopaas-smoke-") as journal:
        t_phase = time.perf_counter()
        svc = Service(journal, env)
        try:
            log(f"(b) service up in {time.perf_counter() - t_phase:.1f} s: "
                f"{svc.banner[0]}")
            log(f"(b) sampler device: {json.dumps(svc.device)}")
            result["device_b"] = svc.device
            cache0 = _cache_entries(svc.device)
            api = Api(svc.url, svc.token)
            tpe = Study(api, f"smoke-tpe-{seed}", tpe_obj.props,
                        {"name": "tpe", "seed": seed}, tpe_obj)
            gp = Study(api, f"smoke-gp-{seed}", gp_obj.props,
                       {"name": "gp", "seed": seed}, gp_obj)
            timings = {}
            for name, study, total in (("tpe", tpe, sizes["tpe_trials"]),
                                       ("gp", gp, sizes["gp_trials"])):
                t0 = time.perf_counter()
                build = grow(study, total - asked, sizes["build_batch"])
                t_build = time.perf_counter() - t0
                study.expect_counts(total - asked)
                log(f"(b) {name}: grew to {total - asked} completed in "
                    f"{t_build:.2f} s; ask_batch(n<={sizes['build_batch']})"
                    f" seconds by history {_fmt(build)}")
                t0 = time.perf_counter()
                asks = ask_phase(study)
                study.expect_counts(total)
                log(f"(b) {name}: {SINGLE_ASKS} asks + {BATCH_ASKS} "
                    f"ask_batch({BATCH_N}) at history {total - asked}.."
                    f"{total} in {time.perf_counter() - t0:.2f} s: "
                    + json.dumps(asks))
                timings[name] = asks
            result["asks_b"] = timings
            log(f"(b) passed in {time.perf_counter() - t_phase:.1f} s; "
                f"compile-cache entries {cache0} -> "
                f"{_cache_entries(svc.device)}")
            api.close()
        finally:
            rc = svc.stop()
        if rc != 0:
            raise SmokeError(f"(b) service exited with {rc}: "
                             + "\n".join(svc.tail))

        t_phase = time.perf_counter()
        svc = Service(journal, env)
        try:
            log(f"(c) restarted in {time.perf_counter() - t_phase:.1f} s")
            log(f"(c) sampler device: {json.dumps(svc.device)}")
            result["device_c"] = svc.device
            api = Api(svc.url, svc.token)
            for name, study, total in (("tpe", tpe, sizes["tpe_trials"]),
                                       ("gp", gp, sizes["gp_trials"])):
                study.api = api
                study.expect_counts(total)
                trial, first = study.ask()       # numbering checked inside
                study.tell(trial)
                _, again = study.ask()
                b = result["asks_b"][name]
                log(f"(c) {name}: counts intact at {total}; next ask is "
                    f"trial {trial['trial_id']}; first ask {first:.3f} s, "
                    f"second {again:.3f} s; in (b) the first single ask "
                    f"took {b['single_first_s']:.3f} s, the median "
                    f"{b['single_median_s']:.3f} s")
                result[f"restart_first_ask_{name}_s"] = first
            log(f"(c) passed in {time.perf_counter() - t_phase:.1f} s; "
                f"compile-cache entries {_cache_entries(svc.device)}")
            api.close()
        finally:
            rc = svc.stop()
        if rc != 0:
            raise SmokeError(f"(c) service exited with {rc}: "
                             + "\n".join(svc.tail))
    return result


# ------------------------------------------------------------- driver
def run_child_phase(phase: str, seed: int, rehearse: bool,
                    env: dict) -> dict:
    cmd = [sys.executable, str(Path(__file__).resolve()), "--phase", phase,
           "--seed", str(seed)] + (["--rehearse"] if rehearse else [])
    try:
        proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                              text=True, timeout=CHILD_S)
    except subprocess.TimeoutExpired:
        raise SmokeError(f"phase {phase} timed out after {CHILD_S:.0f} s")
    lines = proc.stdout.strip().splitlines()
    if proc.returncode != 0 or not lines:
        tail = "\n".join(proc.stderr.strip().splitlines()[-20:])
        raise SmokeError(f"phase {phase} exited with {proc.returncode}:\n"
                         f"{tail}")
    return json.loads(lines[-1])


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, any platform; never prints a result")
    ap.add_argument("--phase", choices=("kernels",), help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    sizes = REHEARSE if args.rehearse else FULL

    if args.phase == "kernels":
        print(json.dumps(kernels_phase(args.seed, sizes)))
        return 0

    def log(msg: str) -> None:
        print(msg, flush=True)

    failures: list[str] = []
    if not (SRC / "repro" / "core" / "service.py").is_file():
        failures.append(f"no repro sources under {SRC}")
    platforms = os.environ.get("JAX_PLATFORMS")
    if not args.rehearse and platforms is not None \
            and "tpu" not in platforms.split(","):
        failures.append(f"JAX_PLATFORMS={platforms!r} excludes the TPU")
    if failures:
        for f in failures:
            print(f"FAIL: {f}")
        return 1

    env = _child_env(args.rehearse)
    t_all = time.perf_counter()
    device: dict | None = None
    try:
        t0 = time.perf_counter()
        kern = run_child_phase("kernels", args.seed, args.rehearse, env)
        device = kern["device"]
        log(f"(a) device: {json.dumps(device)}")
        for name in ("parzen", "matern", "gp_ei"):
            r = kern[name]
            log(f"(a) {name} {r['shape']}: max_abs={r['max_abs']!r} "
                f"max_rel={r['max_rel']!r} first call {r['first_s']:.3f} s, "
                f"again {r['again_s']:.6f} s -> "
                f"{'ok' if r['ok'] else 'FAILED'}")
            if not r["ok"]:
                failures.append(f"(a) {name} outside tolerance")
        log(f"(a) finished in {time.perf_counter() - t0:.1f} s")
        if device["platform"] != "tpu" and not args.rehearse:
            raise SmokeError(f"JAX found no TPU: {json.dumps(device)}")
        svc = service_phases(args.seed, sizes, env, log)
        for key in ("device_b", "device_c"):
            d = svc[key] or {}
            if (d.get("platform"), d.get("kernels")) != ("tpu", "pallas"):
                failures.append(f"service {key[-1]} samples on "
                                f"{d.get('platform')}/{d.get('kernels')}")
    except SmokeError as e:
        failures.append(str(e))
    log(f"total {time.perf_counter() - t_all:.1f} s")

    if device is not None and (device["platform"], device["kernels"]) \
            != ("tpu", "pallas"):
        failures.append(f"kernels ran on {device['platform']}/"
                        f"{device['kernels']}, not tpu/pallas")
    if args.rehearse:
        failures.append("rehearsal size: no result")
    if failures:
        for f in failures:
            print(f"FAIL: {f}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
