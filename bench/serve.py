"""Launches the service under test for one benchmark run.

    python bench/serve.py --plan PLAN.json --journal-dir DIR

This process owns the chip.  Before the service starts it writes the
studies' history as the service's own snapshot (``snapshot-00000001.json``
under ``--journal-dir``), so start-up is the restart path users take, and
has the sampler's plug-in (``samplers/<name>.py``, found by the name the
studies give) compile the sampler's program at every shape the run's
schedule can reach.  Then it calls ``repro.core.service.main`` with the
flags a user passes (``--workers 1 --journal-dir DIR --fsync group``).

A control thread reads one JSON command per line on stdin and answers on
stdout with a ``BENCH {...}`` line:

* ``window_start`` / ``window_stop``: opens and closes the measured window:
  the compile-event count, the plug-in's record of sampler calls, the WAL
  fsync count and the dispatch lanes' counters (``/api/v2/health``) and,
  when asked, the profiler trace with the service's own spans enabled
  (``repro.core.tracing``) for just as long;
* ``collect``: peak device memory, the counts, the host spans inside the
  window (WAL compactions, garbage-collector passes), the reduced trace
  and the service's spans reduced from it (``bench/spans.py``), and the
  recorded sampler calls written to ``<plan dir>/calls.npz``.

The harness then kills this process (SIGKILL: nothing is flushed on the
way out) and runs it again with ``--readback``, which opens the journal
(a replay) and writes every trial the run created to
``<plan dir>/readback.json``; that run starts no service.

``--control high`` has the plug-in put the sampler's plain scoring,
computed one precision step below the one the kernels state, in the
program's place; ``--fault`` plants a fault for the harness's tests, the
plug-in's own or one of those below.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import urllib.request
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import numpy as np  # noqa: E402

import plan as plans  # noqa: E402
import registry  # noqa: E402
from spaces import SPACES, intermediates  # noqa: E402

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def reply(msg: dict) -> None:
    print("BENCH " + json.dumps(msg), flush=True)


def study_config(s: dict):
    from repro.core.types import Direction, StudyConfig
    return StudyConfig(name=s["name"], properties=SPACES[s["space"]](),
                       direction=Direction.MINIMIZE,
                       sampler=dict(s["sampler"]), pruner=dict(s["pruner"]))


def write_history(plan: dict, journal_dir: str) -> None:
    """The studies and their completed trials, as the service's snapshot."""
    from repro.core.storage import InMemoryStorage
    from repro.core.types import Trial, TrialState

    store = InMemoryStorage()
    seed, n_reports = plan["seed"], plan["reports"]
    t = time.time()
    for s in plan["studies"]:
        key = store.get_or_create_study(study_config(s))[0].key
        params, values = plans.history(s, seed)
        for j, (p, v) in enumerate(zip(params, values)):
            inter = dict(enumerate(intermediates(float(v), n_reports)))
            store._insert_trial(Trial(
                trial_id=j, uid=f"{key}:{j}", study_key=key,
                params=p, state=TrialState.COMPLETED, value=float(v),
                intermediates=inter, worker_id="history", created_at=t,
                finished_at=t))
    os.makedirs(journal_dir, exist_ok=True)
    path = os.path.join(journal_dir, "snapshot-00000001.json")
    with open(path + ".tmp", "w") as f:
        f.write(json.dumps({"covers": 1, "state": store.state_record()},
                           allow_nan=False))
    os.replace(path + ".tmp", path)


class Spans:
    """Host spans the service does not report itself: each WAL compaction
    (``DurableStorage.compact``) and each garbage-collector pass, as
    (start, end) on the ``perf_counter`` clock."""

    def __init__(self):
        self.compactions: list[tuple] = []
        self.gc: list[tuple] = []
        self._gc_start = None

    def install(self) -> None:
        import gc

        from repro.core.durable import DurableStorage
        compact, spans = DurableStorage.compact, self

        def timed_compact(self, *a, **kw):
            t0 = time.perf_counter()
            try:
                return compact(self, *a, **kw)
            finally:
                spans.compactions.append((t0, time.perf_counter()))
        DurableStorage.compact = timed_compact
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_start = time.perf_counter()
        elif self._gc_start is not None:
            self.gc.append((self._gc_start, time.perf_counter()))
            self._gc_start = None

    @staticmethod
    def within(spans: list[tuple], t0: float, t1: float) -> list[float]:
        """Seconds of each span inside [t0, t1]."""
        return [min(b, t1) - max(a, t0) for a, b in spans
                if b > t0 and a < t1]


class CompileCounter:
    def __init__(self):
        self.active = False
        self.count = 0
        self.names: list[str] = []

    def __call__(self, event: str, duration: float, **kwargs) -> None:
        if self.active and event == BACKEND_COMPILE:
            self.count += 1
            self.names.append(str(kwargs.get("fun_name", "?")))


def health(url: str) -> tuple:
    """(WAL fsyncs, the dispatch lanes' counters) as the service serves
    them."""
    with urllib.request.urlopen(url + "/api/v2/health", timeout=30) as r:
        h = json.loads(r.read())
    return h["storage"].get("fsyncs"), h.get("frontend")


class Control:
    def __init__(self, plan: dict, out_dir: str, sampler, recorder,
                 compiles: CompileCounter, spans: Spans):
        self.plan, self.out_dir, self.sampler = plan, out_dir, sampler
        self.recorder, self.compiles, self.spans = recorder, compiles, spans
        self.trace_dir: str | None = None
        self.state: dict = {}

    def run(self) -> None:
        for line in sys.stdin:
            msg = json.loads(line)
            try:
                reply(getattr(self, msg["cmd"])(msg))
            except Exception as e:      # report, keep serving the run
                reply({"cmd": msg["cmd"], "error": repr(e)})

    def window_start(self, msg: dict) -> dict:
        import jax

        from repro.core import tracing
        self.state["fsyncs0"], self.state["frontend0"] = health(msg["url"])
        self.state["t0_ns"] = time.time_ns()      # the trace's clock starts
        self.state["t0"] = time.perf_counter()
        if msg.get("trace"):
            self.trace_dir = os.path.join(self.out_dir, "trace")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            tracing.enable()
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.recorder.active = self.compiles.active = True
        return {"cmd": "window_start"}

    def window_stop(self, msg: dict) -> dict:
        import jax

        from repro.core import tracing
        self.recorder.active = self.compiles.active = False
        self.state["t1_ns"] = time.time_ns()
        self.state["t1"] = time.perf_counter()
        if self.trace_dir:
            jax.profiler.stop_trace()
            tracing.disable()
        self.state["fsyncs1"], self.state["frontend1"] = health(msg["url"])
        return {"cmd": "window_stop"}

    def collect(self, msg: dict) -> dict:
        import jax
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.local_devices()]
        out = {"cmd": "collect",
               "memory_peak_bytes": max(
                   (p for p in peaks if p is not None), default=None),
               "compiles": self.compiles.count,
               "compile_names": self.compiles.names[:20],
               "fsyncs": [self.state.get("fsyncs0"),
                          self.state.get("fsyncs1")],
               "frontend": [self.state.get("frontend0"),
                            self.state.get("frontend1")],
               "calls": len(self.recorder.shapes),
               "calls_unmatched": self.recorder.unmatched,
               "compaction_s": Spans.within(self.spans.compactions,
                                            self.state["t0"],
                                            self.state["t1"]),
               "gc_s": Spans.within(self.spans.gc, self.state["t0"],
                                    self.state["t1"]),
               "call_shapes": sorted(
                   {s: self.recorder.shapes.count(s)
                    for s in set(self.recorder.shapes)}.items()),
               "sampled": self.recorder.save(
                   os.path.join(self.out_dir, "calls.npz"))}
        if self.trace_dir:
            import devtrace
            out.update(reduce_trace(
                devtrace.extract(self.trace_dir),
                self.state["t1_ns"] - self.state["t0_ns"], self.sampler))
        return out


def reduce_trace(events: dict, window_ns: int, sampler) -> dict:
    """The traced window reduced: the device's side (``trace``) and the
    program's spans (``spans``), the service's own and those the sampler
    plug-in declares."""
    import devtrace
    import spans
    names = spans.names(sampler)
    return {"trace": devtrace.reduce(events, window_ns, names),
            "spans": spans.reduce(events, window_ns, names)}


def readback(plan: dict, journal_dir: str, path: str) -> None:
    """Every trial the run created, as a replay of the journal finds it."""
    from repro.core.durable import DurableStorage
    store = DurableStorage(journal_dir, fsync="off", auto_compact=False)
    try:
        out = {}
        for s in plan["studies"]:
            study = store.get_study(study_config(s).key())
            for t in study.trials[s["n_history"]:]:
                out[t.uid] = [t.state.value, t.value,
                              {str(k): v for k, v in t.intermediates.items()}]
    finally:
        store.close()
    with open(path, "w") as f:
        json.dump(out, f)


def plant_fault(fault: str, sampler_faults: dict) -> None:
    """Faults the tests plant under a run to see ``correct`` turn false:
    the sampler plug-in's own, and these beneath the rest of the path."""
    if fault in sampler_faults:
        sampler_faults[fault]()
    elif fault == "tell_ignored":          # a tell leaves the trial as it was
        from repro.core.storage import InMemoryStorage
        update = InMemoryStorage.update_trial

        def ignored(self, uid, *, idem=None, **fields):
            if fields.get("finished_at") is not None:
                fields = {}
            return update(self, uid, idem=idem, **fields)
        InMemoryStorage.update_trial = ignored
    elif fault == "liar_value":            # in-flight rows at the best value
        from repro.core import obs_cache
        obs_cache.liar_value = lambda y, mode: float(np.min(y))
    elif fault == "wal_in_process":        # acknowledged before the OS has it
        from repro.core.durable import DurableStorage
        log, close, held = DurableStorage._log, DurableStorage.close, []

        def hold(self, record):
            if self._replaying:
                return
            held.append((self, record))

        def close_and_write(self):
            for store, record in held:
                log(store, record)
            held.clear()
            return close(self)
        DurableStorage._log, DurableStorage.close = hold, close_and_write
    elif fault == "tell_value":            # a stored tell is altered
        from repro.core.storage import InMemoryStorage
        update = InMemoryStorage.update_trial

        def altered(self, uid, *, idem=None, **fields):
            if fields.get("finished_at") is not None \
                    and fields.get("value") is not None:
                fields["value"] += 1.0
            return update(self, uid, idem=idem, **fields)
        InMemoryStorage.update_trial = altered
    elif fault:
        raise ValueError(f"unknown fault {fault!r}")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--plan", required=True)
    ap.add_argument("--journal-dir", required=True)
    ap.add_argument("--control", choices=("high",), default=None)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--readback", action="store_true",
                    help="replay the journal of a stopped run and write "
                         "readback.json; starts no service")
    args = ap.parse_args(argv)
    with open(args.plan) as f:
        plan = json.load(f)
    out_dir = os.path.dirname(os.path.abspath(args.plan))
    if args.readback:
        readback(plan, args.journal_dir, os.path.join(out_dir,
                                                      "readback.json"))
        return 0

    from repro.core.kernels import device_report
    t0 = time.perf_counter()
    # the TPU runtime starts while the history is written
    init = threading.Thread(target=device_report, daemon=True)
    init.start()
    write_history(plan, args.journal_dir)
    t_hist = time.perf_counter() - t0
    init.join()

    import jax
    from repro.core import service
    # the studies of one configuration share its sampler
    sampler = registry.sampler(plan["studies"][0]["sampler"]["name"])
    if args.control == "high":
        sampler.control_high()
    plant_fault(args.fault, sampler.FAULTS)
    spans = Spans()
    spans.install()
    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    shapes = sampler.warm_shapes(plan)
    t1 = time.perf_counter()
    sampler.warm(shapes)
    print(f"bench: history {t_hist:.2f} s, runtime up "
          f"{t1 - t0:.2f} s, {len(shapes)} sampler shapes warmed in "
          f"{time.perf_counter() - t1:.2f} s", file=sys.stderr, flush=True)
    recorder = sampler.Recorder(plan["seed"], plan["sample_calls"])
    recorder.install()
    control_thread = Control(plan, out_dir, sampler, recorder, compiles,
                             spans)
    threading.Thread(target=control_thread.run, daemon=True).start()
    return service.main(["--port", "0", "--workers", "1", "--journal-dir",
                         args.journal_dir, *plan["service_flags"]])


if __name__ == "__main__":
    sys.exit(main())
