"""Reduces a JAX profiler trace of the service process to the numbers the
per-layer metrics read.

``extract`` (needs JAX) turns the ``.xplane.pb`` the profiler wrote into
plain lists: per plane and line, ``[name, start_ns, duration_ns]`` events
on the trace's own clock.  ``reduce`` (plain Python and numpy) computes
from them, over the window ``[0, window_ns)`` of that clock:

* ``busy_s``: the union of the intervals in which an operation ran on the
  device (the ``XLA Ops`` lines of the device planes), averaged over the
  devices; ``window_s``;
* ``programs``: per jitted program (``XLA Modules`` events, named without
  the ``jit_`` prefix, leading underscores and the trailing id),
  ``[executions, device seconds]``;
* ``kernels``: per Pallas kernel (ops whose name holds the kernel's name,
  ``parzen`` or ``matern``), ``[calls, device seconds]``;
* ``top_ops``: the ten device operations that took most time;
* ``idle_gaps``: the device's idle time split by what the host was doing
  meanwhile: each gap goes to the host event (JAX's own dispatch,
  transfer and compile events, not the program spans named in
  ``spans``) that overlaps most of it, or to ``host (no event)``; the ten
  largest shares.
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

KERNELS = ("parzen", "matern")
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
MAX_HOST_EVENT_NS = 1_000_000_000      # longer host events are containers


def extract(trace_dir: str) -> dict:
    import jax
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for e in line.events]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _op(name: str) -> str:
    """An op's instruction name without its HLO text."""
    return name.split(" = ", 1)[0].lstrip("%")


def _program(name: str) -> str:
    name = re.sub(r"\(\d+\)$", "", name)
    name = re.sub(r"^jit_", "", name)
    return name.lstrip("_")


def _union(intervals: np.ndarray) -> np.ndarray:
    """Disjoint sorted union of (start, end) rows."""
    if not len(intervals):
        return intervals.reshape(0, 2)
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    idx = np.flatnonzero(new)
    stops = np.maximum.reduceat(ends, idx)
    return np.stack([starts, stops], axis=1)


def _clip(events: list, w: int) -> np.ndarray:
    a = np.array([[s, s + d] for _n, s, d in events], dtype=np.int64)
    if not len(a):
        return a.reshape(0, 2)
    a = np.clip(a, 0, w)
    return a[a[:, 1] > a[:, 0]]


def reduce(trace: dict, window_ns: int, spans: tuple = ()) -> dict:
    device_planes = [p for p in trace["planes"]
                     if DEVICE_PLANE.match(p["name"])]
    busy, programs, kernels, ops = [], {}, {}, {}
    gaps_all = []
    for plane in device_planes:
        op_events = []
        for line in plane["lines"]:
            if line["name"] == "XLA Modules":
                for name, s, d in line["events"]:
                    if 0 <= s < window_ns:
                        p = programs.setdefault(_program(name), [0, 0.0])
                        p[0] += 1
                        p[1] += d / 1e9
            elif line["name"] == "XLA Ops":
                op_events += [e for e in line["events"]
                              if 0 <= e[1] < window_ns]
        for name, _s, d in op_events:
            name = _op(name)
            ops[name] = ops.get(name, 0.0) + d / 1e9
            low = name.lower()
            for k in KERNELS:
                if k in low:
                    kk = kernels.setdefault(k, [0, 0.0])
                    kk[0] += 1
                    kk[1] += d / 1e9
        u = _union(_clip(op_events, window_ns))
        busy.append(int((u[:, 1] - u[:, 0]).sum()))
        edges = np.concatenate([[0], u.ravel(), [window_ns]]).reshape(-1, 2)
        gaps_all.append(edges[edges[:, 1] > edges[:, 0]])
    gaps = (np.concatenate(gaps_all) if gaps_all
            else np.array([[0, window_ns]], dtype=np.int64))
    return {"window_s": window_ns / 1e9,
            "busy_s": (sum(busy) / len(busy) / 1e9) if busy else 0.0,
            "devices": len(device_planes),
            "programs": programs, "kernels": kernels,
            "top_ops": sorted(([n, s] for n, s in ops.items()),
                              key=lambda x: -x[1])[:10],
            "idle_gaps": attribute_gaps(gaps, _host_events(trace, window_ns,
                                                           spans))}


def _host_events(trace: dict, window_ns: int, spans: tuple) -> list:
    """JAX's own host events in the window.  The program's spans
    (``spans``) are left out: ``bench/spans.py`` reads them, and the
    request span around each gap would outweigh every event inside it."""
    service = frozenset(spans)
    out = []
    for plane in trace["planes"]:
        if not plane["name"].startswith("/host:"):
            continue
        for line in plane["lines"]:
            out += [e for e in line["events"]
                    if e[2] < MAX_HOST_EVENT_NS and e[1] < window_ns
                    and e[1] + e[2] > 0 and e[0] not in service]
    return out


def attribute_gaps(gaps: np.ndarray, host: list) -> list:
    """[[what the host was doing, idle seconds], ...], largest first."""
    share: dict[str, float] = {}
    if host:
        host = sorted(host, key=lambda e: e[1])
        starts = np.array([e[1] for e in host], dtype=np.int64)
        ends = np.array([e[1] + e[2] for e in host], dtype=np.int64)
        longest = int((ends - starts).max())
    for g0, g1 in gaps:
        name = "host (no event)"
        if host:
            lo = np.searchsorted(starts, g0 - longest)
            hi = np.searchsorted(starts, g1)
            if hi > lo:
                ov = (np.minimum(ends[lo:hi], g1)
                      - np.maximum(starts[lo:hi], g0))
                i = int(np.argmax(ov))
                if ov[i] > 0:
                    name = host[lo + i][0]
        share[name] = share.get(name, 0.0) + (g1 - g0) / 1e9
    return sorted(([n, s] for n, s in share.items()),
                  key=lambda x: -x[1])[:10]
