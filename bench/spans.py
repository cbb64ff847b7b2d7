"""Reduces the service's own spans in a JAX profiler trace to the numbers
the host-layer metrics read.

The service writes its spans (``repro.core.tracing``) into the profiler's
trace while tracing is enabled: one line per host thread, on the clock of
the device planes.  The spans a run reads are the service's own
(``SPANS``) and those its sampler plug-in declares (``names``).
``reduce`` (plain Python and numpy) works on the events
``devtrace.extract`` returns, over the same window ``[0, window_ns)``,
and keeps the spans it is given by name; it gives:

* ``per_name``: per span name, ``[count, seconds]`` of the spans that
  start in the window, however long;
* ``idle_by_span``: the device's idle time (the complement of the union
  of its op intervals, as ``devtrace`` computes it) split over what the
  host was doing meanwhile, ``[[label, seconds], ...]`` largest first.
  Each instant of a gap goes to the innermost span open on a thread that
  has a request span (``REQUEST``) open, ``"<request span> (self)"`` when
  that is the request span itself; else to the innermost span open on
  any other thread (``wal.compact``, ``wal.fsync``, ``http.read``); else
  to ``no span``.  Where several threads qualify, the span opened last
  wins.  The shares sum to the idle time (averaged over the devices).
"""
from __future__ import annotations

import numpy as np

import devtrace

REQUEST = ("http.ask", "http.report", "http.tell", "http.request")
SPANS = REQUEST + ("http.read", "study.lock_wait", "obs_cache.sync",
                   "pruner.should_prune", "wal.append", "wal.fsync",
                   "wal.compact")
NO_SPAN = "no span"


def names(sampler) -> tuple:
    """The spans a run reads: the service's own and those of its sampler
    plug-in (``sampler.SPANS``)."""
    return SPANS + tuple(sampler.SPANS)


def reduce(trace: dict, window_ns: int, names: tuple) -> dict:
    threads = _threads(trace, frozenset(names))
    per_name: dict[str, list] = {}
    for spans in threads:
        for s, e, name in spans:
            if 0 <= s < window_ns:
                p = per_name.setdefault(name, [0, 0.0])
                p[0] += 1
                p[1] += (e - s) / 1e9
    gaps, devices = _gaps(trace, window_ns)
    pieces = _pieces([_clip(t, window_ns) for t in threads], window_ns)
    starts = np.array([a for a, _b, _l in pieces], dtype=np.int64)
    share: dict[str, float] = {}
    for g0, g1 in gaps.tolist():
        j = int(np.searchsorted(starts, g0, side="right")) - 1
        while j < len(pieces) and pieces[j][0] < g1:
            a, b, label = pieces[j]
            share[label] = (share.get(label, 0.0)
                            + (min(b, g1) - max(a, g0)) / 1e9 / devices)
            j += 1
    return {"per_name": per_name,
            "idle_by_span": sorted(([n, s] for n, s in share.items()),
                                   key=lambda x: -x[1])}


def _threads(trace: dict, names: frozenset) -> list[list[tuple]]:
    """Per host line, its spans named in ``names`` as (start, end, name),
    sorted by start with the outer of two spans that start together
    first."""
    out = []
    for plane in trace["planes"]:
        if not plane["name"].startswith("/host:"):
            continue
        for line in plane["lines"]:
            spans = sorted(((s, s + d, n) for n, s, d in line["events"]
                            if n in names), key=lambda x: (x[0], -x[1]))
            if spans:
                out.append(spans)
    return out


def _clip(spans: list[tuple], w: int) -> list[tuple]:
    return [(max(s, 0), min(e, w), n) for s, e, n in spans
            if min(e, w) > max(s, 0)]


def _gaps(trace: dict, window_ns: int) -> tuple[np.ndarray, int]:
    """Idle intervals of every device plane, sorted per device, and the
    number of devices."""
    planes = [p for p in trace["planes"]
              if devtrace.DEVICE_PLANE.match(p["name"])]
    out = []
    for plane in planes:
        ops = [e for line in plane["lines"] if line["name"] == "XLA Ops"
               for e in line["events"] if 0 <= e[1] < window_ns]
        u = devtrace._union(devtrace._clip(ops, window_ns))
        edges = np.concatenate([[0], u.ravel(), [window_ns]]).reshape(-1, 2)
        out.append(edges[edges[:, 1] > edges[:, 0]])
    if not out:
        return np.array([[0, window_ns]], dtype=np.int64), 1
    return np.concatenate(out), len(planes)


def _innermost(stacks: dict) -> str:
    """Label of one instant, from each thread's stack of open spans."""
    best = None
    for stack in stacks.values():
        request = any(n in REQUEST for _s, n in stack)
        key = (request, stack[-1][0])
        if best is None or key > best[0]:
            best = (key, stack)
    if best is None:
        return NO_SPAN
    (request, _), stack = best
    name = stack[-1][1]
    return f"{name} (self)" if request and name in REQUEST else name


def _pieces(threads: list[list[tuple]], w: int) -> list[tuple]:
    """(start, end, label) pieces that tile [0, w) in order."""
    events = []
    for t, spans in enumerate(threads):
        for s, e, name in spans:
            events.append((s, 1, -e, t, name))     # opens, outer first
            events.append((e, 0, 0, t, name))      # closes before opens
    events.sort()
    stacks: dict[int, list] = {}
    pieces, at, k = [], 0, 0
    while k < len(events):
        x = events[k][0]
        if x > at:
            pieces.append((at, x, _innermost(stacks)))
            at = x
        while k < len(events) and events[k][0] == x:
            s, opens, _e, t, name = events[k]
            if opens:
                stacks.setdefault(t, []).append((s, name))
            else:
                stack = stacks[t]
                stack.pop(max(i for i, (_s, n) in enumerate(stack)
                              if n == name))
                if not stack:
                    del stacks[t]
            k += 1
    if at < w:
        pieces.append((at, w, NO_SPAN))
    return pieces
