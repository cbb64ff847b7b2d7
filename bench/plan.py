"""The deployment and the request schedule of one run, from the seed.

A configuration fixes the studies (how many, their spaces and the history
each holds at the window's start); a mix fixes the traffic (rate, trials in
flight, reports per trial, ask batch, popularity over the studies).  The
layout of the studies comes from the configuration's own ``layout_seed``,
so every ``--seed`` runs the same deployment; the seed draws the history's
points and values, the arrival times and the order of think times and
study picks.  Every seed gets the same number of trial starts, the same
set of think times and the same number of starts per study: only their
order and the data change, so two seeds do the same work.  Arrivals are
Poisson in shape (exponential gaps) with the gaps, too, a fixed set.
"""
from __future__ import annotations

import math

import numpy as np

from spaces import SPACES, Codec, Objective


def apply_rehearse(block: dict) -> dict:
    """``block`` with its ``rehearse`` overrides applied (tiny sizes)."""
    out = {k: v for k, v in block.items() if k != "rehearse"}
    out.update(block.get("rehearse", {}))
    return out


def studies(config: dict, seed: int) -> list[dict]:
    spec = config["studies"]
    layout = np.random.default_rng(spec["layout_seed"])
    names, shares = zip(*sorted(spec["spaces"].items()))
    p = np.asarray(shares, float) / sum(shares)
    kinds = layout.choice(len(names), size=spec["count"], p=p)
    lo, hi = spec["history"]
    hist = layout.integers(lo, hi + 1, size=spec["count"])
    return [{"index": i, "name": f"{config['name']}-{seed}-{i}",
             "space": names[k], "n_history": int(n),
             "sampler": spec["sampler"], "pruner": spec["pruner"]}
            for i, (k, n) in enumerate(zip(kinds, hist))]


def objective(study: dict, seed: int) -> Objective:
    codec = Codec(SPACES[study["space"]]())
    return Objective(codec, np.random.default_rng([seed, study["index"], 1]))


def history(study: dict, seed: int) -> tuple[list[dict], np.ndarray]:
    """The completed trials a study holds at the start: seeded uniform
    points of its space and their objective values."""
    obj = objective(study, seed)
    rng = np.random.default_rng([seed, study["index"], 2])
    U = rng.uniform(size=(study["n_history"], obj.codec.dim))
    params = obj.codec.from_unit_rows(U)
    return params, obj.values(obj.codec.to_unit_rows(params))


def _stratified(n: int, rng: np.random.Generator) -> np.ndarray:
    """n quantile levels (i + 0.5) / n in a seeded order."""
    return rng.permutation((np.arange(n) + 0.5) / n)


def schedule(mix: dict, n_studies: int, seconds: float, seed: int,
             rehearse: bool = False) -> dict:
    """Jobs (one ask each, of ``batch`` trials) over warm-up + window.

    Returns arrays: ``due`` (job start, s from the schedule's origin,
    sorted), ``study`` (index per job), ``think`` ((jobs, batch) seconds
    from the ask to the tell of each trial), plus ``warm`` and ``end``.
    """
    if rehearse:
        mix = apply_rehearse(mix)
    if mix["loop"] != "open":
        raise ValueError(f"unsupported loop {mix['loop']!r}")
    rng = np.random.default_rng([seed, 7])
    warm, batch = float(mix["warm_seconds"]), int(mix["batch"])
    span = warm + seconds
    rate = float(mix["rate"])                      # trial starts per second
    n_jobs = max(1, int(round(rate * span / batch)))
    # Poisson arrivals: exponential gaps, the same set of them under every
    # seed (stratified quantiles) in a seeded order, scaled to the span
    gaps = -np.log1p(-_stratified(n_jobs, rng))
    due = (np.cumsum(gaps) - gaps[0]) * (span / gaps.sum())
    pop = mix["popularity"]
    if pop == "uniform":
        study = rng.integers(0, n_studies, size=n_jobs)
    elif "zipf" in pop:
        # Zipfian ranks (YCSB's constant), drawn at stratified levels so
        # each study gets the same number of jobs under every seed
        w = 1.0 / np.arange(1, n_studies + 1) ** float(pop["zipf"])
        cdf = np.cumsum(w) / w.sum()
        study = np.minimum(np.searchsorted(cdf, _stratified(n_jobs, rng)),
                           n_studies - 1)
    else:
        raise ValueError(f"unsupported popularity {pop!r}")
    # think time of each trial: exponential with the mean that keeps
    # `in_flight` trials running at `rate` (Little's law)
    mean = float(mix["in_flight"]) / rate
    think = -mean * np.log1p(-_stratified(n_jobs * batch, rng))
    return {"due": due, "study": study.astype(np.int64),
            "think": think.reshape(n_jobs, batch), "warm": warm,
            "end": span, "batch": batch,
            "reports": int(mix["reports_per_trial"])}


def sweep_schedule(mix: dict, n_studies: int, rates: list[float],
                   step_seconds: float, seed: int) -> dict:
    """Stepped rates back to back (the knee sweep): one schedule whose
    ``due`` times run through each rate for ``step_seconds``."""
    parts, t0 = [], 0.0
    for i, r in enumerate(rates):
        m = dict(mix, rate=r, warm_seconds=0.0)
        s = schedule(m, n_studies, step_seconds, seed + 1000 * i)
        s["due"] = s["due"] + t0
        parts.append(s)
        t0 += step_seconds
    return {"due": np.concatenate([p["due"] for p in parts]),
            "study": np.concatenate([p["study"] for p in parts]),
            "think": np.concatenate([p["think"] for p in parts]),
            "warm": float(mix["warm_seconds"]), "end": t0,
            "batch": parts[0]["batch"], "reports": parts[0]["reports"],
            "steps": [[i * step_seconds, (i + 1) * step_seconds, r]
                      for i, r in enumerate(rates)]}


def history_ranges(plan_studies: list[dict], sched: dict) -> list[list[int]]:
    """Per study, the fewest and most observations (completed plus
    in-flight) any ask of the schedule can see, with the batch's own
    fantasy rows: what the warm-up has to cover."""
    starts = np.bincount(sched["study"], minlength=len(plan_studies))
    b = sched["batch"]
    return [[s["n_history"], s["n_history"] + int(k) * b + b]
            for s, k in zip(plan_studies, starts)]


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (``inf`` entries are failed requests)."""
    xs = sorted(values)
    if not xs:
        return math.nan
    return float(xs[max(0, math.ceil(q / 100.0 * len(xs)) - 1)])
