"""Plain reference of the TPE acquisition the service computes on the chip.

TPE (Bergstra et al. 2011, as the service documents it) splits the
observations into a good set l and a bad set g, models each as a mixture
of per-dimension Gaussians with a data-driven bandwidth plus one wide
prior component, and ranks candidates by log l(x) - log g(x).  Here that
is written out in float64 with the direct per-dimension sum (no expanded
square, no tiling), from the description and not from the program.

``check_calls`` takes each recorded sampler call with the observations
it was made from, splits them itself, scores the program's candidates
and reports how far the program's ranking strays from the reference's:
the widest gap by which the candidate the program put at rank k lies
below the best reference score among the candidates it put at rank k or
later.  A batch ask serves the top k in that order, so every rank is
served to someone; for a single ask the rank-0 gap is the served
point's.  It also holds the observations themselves to the run: the
split on the chip, the in-flight rows' imputed value, and every row
against the trials the run has.
"""
from __future__ import annotations

import math

import numpy as np
from scipy.spatial.distance import cdist

LOG_SQRT_2PI = 0.5 * math.log(2.0 * math.pi)


def bandwidth(obs: np.ndarray, mask: np.ndarray, lo: float,
              hi: float) -> np.ndarray:
    """Scott-style bandwidth per dimension, clipped to [lo, hi]."""
    d = obs.shape[1]
    rows = obs[mask > 0]
    n = max(len(rows), 1)
    var = ((rows - rows.mean(0)) ** 2).sum(0) / n if len(rows) else 0.0
    return np.clip(np.sqrt(var + 1e-12) * n ** (-1.0 / (d + 4)), lo, hi)


def log_mixture(x: np.ndarray, obs: np.ndarray, bw: np.ndarray
                ) -> np.ndarray:
    """log of (sum over observations of the product over dimensions of
    N(x_d; obs_d, bw_d) + N(x; 0.5, 1)) / (n + 1), per candidate row.

    The squared scaled distances are summed difference by difference
    (``cdist``), never through the expanded square."""
    d2 = cdist(x / bw, obs / bw, "sqeuclidean")
    logk = -0.5 * d2 - np.log(bw).sum() - x.shape[1] * LOG_SQRT_2PI
    prior = (-0.5 * (x - 0.5) ** 2 - LOG_SQRT_2PI).sum(-1)
    logs = np.concatenate([logk, prior[:, None]], axis=1)
    m = logs.max(1, keepdims=True)
    return (m[:, 0] + np.log(np.exp(logs - m).sum(1))
            - math.log(len(obs) + 1.0))


def acquisition(cands, xg, mg, xb, mb) -> np.ndarray:
    """log l(x) - log g(x) of each candidate, in float64."""
    cands, xg, xb = (np.asarray(a, np.float64) for a in (cands, xg, xb))
    mg, mb = np.asarray(mg), np.asarray(mb)
    good = log_mixture(cands, xg[mg > 0], bandwidth(xg, mg, 0.05, 0.5))
    bad = log_mixture(cands, xb[mb > 0], bandwidth(xb, mb, 0.08, 0.7))
    return good - bad


def rank_gap(scores_in_program_order: np.ndarray) -> float:
    s = np.asarray(scores_in_program_order, np.float64)
    best_after = np.maximum.accumulate(s[::-1])[::-1]
    return float((best_after - s).max())


def n_good(n: int) -> int:
    """Optuna's default TPE split: the best min(ceil(0.1 n), 25), at least
    two."""
    return max(2, min(int(math.ceil(0.1 * n)), 25))


def split(X: np.ndarray, y: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(good rows, bad rows) of the observations: the ``n_good`` lowest
    values are good (every row counts, the in-flight ones too)."""
    order = np.argsort(y, kind="stable")
    k = n_good(len(y))
    return X[order[:k]], X[order[k:]]


def _same_rows(a: np.ndarray, b: np.ndarray) -> bool:
    return a.shape == b.shape and np.array_equal(
        a[np.lexsort(a.T[::-1])], b[np.lexsort(b.T[::-1])])


def rows_wrong(X: np.ndarray, y: np.ndarray, n_obs: int, known: dict,
               history: set, served: np.ndarray, tol: float = 1e-9) -> int:
    """Rows of one call's observations that are not what the run holds:
    an observed row whose value is no trial's, or whose point is not that
    trial's; a value twice; a history trial missing; an in-flight row that
    is no served trial's point."""
    obs = y[:n_obs].tolist()
    wrong = n_obs - len(set(obs)) + len(history.difference(obs))
    rows = [known.get(v) for v in obs]
    wrong += sum(r is None for r in rows)
    have = [i for i, r in enumerate(rows) if r is not None]
    if have:
        want = np.stack([rows[i] for i in have])
        wrong += int((np.abs(X[have] - want).max(1) > tol).sum())
    pending = X[n_obs:]
    if len(pending):
        if not len(served):
            return wrong + len(pending)
        near = cdist(pending, served, "chebyshev").min(1)
        wrong += int((near > tol).sum())
    return wrong


def check_calls(calls: list[dict], known: dict, history: set,
                served: np.ndarray) -> dict:
    """Each recorded sampler call against its observations.

    ``known`` maps every completed value the run can hold (the history's
    and every served trial's) to its point on the unit cube; ``history``
    is the set of the history's values; ``served`` the points of every
    served trial.  Returns the widest ``rank_gap`` over the calls (the
    program's candidate order scored on the reference's own split of X
    and y); ``split_violations``, calls whose good rows on the chip are
    not the reference's; ``liar_gap``, the widest relative distance of an
    in-flight row's value from the mean of the observed values (the
    configuration's ``liar=mean``); and ``rows_wrong`` (see above)."""
    gap = liar = 0.0
    bad_split = wrong = 0
    for c in calls:
        X, y, n_obs = c["X"], c["y"], int(c["n_obs"])
        good, bad = split(X, y)
        # on the chip the rows are float32
        good32, bad32 = good.astype(np.float32), bad.astype(np.float32)
        chip = np.asarray(c["xg"])[np.asarray(c["mg"]) > 0]
        if (not _same_rows(chip.astype(np.float32), good32)
                or int(np.sum(np.asarray(c["mb"]) > 0)) != len(bad)):
            bad_split += 1
        gap = max(gap, rank_gap(acquisition(
            c["out"], good32, np.ones(len(good)), bad32,
            np.ones(len(bad)))))
        if len(y) > n_obs:
            lv = float(np.sum(y[:n_obs]) / n_obs)
            liar = max(liar, float(np.abs(y[n_obs:] - lv).max())
                       / max(1.0, abs(lv)))
        wrong += rows_wrong(X, y, n_obs, known, history, served)
    return {"rank_gap": gap, "split_violations": bad_split,
            "liar_gap": liar, "rows_wrong": wrong}


def load_calls(path: str) -> list[dict]:
    names = ("X", "y", "n_obs", "xg", "mg", "mb", "out")
    with np.load(path) as z:
        return [{k: z[f"{i}_{k}"] for k in names} for i in range(int(z["n"]))]
