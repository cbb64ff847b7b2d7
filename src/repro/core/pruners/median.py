from __future__ import annotations

import math
from bisect import bisect_left

from ..types import StepView, Study, Trial
from .base import Pruner


def percentile_of_others(view: StepView, own: float | None,
                         percentile: float) -> float:
    """``float(np.percentile(others, percentile))``, bit for bit, where
    ``others`` is ``view`` less one copy of ``own`` (None: nothing left
    out) and is not empty.  NumPy's default ``method="linear"``: virtual
    index ``(m - 1) * q``, its floor and the next as neighbours (both the
    last at or past the end), and NumPy's two-sided ``_lerp``."""
    q = percentile / 100
    if not 0 <= q <= 1:
        raise ValueError("Percentiles must be in the range [0, 100]")
    if view.nans - (own is not None and own != own):
        return math.nan                 # NumPy: a NaN among the others
    m = len(view) - (own is not None)
    virtual = (m - 1) * q
    if virtual >= m - 1:
        lo = hi = m - 1
        gamma = virtual + 1             # NumPy takes it from index -1
    else:
        lo = math.floor(virtual)
        hi = lo + 1
        gamma = virtual - lo
    values = view.values
    if own is not None and own == own:
        skip = bisect_left(values, own)
        lo += lo >= skip
        hi += hi >= skip
    a, b = values[lo], values[hi]
    diff = b - a
    if gamma >= 0.5:
        return b - diff * (1 - gamma)
    return a + diff * gamma


class PercentilePruner(Pruner):
    """Prune if the trial's intermediate is worse than the given percentile
    of other trials' intermediates at the same step (Optuna semantics)."""

    def __init__(self, percentile: float = 50.0, n_startup_trials: int = 4,
                 n_warmup_steps: int = 0, interval_steps: int = 1):
        self.percentile = float(percentile)
        self.n_startup_trials = int(n_startup_trials)
        self.n_warmup_steps = int(n_warmup_steps)
        self.interval_steps = max(int(interval_steps), 1)

    def should_prune(self, study: Study, trial: Trial, step: int) -> bool:
        if step < self.n_warmup_steps:
            return False
        if (step - self.n_warmup_steps) % self.interval_steps != 0:
            return False
        sign = self._sign(study)
        # competitors: every other trial that reported at `step`, read from
        # the study's sorted per-step view (kept on report under the shard
        # lock) — an order statistic is an index, with no scan or sort
        view, own = study.step_view(step, sign, trial.uid)
        m = len(view) - (own is not None)
        if m == 0 or m < self.n_startup_trials:
            return False
        threshold = percentile_of_others(view, own, self.percentile)
        # best value this trial has achieved up to `step` (noise-robust)
        mine = min(sign * v for s, v in trial.intermediates.items() if s <= step)
        return mine > threshold


class MedianPruner(PercentilePruner):
    """Prune if worse than the median of other trials at the same step
    (Optuna's default pruner)."""

    def __init__(self, n_startup_trials: int = 4, n_warmup_steps: int = 0,
                 interval_steps: int = 1):
        super().__init__(percentile=50.0, n_startup_trials=n_startup_trials,
                         n_warmup_steps=n_warmup_steps, interval_steps=interval_steps)
