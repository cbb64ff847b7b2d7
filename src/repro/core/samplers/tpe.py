"""Tree-structured Parzen Estimator (Bergstra et al. 2011) — the Optuna
default sampler the paper's reference implementation relies on.

The surrogate split/score path is implemented with JAX and jitted: trial
histories are padded to power-of-two lengths so that the jit cache stays
small while the KDE math runs as one fused XLA computation.  The Parzen
mixture scores go through ``repro.core.kernels.parzen_log_density`` — a
Pallas TPU kernel (tiled candidates x observations, online logsumexp,
no (C, N, D) intermediate) with an equivalent matmul-form ``jnp``
fallback off-TPU.

On the service ask path the observation matrix comes from the per-study
``ObservationCache`` (``cache=`` kwarg): history featurization is an O(1)
incremental append on tell, not a per-ask rescan of every trial.

Model: completed observations are split into the best ``gamma``-fraction
(l, "good") and the rest (g, "bad").  Each set defines a per-dimension
Parzen mixture (truncated Gaussians on the unit cube; categorical weights
for discrete dims).  ``n_candidates`` points are drawn from l(x) and the
one maximizing  log l(x) - log g(x)  (equivalently EI) is suggested.
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .. import tracing
from ..kernels import parzen_log_density
from ..obs_cache import check_liar, liar_value
from ..obs_cache import pad_pow2 as _pad_pow2
from ..space import SearchSpace
from ..types import Direction, Trial
from .base import Sampler
from .quasirandom import QuasiRandomSampler


@functools.partial(jax.jit, static_argnames=("n_candidates",))
def _tpe_propose(xg: jnp.ndarray, mg: jnp.ndarray,
                 xb: jnp.ndarray, mb: jnp.ndarray,
                 key: jax.Array, n_candidates: int) -> jnp.ndarray:
    """Propose points on the unit cube, best acquisition score first (the
    caller slices the top-k it needs — keeping the batch size out of the
    jit signature avoids a recompile per distinct k).

    xg: (Ng, D) good observations (padded), mg: (Ng,) validity mask.
    xb: (Nb, D) bad observations (padded),  mb: (Nb,) validity mask.
    Returns (n_candidates, D) candidates sorted by descending score.

    Both mixtures carry a uniform-prior component (a wide Gaussian at the
    cube center with weight 1, Optuna's ``prior_weight``): without it the
    l/g ratio over-exploits the incumbent cluster and TPE degenerates to
    local search.
    """
    d = xg.shape[1]
    kcand, kpick, kunif = jax.random.split(key, 3)

    def _bandwidth(obs, mask, lo, hi):
        n = jnp.maximum(mask.sum(), 1.0)
        mean = (obs * mask[:, None]).sum(0) / n
        var = ((obs - mean) ** 2 * mask[:, None]).sum(0) / n
        return jnp.clip(jnp.sqrt(var + 1e-12) * n ** (-1.0 / (d + 4)), lo, hi)

    bw = _bandwidth(xg, mg, 0.05, 0.5)
    bw_b = _bandwidth(xb, mb, 0.08, 0.7)

    # Candidates: 3/4 sampled from l(x) (good point + bandwidth jitter),
    # 1/4 uniform exploration.
    ng = jnp.maximum(mg.sum(), 1.0)
    idx = jax.random.categorical(kcand, jnp.log(mg / ng + 1e-20),
                                 shape=(n_candidates,))
    noise = jax.random.normal(kpick, (n_candidates, d)) * bw
    from_l = jnp.clip(xg[idx] + noise, 0.0, 1.0)
    uniform = jax.random.uniform(kunif, (n_candidates, d))
    take_l = (jnp.arange(n_candidates) % 4 != 3)[:, None]
    cands = jnp.where(take_l, from_l, uniform)

    def log_parzen(x, obs, mask, bws):
        # fused mixture log-density (Pallas on TPU, matmul-form jnp
        # fallback elsewhere) + the uniform-prior component
        logk = parzen_log_density(x, obs, mask, bws)
        zp = (x - 0.5) / 1.0
        logp = (-0.5 * zp * zp - jnp.log(math.sqrt(2 * math.pi))).sum(-1)
        n = jnp.maximum(mask.sum(), 1.0)
        return jnp.logaddexp(logk, logp) - jnp.log(n + 1.0)

    score = log_parzen(cands, xg, mg, bw) - log_parzen(cands, xb, mb, bw_b)
    return cands[jnp.argsort(-score)]


class TPESampler(Sampler):
    uses_cache = True
    pending_aware = True

    def __init__(self, n_startup_trials: int = 10, gamma: float | None = None,
                 n_candidates: int = 64, seed: int = 0, liar: str = "mean",
                 liar_chunk: int = 4):
        self.n_startup_trials = int(n_startup_trials)
        self.gamma = gamma                 # None -> Optuna default schedule
        self.n_candidates = int(n_candidates)
        self.liar = check_liar(liar)
        # batched asks re-split after every `liar_chunk` fantasy appends:
        # within a chunk the proposals are distinct top-scored candidates
        # of one fused evaluation, across chunks the liar rows push the
        # next chunk away from what the batch already claimed
        self.liar_chunk = max(1, int(liar_chunk))
        self._startup = QuasiRandomSampler(seed=seed)
        # good/bad split of the cached observations, memoized on the
        # cache token (observed count + pending-set fingerprint): the
        # split (and the padded device buffers) only changes when a tell
        # lands or the in-flight set churns — repeat asks against an
        # unchanged history skip straight to the jitted proposal
        self._split_key: tuple | None = None
        self._split: tuple | None = None

    def _n_good(self, n: int) -> int:
        if self.gamma is not None:
            return max(2, int(math.ceil(self.gamma * n)))
        return max(2, min(int(math.ceil(0.1 * n)), 25))   # Optuna default_gamma

    def _split_xy(self, space: SearchSpace, X: np.ndarray, y: np.ndarray
                  ) -> tuple:
        """Good/bad Parzen split of (X, y) as padded device buffers."""
        n_good = self._n_good(len(y))
        order = np.argsort(y)
        good, bad = X[order[:n_good]], X[order[n_good:]]
        if len(bad) == 0:       # degenerate split: everything is "good"
            bad = good

        ng, nb = _pad_pow2(len(good)), _pad_pow2(len(bad))
        xg = np.zeros((ng, space.dim)); xg[: len(good)] = good
        mg = np.zeros(ng); mg[: len(good)] = 1.0
        xb = np.zeros((nb, space.dim)); xb[: len(bad)] = bad
        mb = np.zeros(nb); mb[: len(bad)] = 1.0
        return (jnp.asarray(xg), jnp.asarray(mg),
                jnp.asarray(xb), jnp.asarray(mb))

    def _split_observations(self, space: SearchSpace, trials: list[Trial],
                            direction: Direction, cache: Any) -> tuple | None:
        """Padded (xg, mg, xb, mb) device buffers, or None in startup."""
        memo_key = None if cache is None else (id(cache), cache.token)
        if memo_key is not None and memo_key == self._split_key:
            return self._split
        X, y, n_obs = self.observations_pending(
            space, trials, direction, cache=cache, liar=self.liar)
        if n_obs < self.n_startup_trials or space.dim == 0:
            return None
        split = self._split_xy(space, X, y)
        if memo_key is not None:
            self._split_key, self._split = memo_key, split
        return split

    def speculative_ready(self, cache: Any) -> bool:
        return (self.liar != "none"
                and cache.count >= self.n_startup_trials)

    def _propose(self, space: SearchSpace, trials: list[Trial],
                 direction: Direction, rng: np.random.Generator,
                 k: int, cache: Any = None) -> np.ndarray | None:
        """(k, D) unit-cube proposals, or None while still in startup."""
        with tracing.span("tpe.propose"):
            split = self._split_observations(space, trials, direction,
                                             cache)
            if split is None:
                return None
            xg, mg, xb, mb = split
            key = jax.random.PRNGKey(int(rng.integers(0, 2**31 - 1)))
            u = _tpe_propose(xg, mg, xb, mb, key, self._pool(k))
            with tracing.span("tpe.readback"):
                return np.asarray(u[:k])

    def _pool(self, k: int) -> int:
        """Candidate-pool size for a top-``k`` draw: at least 4x the
        ask so the acquisition keeps selection pressure (top-k of a
        k-sized pool is just the pool, ranked), pow-2-padded so the jit
        cache stays small when k varies."""
        return max(self.n_candidates, _pad_pow2(4 * k))

    def suggest(self, space: SearchSpace, trials: list[Trial],
                direction: Direction, rng: np.random.Generator,
                cache: Any = None) -> dict[str, Any]:
        u = self._propose(space, trials, direction, rng, 1, cache=cache)
        if u is None:
            return self._startup.suggest(space, trials, direction, rng)
        return space.from_unit_vector(u[0])

    def suggest_batch(self, space: SearchSpace, trials: list[Trial],
                      direction: Direction, rng: np.random.Generator,
                      n: int, cache: Any = None, chunk: int | None = None,
                      **kwargs: Any) -> list[dict[str, Any]]:
        """Batch proposal with incremental constant-liar updates.

        The batch is built in chunks of ``liar_chunk``: each chunk takes
        the top-scored candidates of one fused KDE evaluation (distinct
        points, not copies of the argmax), then the chunk is appended to
        the history as fantasy rows at the liar value and the split is
        recomputed — so later chunks are repelled from what the batch
        already claimed, the same way concurrent workers repel each
        other through the pending view.  With ``liar="none"`` this
        degrades to the legacy single fused top-n draw.

        ``chunk`` overrides the adaptive chunk size — the speculative
        precompute streams a round as slices whose liar chaining happens
        in the caller (``CacheSnapshot.with_fantasies``), so each slice
        must be exactly one fused evaluation, not re-chunked here.
        """
        if self.liar == "none":
            u = self._propose(space, trials, direction, rng, n, cache=cache)
            if u is None:       # startup: fall back to the sequential path
                return super().suggest_batch(space, trials, direction, rng,
                                             n, cache=cache, **kwargs)
            return space.from_unit_matrix(u)

        X, y, n_obs = self.observations_pending(
            space, trials, direction, cache=cache, liar=self.liar)
        if n_obs < self.n_startup_trials or space.dim == 0:
            return super().suggest_batch(space, trials, direction, rng, n,
                                         cache=cache, **kwargs)
        lv = liar_value(y[:n_obs], self.liar)
        # large batches (speculative precompute at high parallelism) cap
        # the split count at 8: re-splitting every `liar_chunk` rows
        # would make a 256-proposal round ~64 KDE rebuilds, slow enough
        # to starve the queue it is meant to fill
        if chunk is None:
            chunk = max(self.liar_chunk, -(-n // 8))
        else:
            chunk = max(1, int(chunk))
        chunks: list[np.ndarray] = []
        got = 0
        while got < n:
            k = min(chunk, n - got)
            with tracing.span("tpe.propose"):
                xg, mg, xb, mb = self._split_xy(space, X, y)
                key = jax.random.PRNGKey(int(rng.integers(0, 2**31 - 1)))
                u = _tpe_propose(xg, mg, xb, mb, key, self._pool(k))
                with tracing.span("tpe.readback"):
                    u = np.asarray(u[:k])
            chunks.append(u)
            got += k
            if got < n:
                X = np.concatenate([X, u])
                y = np.concatenate([y, np.full(k, lv)])
        return space.from_unit_matrix(np.concatenate(chunks))
