"""The TPE sampler's plug-in (``repro.core.samplers.tpe``): its program
``_tpe_propose`` warmed and recorded, its calls checked against the plain
reference (``bench/reference.py``), its control and its own faults.  The
contract is in ``samplers/__init__.py``."""
from __future__ import annotations

import threading

import numpy as np

from spaces import SPACES

# the sampler's spans (``repro.core.tracing``) that its metrics read
SPANS = ("tpe.propose", "tpe.readback")


# ``reference`` (numpy and scipy) is imported where it is used, by the
# harness: the launcher, which serves the window, does not carry it
def load(path: str) -> list[dict]:
    import reference
    return reference.load_calls(path)


def check(calls: list[dict], known: dict, history: set,
          served: np.ndarray) -> dict:
    import reference
    return reference.check_calls(calls, known, history, served)


def top(call: dict) -> np.ndarray:
    """The call's best-ranked candidate: what a single ask served."""
    return call["out"][0]


def control_high() -> None:
    """The plain Parzen log-density, contracted in three bfloat16 passes
    (``bench/control.py``), in the kernels' place."""
    import control
    from repro.core.samplers import tpe
    tpe.parzen_log_density = control.parzen_log_density_bf16x3


def _proposal_order() -> None:             # worst candidate served first
    from repro.core.samplers import tpe
    fn = tpe._tpe_propose
    tpe._tpe_propose = lambda *a: fn(*a)[::-1]


def _split_order() -> None:                # the worst rows taken as good
    from repro.core.samplers import tpe
    split = tpe.TPESampler._split_xy
    tpe.TPESampler._split_xy = \
        lambda self, space, X, y: split(self, space, X, -y)


FAULTS = {"proposal_order": _proposal_order, "split_order": _split_order}


def warm_shapes(plan: dict) -> set:
    """(dim, good rows, bad rows, candidates, top-k) of every sampler call
    the schedule can make, by the sampler's own bucketing rules."""
    from repro.core.obs_cache import pad_pow2
    from repro.core.samplers import make_sampler

    shapes = set()
    b = plan["batch"]
    for s, (lo, hi) in zip(plan["studies"], plan["ranges"]):
        sampler = make_sampler(dict(s["sampler"]))
        dim = len(SPACES[s["space"]]())
        chunk = 1 if b == 1 else max(sampler.liar_chunk, -(-b // 8))
        ks = {1} if b == 1 else {min(chunk, b - g) for g in range(0, b, chunk)}
        for n in range(lo, hi + 1):
            ng = sampler._n_good(n)
            nb = n - ng if n > ng else ng
            for k in ks:
                shapes.add((dim, pad_pow2(ng), pad_pow2(nb),
                            sampler._pool(k), k))
    return shapes


def warm(shapes: set) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core.samplers import tpe

    for dim, ng, nb, pool, k in sorted(shapes):
        mg, mb = np.zeros(ng), np.zeros(nb)
        mg[0] = mb[0] = 1.0
        # built as the sampler builds its buffers, so the call hits the
        # same compiled program
        args = (jnp.asarray(np.zeros((ng, dim))), jnp.asarray(mg),
                jnp.asarray(np.zeros((nb, dim))), jnp.asarray(mb))
        key = jax.random.PRNGKey(0)
        np.asarray(tpe._tpe_propose(*args, key, pool)[:k])


class Recorder:
    """Inside the window, wraps the TPE sampler's observation view, its
    good/bad split and its proposal program.  It counts proposal calls by
    shape and keeps a seeded sample of them, each with the observations
    (X, y and the count of real rows) that its split was made from, and
    the latest call at the largest history.  Only the small buffers (the
    good rows, both masks and the ranked candidates) stay on the device
    until the window has closed; X and y are host arrays the cache never
    changes in place."""

    KEEP = 8                        # recent views and splits to match

    def __init__(self, seed: int, sample: int):
        self.fn = None
        self.lock = threading.Lock()
        self.active = False
        self.rng = np.random.default_rng([seed, 11])
        self.cap = sample
        self.shapes: list[tuple] = []
        self.views: dict = {}
        self.splits: dict = {}
        self.kept: list[tuple] = []
        self.longest: tuple | None = None
        self.unmatched = 0

    def _remember(self, table: dict, key, value) -> None:
        table[key] = value
        while len(table) > self.KEEP:
            del table[next(iter(table))]

    def install(self) -> None:
        from repro.core.samplers import tpe
        rec, sampler = self, tpe.TPESampler
        view, split = sampler.observations_pending, sampler._split_xy

        def observations_pending(cls, *a, **kw):
            X, y, n_obs = out = view(*a, **kw)
            if rec.active:
                with rec.lock:
                    rec._remember(rec.views, id(X), (X, y, n_obs))
            return out

        def split_xy(self, space, X, y):
            out = split(self, space, X, y)
            if rec.active:
                with rec.lock:
                    v = rec.views.get(id(X))
                    if v is not None and v[0] is X:
                        rec._remember(rec.splits, id(out[0]), (out, *v))
            return out
        sampler.observations_pending = classmethod(observations_pending)
        sampler._split_xy = split_xy
        self.fn = tpe._tpe_propose
        tpe._tpe_propose = self

    def __call__(self, xg, mg, xb, mb, key, n_candidates):
        out = self.fn(xg, mg, xb, mb, key, n_candidates)
        if self.active:
            with self.lock:
                self.shapes.append((xg.shape[0], xb.shape[0], xg.shape[1],
                                    int(n_candidates)))
                s = self.splits.get(id(xg))
                if s is None or s[0][0] is not xg:
                    self.unmatched += 1
                    return out
                call = (s[1], s[2], s[3], xg, mg, mb, out)
                # a uniform sample of the window's calls (reservoir)
                n = len(self.shapes)
                if len(self.kept) < self.cap:
                    self.kept.append(call)
                else:
                    j = int(self.rng.integers(0, n))
                    if j < self.cap:
                        self.kept[j] = call
                if self.longest is None or len(s[2]) >= len(self.longest[1]):
                    self.longest = call
        return out

    def save(self, path: str) -> int:
        with self.lock:
            calls = list(self.kept)
            if self.longest is not None and not any(
                    c is self.longest for c in calls):
                calls.append(self.longest)
        arrays = {}
        for i, call in enumerate(calls):
            for name, a in zip(("X", "y", "n_obs", "xg", "mg", "mb", "out"),
                               call):
                arrays[f"{i}_{name}"] = np.asarray(a)
        np.savez(path, n=len(calls), **arrays)
        return len(calls)
