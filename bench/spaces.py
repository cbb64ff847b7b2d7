"""Search spaces, the seeded objective and a plain unit-cube codec.

A space is the published search space of a configuration: ``lcbench7``
is LCBench's.  The codec follows the unit-cube mapping the service
documents (linear or log scale, ints rounded on either scale,
categoricals in equal-width bins); it is written here from that
description so that the benchmark's checks do not lean on the code they
check.
"""
from __future__ import annotations

import math

import numpy as np


def lcbench7() -> dict:
    """The seven hyperparameters of LCBench's funnel-shaped MLPs (Zimmer,
    Lindauer and Hutter, arXiv:2006.13799), with their ranges and
    scales."""
    return {"batch_size": {"type": "logint", "low": 16, "high": 512},
            "learning_rate": {"type": "loguniform", "low": 1e-4,
                              "high": 1e-1},
            "max_dropout": {"type": "uniform", "low": 0.0, "high": 1.0},
            "max_units": {"type": "logint", "low": 64, "high": 1024},
            "momentum": {"type": "uniform", "low": 0.1, "high": 0.99},
            "num_layers": {"type": "int", "low": 1, "high": 5},
            "weight_decay": {"type": "uniform", "low": 1e-5, "high": 1e-1}}


SPACES = {"lcbench7": lcbench7}
LOG = ("loguniform", "logint")
INTS = ("int", "logint")


class Codec:
    """Unit cube <-> parameters, dimensions in sorted-name order."""

    def __init__(self, props: dict):
        self.props = props
        self.names = sorted(props)
        self.dim = len(self.names)

    def to_unit(self, params: dict) -> np.ndarray:
        u = np.empty(self.dim)
        for i, k in enumerate(self.names):
            s, v = self.props[k], params[k]
            if s["type"] == "categorical":
                u[i] = (s["choices"].index(v) + 0.5) / len(s["choices"])
            elif s["type"] in LOG:
                lo, hi = math.log(s["low"]), math.log(s["high"])
                u[i] = (math.log(v) - lo) / (hi - lo)
            else:
                u[i] = (v - s["low"]) / (s["high"] - s["low"])
        return u

    def from_unit(self, u) -> dict:
        out = {}
        for i, k in enumerate(self.names):
            s, x = self.props[k], min(max(float(u[i]), 0.0), 1.0)
            if s["type"] == "categorical":
                n = len(s["choices"])
                out[k] = s["choices"][min(int(x * n), n - 1)]
            elif s["type"] == "loguniform":
                lo, hi = math.log(s["low"]), math.log(s["high"])
                out[k] = math.exp(lo + x * (hi - lo))
            elif s["type"] == "logint":
                lo, hi = math.log(s["low"]), math.log(s["high"])
                out[k] = int(round(math.exp(lo + x * (hi - lo))))
            elif s["type"] == "int":
                out[k] = int(round(s["low"] + x * (s["high"] - s["low"])))
            else:
                out[k] = s["low"] + x * (s["high"] - s["low"])
        return out

    def from_unit_rows(self, U: np.ndarray) -> list[dict]:
        """``from_unit`` of each row of U, a column at a time."""
        cols = []
        for i, k in enumerate(self.names):
            s, x = self.props[k], np.clip(U[:, i], 0.0, 1.0)
            if s["type"] == "categorical":
                n = len(s["choices"])
                idx = np.minimum((x * n).astype(np.int64), n - 1)
                cols.append([s["choices"][j] for j in idx])
            elif s["type"] == "loguniform":
                lo, hi = math.log(s["low"]), math.log(s["high"])
                cols.append(np.exp(lo + x * (hi - lo)).tolist())
            elif s["type"] == "logint":
                lo, hi = math.log(s["low"]), math.log(s["high"])
                cols.append(np.rint(np.exp(lo + x * (hi - lo)))
                            .astype(np.int64).tolist())
            elif s["type"] == "int":
                cols.append(np.rint(s["low"] + x * (s["high"] - s["low"]))
                            .astype(np.int64).tolist())
            else:
                cols.append((s["low"] + x * (s["high"] - s["low"])).tolist())
        return [dict(zip(self.names, row)) for row in zip(*cols)]

    def to_unit_rows(self, rows: list[dict]) -> np.ndarray:
        """``to_unit`` of each parameter dict, a column at a time."""
        U = np.empty((len(rows), self.dim))
        for i, k in enumerate(self.names):
            s = self.props[k]
            col = [r[k] for r in rows]
            if s["type"] == "categorical":
                index = {c: j for j, c in enumerate(s["choices"])}
                U[:, i] = (np.array([index[v] for v in col]) + 0.5) \
                    / len(s["choices"])
            elif s["type"] in LOG:
                lo, hi = math.log(s["low"]), math.log(s["high"])
                U[:, i] = (np.log(np.array(col, float)) - lo) / (hi - lo)
            else:
                U[:, i] = ((np.array(col, float) - s["low"])
                           / (s["high"] - s["low"]))
        return U

    def in_space(self, params: dict) -> bool:
        if set(params) != set(self.props):
            return False
        for k, s in self.props.items():
            v = params[k]
            if s["type"] == "categorical":
                ok = v in s["choices"]
            elif s["type"] in INTS:
                ok = (isinstance(v, int) and not isinstance(v, bool)
                      and s["low"] <= v <= s["high"])
            else:
                slack = 1e-9 * max(abs(s["low"]), abs(s["high"]))
                ok = (isinstance(v, (int, float)) and math.isfinite(v)
                      and s["low"] - slack <= v <= s["high"] + slack)
            if not ok:
                return False
        return True

    def same(self, a: dict, b: dict, rel: float = 1e-9) -> bool:
        """Equal parameters: discrete ones exactly, floats to ``rel``."""
        for k, s in self.props.items():
            x, y = a.get(k), b.get(k)
            if s["type"] == "categorical" or s["type"] in INTS:
                if x != y:
                    return False
            elif not (isinstance(x, (int, float)) and isinstance(y, (int, float))
                      and abs(x - y) <= rel * max(abs(x), abs(y), 1e-300)):
                return False
        return True


class Objective:
    """Seeded weighted quadratic bowl over the unit-mapped parameters."""

    def __init__(self, codec: Codec, rng: np.random.Generator):
        self.codec = codec
        self.opt = rng.uniform(0.1, 0.9, size=codec.dim)
        self.w = rng.uniform(0.5, 2.0, size=codec.dim)

    def values(self, U: np.ndarray) -> np.ndarray:
        """Objective of each row of a (k, dim) unit-cube matrix."""
        return ((U - self.opt) ** 2 * self.w).sum(-1)

    def __call__(self, params: dict) -> float:
        return float(self.values(self.codec.to_unit(params)[None])[0])


def intermediates(value: float, n_reports: int) -> list[float]:
    """Reported losses of a trial whose final value is ``value``: they fall
    towards it, so the median pruner ranks trials as their finals do."""
    return [value * (1.0 + 0.5 * (n_reports - 1 - k) / n_reports)
            for k in range(n_reports)]
