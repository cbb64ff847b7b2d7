"""Operations and bytes of one call of the Parzen log-density kernel.

The kernel scores C candidates against N observations in D dimensions as
one (C, D+1) x (D+1, N) contraction (the expanded square, with the
per-observation term folded into an extra column) and an online
logsumexp over N.  What the algorithm needs, whatever the tiling:

* operations: the contraction's 2 C N (D+1) multiply-adds, which the
  chip's matrix unit runs at its peak (the logsumexp's C N exponentials
  run elsewhere and are not counted, so the bound stays a lower bound);
* bytes: each operand read once from memory (float32) and C float32
  scores written.

The least time of a call is the larger of operations over peak operations
per second and bytes over peak memory bandwidth; ``bound`` names which.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


def flops(c: int, n: int, d: int) -> int:
    return 2 * c * n * (d + 1)


def bytes_moved(c: int, n: int, d: int) -> int:
    return 4 * (c * (d + 1) + n * (d + 1) + c)


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device {device_kind!r} in {PEAKS}")
    return table[device_kind]


def least_time(c: int, n: int, d: int, device_kind: str) -> tuple[float, str]:
    """(seconds, "compute" | "memory") for one call on ``device_kind``."""
    p = peaks(device_kind)
    t_op = flops(c, n, d) / p["flops_per_s"]
    t_mem = bytes_moved(c, n, d) / p["hbm_bytes_per_s"]
    return (t_op, "compute") if t_op >= t_mem else (t_mem, "memory")
