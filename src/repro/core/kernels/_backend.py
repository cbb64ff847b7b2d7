"""Backend selection, device report and tiling shared by the acquisition
kernels (see package doc)."""
from __future__ import annotations

import os
from pathlib import Path
from typing import Any

import jax

_VALID = ("pallas", "pallas_interpret", "jnp")

# JAX keys persistent-cache entries by path too, so the default must be a
# fixed place: the checkout this package was imported from.
CACHE_DIR = Path(__file__).resolve().parents[4] / ".jax_cache"


def backend() -> str:
    """The kernel backend in effect for this process."""
    env = os.environ.get("REPRO_HPO_KERNELS", "").strip().lower()
    if env:
        if env not in _VALID:
            raise ValueError(
                f"REPRO_HPO_KERNELS={env!r}; expected one of {_VALID}")
        return env
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def device_report() -> dict[str, Any]:
    """Initialise JAX for the samplers and say where they will run.

    The persistent compile cache goes to ``$JAX_COMPILATION_CACHE_DIR``
    when that is set (JAX reads it itself) and to ``<repo>/.jax_cache``
    otherwise, so each pow-2 history bucket compiles once per checkout
    rather than once per process.  On a TPU every program is kept: on a
    v5e most sampler programs compile in under JAX's default 1 s minimum,
    so with it a restarted service recompiled them inside its first
    asks.  The CPU keeps that minimum, since XLA:CPU logs an error line
    for each entry it loads back.  Call before the first compile.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    devices = jax.devices()
    if devices[0].platform == "tpu":
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "kernels": backend(),
            "cache": jax.config.jax_compilation_cache_dir}


def tile(n: int, cap: int = 128) -> tuple[int, int]:
    """``(block, padded)`` for an ``n``-long kernel grid axis.

    Mosaic accepts a block whose last two dims are multiples of
    (8, 128) or span the whole array axis: an axis of at most ``cap``
    is one whole-axis block, a longer one is split into ``cap``-sized
    blocks over ``n`` rounded up to a multiple of ``cap`` (the caller
    pads and slices)."""
    if n <= cap:
        return n, n
    return cap, -(-n // cap) * cap
