"""Fused acquisition kernels for the HPO service samplers.

Two backends per op, selected automatically:

  * ``pallas`` — real TPU kernels (flash-attention-style tiling, online
    logsumexp) that never materialize the (candidates, observations, dim)
    intermediate the naive formulation implies;
  * ``jnp``    — pure jax.numpy fallback with the same matmul-form math
    (still avoids the rank-3 intermediate), used off-TPU and under
    ``JAX_PLATFORMS=cpu`` CI so the fallback path stays exercised.

Selection: ``REPRO_HPO_KERNELS`` env var (``pallas`` | ``pallas_interpret``
| ``jnp``) wins; otherwise ``pallas`` on a TPU backend, ``jnp`` elsewhere.
``pallas_interpret`` runs the Pallas kernels in interpret mode (Python
emulation) — slow, but it lets CPU tests exercise the kernel bodies.
``device_report()`` initialises JAX, points the persistent compile cache
at its directory and returns the platform, device kind and count and the
backend in effect; the service prints it at start-up.

All public ops are jit-composable: the backend branch happens at trace
time, so they can be called from inside ``jax.jit``-ted sampler code.
"""
from __future__ import annotations

from ._backend import backend, device_report
from .matern import matern52_cross
from .parzen import parzen_log_density

__all__ = ["backend", "device_report", "matern52_cross",
           "parzen_log_density"]
