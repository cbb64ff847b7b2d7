"""Fused TPE Parzen-mixture log-density.

The TPE acquisition scores C candidates against N observations under a
per-dimension truncated-Gaussian mixture:

    out[c] = logsumexp_n[ sum_d( -0.5 z²  - log(bw_d √2π) ) ],
    z = (x[c,d] - obs[n,d]) / bw_d

The naive formulation materializes the (C, N, D) ``z`` tensor.  Expanding
the square turns the inner sum into a matmul over D:

    logk[c,n] = xs_c · os_n - 0.5|xs_c|² - (0.5|os_n|² + Σ_d log(bw_d√2π))
    (xs = x / bw, os = obs / bw)

so the whole score is one (C, D)x(D, N) contraction plus rank-1 terms —
MXU-shaped, no rank-3 intermediate.  The per-candidate term is pulled out
of the logsumexp (it is constant in n) and the per-observation term is
folded into the matmul by augmenting each operand with one extra column
(xa = [xs, -1], oa = [os, so]), so the Pallas kernel is a single tiled
matmul with a flash-attention-style *online logsumexp* across observation
tiles: running (max, sumexp) state lives in VMEM scratch across the
sequential trailing grid axis and the (C, N) score matrix never exists in
HBM either.

Masked observations (padding rows) get ``so = +LARGE`` which drives their
scores to -inf; if a whole tile is masked the online rescale wipes its
(garbage) contribution as soon as a valid tile arrives — callers always
have >= 1 valid observation.

The expanded square cancels large terms (|xs|², |os|² against 2 xs·os),
so the contraction runs at ``Precision.HIGHEST``: at the TPU's default,
one bfloat16 MXU pass, the log-density errs by ~0.9 at 4,096
observations in 24 dims.

The ``jnp`` fallback uses the same matmul-form math without the tiling.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._backend import backend as _select_backend
from ._backend import tile

NEG_INF = -1e30


def _parzen_kernel(xa_ref, oa_ref, out_ref, m_scr, l_scr, *,
                   n_obs_blocks: int):
    ni = pl.program_id(1)

    @pl.when(ni == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    xa = xa_ref[...].astype(jnp.float32)               # (bc, D+1)
    oa = oa_ref[...].astype(jnp.float32)               # (bn, D+1)
    s = jax.lax.dot_general(
        xa, oa, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,  # see module doc
        preferred_element_type=jnp.float32)            # (bc, bn)

    m_prev = m_scr[...]                                # (bc, 128)
    l_prev = l_scr[...]
    m_cur = jnp.max(s, axis=1, keepdims=True)          # (bc, 1)
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
    alpha = jnp.exp(m_prev - m_new)                    # rescale old sum
    p = jnp.exp(s - m_new[:, :1])                      # (bc, bn)
    l_new = alpha * l_prev + jnp.broadcast_to(
        jnp.sum(p, axis=1, keepdims=True), l_prev.shape)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ni == n_obs_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-37)             # fully-masked guard
        out_ref[...] = (jnp.log(l) + m_scr[...]).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _parzen_pallas(xa: jax.Array, oa: jax.Array, *,
                   interpret: bool = False) -> jax.Array:
    C, da = xa.shape
    N, _ = oa.shape
    bc, cp = tile(C)
    bn, n_pad = tile(N)
    xa = jnp.pad(xa, ((0, cp - C), (0, 0)))        # sliced off below
    if n_pad > N:       # padded observations are masked rows (+LARGE)
        fill = jnp.zeros((n_pad - N, da), oa.dtype).at[:, -1].set(-NEG_INF)
        oa = jnp.concatenate([oa, fill])
    n_obs_blocks = n_pad // bn
    out = pl.pallas_call(
        functools.partial(_parzen_kernel, n_obs_blocks=n_obs_blocks),
        grid=(cp // bc, n_obs_blocks),   # trailing obs axis runs in order
        in_specs=[
            pl.BlockSpec((bc, da), lambda ci, ni: (ci, 0)),
            pl.BlockSpec((bn, da), lambda ci, ni: (ni, 0)),
        ],
        out_specs=pl.BlockSpec((bc, 128), lambda ci, ni: (ci, 0)),
        out_shape=jax.ShapeDtypeStruct((cp, 128), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bc, 128), jnp.float32),        # running max
            pltpu.VMEM((bc, 128), jnp.float32),        # running sumexp
        ],
        interpret=interpret,
    )(xa, oa)
    return out[:C, 0]


def parzen_log_density(x: jax.Array, obs: jax.Array, mask: jax.Array,
                       bw: jax.Array, *, backend: str | None = None
                       ) -> jax.Array:
    """(C,) masked Parzen-mixture log-density of candidates ``x``.

    x: (C, D) candidates; obs: (N, D) observations (padded);
    mask: (N,) validity; bw: (D,) per-dim bandwidths.  Jit-composable —
    the backend branch resolves at trace time.
    """
    be = backend or _select_backend()
    xs = x / bw
    os_ = obs / bw
    sx = 0.5 * jnp.sum(xs * xs, axis=-1)                          # (C,)
    log_norm = jnp.sum(jnp.log(bw * math.sqrt(2 * math.pi)))
    so = 0.5 * jnp.sum(os_ * os_, axis=-1) + log_norm             # (N,)
    if be == "jnp":
        s = xs @ os_.T - so[None, :]                              # (C, N)
        s = jnp.where(mask[None, :] > 0, s, -jnp.inf)
        return jax.scipy.special.logsumexp(s, axis=1) - sx
    so_masked = jnp.where(mask > 0, so, -NEG_INF)    # +1e30: kill padding
    xa = jnp.concatenate([xs, -jnp.ones_like(sx)[:, None]], axis=1)
    oa = jnp.concatenate([os_, so_masked[:, None]], axis=1)
    out = _parzen_pallas(xa, oa, interpret=(be == "pallas_interpret"))
    return out - sx
