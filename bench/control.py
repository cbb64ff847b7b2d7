"""The control of the correctness check: the Parzen log-density computed
one precision step below the one the service's kernels state.

The kernels contract at ``Precision.HIGHEST`` (float32 on the MXU).  The
step below, and the one a later change would be tempted to take, is
``Precision.HIGH``: three bfloat16 passes.  It is written out here as the
three passes themselves (each operand split into a bfloat16 head and a
bfloat16 tail, the tail-times-tail product dropped), so that it computes
the same on the chip and on a CPU.  The split rounds with
``reduce_precision``, which XLA keeps: a round trip through a bfloat16
array may be folded away when excess precision is allowed.  Put in the
program's place, it has to make the check fail.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _bf16(a):
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _split(a):
    hi = _bf16(a)
    return hi, _bf16(a - hi)


def _dot_bf16x3(a, b):
    """a @ b.T in three bfloat16 passes with float32 accumulation."""
    ah, al = _split(a)
    bh, bl = _split(b)

    def dot(x, y):
        return jax.lax.dot_general(
            x, y, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,   # exact on bf16 values
            preferred_element_type=jnp.float32)
    return dot(ah, bh) + dot(ah, bl) + dot(al, bh)


def parzen_log_density_bf16x3(x, obs, mask, bw, *, backend=None):
    """(C,) masked Parzen-mixture log-density, expanded-square form."""
    xs, os_ = x / bw, obs / bw
    sx = 0.5 * jnp.sum(xs * xs, axis=-1)
    so = (0.5 * jnp.sum(os_ * os_, axis=-1)
          + jnp.sum(jnp.log(bw * math.sqrt(2 * math.pi))))
    s = _dot_bf16x3(xs, os_) - so[None, :]
    s = jnp.where(mask[None, :] > 0, s, -jnp.inf)
    return jax.scipy.special.logsumexp(s, axis=1) - sx
