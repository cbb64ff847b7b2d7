"""Acquisition-kernel equivalence: the Pallas kernels (interpret mode on
CPU) and the matmul-form jnp fallbacks must both match the naive rank-3
reference formulations the seed code used."""
import json
import math
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.kernels import backend, matern52_cross, parzen_log_density
from repro.core.kernels._backend import CACHE_DIR

TOL = dict(rtol=2e-4, atol=2e-4)


def _naive_parzen(x, obs, mask, bw):
    """The seed formulation: materializes (C, N, D)."""
    z = (x[:, None, :] - obs[None, :, :]) / bw
    logk = (-0.5 * z * z - jnp.log(bw * math.sqrt(2 * math.pi))).sum(-1)
    logk = jnp.where(mask[None, :] > 0, logk, -jnp.inf)
    return jax.scipy.special.logsumexp(logk, axis=1)


def _naive_matern(a, b, ls):
    d = jnp.sqrt(jnp.maximum(
        ((a[:, None, :] - b[None, :, :]) ** 2 / ls ** 2).sum(-1), 1e-12))
    s5d = math.sqrt(5.0) * d
    return (1.0 + s5d + s5d ** 2 / 3.0) * jnp.exp(-s5d)


def _case(c, n, d, n_valid, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.uniform(size=(c, d)), jnp.float32)
    obs = jnp.asarray(rng.uniform(size=(n, d)), jnp.float32)
    mask = jnp.asarray((np.arange(n) < n_valid).astype(np.float32))
    bw = jnp.asarray(rng.uniform(0.05, 0.7, size=d), jnp.float32)
    return x, obs, mask, bw


@pytest.mark.parametrize("backend_name", ["jnp", "pallas_interpret"])
@pytest.mark.parametrize("c,n,d,n_valid", [
    (64, 8, 1, 3),          # minimum pads
    (64, 32, 5, 20),        # partial mask
    (128, 256, 3, 256),     # full mask, multiple obs tiles
    (256, 512, 11, 300),    # masked tail tiles
    (200, 300, 5, 250),     # unaligned counts: padded candidate + obs tiles
])
def test_parzen_matches_naive(backend_name, c, n, d, n_valid):
    x, obs, mask, bw = _case(c, n, d, n_valid)
    ref = _naive_parzen(x, obs, mask, bw)
    out = parzen_log_density(x, obs, mask, bw, backend=backend_name)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


@pytest.mark.parametrize("backend_name", ["jnp", "pallas_interpret"])
@pytest.mark.parametrize("a,b,d", [(8, 8, 2), (64, 32, 5), (256, 128, 7),
                                   (300, 200, 6)])   # unaligned: padded
def test_matern_matches_naive(backend_name, a, b, d):
    rng = np.random.default_rng(1)
    xa = jnp.asarray(rng.uniform(size=(a, d)), jnp.float32)
    xb = jnp.asarray(rng.uniform(size=(b, d)), jnp.float32)
    ls = jnp.asarray(rng.uniform(0.1, 0.5, size=d), jnp.float32)
    ref = _naive_matern(xa, xb, ls)
    out = matern52_cross(xa, xb, ls, backend=backend_name)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


def test_parzen_jit_composable():
    """The op must be callable from inside jax.jit (the TPE path)."""
    x, obs, mask, bw = _case(64, 16, 3, 10)

    @jax.jit
    def f(x, obs, mask, bw):
        return parzen_log_density(x, obs, mask, bw, backend="jnp")

    np.testing.assert_allclose(np.asarray(f(x, obs, mask, bw)),
                               np.asarray(_naive_parzen(x, obs, mask, bw)),
                               **TOL)


def test_backend_auto_selection_off_tpu():
    if jax.default_backend() != "tpu":
        assert backend() == "jnp"


def test_backend_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_HPO_KERNELS", "pallas_interpret")
    assert backend() == "pallas_interpret"
    monkeypatch.setenv("REPRO_HPO_KERNELS", "bogus")
    with pytest.raises(ValueError):
        backend()


@pytest.mark.parametrize("cache_var", [False, True])
def test_service_banner_reports_device_and_cache(tmp_path, cache_var):
    """The single-process service names where its samplers run (cpu/jnp
    under JAX_PLATFORMS=cpu) and where compiled programs are cached."""
    repo = Path(__file__).resolve().parents[2]
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONUNBUFFERED="1",
               PYTHONPATH=str(repo / "src"))
    for var in ("REPRO_HPO_KERNELS", "REPRO_WORKERS", "REPRO_REPLICAS",
                "JAX_COMPILATION_CACHE_DIR"):
        env.pop(var, None)
    expected_cache = repo / ".jax_cache"
    if cache_var:
        expected_cache = tmp_path / "jax-cache"
        env["JAX_COMPILATION_CACHE_DIR"] = str(expected_cache)
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.core.service", "--port", "0",
         "--workers", "1"], env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    lines = []
    try:
        for line in proc.stdout:        # EOF if the service dies
            lines.append(line)
            if line.startswith("API token:"):
                break
    finally:
        proc.kill()
        proc.wait(timeout=10)
    prefix = "sampler device: "
    reports = [json.loads(ln[len(prefix):]) for ln in lines
               if ln.startswith(prefix)]
    assert len(reports) == 1, "".join(lines)
    device = reports[0]
    assert (device["platform"], device["kind"]) == ("cpu", "cpu")
    assert device["count"] >= 1 and device["kernels"] == "jnp"
    assert device["cache"] == str(expected_cache)
    assert CACHE_DIR == repo / ".jax_cache"
