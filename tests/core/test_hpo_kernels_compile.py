"""Compile guards: the acquisition kernels, and the TPE/GP programs that
call them, compile for a described TPU v5e at the sizes the service runs.

Nothing runs here.  The TPU compiler refuses what the chip would refuse
(blocks not aligned to the (8, 128) tiling, too much fast memory), which
interpret mode on the CPU cannot show.  The topology is described inside
a module fixture, never at import: only one process at a time may load
the TPU library, and every test worker imports every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.kernels import matern52_cross, parzen_log_density
from repro.core.samplers.gp import _gp_ei
from repro.core.samplers.tpe import _tpe_propose


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep it out of any cache the process has
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()


@pytest.fixture
def pallas(monkeypatch):
    # backend() would see the CPU; the described chip needs the kernels
    monkeypatch.setenv("REPRO_HPO_KERNELS", "pallas")


def _shapes(sharding, *shapes, dtype=jnp.float32):
    return [jax.ShapeDtypeStruct(s, dtype, sharding=sharding)
            for s in shapes]


def _assert_kernel(compiled):
    # a stale trace of the jnp branch would compile too: check the
    # Pallas kernel is in the program
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("c,n,d", [
    (64, 4096, 24),         # TPE single ask, 4k-trial history, 24 dims
    (256, 16384, 51),       # large pool, 16k history, 51 dims
    (200, 300, 5),          # unaligned counts from a study spec
])
def test_parzen_compiles_for_v5e(one_chip, pallas, c, n, d):
    fn = jax.jit(lambda x, o, m, b: parzen_log_density(x, o, m, b))
    args = _shapes(one_chip, (c, d), (n, d), (n,), (d,))
    _assert_kernel(fn.lower(*args).compile())


@pytest.mark.parametrize("a,b,d", [
    (256, 512, 12),         # GP candidates x 512 observations
    (300, 200, 6),          # unaligned counts from a study spec
])
def test_matern_compiles_for_v5e(one_chip, pallas, a, b, d):
    fn = jax.jit(lambda x, y, ls: matern52_cross(x, y, ls))
    args = _shapes(one_chip, (a, d), (b, d), (d,))
    _assert_kernel(fn.lower(*args).compile())


def test_tpe_propose_compiles_for_v5e(one_chip, pallas):
    # a fresh jit of the body, so no earlier CPU trace is reused
    fn = jax.jit(_tpe_propose.__wrapped__, static_argnames=("n_candidates",))
    xg, mg, xb, mb = _shapes(one_chip, (32, 24), (32,), (4096, 24), (4096,))
    (key,) = _shapes(one_chip, (2,), dtype=jnp.uint32)
    _assert_kernel(fn.lower(xg, mg, xb, mb, key, n_candidates=64).compile())


def test_gp_ei_compiles_for_v5e(one_chip, pallas):
    fn = jax.jit(_gp_ei.__wrapped__)
    args = _shapes(one_chip, (512, 12), (512,), (512,), (256, 12), (12,))
    _assert_kernel(fn.lower(*args).compile())
