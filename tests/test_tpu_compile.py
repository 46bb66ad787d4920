"""Compile-only guard of the score-reduce kernels for a TPU v5e chip.

The TPU compiler compiles for a described ``v5e:2x2`` topology without a
chip attached, so these tests catch what interpret mode cannot (block
tiling rules, scalar stores to VMEM) at no chip time.  Each jitted
reduction compiles in ``pallas`` mode at a shape the 256-node elastic fleet
launches and at a pod-scale shape, and the compiled program must hold the
Pallas kernel (``tpu_custom_call``).  This is the only test file that
describes the chip: the topology is built inside a module fixture, never
at import, so every xdist worker collects the same tests and only the
worker given this file loads the TPU library.  Nothing here runs a kernel.

Also here: how the kernel mode is resolved, which decides whether the
compiled kernel runs at all.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import score_reduce as sr


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off around them."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def table(one_chip, no_persistent_cache):
    """The packed operand table of ``rows`` x ``s_pad`` slots (a leading
    node axis first, for the batch), as a shape on the described chip."""
    def make(*rows, s_pad):
        return jax.ShapeDtypeStruct((*rows, 3 * s_pad + 8), jnp.float32,
                                    sharding=one_chip)

    return make


def assert_kernel(lowered):
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("b_pad,s_pad", [(256, 8), (65536, 16)])
def test_solo_reduce_compiles_for_v5e(table, b_pad, s_pad):
    assert_kernel(sr._reduce_jit.lower(table(b_pad, s_pad=s_pad), mode="pallas"))


@pytest.mark.parametrize("d_pad,b_pad,s_pad", [(16, 256, 8), (64, 4096, 16)])
def test_batch_reduce_compiles_for_v5e(table, d_pad, b_pad, s_pad):
    assert_kernel(sr._reduce_batch_jit.lower(
        table(d_pad, b_pad, s_pad=s_pad), mode="pallas"
    ))


@pytest.mark.parametrize(
    "b_pad,s_pad,n_windows", [(256, 8, 8), (65536, 16, 512)]
)
def test_multi_reduce_compiles_for_v5e(table, b_pad, s_pad, n_windows):
    assert_kernel(sr._reduce_multi_jit.lower(
        table(b_pad, s_pad=s_pad), n_windows=n_windows, mode="pallas",
    ))


@pytest.mark.parametrize("forced", ["pallas", "interpret", "ref"])
def test_backend_mode_honours_known_values(monkeypatch, forced):
    monkeypatch.setenv("REPRO_KERNELS", forced)
    assert sr.backend_mode() == forced


def test_backend_mode_defaults_to_ref_off_tpu(monkeypatch):
    monkeypatch.delenv("REPRO_KERNELS", raising=False)
    assert jax.default_backend() != "tpu"
    assert sr.backend_mode() == "ref"


def test_unknown_kernel_mode_is_refused(monkeypatch):
    """A typo must not silently run the compiled kernel."""
    monkeypatch.setenv("REPRO_KERNELS", "interpet")
    with pytest.raises(ValueError, match="interpet"):
        sr.backend_mode()
    monkeypatch.delenv("REPRO_KERNELS")
    with pytest.raises(ValueError, match="palas"):
        sr.score_reduce(
            jnp.zeros((1, 1)), jnp.zeros((1, 1)), jnp.ones(1),
            lam=0.35, g_free=1, M=1, mode="palas",
        )
