"""The LERN Pallas kernels compile for a TPU v5e (Mosaic), at the sizes the
chip smoke run (``chip_smoke.py``) feeds them: config1's full-preset trace
(78,960 accesses, padded to 81,920), its 8 eligible layers as 16
segmented fit segments over 2 x 14,336 points, and the largest bucketed
capacity (8,192 points).

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology.  Interpret mode (the CPU default) proves nothing about the
tiling rules Mosaic enforces, so each test flips the ops wrappers'
``INTERPRET`` to False for the compile and clears JAX's trace caches on
both sides.  The persistent compilation cache is off around these
compiles: an entry written for a described device cannot be read back
without one.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

M_PAD = 81_920          # config1 trace, padded to the 4096-row block
P_SEG = 2 * 14_336      # RC + RI halves of the segmented fit
N_SEG = 16
CAP = 8_192             # largest bucketed-fit capacity


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Kernels lowered for Mosaic, with the compile cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.kernels.kmeans_assign import ops as kops
    from repro.kernels.ri_histogram import ops as rops
    monkeypatch.setattr(kops, "INTERPRET", False)
    monkeypatch.setattr(rops, "INTERPRET", False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.clear_caches()
    try:
        yield kops, rops
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()
        jax.clear_caches()


def _compile(fn, *specs) -> str:
    text = jax.jit(fn).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


def test_ri_histogram_compiles(one_chip, mosaic):
    _kops, rops = mosaic
    _compile(rops.histogram.__wrapped__,
             jax.ShapeDtypeStruct((M_PAD,), jnp.int32, sharding=one_chip))


def test_kmeans_assign_compiles(one_chip, mosaic):
    kops, _rops = mosaic
    _compile(kops.assign.__wrapped__,
             jax.ShapeDtypeStruct((CAP, 4), jnp.float32, sharding=one_chip),
             jax.ShapeDtypeStruct((4, 4), jnp.float32, sharding=one_chip))


def test_kmeans_assign_vmapped_compiles(one_chip, mosaic):
    """The bucketed fit vmaps the kernel over layers (and the serve
    profile fit over its two feature sets): the batched output block must
    obey the tiling rule too."""
    kops, _rops = mosaic
    _compile(jax.vmap(kops.assign.__wrapped__),
             jax.ShapeDtypeStruct((3, 256, 4), jnp.float32,
                                  sharding=one_chip),
             jax.ShapeDtypeStruct((3, 4, 4), jnp.float32, sharding=one_chip))


def test_kmeans_assign_segmented_compiles(one_chip, mosaic):
    kops, _rops = mosaic
    _compile(kops.assign_segmented.__wrapped__,
             jax.ShapeDtypeStruct((P_SEG, 4), jnp.float32, sharding=one_chip),
             jax.ShapeDtypeStruct((N_SEG, 4, 4), jnp.float32,
                                  sharding=one_chip),
             jax.ShapeDtypeStruct((P_SEG,), jnp.int32, sharding=one_chip))
