"""Compile rehearsals for the chip: the federated path's Pallas kernels at
the shapes of the cold start, compiled through Mosaic for a described TPU
v5e chip (``interpret=False``). Nothing runs, so these say nothing about
results or times — they catch what the chip's compiler refuses (layouts,
tiling, VMEM) before any chip time is spent.

The topology is described in a fixture, never at import: only one process
at a time may load the TPU library, and every test worker imports every
test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.edc_cosine import edc_cosine
from repro.kernels.madc import madc_block

D_W_FEMNIST_MLP = 415_258      # paper Table 2, FEMNIST MLP-512


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                              # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off around it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.usefixtures("no_persistent_cache")
class TestKernelsCompileForV5e:
    @pytest.mark.parametrize("n", [60, 128, 200, 512])
    def test_madc_block(self, one_chip, n):
        M = jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=one_chip)
        text = _compiled_text(lambda m: madc_block(m, interpret=False), M)
        assert "tpu_custom_call" in text

    def test_edc_cosine_at_femnist_mlp_width(self, one_chip):
        dW = jax.ShapeDtypeStruct((60, D_W_FEMNIST_MLP), jnp.float32,
                                  sharding=one_chip)
        V = jax.ShapeDtypeStruct((D_W_FEMNIST_MLP, 3), jnp.float32,
                                 sharding=one_chip)
        text = _compiled_text(lambda a, b: edc_cosine(a, b, interpret=False),
                              dW, V)
        assert "tpu_custom_call" in text
