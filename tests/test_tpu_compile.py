"""Compile the served path's Pallas kernels for a described TPU v5e.

Nothing runs: the TPU compiler, which is installed with JAX, compiles for a
chip that is described and not attached, and refuses what the chip's
compiler would refuse (block shapes off the (8, 128) tiling, gathers
Mosaic cannot lower, more fast memory than a kernel may use). Interpret
mode cannot see any of that. The topology is described inside a module
fixture, so collecting this file loads no TPU library; keep every such
compile in this one file."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.lod_cut import lod_pair_sweep_pallas
from repro.kernels.rasterize import rasterize_slabs_pallas


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs outside
        from jax.experimental import topologies
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_lod_pair_sweep_compiles_for_v5e(one_chip):
    """The pooled (client, slab) pair sweep at 4096 pairs x S = 4096."""
    k, s = 4096, 4096
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    text = _compiled_text(
        lambda *a: lod_pair_sweep_pallas(*a, interpret=False),
        sd((k, s, 3), jnp.float32), sd((k, s), jnp.float32),
        sd((k, s), jnp.int32), sd((k, s), jnp.bool_), sd((k, s), jnp.bool_),
        sd((k,), jnp.bool_), sd((k, 3), jnp.float32), sd((), jnp.float32),
        sd((k,), jnp.float32))
    assert "tpu_custom_call" in text


def test_rasterize_slabs_compiles_for_v5e(one_chip):
    """The fleet-pooled tile rasterizer at 4096 slabs x list_len 256."""
    n, l_max, tile = 4096, 256, 16
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    text = _compiled_text(
        lambda e, c, o: rasterize_slabs_pallas(e, c, o, tile=tile,
                                               interpret=False),
        sd((n, l_max, 9), jnp.float32), sd((n,), jnp.int32),
        sd((n, 2), jnp.int32))
    assert "tpu_custom_call" in text
