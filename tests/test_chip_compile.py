"""Compile the CFD kernel of ``chip_smoke.py``, and the AR cell's
reconstruct kernel, for a TPU v5e that is described, not attached. This
catches what the chip's compiler refuses, a lattice that does not fit
the chip's HBM, and a bf16 copy of the distribution (the TPU lowering of
a DEFAULT-precision f32 contraction) without a chip.

The topology is described inside a fixture: only one process at a time
may load the TPU compiler's library, so no module may touch it while it is
imported."""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.apps import lbm

H = W = 8192                    # chip_smoke.py's lattice
HBM_BYTES = 16 * 2**30          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else the compiler logs to /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("width", [W, W // 2 + 2, W // 4 + 2],
                         ids=["monolithic", "slab_2_servers",
                              "slab_4_servers"])
def test_lbm_step_compiles_for_v5e_in_f32(width, one_chip,
                                          no_persistent_cache):
    x = jax.ShapeDtypeStruct((9, H, width), jnp.float32, sharding=one_chip)
    compiled = lbm.lbm_step.lower(x).compile()
    assert "bf16[9," not in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES


AR_POINTS = 860_000             # the AR cell's frame capacity


def test_ar_reconstruct_compiles_for_v5e_in_f32(one_chip,
                                                no_persistent_cache):
    """The pose transform stays float32 (no bf16 operand), unpacking the
    frame's words needs no padded copy, and the frame's argument lies on
    the device as the host holds it (plain 32-bit words, no tiling the
    host would have to rearrange it into). (``ar_sort`` is left out: its
    860k-key sort takes the compiler some 80 s.)"""
    from repro.apps import ar

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = ar.ar_reconstruct.lower(
        arg((ar.PARAMS,), jnp.float32), arg((1,), jnp.uint32),
        arg((3 * AR_POINTS // 2,), jnp.uint32)).compile()
    assert "bf16" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**20
    layout = compiled.input_formats[0][2].layout
    assert layout.major_to_minor == (0,)
    assert layout.tiling == ((1024,),)

