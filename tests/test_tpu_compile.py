"""Compile the Eq. (20) sign-consensus kernels for a described TPU v5e.

No chip is attached: the TPU compiler builds each program for one chip of
a described ``v5e:2x2`` topology from shapes alone, and refuses what the
chip would refuse (a kernel over its VMEM, a misaligned tile).  Nothing
runs, so this says nothing about results or times.  The topology is
described inside a fixture, never at import: only one process at a time may
load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

D = 16_384          # the MLP forecaster's widest leaf (128 x 128)

FLAVOURS = {  # name -> (message, weighted, n_total)
    "f32": ("f32", False, False),
    "f32_weighted_n_total": ("f32", True, True),
    "int8_scaled": ("int8", True, False),
    "int8": ("int8", False, False),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile written to the persistent cache cannot be read back
    # without a chip; keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.mark.parametrize("flavour", sorted(FLAVOURS))
@pytest.mark.parametrize("C", [64, 10_000])
def test_sign_consensus_compiles_for_v5e(one_chip, C, flavour):
    message, weighted, padded = FLAVOURS[flavour]

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    w = spec((C,)) if weighted else None
    lowered = ops.sign_consensus.lower(
        spec((D,)), spec((C, D)), spec((D,)), w, 0.005, 0.01,
        message=message, impl="pallas", n_total=C if padded else None)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shape", [(10_000, 128, 128), (6_000, 64, 24)],
                         ids=str)
def test_dual_mean_compiles_for_v5e_as_one_reduction(one_chip, shape):
    """The fold's dual mean on the TPU: no loop over the client rows, and
    no Pallas call (``fold_roofline`` counts each custom call of the
    round as one sign-fold call)."""
    lowered = ops.dual_mean.lower(
        jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct(shape[:1], jnp.float32, sharding=one_chip),
        shape[0], impl="pallas")
    text = lowered.compile().as_text()
    assert "while" not in text
    assert "tpu_custom_call" not in text
