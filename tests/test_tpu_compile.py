"""The Pallas kernels compile for a TPU v5e at real widths.

The chip is described, not attached: the TPU compiler that ships with
libtpu lowers and compiles each kernel for one chip of a ``v5e:2x2``
topology, with ``interpret=False``, and the compiled text must hold the
Mosaic kernel (``tpu_custom_call``).  This catches what interpret mode
cannot — block shapes the TPU lowering refuses, unsupported ops, VMEM
overuse — without chip time.  Nothing runs, so results are not checked
here (chip_smoke.py does that on the chip).

The topology is described inside the module fixture, never at import: one
process at a time may hold libtpu, and only the test worker given this file
should load it.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.crossbar_vmm.kernel import crossbar_vmm_tiles
from repro.kernels.lif_step.kernel import lif_step_tiles
from repro.vp.cim import XBAR

U = 4  # crossbar units per call
R = C = XBAR


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile for
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()
    mp.undo()


def _shapes(one_chip, **shapes):
    return {k: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for k, (s, d) in shapes.items()}


LIF = dict(weights=((U, R, C), jnp.int8), spikes=((U, C), jnp.int32),
           v=((U, R), jnp.int32), refrac=((U, R), jnp.int32),
           thresh=((U,), jnp.int32), leak=((U,), jnp.int32),
           refrac_period=((U,), jnp.int32))
LIF_EXTRA = dict(extra=((U, R), jnp.int32))
LIF_FAULTS = dict(f_and=((U, R, C), jnp.int8), f_xor=((U, R, C), jnp.int8),
                  dead=((U, R), jnp.bool_), dth=((U, R), jnp.int32))
XB = dict(x=((U, C), jnp.int32), weights=((R, C), jnp.int8))
XB_FAULTS = dict(f_and=((R, C), jnp.int8), f_xor=((R, C), jnp.int8))


@pytest.mark.parametrize("kernel, shapes", [
    (lif_step_tiles, LIF),
    (lif_step_tiles, {**LIF, **LIF_EXTRA}),
    (lif_step_tiles, {**LIF, **LIF_EXTRA, **LIF_FAULTS}),
    (crossbar_vmm_tiles, XB),
    (crossbar_vmm_tiles, {**XB, **XB_FAULTS}),
], ids=["lif_step", "lif_step-extra", "lif_step-faults", "crossbar_vmm",
        "crossbar_vmm-faults"])
def test_kernel_compiles_for_v5e(one_chip, kernel, shapes):
    compiled = kernel.lower(**_shapes(one_chip, **shapes),
                            interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
