"""Ahead-of-time compiles of the int8 SFC kernels for a TPU v5e.

No chip is needed: the TPU compiler builds each kernel for a described
``v5e:2x2`` topology at the VGG-16 / ResNet-18 layer widths, with
``interpret=False``, so what Mosaic refuses (unaligned blocks, VMEM
overflow, unsupported vector shapes) fails here instead of on the chip.
The topology is described inside a module-scoped fixture: only the test
worker that runs this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.api import registry
from repro.kernels import ops
from repro.kernels import sfc_fused as sf
from repro.kernels.sfc_fused import sfc_fused_conv2d

ALGO = registry.get_algorithm("sfc6_6")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _fused(**kw):
    def run(x, wq, act, ws):
        return sfc_fused_conv2d(x, wq, act, ws, ALGO, interpret=False, **kw)
    return run


def _staged(x, wq, act, ws):
    return ops.quantized_fastconv2d(x, wq, act, ws, ALGO, interpret=False)


CASES = {
    # name: (launch, batch, H=W, C_in, C_out, depthwise); every fused
    # launch without rows_per_step takes the shape-resolved grouping
    "fused_56_128_256": (_fused(), 8, 56, 128, 256, False),
    "fused_28_512_512": (_fused(), 8, 28, 512, 512, False),
    "fused_224_64_64": (_fused(), 8, 224, 64, 64, False),
    "fused_dw_28_256": (_fused(depthwise=True), 8, 28, 256, 256, True),
    "fused_db_28_512_512": (_fused(double_buffer=True), 8, 28, 512, 512,
                            False),
    "staged_56_128_256": (_staged, 8, 56, 128, 256, False),
    # VGG-16 at b32: 28x28 folds 4 whole images a step, 14x14 folds 8
    "fused_b32_28_512_512": (_fused(), 32, 28, 512, 512, False),
    "fused_b32_14_512_512": (_fused(), 32, 14, 512, 512, False),
}
# whole images the default grouping folds per step, where a case is
# there to rehearse it
FOLDS = {"fused_b32_28_512_512": 4, "fused_b32_14_512_512": 8}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    launch, batch, hw, cin, cout, dw = CASES[case]
    t, P = ALGO.t, ALGO.t ** 2
    if case in FOLDS:
        assert sf.fused_geometry(ALGO, batch, hw, hw, cin, cout).imgs \
            == FOLDS[case]

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (spec((batch, hw, hw, cin), jnp.float32),
            spec((P, 1 if dw else cin, cout), jnp.int8),
            spec((t, t), jnp.float32),
            spec((t, t, cout), jnp.float32))
    compiled = jax.jit(launch).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None
