"""AOT compiles of the ``fused_conv`` Pallas kernel for a TPU v5e, at every
conv geometry of ResNet18 on 224×224 images with a batch of 128.

Nothing runs: each test lowers the compiled kernel (``interpret=False``)
for one chip of a v5e:2x2 topology described without hardware, so what
Mosaic refuses (an unaligned slice, a strided access it cannot lower, too
much VMEM) fails here instead of on the chip.  Each test also checks that
XLA left the kernel's operands and output in HBM.  The topology is described
inside a fixture, never at import: only one process at a time may load the
TPU library, and every test worker imports this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_conv import fused_conv_kernel
from repro.models.resnet import conv_geometries

BATCH = 128


@pytest.fixture(scope="module")
def one_chip():
    pytest.importorskip("libtpu", reason="libtpu (requirements-dev.txt) "
                        "is not installed: no TPU compiler to describe a v5e")
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.mark.parametrize("g", conv_geometries(224), ids=lambda g: g.name)
def test_fused_conv_compiles_for_v5e(g, one_chip, no_persistent_cache):
    def spec(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    oh = (g.hw + 2 * g.padding - g.k) // g.stride + 1
    args = [spec((BATCH, g.hw, g.hw, g.cin)),
            spec((g.k, g.k, g.cin, g.cout)), spec((g.cout,)),
            spec((g.cout,))]
    if g.residual:
        args.append(spec((BATCH, oh, oh, g.cout)))

    def conv(x, w, scale, shift, residual=None):
        return fused_conv_kernel(x, w, scale, shift, stride=g.stride,
                                 padding=g.padding, relu=g.relu,
                                 residual=residual, interpret=False)

    compiled = jax.jit(conv).lower(*args).compile()
    assert compiled.out_info.shape == (BATCH, oh, oh, g.cout)
    hlo = compiled.as_text()
    # the kernel's operands and output stay in HBM: no VMEM space S(1)
    types = custom_call_types(hlo)
    assert types and not [t for t in types if "S(1)" in t], types
    # the space-to-depth is reshapes and slices; its strided-slice form
    # compiled to XLA gathers, and that program hung on a v5e
    assert " gather(" not in hlo


def custom_call_types(hlo: str) -> list[str]:
    """Types of the tpu_custom_call's result and operands in optimized HLO
    text; the layout suffix ``S(n)`` names a non-default memory space."""
    defs = dict(re.findall(r"^\s*(?:ROOT )?%(\S+) = (\S+)", hlo, re.M))
    call = re.search(r"^\s*(?:ROOT )?%\S+ = (\S+) custom-call\(([^)]*)\)"
                     r".*custom_call_target=\"tpu_custom_call\"", hlo, re.M)
    if call is None:
        return []
    operands = [o.strip().lstrip("%") for o in call.group(2).split(",")]
    return [call.group(1)] + [defs[o] for o in operands]
