"""AOT compiles for a TPU v5e: the ``fused_conv`` Pallas kernel at every
conv geometry of ResNet18 on 224×224 images with a batch of 128, and
ResNet18 and ConvNeXt-T themselves, whose operations must each carry one
layer scope.

Nothing runs: each test lowers the compiled kernel (``interpret=False``)
for one chip of a v5e:2x2 topology described without hardware, so what
Mosaic refuses (an unaligned slice, a strided access it cannot lower, too
much VMEM) fails here instead of on the chip.  Each test also checks that
XLA left the kernel's operands and output in HBM.  The ResNet18 compiles
check that every operation that computes (a fusion, a conv, a pooling
window, a custom-call) carries exactly one of the model's layer scopes in
its ``op_name``: the chip benchmark attributes device time to layers and
fused groups by them; and that the 7x7/2 stem became a 4x4 conv over a
space-to-depth input of 12 channels, and that no ResNet18 conv runs the
depthwise kernel.  The ConvNeXt-T compiles check the same of its stage
scopes, that its blocks' ``dwconv`` and ``mlp`` scopes reach the compiled
operations, that its depthwise convs of stages 1-3 are calls of the
depthwise kernel and, at a batch of 128, its MLPs of stages 1-2 calls of
the block-MLP kernel, with no relayout beside either.  The Swin-T
compiles check the same of its stage scopes, that its blocks' ``attn``
and ``mlp`` scopes reach the compiled operations, and that it holds no
gather (its bias tables are read by a one-hot product) and no kernel.
The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.graph import conv_geometries
from repro.kernels.fused_conv import fused_conv_kernel
from repro.models import convnext, swin
from repro.models.resnet import forward, forward_fused_groups, init_resnet18

BATCH = 128
LAYERS = {"stem", "maxpool", "stage1", "stage2", "stage3", "stage4", "head"}
# operations that compute; the rest of ENTRY moves weights or the input
SCOPED = {"fusion", "convolution", "reduce-window", "custom-call"}


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


PROGRAMS = pytest.mark.parametrize(
    "entry, batch", [(forward, 1), (forward_fused_groups, BATCH)],
    ids=["forward-b1", "forward_fused_groups-b128"])


@pytest.fixture(scope="module")
def resnet18_hlo(one_chip, no_persistent_cache):
    """Optimized HLO text of ResNet18 at ``highest``, compiled once per
    entry point and batch for the described chip."""
    compiled = {}

    def spec(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    def hlo(entry, batch):
        if (entry, batch) not in compiled:
            params = jax.tree.map(spec, jax.eval_shape(init_resnet18,
                                                       jax.random.key(0)))
            x = spec(jax.ShapeDtypeStruct((batch, 224, 224, 3), jnp.float32))

            def call(p, x):
                with jax.default_matmul_precision("highest"):
                    return entry(p, x)
            compiled[entry, batch] = \
                jax.jit(call).lower(params, x).compile().as_text()
        return compiled[entry, batch]
    return hlo


@PROGRAMS
def test_resnet18_ops_carry_one_layer_scope(entry, batch, resnet18_hlo):
    seen = set()
    for opcode, target, op_name in entry_instructions(
            resnet18_hlo(entry, batch)):
        # no ResNet18 conv is grouped: none runs the depthwise kernel
        assert "/depthwise_conv/" not in op_name, op_name
        # XLA's reassembly of weight slices prefetched into VMEM
        if opcode not in SCOPED or target == "ConcatBitcast":
            continue
        layers = [c for c in op_name.split("/") if c in LAYERS]
        assert len(layers) == 1, (opcode, target, op_name)
        seen.update(layers)
    assert seen == LAYERS


@PROGRAMS
def test_resnet18_stem_conv_runs_over_space_to_depth(entry, batch,
                                                      resnet18_hlo):
    """The 7x7/2 stem on 3 channels compiles to a 4x4 stride-1 conv over
    12 channels, and no space-to-depth became a gather."""
    hlo = resnet18_hlo(entry, batch)
    shapes = {name: [int(d) for d in dims.split(",") if d] for name, dims
              in re.findall(r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]",
                            hlo, re.M)}
    convs = re.findall(r" convolution\(%(\S+), %\S+\), "
                       r"window=\{size=(\w+)[^}]*\}, dim_labels=(\w+)_"
                       r".*op_name=\"[^\"]*/stem/", hlo)
    assert convs
    for lhs, window, labels in convs:
        assert window == "4x4"
        assert shapes[lhs][labels.index("f")] == 12
    assert " gather(" not in hlo


CONVNEXT_LAYERS = {"stem", "stage1", "stage2", "stage3", "stage4", "head"}


def stage_of(op_name: str) -> str:
    return next(c for c in op_name.split("/") if c in CONVNEXT_LAYERS)


@pytest.mark.parametrize("batch", [BATCH, 1])
def test_convnext_ops_carry_one_stage_scope_and_split_by_kind(
        batch, one_chip, no_persistent_cache):
    """ConvNeXt-T at 224x224, float32 at ``highest``: every operation that
    computes carries exactly one stage-level scope, and the block's
    ``dwconv`` and ``mlp`` scopes reach the compiled operations.  Each
    depthwise conv is under ``dwconv``: one call of the depthwise kernel
    in each block of stages 1-3, XLA's grouped conv on stage 4's 7x7
    maps.  Where the maps keep the batch in lanes (stages 1-2 at a batch
    of 128, none at 1) each block's MLP is one call of the block-MLP
    kernel under ``mlp``.  No relayout (``copy``, ``transpose``) is in a
    ``dwconv`` scope or in an ``mlp`` scope that holds the kernel."""
    def spec(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    params = jax.tree.map(spec, jax.eval_shape(convnext.init_convnext_tiny,
                                               jax.random.key(0)))
    x = spec(jax.ShapeDtypeStruct((batch, 224, 224, 3), jnp.float32))

    def call(p, x):
        with jax.default_matmul_precision("highest"):
            return convnext.forward(p, x)
    hlo = jax.jit(call).lower(params, x).compile().as_text()
    seen, kinds = set(), set()
    for opcode, target, op_name in entry_instructions(hlo):
        if opcode not in SCOPED or target == "ConcatBitcast":
            continue
        path = op_name.split("/")
        layers = [c for c in path if c in CONVNEXT_LAYERS]
        assert len(layers) == 1, (opcode, target, op_name)
        seen.update(layers)
        kinds.update(c for c in path if c in ("dwconv", "mlp"))
    assert seen == CONVNEXT_LAYERS and kinds == {"dwconv", "mlp"}
    kernels = [op_name for opcode, target, op_name in entry_instructions(hlo)
               if target == "tpu_custom_call"]
    dw_kernels = [k for k in kernels if "/depthwise_conv/" in k]
    assert len(dw_kernels) == sum(convnext.DEPTHS[:-1])
    for op_name in dw_kernels:
        assert "/dwconv/" in op_name
    mlps = [k for k in kernels if "/convnext_mlp/" in k]
    assert len(dw_kernels) + len(mlps) == len(kernels)
    fused = {"stage1", "stage2"} if batch == BATCH else set()
    assert len(mlps) == (sum(convnext.DEPTHS[:2]) if fused else 0)
    for op_name in mlps:
        assert "/mlp/" in op_name and stage_of(op_name) in fused
    depthwise = re.findall(r" convolution\(.*feature_group_count=(\d+).*"
                           r'op_name="([^"]*)"', hlo)
    assert len(depthwise) == convnext.DEPTHS[-1]
    for groups, op_name in depthwise:
        assert int(groups) == convnext.DIMS[-1] and "/stage4/dwconv/" in op_name
    relayouts = [op_name for opcode, _, op_name in entry_instructions(hlo)
                 if opcode in ("copy", "transpose") and (
                     "/dwconv/" in op_name or "/mlp/" in op_name
                     and stage_of(op_name) in fused)]
    assert not relayouts, relayouts


@pytest.mark.parametrize("batch", [BATCH, 1])
def test_swin_ops_carry_one_stage_scope_and_split_by_kind(
        batch, one_chip, no_persistent_cache):
    """Swin-T at 224x224, float32 at ``highest``: every operation that
    computes carries exactly one stage-level scope (each patch merging in
    the stage it opens), and the block's ``attn`` and ``mlp`` scopes reach
    the compiled operations.  No relative-position bias lookup or strided
    slice became a gather, and no custom-call but XLA's reassembly of
    prefetched weights is in the program."""
    def spec(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    params = jax.tree.map(spec, jax.eval_shape(swin.init_swin_tiny,
                                               jax.random.key(0)))
    x = spec(jax.ShapeDtypeStruct((batch, 224, 224, 3), jnp.float32))

    def call(p, x):
        with jax.default_matmul_precision("highest"):
            return swin.forward(p, x)
    hlo = jax.jit(call).lower(params, x).compile().as_text()
    seen, kinds = set(), set()
    for opcode, target, op_name in entry_instructions(hlo):
        assert opcode != "custom-call" or target == "ConcatBitcast", target
        if opcode not in SCOPED or target == "ConcatBitcast":
            continue
        path = op_name.split("/")
        # Swin-T's stage scopes are ConvNeXt-T's
        layers = [c for c in path if c in CONVNEXT_LAYERS]
        assert len(layers) == 1, (opcode, target, op_name)
        seen.update(layers)
        kinds.update(c for c in path if c in ("attn", "mlp"))
    assert seen == CONVNEXT_LAYERS and kinds == {"attn", "mlp"}
    assert " gather(" not in hlo


def entry_instructions(hlo: str) -> list[tuple[str, str, str]]:
    """(opcode, custom-call target, op_name) of each instruction of the
    ENTRY computation in optimized HLO text."""
    body = hlo[hlo.index("\nENTRY "):]
    body = body[body.index("\n") + 1:body.index("\n}\n")]
    out = []
    for line in body.splitlines():
        opcode = re.search(r"(?:\)|\]|\}) ([a-z][\w\-]*)\(", line)
        target = re.search(r'custom_call_target="([^"]*)"', line)
        op_name = re.search(r'op_name="((?:[^"\\]|\\.)*)"', line)
        out.append((opcode.group(1) if opcode else "",
                    target.group(1) if target else "",
                    op_name.group(1) if op_name else ""))
    return out
