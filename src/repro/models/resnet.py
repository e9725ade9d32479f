"""ResNet18 in pure JAX (NHWC) — the paper's benchmark CNN (§V).

Two execution paths:
* ``forward`` — monolithic reference;
* ``forward_fused_groups`` — executes the paper's fused-layer grouping
  (stem+stage1 / stage2 / stage3 fused; stage4 + head layer-by-layer),
  structured so each fused group is a single fusable region (consumed by
  the Pallas fused-conv kernel and the halo-sharded distribution path).

Both paths run the same layer functions, each under a ``jax.named_scope``
named as the configurations' ``groups`` name their parts: ``stem``,
``maxpool``, ``stage1`` … ``stage4``, ``head``.  The scopes are siblings,
never nested in one another, so XLA carries exactly one layer name into
each operation's ``op_name`` metadata; a profiler trace is attributed to
layers by it.  Scopes are metadata only: they change no operation.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.graph import OpKind, build_resnet18
from repro.models import layers as L

Params = dict[str, Any]

STAGE_CHANNELS = (64, 128, 256, 512)


def init_basic_block(key, cin: int, cout: int, stride: int, dtype) -> Params:
    ks = jax.random.split(key, 3)
    p: Params = {
        "conv1": L.init_conv(ks[0], 3, 3, cin, cout, dtype),
        "bn1": L.init_bn(cout, dtype),
        "conv2": L.init_conv(ks[1], 3, 3, cout, cout, dtype),
        "bn2": L.init_bn(cout, dtype),
    }
    if stride != 1 or cin != cout:
        p["down"] = L.init_conv(ks[2], 1, 1, cin, cout, dtype)
        p["down_bn"] = L.init_bn(cout, dtype)
    return p


def basic_block(p: Params, x: jnp.ndarray, stride: int) -> jnp.ndarray:
    h = jax.nn.relu(L.batchnorm(p["bn1"], L.conv2d(p["conv1"], x, stride, 1)))
    h = L.batchnorm(p["bn2"], L.conv2d(p["conv2"], h, 1, 1))
    shortcut = x
    if "down" in p:
        shortcut = L.batchnorm(p["down_bn"], L.conv2d(p["down"], x, stride, 0))
    return jax.nn.relu(h + shortcut)


def init_resnet18(key, num_classes: int = 1000,
                  dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 10)
    p: Params = {
        "conv1": L.init_conv(ks[0], 7, 7, 3, 64, dtype),
        "bn1": L.init_bn(64, dtype),
        "fc_w": L.dense_init(ks[1], 512, num_classes, dtype),
        "fc_b": jnp.zeros((num_classes,), dtype),
    }
    cin = 64
    ki = 2
    for si, cout in enumerate(STAGE_CHANNELS):
        for bi in range(2):
            stride = 2 if (si > 0 and bi == 0) else 1
            p[f"s{si + 1}b{bi + 1}"] = init_basic_block(
                ks[ki], cin, cout, stride, dtype)
            cin = cout
            ki += 1
    return p


def stem(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    """The 7x7/2 conv, BN and ReLU, then the 3x3/2 max-pool, each in a
    named scope of its own (``stem``, ``maxpool``)."""
    with jax.named_scope("stem"):
        h = jax.nn.relu(L.batchnorm(p["bn1"], L.conv2d(p["conv1"], x, 2, 3)))
    with jax.named_scope("maxpool"):
        return L.maxpool2d(h, 3, 2, 1)


def stage(p: Params, x: jnp.ndarray, si: int) -> jnp.ndarray:
    """Stage ``si`` (0-based), in the named scope ``stage{si + 1}``."""
    with jax.named_scope(f"stage{si + 1}"):
        for bi in range(2):
            stride = 2 if (si > 0 and bi == 0) else 1
            x = basic_block(p[f"s{si + 1}b{bi + 1}"], x, stride)
        return x


def head(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    """Global average pool and the fully connected layer, in the named
    scope ``head``."""
    with jax.named_scope("head"):
        return L.avgpool_global(x) @ p["fc_w"] + p["fc_b"]


def forward(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    """x: (B, H, W, 3) → logits (B, classes)."""
    h = stem(p, x)
    for si in range(4):
        h = stage(p, h, si)
    return head(p, h)


# --- fused-group structure (paper's Fused4 grouping) ---

def fused_group_fns(p: Params):
    """The three fused groups + the layer-by-layer tail, as callables.
    Group boundaries follow plan_fused(graph, 2, 2): [stem+stage1, stage2,
    stage3], tail = stage4 + head."""
    return [
        lambda x: stage(p, stem(p, x), 0),
        lambda x: stage(p, x, 1),
        lambda x: stage(p, x, 2),
    ], lambda x: head(p, stage(p, x, 3))


def forward_fused_groups(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    groups, tail = fused_group_fns(p)
    for g in groups:
        x = g(x)
    return tail(x)


class ConvGeometry(NamedTuple):
    """One ResNet18 conv as the fused CONV_BN[+ADD][+RELU] op sees it."""
    hw: int            # input height = width
    cin: int
    cout: int
    k: int
    stride: int
    padding: int
    relu: bool
    residual: bool     # ADD of the block's shortcut before the ReLU

    @property
    def name(self) -> str:
        flags = "+ADD_RELU" if self.residual else "+RELU" if self.relu else ""
        return (f"{self.k}x{self.k}/{self.stride} "
                f"{self.hw}x{self.cin}->{self.cout}{flags}")


def conv_geometries(image: int = 224) -> list[ConvGeometry]:
    """The distinct conv geometries of ResNet18 on image×image inputs, in
    network order, read off ``core.graph.build_resnet18``: each conv layer,
    with a block's conv2 fused with the ADD_RELU that consumes it."""
    layers = build_resnet18(image).layers
    fused_add = {lyr.input_of for lyr in layers
                 if lyr.kind is OpKind.ADD_RELU}
    geoms = [ConvGeometry(lyr.iy, lyr.cin, lyr.cout, lyr.kh, lyr.stride,
                          lyr.padding,
                          relu=(lyr.kind is OpKind.CONV_BN_RELU
                                or lyr.name in fused_add),
                          residual=lyr.name in fused_add)
             for lyr in layers if lyr.kind.is_conv]
    return list(dict.fromkeys(geoms))

