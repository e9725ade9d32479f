"""ResNet18 in pure JAX (NHWC) — the paper's benchmark CNN (§V).

``init_resnet18`` draws the parameter pytree and ``forward`` maps a batch
of images to logits.  ``forward_fused_groups`` only calls ``forward``: it
is the name the chip benchmark's Fused4 configuration gives as its entry.

Each layer function runs under a ``jax.named_scope`` named as the
configurations' ``groups`` name their parts: ``stem``, ``maxpool``,
``stage1`` … ``stage4``, ``head``.  The scopes are siblings, never nested
in one another, so XLA carries exactly one layer name into each
operation's ``op_name`` metadata; a profiler trace is attributed to layers
by it.  Scopes are metadata only: they change no operation.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

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


def forward_fused_groups(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    """``forward`` under the name the Fused4 configuration gives as its
    entry.  On XLA a Python group boundary is no program boundary, so a
    grouped forward compiles to ``forward``'s program.  The Fused4 grouping
    lives in ``core.fusion.plan_fused`` for the PIM model and in the chip
    benchmark configuration's ``groups`` for the trace readers; a kernel
    that keeps a group's tile in VMEM will take a ``FusionPlan``."""
    return forward(p, x)
