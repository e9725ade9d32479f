"""ConvNeXt-T in pure JAX (NHWC): Liu et al. 2022, "A ConvNet for the
2020s" (arXiv:2201.03545, §2 and Table 9); torchvision ``convnext_tiny``.

A 4×4/4 patchify conv with bias and a LayerNorm, four stages of ConvNeXt
blocks (depths 3/3/9/3, widths 96/192/384/768), each stage after the first
opened by a LayerNorm and a 2×2/2 conv with bias, then the global average
pool, a LayerNorm and the classifier.  A block is built like a transformer
block over the pixels of a map:

    x + γ · (gelu(LN(dwconv7×7(x) + b_dw) W1 + b1) W2 + b2)

with a depthwise 7×7 conv (padding 3), LayerNorm over channels (eps 1e-6),
a d → 4d expansion, exact (erf) GELU, a 4d → d projection and a
per-channel layer scale γ.

Scopes follow ``resnet.py``: ``stem``, ``stage1`` … ``stage4`` (each
downsample in the scope of the stage it opens) and ``head`` are siblings,
so each operation carries exactly one of them.  Inside each block two
nested scopes split a trace by kind: ``dwconv`` (``layers.depthwise_conv``:
the depthwise conv, its bias, and each pixel's sum over channels, which
the LayerNorm takes for its mean) and ``mlp`` (LayerNorm, expansion,
GELU, projection, layer scale).  Where the maps keep the batch in lanes
(stages 1-2 at a batch of 128) the MLP and the residual add are one call
of the ``convnext_mlp`` Pallas kernel under ``mlp``, which keeps the
4d-wide hidden map out of HBM; elsewhere they are XLA's ops and the
residual add is in neither scope.

``forward`` reads every size from the parameters, so the same function
runs any depth and width of the family.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels.convnext_mlp import convnext_mlp_kernel
from repro.kernels.depthwise_conv import batch_in_lanes
from repro.models import layers as L

Params = dict[str, Any]

DEPTHS = (3, 3, 9, 3)
DIMS = (96, 192, 384, 768)
PATCH = 4
KERNEL = 7
MLP_RATIO = 4
LN_EPS = 1e-6
LAYER_SCALE_INIT = 1e-6


def init_block(key, dim: int, dtype) -> Params:
    ks = jax.random.split(key, 3)
    hidden = MLP_RATIO * dim
    return {
        "dw_w": L.init_conv(ks[0], KERNEL, KERNEL, 1, dim, dtype),
        "dw_b": jnp.zeros((dim,), dtype),
        "ln": L.init_layernorm(dim, dtype),
        "w1": L.dense_init(ks[1], dim, hidden, dtype),
        "b1": jnp.zeros((hidden,), dtype),
        "w2": L.dense_init(ks[2], hidden, dim, dtype),
        "b2": jnp.zeros((dim,), dtype),
        "gamma": jnp.full((dim,), LAYER_SCALE_INIT, dtype),
    }


def init_convnext_tiny(key, num_classes: int = 1000,
                       dtype=jnp.float32) -> Params:
    ks = iter(jax.random.split(key, 3 + len(DEPTHS) + sum(DEPTHS)))
    p: Params = {
        "stem": {"w": L.init_conv(next(ks), PATCH, PATCH, 3, DIMS[0], dtype),
                 "b": jnp.zeros((DIMS[0],), dtype),
                 "ln": L.init_layernorm(DIMS[0], dtype)},
        "stages": [],
        "head": {"ln": L.init_layernorm(DIMS[-1], dtype),
                 "fc_w": L.dense_init(next(ks), DIMS[-1], num_classes, dtype),
                 "fc_b": jnp.zeros((num_classes,), dtype)},
    }
    for si, (depth, dim) in enumerate(zip(DEPTHS, DIMS)):
        s: Params = {"blocks": [init_block(next(ks), dim, dtype)
                                for _ in range(depth)]}
        if si > 0:
            s["down"] = {"ln": L.init_layernorm(DIMS[si - 1], dtype),
                         "w": L.init_conv(next(ks), 2, 2, DIMS[si - 1], dim,
                                          dtype),
                         "b": jnp.zeros((dim,), dtype)}
        p["stages"].append(s)
    return p


def block(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    with jax.named_scope("dwconv"):
        h, total = L.depthwise_conv(p["dw_w"], x, p["dw_b"])
    with jax.named_scope("mlp"):
        # maps with the batch in lanes (stages 1-2 at a batch of 128): the
        # kernel, which a v5e ran faster than XLA's expansion and
        # projection there; with the channels in lanes its layout would
        # cost a relayout each way
        if batch_in_lanes(x.shape[0], x.shape[-1]):
            return _mlp_kernel(p, h, total, x)
        h = L.layernorm(p["ln"], h, LN_EPS, total)
        h = L.gelu(h @ p["w1"] + p["b1"])
        h = (h @ p["w2"] + p["b2"]) * p["gamma"]
    return x + h


def _mlp_kernel(p: Params, h, total, x) -> jnp.ndarray:
    """The block's MLP and residual add as one call of the block-MLP
    kernel: compiled where the program is lowered for a TPU, interpreted
    elsewhere."""
    kernel = functools.partial(convnext_mlp_kernel, eps=LN_EPS)
    return jax.lax.platform_dependent(
        h, total, x, p["ln"]["scale"], p["ln"]["bias"], p["w1"], p["b1"],
        p["w2"], p["b2"], p["gamma"],
        tpu=kernel, default=functools.partial(kernel, interpret=True))


def stem(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    """The patchify conv and its LayerNorm, in the named scope ``stem``."""
    with jax.named_scope("stem"):
        patch = p["w"].shape[0]
        return L.layernorm(p["ln"], L.conv2d(p["w"], x, patch) + p["b"],
                           LN_EPS)


def stage(p: Params, x: jnp.ndarray, si: int) -> jnp.ndarray:
    """Stage ``si`` (0-based), its downsample first, in the named scope
    ``stage{si + 1}``."""
    with jax.named_scope(f"stage{si + 1}"):
        if "down" in p:
            d = p["down"]
            x = L.conv2d(d["w"], L.layernorm(d["ln"], x, LN_EPS), 2) + d["b"]
        for b in p["blocks"]:
            x = block(b, x)
        return x


def head(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    """Global average pool, LayerNorm and the classifier, in the named
    scope ``head``."""
    with jax.named_scope("head"):
        h = L.layernorm(p["ln"], L.avgpool_global(x), LN_EPS)
        return h @ p["fc_w"] + p["fc_b"]


def forward(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    """x: (B, H, W, 3) → logits (B, classes)."""
    h = stem(p["stem"], x)
    for si, s in enumerate(p["stages"]):
        h = stage(s, h, si)
    return head(p["head"], h)
