"""Swin-T in pure JAX (NHWC): Liu et al. 2021, "Swin Transformer:
Hierarchical Vision Transformer using Shifted Windows" (arXiv:2103.14030,
§3.1-3.4); the official ``swin_tiny_patch4_window7_224``, torchvision
``swin_t``.

A 4×4/4 patch-embedding conv with bias and a LayerNorm, four stages of
Swin blocks (depths 2/2/6/2, widths 96/192/384/768, heads 3/6/12/24 of
width 32), each stage after the first opened by a patch merging, then a
LayerNorm, the global average pool and the classifier.  A block is

    x = x + proj(WMSA(LN1(x)))
    x = x + fc2(gelu(fc1(LN2(x))))

with multi-head self-attention inside each non-overlapping 7×7 window:
logits ``q kᵀ / √32 + B``, ``B`` read from a learned (2·7 − 1)² × heads
table at each pair's relative offset.  Odd blocks roll the map by
(−3, −3) before the windows are cut and by (+3, +3) after, and add −100
to the logits of pairs from different regions of the rolled map (the
published mask).  A map no larger than the window is one window and is
never shifted (stage 4's 7×7 maps).  A patch merging concatenates the
four 2×2 phases ``x[0::2, 0::2], x[1::2, 0::2], x[0::2, 1::2],
x[1::2, 1::2]``, LayerNorms the 4C channels and projects them to 2C
without bias.  LayerNorm eps 1e-5, MLP ratio 4, exact (erf) GELU.

The relative-position index and the shift mask are numpy constants built
while tracing; the table is read through a one-hot product at
``HIGHEST``, which is exact, and not through a gather.

Scopes follow ``convnext.py``: ``stem``, ``stage1`` … ``stage4`` (each
patch merging in the scope of the stage it opens) and ``head`` are
siblings, so each operation carries exactly one of them.  Inside each
block two sibling scopes split a trace by kind: ``attn`` (LN1, the roll,
window partition, qkv, logits with bias and mask, softmax, AV, proj,
window reverse, the roll back and the residual add) and ``mlp`` (LN2,
fc1, GELU, fc2 and the residual add).

``forward`` reads every size from the parameters: each block's window
from its bias table's (2w − 1)² rows, its heads from the table's columns.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L

Params = dict[str, Any]

DEPTHS = (2, 2, 6, 2)
DIMS = (96, 192, 384, 768)
HEADS = (3, 6, 12, 24)
PATCH = 4
WINDOW = 7
IMAGE = 224
MLP_RATIO = 4
LN_EPS = 1e-5
MASK_VALUE = -100.0


def init_block(key, dim: int, heads: int, window: int, dtype) -> Params:
    ks = jax.random.split(key, 5)
    hidden = MLP_RATIO * dim
    return {
        "ln1": L.init_layernorm(dim, dtype),
        "qkv_w": L.dense_init(ks[0], dim, 3 * dim, dtype),
        "qkv_b": jnp.zeros((3 * dim,), dtype),
        "table": (0.02 * jax.random.normal(
            ks[1], ((2 * window - 1) ** 2, heads), jnp.float32)).astype(dtype),
        "proj_w": L.dense_init(ks[2], dim, dim, dtype),
        "proj_b": jnp.zeros((dim,), dtype),
        "ln2": L.init_layernorm(dim, dtype),
        "w1": L.dense_init(ks[3], dim, hidden, dtype),
        "b1": jnp.zeros((hidden,), dtype),
        "w2": L.dense_init(ks[4], hidden, dim, dtype),
        "b2": jnp.zeros((dim,), dtype),
    }


def init_swin_tiny(key, num_classes: int = 1000,
                   dtype=jnp.float32) -> Params:
    ks = iter(jax.random.split(key, 2 + len(DEPTHS) + sum(DEPTHS)))
    p: Params = {
        "stem": {"w": L.init_conv(next(ks), PATCH, PATCH, 3, DIMS[0], dtype),
                 "b": jnp.zeros((DIMS[0],), dtype),
                 "ln": L.init_layernorm(DIMS[0], dtype)},
        "stages": [],
        "head": {"ln": L.init_layernorm(DIMS[-1], dtype),
                 "fc_w": L.dense_init(next(ks), DIMS[-1], num_classes, dtype),
                 "fc_b": jnp.zeros((num_classes,), dtype)},
    }
    hw = IMAGE // PATCH
    for si, (depth, dim, heads) in enumerate(zip(DEPTHS, DIMS, HEADS)):
        if si > 0:
            hw //= 2
        w = min(hw, WINDOW)     # a map no larger than a window is one window
        s: Params = {"blocks": [init_block(next(ks), dim, heads, w, dtype)
                                for _ in range(depth)]}
        if si > 0:
            c = DIMS[si - 1]
            s["merge"] = {"ln": L.init_layernorm(4 * c, dtype),
                          "w": L.dense_init(next(ks), 4 * c, dim, dtype)}
        p["stages"].append(s)
    return p


# --- trace-time constants ---------------------------------------------------

def relative_index(w: int) -> np.ndarray:
    """(w², w²) index into the bias table of each (query, key) pair of a
    window: ``(Δy + w − 1) · (2w − 1) + (Δx + w − 1)``, Δ = query − key."""
    yx = np.stack(np.meshgrid(np.arange(w), np.arange(w), indexing="ij"),
                  -1).reshape(-1, 2)
    d = yx[:, None] - yx[None, :] + (w - 1)
    return d[..., 0] * (2 * w - 1) + d[..., 1]


def shift_mask(hw: int, w: int, shift: int) -> np.ndarray:
    """(windows, w², w²) float32: ``MASK_VALUE`` between tokens of one
    window that come from different regions of the rolled map (rows and
    columns each cut at ``hw − w`` and ``hw − shift``), else 0."""
    band = np.digitize(np.arange(hw), [hw - w, hw - shift])
    lab = 3 * band[:, None] + band[None, :]
    lab = window_partition(lab[None, :, :, None], w)[..., 0]
    return np.where(lab[:, :, None] != lab[:, None, :], MASK_VALUE,
                    0.0).astype(np.float32)


# --- the block ----------------------------------------------------------------

def window_partition(x, w: int):
    """(B, H, W, C) → (B · H/w · W/w, w², C): windows in row-major order,
    tokens row-major inside each (numpy or jax arrays)."""
    b, h, wd, c = x.shape
    x = x.reshape(b, h // w, w, wd // w, w, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(-1, w * w, c)


def window_reverse(x: jnp.ndarray, w: int, h: int, wd: int) -> jnp.ndarray:
    """The inverse of ``window_partition`` for (H, W) = (h, wd) maps."""
    c = x.shape[-1]
    x = x.reshape(-1, h // w, wd // w, w, w, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(-1, h, wd, c)


def relative_bias(table: jnp.ndarray, w: int) -> jnp.ndarray:
    """(heads, w², w²): the table's row for each pair, as a one-hot
    product, exact at ``HIGHEST``."""
    n = w * w
    onehot = np.eye(table.shape[0], dtype=np.float32)[relative_index(w).ravel()]
    b = jnp.dot(onehot, table.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
    return b.T.reshape(-1, n, n)


def window_attention(p: Params, x: jnp.ndarray, w: int,
                     shift: int) -> jnp.ndarray:
    """Multi-head self-attention inside each w×w window of the map
    rolled by −shift, rolled back after."""
    b, h, wd, c = x.shape
    heads = p["table"].shape[1]
    # columns rolled first: rolled rows first, XLA splits the roll back of
    # a batch of one into copies that carry no op_name, so no scope
    if shift:
        x = jnp.roll(x, (-shift, -shift), axis=(2, 1))
    xw = window_partition(x, w)
    n = w * w
    qkv = (xw @ p["qkv_w"] + p["qkv_b"]).reshape(-1, n, 3, heads, c // heads)
    bias = relative_bias(p["table"], w)[None]
    if shift:
        bias = bias + shift_mask(h, w, shift)[:, None]
        nw = bias.shape[0]
        bias = jnp.broadcast_to(bias, (b, *bias.shape)).reshape(
            b * nw, heads, n, n)
    o = L.attention_scores(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2], None,
                           bias=bias)
    o = o.reshape(-1, n, c) @ p["proj_w"] + p["proj_b"]
    x = window_reverse(o, w, h, wd)
    if shift:
        x = jnp.roll(x, (shift, shift), axis=(2, 1))
    return x


def block(p: Params, x: jnp.ndarray, shifted: bool) -> jnp.ndarray:
    """One Swin block; ``shifted`` (odd blocks) rolls the windows by half
    a window where the map is larger than one."""
    w = (math.isqrt(p["table"].shape[0]) + 1) // 2
    shift = w // 2 if shifted and x.shape[1] > w else 0
    with jax.named_scope("attn"):
        x = x + window_attention(p, L.layernorm(p["ln1"], x, LN_EPS), w, shift)
    with jax.named_scope("mlp"):
        h = L.layernorm(p["ln2"], x, LN_EPS)
        h = L.gelu(h @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]
        return x + h


def patch_merge(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    """(B, H, W, C) → (B, H/2, W/2, 2C): the 2×2 phases concatenated in
    the published order (row phase fastest), LayerNorm, a linear map."""
    b, h, wd, c = x.shape
    # reshapes and one transpose: XLA lowers strided slices to gathers
    x = x.reshape(b, h // 2, 2, wd // 2, 2, c).transpose(0, 1, 3, 4, 2, 5)
    x = x.reshape(b, h // 2, wd // 2, 4 * c)
    return L.layernorm(p["ln"], x, LN_EPS) @ p["w"]


def stem(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    """The patch-embedding conv and its LayerNorm, in the named scope
    ``stem``."""
    with jax.named_scope("stem"):
        patch = p["w"].shape[0]
        return L.layernorm(p["ln"], L.conv2d(p["w"], x, patch) + p["b"],
                           LN_EPS)


def stage(p: Params, x: jnp.ndarray, si: int) -> jnp.ndarray:
    """Stage ``si`` (0-based), its patch merging first, in the named scope
    ``stage{si + 1}``."""
    with jax.named_scope(f"stage{si + 1}"):
        if "merge" in p:
            x = patch_merge(p["merge"], x)
        for bi, b in enumerate(p["blocks"]):
            x = block(b, x, bi % 2 == 1)
        return x


def head(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    """LayerNorm per token, the global average pool and the classifier,
    in the named scope ``head``."""
    with jax.named_scope("head"):
        h = L.avgpool_global(L.layernorm(p["ln"], x, LN_EPS))
        return h @ p["fc_w"] + p["fc_b"]


def forward(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    """x: (B, H, W, 3) → logits (B, classes)."""
    h = stem(p["stem"], x)
    for si, s in enumerate(p["stages"]):
        h = stage(s, h, si)
    return head(p["head"], h)
