"""Model adapter for Swin Transformer image classifiers.

Builds, from a seed and a configuration file's sizes, the parameters in
the pytree layout the program's ``repro.models.swin`` takes, the input
images, the plain reference forward pass the outputs are compared with,
and the FLOPs and minimal bytes of its parts.  Nothing here imports the
program.

The reference follows Liu et al. 2021 (arXiv:2103.14030, §3.1-3.4) and
the official ``swin_tiny_patch4_window7_224``: a ``patch_size`` x
``patch_size`` conv of the same stride with bias and a LayerNorm; stages
of blocks ``x + proj(WMSA(LN1(x)))`` then ``x + fc2(gelu(fc1(LN2(x))))``,
each stage after the first opened by a patch merging (the four 2x2
phases concatenated, LayerNorm over 4C, a linear map to 2C without
bias); a LayerNorm per token, the global average pool and the classifier.

Attention is written from its definition, not from window reshapes.  In
odd blocks of a stage whose maps are larger than the window the map is
rolled by half a window up and left first and rolled back after.  The
rolled map is cut into bands of ``window`` token rows; each query attends
to the keys of its own band that lie in its own window column, with
logits ``q k^T / sqrt(head_dim) + B + M``: ``B`` the bias table's entry at
the pair's (dy, dx), read by one-hot products at ``HIGHEST`` (exact), and
``M`` -100 between tokens of different regions of the rolled map (rows
and columns each cut at ``H - window`` and ``H - shift``), the published
mask.  A map no larger than the window is one window, unshifted, and its
table is sized for that window.  The stem is ``convnext_classifier``'s sum
of 16 strided products; a patch merging takes its phases by strided
slices.

The reference runs in float32 at ``Precision.HIGHEST``; ``control`` is
the same code at ``high``, every product but the table lookup in three
bfloat16 passes (as ``cnn_classifier._mm``).

Parameters are drawn so that no part is an identity: every bias, every
LayerNorm's scale and bias, and the bias tables come from the seed.  The
tables are drawn from N(0, 1): the published std 0.02 would hide a wrong
index under any tolerance.
"""

from __future__ import annotations

import itertools
import math
from typing import Any

import jax
import jax.numpy as jnp

from models import cnn_classifier as cnn
from models import convnext_classifier as cx

Params = dict[str, Any]

HIGHEST = jax.lax.Precision.HIGHEST
MASK_VALUE = -100.0

# the scopes inside each block that ``kind_work`` and the trace split by
KINDS = ("attn", "mlp")

# the answers are compared as for the other image classifiers: dtype,
# non-finite answers, and the widest logit gap over the largest logit
checks = cnn.checks
init_inputs = cnn.init_inputs
host_inputs = cnn.host_inputs


def stages(cfg: dict):
    """(map height, width, depth, heads, window) of each stage."""
    hw = cfg["image_size"] // cfg["patch_size"]
    for si, (d, depth, heads) in enumerate(zip(cfg["dims"], cfg["depths"],
                                               cfg["heads"])):
        if si > 0:
            hw //= 2
        yield hw, d, depth, heads, min(hw, cfg["window_size"])


def init_params(cfg: dict, key) -> Params:
    """The whole parameter pytree from one key.  Call it under ``jax.jit``
    so that it is one program on the device."""
    keys = (jax.random.fold_in(key, i) for i in itertools.count())
    dims = cfg["dims"]
    p: Params = {
        "stem": {"w": cnn._conv_w(next(keys), cfg["patch_size"],
                                  cfg["in_channels"], dims[0]),
                 "b": cx._bias(next(keys), dims[0]),
                 "ln": cx._ln(next(keys), dims[0])},
        "stages": [],
        "head": {"ln": cx._ln(next(keys), dims[-1]),
                 "fc_w": cx._dense(next(keys), dims[-1], cfg["num_classes"]),
                 "fc_b": jax.random.normal(next(keys), (cfg["num_classes"],),
                                           jnp.float32)},
    }
    for si, (_, d, depth, heads, w) in enumerate(stages(cfg)):
        hidden = cfg["mlp_ratio"] * d
        s: Params = {"blocks": [
            {"ln1": cx._ln(next(keys), d),
             "qkv_w": cx._dense(next(keys), d, 3 * d),
             "qkv_b": cx._bias(next(keys), 3 * d),
             "table": jax.random.normal(next(keys), ((2 * w - 1) ** 2, heads),
                                        jnp.float32),
             "proj_w": cx._dense(next(keys), d, d),
             "proj_b": cx._bias(next(keys), d),
             "ln2": cx._ln(next(keys), d),
             "w1": cx._dense(next(keys), d, hidden),
             "b1": cx._bias(next(keys), hidden),
             "w2": cx._dense(next(keys), hidden, d),
             "b2": cx._bias(next(keys), d)}
            for _ in range(depth)]}
        if si > 0:
            c = dims[si - 1]
            s["merge"] = {"ln": cx._ln(next(keys), 4 * c),
                          "w": cx._dense(next(keys), 4 * c, d)}
        p["stages"].append(s)
    return p


# --- the plain reference --------------------------------------------------

def _region(coord: jnp.ndarray, hw: int, w: int, shift: int) -> jnp.ndarray:
    """Band (0, 1, 2) of a row or column of the rolled map, cut at
    ``hw - w`` and ``hw - shift``."""
    return (coord >= hw - w).astype(jnp.int32) + (coord >= hw - shift)


def window_attention(p: Params, x: jnp.ndarray, w: int, shift: int,
                     precision: str) -> jnp.ndarray:
    """Multi-head self-attention of a normalised map ``x`` (N, H, W, C)
    inside w x w windows of the map rolled by ``-shift``, rolled back."""
    n, hw, _, c = x.shape
    heads = p["table"].shape[1]
    hd = c // heads
    if shift:
        x = jnp.roll(x, (-shift, -shift), axis=(1, 2))
    qkv = cnn._mm(x, p["qkv_w"], "nhwc,cd->nhwd", precision) + p["qkv_b"]
    # bands of w rows: (N, bands, w * W tokens, heads, head_dim)
    q, k, v = (qkv[..., i * c:(i + 1) * c].reshape(n, hw // w, w * hw, heads, hd)
               for i in range(3))
    logits = cnn._mm(q / math.sqrt(hd), k, "nbqhd,nbkhd->nbhqk", precision)
    # each token's row inside its band and its column
    t = jnp.arange(w * hw)
    row, col = t // hw, t % hw
    dy = row[:, None] - row[None, :]
    dx = col[:, None] - col[None, :]
    offsets = jnp.arange(-(w - 1), w)
    ey = (dy[..., None] == offsets).astype(jnp.float32)
    ex = (dx[..., None] == offsets).astype(jnp.float32)
    table = p["table"].reshape(2 * w - 1, 2 * w - 1, heads)
    bias = jnp.einsum("qki,qkih->hqk", ey,
                      jnp.einsum("qkj,ijh->qkih", ex, table, precision=HIGHEST),
                      precision=HIGHEST)
    logits = logits + bias
    if shift:
        ys = jnp.arange(hw // w)[:, None] * w + row[None, :]
        lab = 3 * _region(ys, hw, w, shift) + _region(col, hw, w, shift)[None]
        region = jnp.where(lab[:, :, None] != lab[:, None, :], MASK_VALUE, 0.0)
        logits = logits + region[None, :, None]
    same_window = (col[:, None] // w) == (col[None, :] // w)
    probs = jax.nn.softmax(jnp.where(same_window, logits, -jnp.inf), axis=-1)
    o = cnn._mm(probs, v, "nbhqk,nbkhd->nbqhd", precision).reshape(n, hw, hw, c)
    o = cnn._mm(o, p["proj_w"], "nhwc,cd->nhwd", precision) + p["proj_b"]
    if shift:
        o = jnp.roll(o, (shift, shift), axis=(1, 2))
    return o


def patch_merge(p: Params, x: jnp.ndarray, eps: float,
                precision: str) -> jnp.ndarray:
    n, hw, _, c = x.shape

    def phase(a, b):   # x[:, a::2, b::2]
        return jax.lax.slice(x, (0, a, b, 0), (n, hw, hw, c), (1, 2, 2, 1))
    x = jnp.concatenate([phase(0, 0), phase(1, 0), phase(0, 1), phase(1, 1)],
                        axis=-1)
    return cnn._mm(cx.layernorm(p["ln"], x, eps), p["w"], "nhwc,cd->nhwd",
                   precision)


def reference(cfg: dict, p: Params, x: jnp.ndarray,
              precision: str = "highest") -> jnp.ndarray:
    """Logits (N, classes) of images ``x`` (N, H, W, C), in float32, every
    conv and weight product at ``precision``."""
    eps = cfg["layer_norm_eps"]
    h = cx.layernorm(p["stem"]["ln"], cx.patch_conv(x, p["stem"]["w"], precision)
                     + p["stem"]["b"], eps)
    for s, (hw, _, _, _, w) in zip(p["stages"], stages(cfg)):
        if "merge" in s:
            h = patch_merge(s["merge"], h, eps, precision)
        for bi, b in enumerate(s["blocks"]):
            shift = w // 2 if bi % 2 and hw > w else 0
            h = h + window_attention(b, cx.layernorm(b["ln1"], h, eps), w,
                                     shift, precision)
            y = cx.layernorm(b["ln2"], h, eps)
            y = cx.gelu(cnn._mm(y, b["w1"], "nhwc,cd->nhwd", precision) + b["b1"])
            h = h + cnn._mm(y, b["w2"], "nhwc,cd->nhwd", precision) + b["b2"]
    feat = jnp.mean(cx.layernorm(p["head"]["ln"], h, eps), axis=(1, 2))
    return cnn._mm(feat, p["head"]["fc_w"], "nc,cd->nd", precision) \
        + p["head"]["fc_b"]


def control(cfg: dict, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    """The lower-precision control: the reference at ``high`` (three
    bfloat16 passes), the step below the configuration's ``highest``.  The
    benchmark's own runs never call it; its readings set the upper end of
    each limit."""
    return reference(cfg, p, x, "high")


# --- work -------------------------------------------------------------------

def parts(cfg: dict) -> list[dict]:
    """The model's parts in network order, each with its ``kind`` (``stem``,
    ``merge``, ``attn``, ``mlp``, ``head``), its configuration ``group``,
    and per image its multiply-adds, its input and output elements, its
    output height (``rows``); and its parameter count.  ``attn`` counts
    qkv, q k^T, AV and proj; its weights are LN1's, qkv's, the bias
    table and proj's."""
    group_of = {part: g for g, ps in cfg["groups"].items() for part in ps}
    dims = cfg["dims"]
    hw = cfg["image_size"] // cfg["patch_size"]
    cin = cfg["in_channels"] * cfg["patch_size"] ** 2

    def part(kind, group, macs, in_elems, out_elems, rows, weights):
        return {"kind": kind, "group": group, "macs": macs, "in": in_elems,
                "out": out_elems, "rows": rows, "weights": weights}

    out = [part("stem", group_of["stem"], hw * hw * cin * dims[0],
                hw * hw * cin, hw * hw * dims[0], hw, cin * dims[0] + 3 * dims[0])]
    for si, (hw, d, depth, heads, w) in enumerate(stages(cfg)):
        g = group_of[f"stage{si + 1}"]
        if si > 0:
            c = dims[si - 1]
            out.append(part("merge", g, hw * hw * 4 * c * d, 4 * hw * hw * c,
                            hw * hw * d, hw, 8 * c + 4 * c * d))
        m = hw * hw * d
        hidden = cfg["mlp_ratio"] * d
        for _ in range(depth):
            out.append(part("attn", g, 4 * m * d + 2 * m * w * w, m, m, hw,
                            4 * d * d + 6 * d + (2 * w - 1) ** 2 * heads))
            out.append(part("mlp", g, 2 * m * hidden, m, m, hw,
                            2 * d * hidden + hidden + 3 * d))
    c, n = dims[-1], cfg["num_classes"]
    out.append(part("head", group_of["head"], c * n, hw * hw * c, n, 1,
                    2 * c + c * n + n))
    return out


def flops_per_input(cfg: dict) -> int:
    """FLOPs one image needs: the multiply-adds of the stem conv and of
    every matrix product (qkv, q k^T, AV, proj, the MLPs, the patch
    mergings, the classifier), twice.  LayerNorm, GELU, softmax, the
    bias, mask and residual adds are elementwise work and not counted."""
    return 2 * sum(q["macs"] for q in parts(cfg))


def group_work(cfg: dict, group: str, batch: int) -> dict:
    """A group's FLOPs and minimal HBM bytes for one batch (its input map,
    its parameters and its output map, each moved once), and its output
    heights."""
    qs = [q for q in parts(cfg) if q["group"] == group]
    if not qs:
        raise KeyError(f"no part in group {group!r}")
    size = jnp.dtype(cfg["dtype"]).itemsize
    return {"flops": 2 * batch * sum(q["macs"] for q in qs),
            "bytes": size * (batch * (qs[0]["in"] + qs[-1]["out"])
                             + sum(q["weights"] for q in qs)),
            "rows": {q["rows"] for q in qs}}


def kind_work(cfg: dict, kind: str, batch: int) -> dict:
    """FLOPs and minimal HBM bytes of every part of one ``kind`` for one
    batch: each part reads its input map and parameters and writes its
    output map once (``attn``: the block's input in, the map after the
    attention's residual add out; the logits need never reach HBM)."""
    qs = [q for q in parts(cfg) if q["kind"] == kind]
    if not qs:
        raise KeyError(f"no part of kind {kind!r}")
    size = jnp.dtype(cfg["dtype"]).itemsize
    return {"flops": 2 * batch * sum(q["macs"] for q in qs),
            "bytes": size * sum(batch * (q["in"] + q["out"]) + q["weights"]
                                for q in qs)}
