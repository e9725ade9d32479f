"""Model adapter for ConvNeXt image classifiers.

Builds, from a seed and a configuration file's sizes, the parameters in
the pytree layout the program's ``repro.models.convnext`` takes, the input
images, the plain reference forward pass the outputs are compared with,
and the FLOPs and minimal bytes of its parts.  Nothing here imports the
program.

The reference follows Liu et al. 2022 (arXiv:2201.03545, §2 and Table 9)
and torchvision's ``convnext_tiny``: a ``patch_size`` x ``patch_size``
conv of the same stride with bias and a LayerNorm; stages of blocks, each
stage after the first opened by a LayerNorm and a 2x2/2 conv with bias; a
block is ``x + gamma * (gelu(LN(dwconv(x)) W1 + b1) W2 + b2)`` with a
depthwise ``kernel_size`` conv with bias (padding ``kernel_size // 2``),
LayerNorm over channels, a ``mlp_ratio``-fold expansion and exact (erf)
GELU; then the global average pool, a LayerNorm and the classifier.  Each
dense conv is a sum of k*k strided matrix products, the depthwise conv a
sum of k*k shifted elementwise products, LayerNorm and GELU written out.

The reference runs in float32 at ``Precision.HIGHEST``; ``control`` is
the same code at ``high``, every conv, matrix and elementwise weight
product in three bfloat16 passes (as ``cnn_classifier._mm``).

Parameters are drawn so that no part is an identity: every bias, every
LayerNorm's scale and bias and the layer scale ``gamma`` come from the
seed.  ``gamma`` is drawn from U(0.1, 1): the published initial 1e-6 would
make every block's branch vanish under any tolerance.
"""

from __future__ import annotations

import itertools
import math
from typing import Any

import jax
import jax.numpy as jnp

from models import cnn_classifier as cnn

Params = dict[str, Any]

# the scopes inside each block that ``kind_work`` and the trace split by
KINDS = ("dwconv", "mlp")

# the answers are compared as for the ResNet classifiers: dtype, non-finite
# answers, and the widest logit gap over the reference's largest logit
checks = cnn.checks
init_inputs = cnn.init_inputs
host_inputs = cnn.host_inputs


def _ln(key, c: int) -> Params:
    ks = jax.random.split(key)
    return {"scale": jax.random.uniform(ks[0], (c,), jnp.float32, 0.5, 1.5),
            "bias": 0.2 * jax.random.normal(ks[1], (c,), jnp.float32)}


def _bias(key, c: int) -> jnp.ndarray:
    return 0.2 * jax.random.normal(key, (c,), jnp.float32)


def _dense(key, cin: int, cout: int) -> jnp.ndarray:
    return jax.random.normal(key, (cin, cout), jnp.float32) / math.sqrt(cin)


def init_params(cfg: dict, key) -> Params:
    """The whole parameter pytree from one key.  Call it under ``jax.jit``
    so that it is one program on the device."""
    keys = (jax.random.fold_in(key, i) for i in itertools.count())
    dims, k = cfg["dims"], cfg["kernel_size"]
    p: Params = {
        "stem": {"w": cnn._conv_w(next(keys), cfg["patch_size"],
                                  cfg["in_channels"], dims[0]),
                 "b": _bias(next(keys), dims[0]), "ln": _ln(next(keys), dims[0])},
        "stages": [],
        "head": {"ln": _ln(next(keys), dims[-1]),
                 "fc_w": _dense(next(keys), dims[-1], cfg["num_classes"]),
                 "fc_b": jax.random.normal(next(keys), (cfg["num_classes"],),
                                           jnp.float32)},
    }
    for si, (depth, d) in enumerate(zip(cfg["depths"], dims)):
        hidden = cfg["mlp_ratio"] * d
        s: Params = {"blocks": [
            {"dw_w": cnn._conv_w(next(keys), k, 1, d), "dw_b": _bias(next(keys), d),
             "ln": _ln(next(keys), d),
             "w1": _dense(next(keys), d, hidden), "b1": _bias(next(keys), hidden),
             "w2": _dense(next(keys), hidden, d), "b2": _bias(next(keys), d),
             "gamma": jax.random.uniform(next(keys), (d,), jnp.float32, 0.1, 1.0)}
            for _ in range(depth)]}
        if si > 0:
            s["down"] = {"ln": _ln(next(keys), dims[si - 1]),
                         "w": cnn._conv_w(next(keys), 2, dims[si - 1], d),
                         "b": _bias(next(keys), d)}
        p["stages"].append(s)
    return p


# --- the plain reference --------------------------------------------------

def layernorm(p: Params, x: jnp.ndarray, eps: float) -> jnp.ndarray:
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def gelu(x: jnp.ndarray) -> jnp.ndarray:
    """The exact GELU, x * Phi(x)."""
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def patch_conv(x: jnp.ndarray, w: jnp.ndarray, precision: str) -> jnp.ndarray:
    """A k x k conv of stride k, no padding (the stem and the downsamples):
    a sum of k*k strided matrix products, each stride taken by a
    reshape."""
    k = w.shape[0]
    n, h, wd, c = x.shape
    xs = x.reshape(n, h // k, k, wd // k, k, c)
    out = None
    for i in range(k):
        for j in range(k):
            t = cnn._mm(xs[:, :, i, :, j], w[i, j], "nhwc,cd->nhwd", precision)
            out = t if out is None else out + t
    return out


def _mul(a: jnp.ndarray, b: jnp.ndarray, precision: str) -> jnp.ndarray:
    """``a * b`` in float32: exact at ``highest``; at ``high``, as
    ``cnn_classifier._mm`` takes a product, in three bfloat16 passes."""
    if precision == "highest":
        return a * b
    (ah, al), (bh, bl) = cnn._bf16_split(a), cnn._bf16_split(b)
    return (al * bh + ah * bl) + ah * bh


def dwconv(x: jnp.ndarray, w: jnp.ndarray, precision: str) -> jnp.ndarray:
    """Depthwise k x k conv, stride 1, padding k // 2, of ``w`` (k, k, 1,
    C): a sum of k*k shifted elementwise products."""
    k = w.shape[0]
    _, h, wd, _ = x.shape
    xp = cnn._pad(x, k // 2)
    out = None
    for i in range(k):
        for j in range(k):
            t = _mul(xp[:, i:i + h, j:j + wd], w[i, j, 0], precision)
            out = t if out is None else out + t
    return out


def reference(cfg: dict, p: Params, x: jnp.ndarray,
              precision: str = "highest") -> jnp.ndarray:
    """Logits (N, classes) of images ``x`` (N, H, W, C), in float32, every
    conv and weight product at ``precision``."""
    eps = cfg["layer_norm_eps"]
    h = layernorm(p["stem"]["ln"],
                  patch_conv(x, p["stem"]["w"], precision) + p["stem"]["b"], eps)
    for s in p["stages"]:
        if "down" in s:
            d = s["down"]
            h = patch_conv(layernorm(d["ln"], h, eps), d["w"], precision) + d["b"]
        for b in s["blocks"]:
            y = layernorm(b["ln"], dwconv(h, b["dw_w"], precision) + b["dw_b"], eps)
            y = gelu(cnn._mm(y, b["w1"], "nhwc,cd->nhwd", precision) + b["b1"])
            y = cnn._mm(y, b["w2"], "nhwc,cd->nhwd", precision) + b["b2"]
            h = h + y * b["gamma"]
    feat = layernorm(p["head"]["ln"], jnp.mean(h, axis=(1, 2)), eps)
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
    ``down``, ``dwconv``, ``mlp``, ``head``), its configuration ``group``,
    and per image its multiply-adds, its input and output elements, its
    output height (``rows``); and its parameter count."""
    group_of = {part: g for g, ps in cfg["groups"].items() for part in ps}
    dims, k = cfg["dims"], cfg["kernel_size"]
    hw = cfg["image_size"] // cfg["patch_size"]
    cin = cfg["in_channels"] * cfg["patch_size"] ** 2

    def part(kind, group, macs, in_elems, out_elems, rows, weights):
        return {"kind": kind, "group": group, "macs": macs, "in": in_elems,
                "out": out_elems, "rows": rows, "weights": weights}

    out = [part("stem", group_of["stem"], hw * hw * cin * dims[0],
                hw * hw * cin, hw * hw * dims[0], hw, cin * dims[0] + 3 * dims[0])]
    for si, (depth, d) in enumerate(zip(cfg["depths"], dims)):
        g = group_of[f"stage{si + 1}"]
        if si > 0:
            c, hw = dims[si - 1], hw // 2
            out.append(part("down", g, hw * hw * 4 * c * d, 4 * hw * hw * c,
                            hw * hw * d, hw, 2 * c + 4 * c * d + d))
        m = hw * hw * d
        hidden = cfg["mlp_ratio"] * d
        for _ in range(depth):
            out.append(part("dwconv", g, m * k * k, m, m, hw, k * k * d + d))
            out.append(part("mlp", g, 2 * m * hidden, m, m, hw,
                            2 * d * hidden + hidden + 4 * d))
    c, n = dims[-1], cfg["num_classes"]
    out.append(part("head", group_of["head"], c * n, hw * hw * c, n, 1,
                    2 * c + c * n + n))
    return out


def flops_per_input(cfg: dict) -> int:
    """FLOPs one image needs: the multiply-adds of every conv, matrix
    product and depthwise conv, twice.  LayerNorm, GELU, the layer scale
    and the adds are elementwise work and not counted."""
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
    output map once, for the parts are not adjacent (``mlp``: the
    depthwise conv's output in, the branch's output before the residual
    add out)."""
    qs = [q for q in parts(cfg) if q["kind"] == kind]
    if not qs:
        raise KeyError(f"no part of kind {kind!r}")
    size = jnp.dtype(cfg["dtype"]).itemsize
    return {"flops": 2 * batch * sum(q["macs"] for q in qs),
            "bytes": size * sum(batch * (q["in"] + q["out"]) + q["weights"]
                                for q in qs)}
