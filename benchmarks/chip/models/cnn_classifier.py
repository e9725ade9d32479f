"""Model adapter for ResNet basic-block image classifiers.

Builds, from a seed and a configuration file's sizes, the parameters in
the pytree layout the program's ``repro.models.resnet`` takes, the input
images, the plain reference forward pass the outputs are compared with,
the comparison itself, and the layers' FLOPs and bytes.  Nothing here
imports the program.

The reference follows He et al. 2016 (arXiv:1512.03385, Table 1 and
Fig. 2 left) and torchvision's ``resnet18``: a 7x7/2 conv, batch-norm and
ReLU, a 3x3/2 max-pool, stages of two basic blocks (3x3 conv, BN, ReLU,
3x3 conv, BN, plus the shortcut, ReLU), the shortcut a 1x1 conv and BN
where the block changes stride or width, then the global average pool and
the fully connected layer.  Batch-norm is inference-mode:
``(x - mean) / sqrt(var + eps) * scale + bias``.  Every conv is a sum of
``k * k`` shifted matrix products, each stride taken by a reshape.

The reference runs in float32 at ``Precision.HIGHEST``: the answer the
float32 configuration, run at ``highest``, states.  ``control`` is the
same code one precision step below, ``high``: every conv and matrix
product in three bfloat16 passes, as XLA computes float32 at ``high`` on
a TPU (each operand split into a bfloat16 high part and a bfloat16 low
part; the product of the two low parts dropped).  It is written out, so
it reads the same on any backend.

Parameters are drawn so that no layer is an identity: batch-norm running
statistics, scale and bias, and the classifier bias, all come from the
seed, so a program that skipped batch-norm or the bias reads wrong.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

import flops

Params = dict[str, Any]

HIGHEST = jax.lax.Precision.HIGHEST


def _blocks(cfg: dict):
    """(name, cin, cout, stride) of each basic block, in order."""
    cin = cfg["stem_channels"]
    for si, (cout, n) in enumerate(zip(cfg["stage_channels"],
                                       cfg["blocks_per_stage"])):
        for bi in range(n):
            stride = 2 if (si > 0 and bi == 0) else 1
            yield f"s{si + 1}b{bi + 1}", cin, cout, stride
            cin = cout


def _conv_w(key, k: int, cin: int, cout: int) -> jnp.ndarray:
    return jax.random.normal(key, (k, k, cin, cout), jnp.float32) \
        * math.sqrt(2.0 / (k * k * cin))


def _bn(key, c: int) -> Params:
    ks = jax.random.split(key, 4)
    return {"scale": jax.random.uniform(ks[0], (c,), jnp.float32, 0.5, 1.5),
            "bias": 0.2 * jax.random.normal(ks[1], (c,), jnp.float32),
            "mean": 0.2 * jax.random.normal(ks[2], (c,), jnp.float32),
            "var": jax.random.uniform(ks[3], (c,), jnp.float32, 0.5, 2.0)}


def init_params(cfg: dict, key) -> Params:
    """The whole parameter pytree from one key.  Call it under ``jax.jit``
    so that it is one program on the device."""
    keys = iter(jax.random.split(key, 64))
    c = cfg["stem_channels"]
    last = cfg["stage_channels"][-1]
    p: Params = {
        "conv1": _conv_w(next(keys), cfg["stem_kernel"], cfg["in_channels"], c),
        "bn1": _bn(next(keys), c),
        "fc_w": jax.random.normal(next(keys), (last, cfg["num_classes"]),
                                  jnp.float32) / math.sqrt(last),
        "fc_b": jax.random.normal(next(keys), (cfg["num_classes"],),
                                  jnp.float32),
    }
    for name, cin, cout, stride in _blocks(cfg):
        b = {"conv1": _conv_w(next(keys), 3, cin, cout), "bn1": _bn(next(keys), cout),
             "conv2": _conv_w(next(keys), 3, cout, cout), "bn2": _bn(next(keys), cout)}
        if stride != 1 or cin != cout:
            b["down"] = _conv_w(next(keys), 1, cin, cout)
            b["down_bn"] = _bn(next(keys), cout)
        p[name] = b
    return p


def init_inputs(cfg: dict, key, n: int) -> jnp.ndarray:
    """``n`` images, NHWC, normalised pixel statistics (mean 0, std 1)."""
    hw = cfg["image_size"]
    return jax.random.normal(key, (n, hw, hw, cfg["in_channels"]), jnp.float32)


def host_inputs(cfg: dict, seed_words: np.ndarray, n: int) -> np.ndarray:
    """As ``init_inputs``, made on the host, for traffic fed from the host."""
    hw = cfg["image_size"]
    rng = np.random.default_rng(seed_words)
    return rng.standard_normal((n, hw, hw, cfg["in_channels"]), np.float32)


# --- the plain reference --------------------------------------------------

def _strided(x: jnp.ndarray, i: int, j: int, oh: int, ow: int,
             s: int) -> jnp.ndarray:
    """x[:, i + s*a, j + s*b] for a < oh, b < ow, by slice and reshape."""
    v = x[:, i:i + s * oh, j:j + s * ow, :]
    if s == 1:
        return v
    n, _, _, c = v.shape
    return v.reshape(n, oh, s, ow, s, c)[:, :, 0, :, 0, :]


def _pad(x: jnp.ndarray, p: int, value: float = 0.0) -> jnp.ndarray:
    return jnp.pad(x, ((0, 0), (p, p), (p, p), (0, 0)), constant_values=value)


def _bf16(t: jnp.ndarray) -> jnp.ndarray:
    """``t`` rounded to bfloat16's 8 bits of mantissa, kept in float32.  A
    round trip through ``astype`` would not do: XLA may keep the float32
    value where a bfloat16 is converted back (excess precision)."""
    return jax.lax.reduce_precision(t, exponent_bits=8, mantissa_bits=7)


def _bf16_split(t: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    hi = _bf16(t)
    return hi, _bf16(t - hi)


def _mm(a: jnp.ndarray, b: jnp.ndarray, spec: str, precision: str) -> jnp.ndarray:
    """``einsum(spec, a, b)`` in float32: exact products at ``highest``;
    at ``high``, three bfloat16 passes (hi*hi + hi*lo + lo*hi), each
    product exact and summed in float32."""
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    if precision != "high":
        raise ValueError(f"no reference at precision {precision!r}")
    (ah, al), (bh, bl) = _bf16_split(a), _bf16_split(b)
    return (jnp.einsum(spec, al, bh, precision=HIGHEST)
            + jnp.einsum(spec, ah, bl, precision=HIGHEST)) \
        + jnp.einsum(spec, ah, bh, precision=HIGHEST)


def conv(x: jnp.ndarray, w: jnp.ndarray, stride: int, pad: int,
         precision: str) -> jnp.ndarray:
    k = w.shape[0]
    oh = (x.shape[1] + 2 * pad - k) // stride + 1
    ow = (x.shape[2] + 2 * pad - k) // stride + 1
    xp = _pad(x, pad)
    out = None
    for i in range(k):
        for j in range(k):
            t = _mm(_strided(xp, i, j, oh, ow, stride), w[i, j],
                    "nhwc,cd->nhwd", precision)
            out = t if out is None else out + t
    return out


def batchnorm(p: Params, x: jnp.ndarray, eps: float) -> jnp.ndarray:
    return (x - p["mean"]) / jnp.sqrt(p["var"] + eps) * p["scale"] + p["bias"]


def maxpool(x: jnp.ndarray, k: int, stride: int, pad: int) -> jnp.ndarray:
    oh = (x.shape[1] + 2 * pad - k) // stride + 1
    xp = _pad(x, pad, -jnp.inf)
    out = None
    for i in range(k):
        for j in range(k):
            t = _strided(xp, i, j, oh, oh, stride)
            out = t if out is None else jnp.maximum(out, t)
    return out


def reference(cfg: dict, p: Params, x: jnp.ndarray,
              precision: str = "highest") -> jnp.ndarray:
    """Logits (N, classes) of images ``x`` (N, H, W, C), in float32, every
    conv and matrix product at ``precision``."""
    eps = cfg["bn_eps"]
    k = cfg["stem_kernel"]
    h = jax.nn.relu(batchnorm(p["bn1"], conv(x, p["conv1"], 2, k // 2, precision),
                              eps))
    h = maxpool(h, 3, 2, 1)
    for name, _cin, _cout, stride in _blocks(cfg):
        b = p[name]
        y = jax.nn.relu(batchnorm(b["bn1"], conv(h, b["conv1"], stride, 1, precision),
                                  eps))
        y = batchnorm(b["bn2"], conv(y, b["conv2"], 1, 1, precision), eps)
        short = h
        if "down" in b:
            short = batchnorm(b["down_bn"], conv(h, b["down"], stride, 0, precision),
                              eps)
        h = jax.nn.relu(y + short)
    feat = jnp.mean(h, axis=(1, 2))
    return _mm(feat, p["fc_w"], "nc,cd->nd", precision) + p["fc_b"]


def control(cfg: dict, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    """The lower-precision control: the reference in the program's place,
    at ``high`` (three bfloat16 passes), the step below the configuration's
    ``highest``.  The benchmark's own runs never call it; its readings set
    the upper end of each limit."""
    return reference(cfg, p, x, "high")


# --- the comparison ---------------------------------------------------------

def checks(cfg: dict, ys: np.ndarray, rs: np.ndarray) -> dict:
    """Each compared number of the answers ``ys`` against the reference's
    ``rs`` (both (N, classes), the same N images), with its limit:

    * ``dtype``: 1 if the answers are not in the configuration's ``dtype``;
    * ``nonfinite``: answers that are NaN or infinite;
    * ``logit_err``: the widest gap of a logit from the reference's, as a
      share of the reference's largest logit."""
    nonfinite = int(ys.size - np.isfinite(ys.astype(np.float64)).sum())
    err = float("inf")
    if ys.shape == rs.shape and nonfinite == 0:
        err = float(np.abs(ys.astype(np.float64) - rs).max() / np.abs(rs).max())
    return {"dtype": {"value": int(str(ys.dtype) != cfg["dtype"]), "limit": 0},
            "nonfinite": {"value": nonfinite, "limit": 0},
            "logit_err": {"value": err,
                          "limit": cfg["check_limits"]["logit_err"]}}


# --- work -------------------------------------------------------------------

def layers(cfg: dict) -> list[flops.Layer]:
    """The layers of a ResNet basic-block classifier, in network order."""
    group_of = {part: g for g, parts in cfg["groups"].items() for part in parts}
    hw = cfg["image_size"]
    c = cfg["stem_channels"]
    k = cfg["stem_kernel"]
    out = flops.out_hw(hw, k, 2, k // 2)
    ls = [flops.Layer("stem_conv", "conv", group_of["stem"], cfg["in_channels"],
                      c, hw, out, k, 2, k // 2)]
    hw, pooled = out, flops.out_hw(out, 3, 2, 1)
    ls.append(flops.Layer("maxpool", "maxpool", group_of["maxpool"], c, c, hw,
                          pooled, 3, 2, 1))
    hw = pooled
    for name, cin, cout, s in _blocks(cfg):
        g = group_of[f"stage{name[1]}"]
        mid = flops.out_hw(hw, 3, s, 1)
        ls.append(flops.Layer(f"{name}_conv1", "conv", g, cin, cout, hw, mid, 3, s, 1))
        ls.append(flops.Layer(f"{name}_conv2", "conv", g, cout, cout, mid, mid, 3, 1, 1))
        if s != 1 or cin != cout:
            ls.append(flops.Layer(f"{name}_down", "conv", g, cin, cout, hw, mid, 1, s, 0))
        ls.append(flops.Layer(f"{name}_add", "add", g, cout, cout, mid, mid))
        hw = mid
    head = group_of["head"]
    c = cfg["stage_channels"][-1]
    ls.append(flops.Layer("avgpool", "avgpool", head, c, c, hw, 1, hw, hw))
    ls.append(flops.Layer("fc", "fc", head, c, cfg["num_classes"], 1, 1))
    return ls


def flops_per_input(cfg: dict) -> int:
    """FLOPs one image needs (conv and fc multiply-adds, twice)."""
    return flops.flops(layers(cfg))


def group_work(cfg: dict, group: str, batch: int) -> dict:
    """A group's FLOPs and minimal HBM bytes for one batch, and the output
    heights that tell its operations apart in a trace."""
    ls = layers(cfg)
    return {"flops": batch * flops.flops(ls, group),
            "bytes": flops.min_bytes(ls, group, batch,
                                     jnp.dtype(cfg["dtype"]).itemsize),
            "rows": flops.out_rows(ls, group)}
