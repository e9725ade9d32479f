"""ConvNeXt's block MLP with its residual add, as one Pallas TPU kernel
that keeps the 4d-wide hidden map in VMEM:

    x + γ · (gelu(LN(h) W1 + b1) W2 + b2)

XLA runs the expansion and the projection as two ops, so the hidden map
(four times the block's width) is written to HBM by the first and read
back by the second: at ConvNeXt-T's stage 1 and a batch of 128, 1.23 GB
per block.  Here a tile of pixels is normalised, expanded, activated,
projected, scaled and added to the block's input in VMEM; HBM sees the
block's input ``x``, the depthwise conv's output ``h`` and its channel
sums ``total`` (the LayerNorm's mean) read once, and the output written
once.

Layout: the maps come with the batch in lanes, (H, W, C, N), the layout
XLA keeps them in where the depthwise kernel's ``tile_plan`` puts the
batch in lanes, so the transposes around the call are bitcasts.  A
pixel's (C, N) slab is a ready operand: the pixels of a tile are laid
side by side along the lanes, (C, pixels · N), and both products take
the weight as the streamed left operand, W1ᵀ (4C, C) and W2ᵀ (C, 4C),
and the map as the operand held in the matrix unit.  W2 is passed
transposed: a v5e keeps a (4C, C) weight with 4C minor when C is no
multiple of 128 (96, 192), so W2ᵀ is a bitcast there, where a row-major
W2 cost a relayout copy before every call.

Same arithmetic as ``models.layers``: float32 throughout, the LayerNorm's
mean from ``total``, its variance the mean of squared deviations, both
products at ``Precision.HIGHEST`` with float32 results, and the exact
GELU, x·Φ(x) through erf.  Mosaic lowers no ``erf``, so ``erf`` here is
a float32 rational approximation (the one XLA's and Eigen's float32 erf
used), within 8 ulps of ``jax.lax.erf`` on the CPU and 6 of the exact
erf (tests/test_layers.py).

Grid: one step per ``PIXELS`` pixels (and per 128 images); the whole
map's channel sums stay in VMEM, since a tile's pixels may span two rows
of it.  At stage 1 on a v5e 16 pixels a step took 2.48 ms a call, 8
took 2.55 (stage 2: 2.24 and 2.25).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
PIXELS = 16
HIGHEST = jax.lax.Precision.HIGHEST

# erf(x) ≈ x · P(x²) / Q(x²) on [-4, 4], where float32 erf is ±1 beyond
_ERF_P = (-2.72614225801306e-10, 2.77068142495902e-08, -2.10102402082508e-06,
          -5.69250639462346e-05, -7.34990630326855e-04, -2.95459980854025e-03,
          -1.60960333262415e-02)
_ERF_Q = (-1.45660718464996e-05, -2.13374055278905e-04, -1.68282697438203e-03,
          -7.37332916720468e-03, -1.42647390514189e-02)


def _horner(x: jnp.ndarray, coeffs) -> jnp.ndarray:
    y = jnp.full(x.shape, coeffs[0], x.dtype)
    for c in coeffs[1:]:
        y = y * x + c
    return y


def erf(x: jnp.ndarray) -> jnp.ndarray:
    """float32 erf, as a rational function of the argument clamped to
    [-4, 4]."""
    x = jnp.clip(x, -4.0, 4.0)
    x2 = x * x
    return x * (_horner(x2, _ERF_P) / _horner(x2, _ERF_Q))


def gelu(x: jnp.ndarray) -> jnp.ndarray:
    """The exact GELU, as ``models.layers.gelu`` writes it, on ``erf``."""
    return 0.5 * x * (1.0 + erf(x * math.sqrt(0.5)))


def _kernel(h_ref, t_ref, x_ref, w1_ref, b1_ref, w2t_ref, v_ref, o_ref, *,
            eps: float, width: int, pixels_total: int):
    pixels, c, lanes = h_ref.shape
    base = pl.program_id(0) * pixels
    cols = []
    for q in range(pixels):
        # the last tile may reach past the map: its extra pixels read the
        # last pixel's sum and are never written back
        m = jnp.minimum(base + q, pixels_total - 1)
        mu = t_ref[m // width, pl.ds(m % width, 1)] / c
        d = h_ref[q] - mu
        var = jnp.sum(d * d, axis=0, keepdims=True) / c
        cols.append(d * jax.lax.rsqrt(var + eps) * v_ref[0] + v_ref[1])
    # W1ᵀ · LN(h) contracts W1's rows: Mosaic transposes the (C, 4C) block
    hidden = jax.lax.dot_general(
        w1_ref[...], jnp.concatenate(cols, axis=1), (((0,), (0,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32) \
        + jnp.concatenate([b1_ref[...]] * pixels, axis=1)
    y = jnp.dot(w2t_ref[...], gelu(hidden), precision=HIGHEST,
                preferred_element_type=jnp.float32)
    for q in range(pixels):
        o_ref[q] = x_ref[q] \
            + (y[:, q * lanes:(q + 1) * lanes] + v_ref[2]) * v_ref[3]


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def convnext_mlp_kernel(h: jnp.ndarray, total: jnp.ndarray, x: jnp.ndarray,
                        ln_scale: jnp.ndarray, ln_bias: jnp.ndarray,
                        w1: jnp.ndarray, b1: jnp.ndarray, w2: jnp.ndarray,
                        b2: jnp.ndarray, gamma: jnp.ndarray, *, eps: float,
                        interpret: bool = False) -> jnp.ndarray:
    """h, x: (N, H, W, C) NHWC, the depthwise conv's output and the
    block's input; total: (N, H, W), h's float32 sum over channels;
    w1: (C, 4C), w2: (4C, C).  Returns the block's output, x + gamma ·
    (gelu(LN(h) w1 + b1) w2 + b2), LN over channels with eps."""
    n, hh, ww, c = h.shape
    hidden = w1.shape[1]
    m = hh * ww
    # (H·W, C, N): bitcasts of the batch-in-lanes layout
    ht = h.transpose(1, 2, 3, 0).reshape(m, c, n)
    xt = x.transpose(1, 2, 3, 0).reshape(m, c, n)
    tt = total.transpose(1, 2, 0)
    # Mosaic slices HBM along the lanes in whole tiles only: pad the batch
    # to them (a batch of 128 needs none)
    if n % LANES:
        def pad(v):
            return jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, -n % LANES)])
        ht, xt, tt = pad(ht), pad(xt), pad(tt)
    # per-channel vectors as columns broadcast over the lanes
    vec = jnp.broadcast_to(
        jnp.stack([ln_scale, ln_bias, b2, gamma])[:, :, None], (4, c, LANES))
    b1 = jnp.broadcast_to(b1[:, None], (hidden, LANES))
    tile = pl.BlockSpec((PIXELS, c, LANES), lambda i, j: (i, 0, j))
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps, width=ww, pixels_total=m),
        grid=(pl.cdiv(m, PIXELS), ht.shape[2] // LANES),
        in_specs=[
            tile,
            pl.BlockSpec((hh, ww, LANES), lambda i, j: (0, 0, j)),
            tile,
            pl.BlockSpec((c, hidden), lambda i, j: (0, 0)),
            pl.BlockSpec((hidden, LANES), lambda i, j: (0, 0)),
            pl.BlockSpec((c, hidden), lambda i, j: (0, 0)),
            pl.BlockSpec((4, c, LANES), lambda i, j: (0, 0, 0)),
        ],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(xt.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=64 << 20),
        name="convnext_mlp",
        interpret=interpret,
    )(ht, tt, xt, w1, b1, w2.T, vec)
    return out[..., :n].reshape(hh, ww, c, n).transpose(3, 0, 1, 2)
