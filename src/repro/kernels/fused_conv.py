"""Fused CONV + BN + [ADD] + [RELU] Pallas TPU kernel — the PIMcore fused
op (paper Table I: CONV_BN / CONV_BN_RELU / ADD_RELU flags) re-tiled for
the TPU memory hierarchy.

PIM→TPU mapping: the paper's LBUF-resident spatial tile becomes a
VMEM-resident output tile; the paper's GBUF weight broadcast becomes the
weight BlockSpec (same weights revisited by every spatial grid step); halo
rows that cross PIM banks are here the extra rows of each tile's input
window.  Neighbouring windows overlap by those rows, so the input
BlockSpec indexes elements, not blocks (``pl.Element``), and the Pallas
pipeline DMAs each window from HBM into VMEM, double-buffered.

The wrapper turns every conv into a stride-1 conv the kernel reads with
unit-stride static slices: a stride-s conv becomes a stride-1 conv over
the space-to-depth input (each phase ``x[a::s, b::s]`` a channel group),
and channels are padded to a multiple of 128 lanes.

Grid: (batch, H-tiles, Cout-blocks).  Each tile spans the full output
width (padded to a multiple of 8 so the tile reshapes cleanly onto the
(8, 128) vreg layout).  Inner loop: kh × kw static unroll of
(tile_pixels × Cin) · (Cin × Cout_blk) MXU matmuls accumulated in f32,
then the BN/residual/ReLU epilogue — one HBM round-trip per tile for the
whole fused layer group member.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.models.layers import space_to_depth


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _kernel(x_ref, w_ref, scale_ref, shift_ref, *rest, kh: int, kw: int,
            th: int, ow: int, relu: bool, has_residual: bool):
    if has_residual:
        res_ref, o_ref = rest
    else:
        (o_ref,) = rest
    cin = x_ref.shape[-1]
    cout_blk = w_ref.shape[-1]
    acc = jnp.zeros((th * ow, cout_blk), jnp.float32)
    for r in range(kh):
        for c in range(kw):
            patch = x_ref[0, r:r + th, c:c + ow, :]         # (th, ow, cin)
            acc += jnp.dot(patch.reshape(th * ow, cin).astype(jnp.float32),
                           w_ref[r, c].astype(jnp.float32),
                           precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)

    y = acc * scale_ref[...].astype(jnp.float32) \
        + shift_ref[...].astype(jnp.float32)
    y = y.reshape(th, ow, cout_blk)
    if has_residual:
        y = y + res_ref[0].astype(jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    o_ref[0] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "stride", "padding", "relu", "tile_h", "cout_block", "interpret"))
def fused_conv_kernel(x: jnp.ndarray, w: jnp.ndarray, scale: jnp.ndarray,
                      shift: jnp.ndarray, *, stride: int = 1,
                      padding: int = 1, relu: bool = True,
                      residual: jnp.ndarray | None = None,
                      tile_h: int = 8, cout_block: int = 128,
                      interpret: bool = False) -> jnp.ndarray:
    """x: (B, H, W, Cin) NHWC; w: (kh, kw, Cin, Cout).
    Returns (B, OH, OW, Cout) with OH = (H + 2p - kh)//s + 1."""
    B, H, W, _ = x.shape
    kh, kw, _, Cout = w.shape
    s = stride
    OH = (H + 2 * padding - kh) // s + 1
    OW = (W + 2 * padding - kw) // s + 1
    cb = min(cout_block, Cout)
    assert Cout % cb == 0, f"cout {Cout} % block {cb}"

    # output tiles: th rows × the full width padded to 8 columns
    th = min(tile_h, OH)
    oh = _round_up(OH, th)
    ow = _round_up(OW, 8)
    # stride-1 kernel extent after space-to-depth, and the input extent
    # (in space-to-depth rows/cols) the padded output needs
    skh, skw = -(-kh // s), -(-kw // s)
    hs = oh + skh - 1
    ws_ = _round_up(ow + skw - 1, 8)
    # conv padding, then pad or crop to exactly hs·s × ws_·s input pixels
    xp = jnp.pad(x, ((0, 0),
                     (padding, max(0, hs * s - H - padding)),
                     (padding, max(0, ws_ * s - W - padding)),
                     (0, 0)))[:, :hs * s, :ws_ * s]
    xs, wsd = space_to_depth(xp, w, s)
    cp = _round_up(xs.shape[-1], 128)
    xs = jnp.pad(xs, ((0, 0), (0, 0), (0, 0), (0, cp - xs.shape[-1])))
    wsd = jnp.pad(wsd, ((0, 0), (0, 0), (0, cp - wsd.shape[2]), (0, 0)))

    res = residual
    if res is not None and (oh != OH or ow != OW):
        res = jnp.pad(res, ((0, 0), (0, oh - OH), (0, ow - OW), (0, 0)))

    grid = (B, oh // th, Cout // cb)
    kern = functools.partial(_kernel, kh=skh, kw=skw, th=th, ow=ow,
                             relu=relu, has_residual=res is not None)
    in_specs = [
        # the tile's halo'd input window: rows [h·th, h·th + th + skh - 1)
        pl.BlockSpec((pl.Element(1), pl.Element(th + skh - 1),
                      pl.Element(ws_), pl.Element(cp)),
                     lambda b, h, co: (b, h * th, 0, 0)),
        pl.BlockSpec((skh, skw, cp, cb), lambda b, h, co: (0, 0, 0, co)),
        pl.BlockSpec((1, cb), lambda b, h, co: (0, co)),
        pl.BlockSpec((1, cb), lambda b, h, co: (0, co)),
    ]
    args = [xs, wsd, scale.reshape(1, Cout), shift.reshape(1, Cout)]
    if res is not None:
        in_specs.append(pl.BlockSpec((1, th, ow, cb),
                                     lambda b, h, co: (b, h, 0, co)))
        args.append(res)

    # Left free, XLA places the call's operands and its output in VMEM from
    # stage 2 on (a 33.5 MB output at 28x128->256, batch 128), beside the
    # kernel's own VMEM blocks.  Pin them to HBM, where the BlockSpec
    # pipeline DMAs from and to.  The interpreter knows no memory spaces.
    space = pl.ANY if interpret else pltpu.HBM
    args = [pltpu.with_memory_space_constraint(a, space) for a in args]
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, th, ow, cb),
                               lambda b, h, co: (b, h, 0, co)),
        out_shape=space((B, oh, ow, Cout), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3),
        interpret=interpret,
    )(*args)
    return out[:, :OH, :OW]
